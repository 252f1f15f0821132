"""One run of one benchmark cell: set-up, measured window, traced window,
reduction and the check against the plain reference.

The system under test is ``ServingEngine.process``, built by
``serving/baselines.build_engine`` with the TPU v5e time model and a
``ModelRunner`` holding the cell's model at its published widths, weights
drawn from the seed by ``reference.make_flat``. The engine is a
discrete-event simulator whose events run real work (prefill, decode
ticks, KIVI compression, the DRAM and SSD tiers, the controller), so the
wall time of ``process`` is real while the times it reports are
simulated.

The configuration's ``reference`` key names its architecture module
(``arch/<name>.py``, interface in ``reference.py``): the program's
``ModelConfig``, the weights' shapes and layout, the counts the readers
use and the plain reference all come from it; nothing here knows an
architecture.

How a run measures:

* Set-up (``setup_s``, from process start to window start): the
  persistent compile cache at the program's fixed in-checkout path with
  JAX's minimum compile time for caching lowered to 0, weights on the
  device, every shape of the cell warmed (prefill at each prompt length,
  the decode step, lane writes, the KIVI kernels at the page shape for
  every bit width): for a document library by prefilling, storing and
  admitting every document through the engine, for unique prompts by
  serving one of each length.
* Window: request segments fed to ``process`` until ``--seconds`` of
  wall time are used; the window is a whole number of segments. Arrival
  times are simulated at the mix's fixed rate; they set lane occupancy
  and event order only.
* Per-request wall TTFT: from when the engine pops the request's arrival
  event (``EventLoop.pop``) to when the decode tick that produced its
  first answer token returns (``ContinuousBatcher.tick``, which ends in a
  host copy of the argmax). Queueing before the engine takes an arrival
  is not counted: the engine has no wall-clock arrivals.
* ``--trace 1``: a profiler trace of one steady segment, with
  ``bench:*`` spans around the calls into each layer; the per-layer
  readers in ``metrics/`` reduce spans, counters and trace.

Program points the harness wraps (``Hooks``): ``EventLoop.pop``,
``ContinuousBatcher.tick`` and ``.admit``, ``AdaptCacheController.fetch``
and ``.insert``, ``PagedPrefixCache.match_prefix``,
``ModelRunner.prefill_entry`` and the KIVI ``quantize``/``dequantize``
entry points. Each wrapper times the call and passes it through
unchanged; all are restored when the run ends.
"""
from __future__ import annotations

import contextlib
import copy
import gc
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

CHECK_TOKENS = 256          # served tokens the reference compares, at least
CHECK_REQUESTS = 8          # and at most this many requests


# ---------------------------------------------------------------------------
# cell description
# ---------------------------------------------------------------------------

def merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool = False):
    """(benchmark, workload entry, configuration file, mix) for a cell of
    ``BENCHMARK.json``. The mix is ``traffic/<traffic>.json`` with the
    configuration's ``engine`` (how many lanes one chip holds for this
    model) merged over its ``engine``, then, for a CPU rehearsal, the
    mix's ``rehearsal`` sizes over that. A configuration that says what
    its architecture module does not model, or whose lanes are longer than
    its positions, is refused here, before any weight is drawn
    (``reference.check_config``)."""
    from bench import reference
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT / conf["file"])
    if "reference" not in cfg:
        raise ValueError(f"{conf['file']}: no 'reference' key naming its "
                         f"architecture module")
    reference.check_config(cfg, reference.load(cfg["reference"]),
                           conf["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    mix = merge(mix, {"engine": cfg.get("engine", {})})
    positions = cfg.get("max_position_embeddings")
    if positions is not None and mix["engine"]["capacity"] > positions:
        raise ValueError(f"{conf['file']}: lanes of "
                         f"{mix['engine']['capacity']} positions exceed "
                         f"max_position_embeddings {positions}")
    if rehearse:
        mix = merge(mix, mix.get("rehearsal", {}))
    return bench, cell, cfg, mix


def architecture(cfg: dict, rehearse: bool):
    """(architecture module, sizes) of a configuration, at the module's
    tiny rehearsal sizes for a CPU rehearsal."""
    from bench import reference
    arch = reference.load(cfg["reference"])
    return arch, arch.dims_from_config(cfg, rehearse)


# ---------------------------------------------------------------------------
# compile counting
# ---------------------------------------------------------------------------

class CompileMeter:
    """Counts XLA backend compiles and their seconds, and persistent
    cache hits, from JAX's monitoring events (as ``chip_smoke.py`` does).
    One listener per process; ``snapshot`` gives the running totals."""
    _inst: Optional["CompileMeter"] = None

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    @classmethod
    def get(cls) -> "CompileMeter":
        if cls._inst is None:
            import jax
            cls._inst = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._inst._dur)
            jax.monitoring.register_event_listener(cls._inst._event)
        return cls._inst

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


# ---------------------------------------------------------------------------
# hooks: spans, arrival and first-token stamps
# ---------------------------------------------------------------------------

class Hooks:
    """Wraps the program points listed in the module doc. Records host
    spans ``(name, t0, t1)`` (``time.perf_counter``), arrival and
    first-token stamps per request id, decode ticks with their active
    lanes and summed positions, prefill lengths and KIVI kernel calls.

    While ``probing`` is set, the device's peak bytes in use (``peak``, a
    callable) are read after every wrapped call and event; ``peak_rises``
    keeps each span after which the peak had risen, to name what sets it."""

    def __init__(self, peak=None):
        self.spans: List[tuple] = []
        self.arrival: Dict[int, float] = {}
        self.first: Dict[int, float] = {}
        self.ticks: List[tuple] = []       # t0, t1, active lanes, sum pos
        self.prefills: List[tuple] = []    # t0, t1, tokens
        self.kivi: List[tuple] = []        # t, kind, rows, cols, bits, group
        self._event = None
        self._undo: List[tuple] = []
        self.peak = peak
        self.probing = False
        self.peak_rises: List[tuple] = []  # span, peak bytes after it
        self._last_peak = 0

    def probe(self, name: str) -> None:
        if self.probing and self.peak is not None:
            p = self.peak()
            if p > self._last_peak:
                self.peak_rises.append((name, p))
                self._last_peak = p

    # -- patching -----------------------------------------------------------
    def _patch(self, owner, name, make):
        orig = getattr(owner, name)
        self._undo.append((owner, name, orig))
        setattr(owner, name, make(orig))

    def __enter__(self):
        import jax
        from repro.core.controller import AdaptCacheController
        from repro.kernels.kivi import ops as kivi_ops
        from repro.serving import scheduler
        from repro.serving.chunking import PagedPrefixCache
        from repro.serving.runner import ModelRunner
        ann = jax.profiler.TraceAnnotation
        clock = time.perf_counter
        hooks = self

        def timed(name):
            def make(orig):
                def wrapper(*a, **k):
                    t0 = clock()
                    with ann("bench:" + name):
                        out = orig(*a, **k)
                    hooks.spans.append((name, t0, clock()))
                    hooks.probe(name)
                    return out
                return wrapper
            return make

        def pop(orig):
            def wrapper(loop):
                hooks.close_event()
                now, kind, payload = orig(loop)
                t = clock()
                if kind == scheduler.EV_ARRIVAL:
                    hooks.arrival[payload.req_id] = t
                name = "ev_" + scheduler.EVENT_NAMES.get(kind, str(kind))
                a = ann("bench:" + name)
                a.__enter__()
                hooks._event = (name, t, a)
                return now, kind, payload
            return wrapper

        def tick(orig):
            def wrapper(bself, now):
                before = {i: (s.req.req_id, len(s.generated))
                          for i, s in enumerate(bself.slots) if s.active}
                pos = sum(bself.slots[i].position for i in before)
                t0 = clock()
                with ann("bench:tick"):
                    done, dt = orig(bself, now)
                t1 = clock()
                for i, (rid, n0) in before.items():
                    s = bself.slots[i]
                    if (n0 == 0 and s.active and s.req.req_id == rid
                            and s.generated):
                        hooks.first.setdefault(rid, t1)
                for r in done:
                    hooks.first.setdefault(r.req_id, t1)
                hooks.spans.append(("tick", t0, t1))
                hooks.ticks.append((t0, t1, len(before), pos))
                hooks.probe("tick")
                return done, dt
            return wrapper

        def prefill(orig):
            def wrapper(rself, ctx_tokens):
                t0 = clock()
                with ann("bench:prefill"):
                    out = orig(rself, ctx_tokens)
                t1 = clock()
                hooks.spans.append(("prefill", t0, t1))
                hooks.prefills.append((t0, t1, len(ctx_tokens)))
                hooks.probe(f"prefill {len(ctx_tokens)}")
                return out
            return wrapper

        def quant(orig):
            def wrapper(x, bits, group_size, axis):
                rows, cols = (x.shape if axis == 0 else x.shape[::-1])
                hooks.kivi.append((clock(), "q", rows, cols, bits,
                                   group_size))
                with ann("bench:kivi_quantize"):
                    return orig(x, bits, group_size, axis)
            return wrapper

        def dequant(orig):
            def wrapper(qt, *a, **k):
                cpb = 8 // qt.bits
                rows, cols = (qt.packed.shape if qt.axis == 0
                              else qt.packed.shape[::-1])
                hooks.kivi.append((clock(), "d", rows * cpb, cols, qt.bits,
                                   qt.group_size))
                with ann("bench:kivi_dequantize"):
                    return orig(qt, *a, **k)
            return wrapper

        self._patch(scheduler.EventLoop, "pop", pop)
        self._patch(scheduler.ContinuousBatcher, "tick", tick)
        self._patch(scheduler.ContinuousBatcher, "admit", timed("admit"))
        self._patch(AdaptCacheController, "fetch", timed("fetch"))
        self._patch(AdaptCacheController, "insert", timed("insert"))
        self._patch(PagedPrefixCache, "match_prefix", timed("match"))
        self._patch(ModelRunner, "prefill_entry", prefill)
        self._patch(kivi_ops, "quantize", quant)
        self._patch(kivi_ops, "dequantize", dequant)
        return self

    def close_event(self) -> None:
        if self._event is not None:
            name, t0, a = self._event
            a.__exit__(None, None, None)
            self.spans.append((name, t0, time.perf_counter()))
            self._event = None
            self.probe(name)

    def __exit__(self, *exc):
        self.close_event()
        for owner, name, orig in reversed(self._undo):
            setattr(owner, name, orig)
        self._undo.clear()
        return False

    def durations(self, name: str, t0: float, t1: float) -> List[float]:
        return [b - a for n, a, b in self.spans if n == name and a >= t0
                and b <= t1]


# ---------------------------------------------------------------------------
# the engine rig
# ---------------------------------------------------------------------------

def quality_estimator(mix: dict):
    from repro.core.estimator import QualityEstimator
    qe = QualityEstimator()
    for method, curve in mix["quality_curves"].items():
        qe.set_curve(mix["task"], method, [tuple(p) for p in curve])
    return qe


def make_rig(runner, docs, mix: dict, cfg, n_active: int, spool: str):
    from repro.serving.baselines import build_engine
    from repro.serving.timemodel import TPU_V5E
    from repro.serving.workload import Context
    eng = mix["engine"]
    contexts = [Context(d.key, mix["task"], d.tokens, []) for d in docs]
    # tier capacities as shares of the documents' lossless stored bytes
    dram, ssd = eng["dram_share"] * len(docs), eng["ssd_share"] * len(docs)
    os.makedirs(spool, exist_ok=True)
    return build_engine(
        runner, contexts, cfg, n_active, policy=eng["policy"],
        alpha=eng.get("alpha", 0.01), dram_entries=dram, ssd_entries=ssd,
        device=TPU_V5E, quality_est=quality_estimator(mix),
        ssd_root=spool, n_lanes=eng["lanes"],
        page_tokens=eng["page_tokens"], chunk_tokens=eng["chunk_tokens"])


def to_requests(reqs, task: str):
    from repro.serving.workload import Request
    return [Request(r.req_id, r.doc_key, r.question, r.arrival_s, task,
                    max_new_tokens=r.answer_tokens) for r in reqs]


def warm_kivi(plane: tuple, page: int) -> None:
    """Compile the KIVI kernels at the page shape for every bit width, in
    both directions, through the program's compression method; ``plane``
    is the architecture's (layers, features) of a K or V page."""
    from repro.core.compression.kivi import BITS_LADDER, KIVICompression
    layers, f = plane
    z = np.zeros((layers, page, f), np.float32)
    kv = {"k": z, "v": z, "positions": np.arange(page, dtype=np.int32)}
    m = KIVICompression()
    for bits in BITS_LADDER:
        m.decompress(m.compress(kv, 0.0, bits=bits))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, rehearse: bool = False, log=None,
        control: bool = False) -> dict:
    """Run one cell once; returns the result object (see ``run.py``)."""
    import jax
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    bench, cell, cfg_json, mix = load_cell(workload, rehearse)
    from repro.launch import serve
    from repro.models import build_model
    from repro.serving.runner import ModelRunner
    from bench import reference
    from bench import traffic as traffic_mod

    if not rehearse:
        log(f"compile cache: {serve.enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    meter = CompileMeter.get()
    arch, dims = architecture(cfg_json, rehearse)
    cfg = arch.program_config(cell["config"], dims)
    eng = mix["engine"]
    gen = traffic_mod.Traffic(mix, seed, dims["vocab"])
    dev = jax.devices()[0]

    def memory(key: str) -> int:
        return int((dev.memory_stats() or {}).get(key, 0))

    def log_memory(when: str) -> None:
        log(f"memory {when}: in use {memory('bytes_in_use')} B, peak "
            f"{memory('peak_bytes_in_use')} B")
    log(f"{workload}: seed {seed}, rate {mix['rate_hz']} req/s simulated, "
        f"{eng['lanes']} lanes of {eng['capacity']}, "
        f"{len(gen.docs) or 'unique'} documents, prompt lengths "
        f"{gen.prompt_lengths()}")

    flat = reference.make_flat(arch, dims, seed)
    params = arch.program_layout(flat)
    jax.block_until_ready(params)
    log_memory("after the weights")
    model = build_model(cfg)
    n_active = model.active_param_count()
    runner = ModelRunner(model, params, capacity=eng["capacity"])
    del flat

    spool_root = tempfile.mkdtemp(prefix="bench_spool_")
    results, window = [], {}
    try:
        with Hooks(peak=lambda: memory("peak_bytes_in_use")) as hooks:
            hooks.probing = True     # set-up only: the window is not probed
            rig, n_rig = None, 0

            def new_rig(docs):
                nonlocal n_rig
                n_rig += 1
                return make_rig(runner, docs, mix, cfg, n_active,
                                os.path.join(spool_root, f"rig{n_rig}"))

            warm_kivi(arch.kv_plane(dims), eng["page_tokens"])
            hooks.probe("warm_kivi")
            if gen.kind == "documents":
                rig = new_rig(gen.docs)
                # every document prefilled, stored and admitted once: the
                # lane writes at every document length compile here too
                fill = gen.fill_requests(start_id=1)
                rig.engine.process(to_requests(fill, mix["task"]),
                                   skip_quality=True)
                hooks.close_event()
            else:
                warm_docs, wreq = gen.warm_requests(start_id=1)
                wrig = new_rig(warm_docs)
                wrig.engine.process(to_requests(wreq, mix["task"]),
                                    skip_quality=True)
                hooks.close_event()
                del wrig
            jax.block_until_ready(runner.params)
            gc.collect()
            hooks.probing = False
            held = memory("bytes_in_use")

            setup_s = time.perf_counter() - t_start
            log_memory("at window start")
            log("memory: spans after which the set-up peak rose (last 6): "
                + ", ".join(f"{n} {p}" for n, p in hooks.peak_rises[-6:]))
            c0 = meter.snapshot()
            counters0 = dict(rig.controller.counters) if rig else None
            written0 = _written(rig) if rig else 0
            log(f"setup_s {setup_s:.3f}; compiles in set-up {c0[0]} "
                f"({c0[1]:.3f} s), persistent cache hits {c0[2]}; SSD tier "
                f"bytes written in set-up {written0}")

            trace_seg = 1 if trace else -1
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace \
                else None
            gc_pauses = GcPauses()
            gc.callbacks.append(gc_pauses)
            w0 = time.perf_counter()
            k, t_sim, attempted = 0, (rig.clock.now + 1.0 if rig else 0.0), 0
            written = 0
            while True:
                docs_k, reqs_k = gen.segment(k, t_sim)
                if gen.kind == "unique":
                    if rig is not None:
                        written += _written(rig)
                        shutil.rmtree(rig.controller.tiers["ssd"].root,
                                      ignore_errors=True)
                    rig = new_rig(docs_k)
                attempted += len(reqs_k)
                if k == trace_seg:
                    jax.profiler.start_trace(trace_dir)
                    window["trace_t0"] = time.perf_counter()
                    seg_ann = jax.profiler.TraceAnnotation("bench:segment")
                    seg_ann.__enter__()
                res = rig.engine.process(to_requests(reqs_k, mix["task"]),
                                         skip_quality=True)
                hooks.close_event()
                if k == trace_seg:
                    jax.block_until_ready(runner.params)
                    seg_ann.__exit__(None, None, None)
                    window["trace_t1"] = time.perf_counter()
                    jax.profiler.stop_trace()
                results.extend(res)
                if k == 0:
                    log_memory("after the window's first segment")
                if gen.kind == "documents":
                    t_sim = rig.clock.now + 1.0
                k += 1
                if time.perf_counter() - w0 >= seconds and k > trace_seg:
                    break
            window_s = time.perf_counter() - w0
            gc.callbacks.remove(gc_pauses)
            c1 = meter.snapshot()
            written += _written(rig) - written0
            window.update(t0=w0, t1=w0 + window_s, window_s=window_s,
                          segments=k, attempted=attempted,
                          compiles=c1[0] - c0[0],
                          compile_s=c1[1] - c0[1],
                          counters0=counters0,
                          counters1=(dict(rig.controller.counters)
                                     if gen.kind == "documents" else None),
                          written=written)
            log(f"window {window_s:.3f} s, {k} segments, {attempted} "
                f"requests; compiles in window {window['compiles']} "
                f"({window['compile_s']:.3f} s); tier bytes written in "
                f"window {written}")
            log(f"garbage collections in window: {gc_pauses}")
            del rig
    finally:
        shutil.rmtree(spool_root, ignore_errors=True)

    log_memory("after the window")
    peak = memory("peak_bytes_in_use")
    out = summarize(results, window, hooks, gen,
                    e2e_names(bench, cell["name"]), setup_s)
    if trace:
        try:
            out["per_layer"] = per_layer(bench, cell, arch, dims, hooks,
                                         window, trace_dir, dev, results,
                                         mix, rehearse)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # free the program's state before the reference runs
    del runner, params, model
    gc.collect()
    sample = check_sample(results, gen, seed)
    out["checks"] = check(sample, gen, arch, dims, seed, mix, out)
    if control:
        out["control_checks"] = check(sample, gen, arch, dims, seed, mix,
                                      out, gap_fn=reference.control_gaps)
    out.update(peak=peak, held=held, dims=dims, cell=cell)
    return out


class GcPauses:
    """A ``gc.callbacks`` entry that times the interpreter's collections,
    so that a host stall in the window can be told from one of them."""

    def __init__(self):
        self.t0 = 0.0
        self.by_gen: Dict[int, List[float]] = {0: [], 1: [], 2: []}

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t0 = time.perf_counter()
        else:
            self.by_gen[info["generation"]].append(
                time.perf_counter() - self.t0)

    def __str__(self) -> str:
        return "; ".join(f"generation {g}: {len(v)}, longest "
                         f"{max(v, default=0.0):.4f} s, total {sum(v):.4f} s"
                         for g, v in self.by_gen.items())


def _written(rig) -> int:
    return sum(t.written_bytes for n, t in rig.controller.tiers.items()
               if n == "ssd")


def summarize(results, window, hooks: Hooks, gen, e2e: List[str],
              setup_s: float) -> dict:
    """End-to-end metrics over every request of the window. A request
    counts as failed when it was truncated, its answer is shorter than
    asked, or it has no arrival or first-token stamp."""
    from bench import stats
    done = [r for r in results if not r.truncated
            and len(r.answer) == gen.asked[r.req_id].answer_tokens
            and r.req_id in hooks.first and r.req_id in hooks.arrival]
    ttft_ms = [1e3 * (hooks.first[r.req_id] - hooks.arrival[r.req_id])
               for r in done]
    if ttft_ms:
        print("ttft_ms by request id: "
              + ", ".join(f"{r.req_id} {t:.1f}" for r, t in sorted(
                  zip(done, ttft_ms), key=lambda x: x[0].req_id))
              + f"; p90 {stats.percentile(ttft_ms, 90):.3f}",
              file=sys.stderr, flush=True)
    tokens = sum(len(r.answer) for r in done)
    m = {"setup_s": (setup_s, "s")}
    if ttft_ms:
        m["ttft_p50_ms"] = (stats.percentile(ttft_ms, 50), "ms")
        m["ttft_p90_ms"] = (stats.percentile(ttft_ms, 90), "ms")
    m["tokens_per_s"] = (stats.rate(tokens, window["window_s"]), "tokens/s")
    if results:
        m["served_quality"] = (float(np.mean([r.composed_quality
                                              for r in results])), "frac")
    m = {k: v for k, v in m.items() if k in e2e}
    return {"e2e": m, "attempted": window["attempted"],
            "failed": window["attempted"] - len(done), "done": len(done),
            "window": window, "results": results}


def per_layer(bench, cell, arch, dims, hooks, window, trace_dir, dev,
              results, mix, rehearse: bool) -> dict:
    """Every per-layer metric of ``BENCHMARK.json`` that applies to this
    cell, read by ``metrics/<name>.py``; a reader that finds nothing
    returns None and the metric is left out."""
    from bench import peaks as peaks_mod
    from bench import trace as tr
    devices, spans = tr.load(tr.find_xplane(trace_dir),
                             host_as_device=rehearse)
    seg = [s for s in spans if s[0] == "segment"]
    if not seg:
        raise RuntimeError("the trace holds no bench:segment span")
    t0, t1 = seg[0][1], seg[0][1] + seg[0][2]
    dev_events = {k: tr.clip(v, t0, t1) for k, v in devices.items()}
    used = {k: v for k, v in dev_events.items() if v}
    if not used:
        raise RuntimeError(f"no device operation in the traced segment; "
                           f"planes {sorted(devices)}")
    busy = np.mean([tr.busy_ns(v) for v in used.values()]) * 1e-9
    first = next(iter(used.values()))
    ctx = {
        "hooks": hooks, "window": window, "mix": mix,
        "params": arch.param_count(dims),
        "attn_width": arch.attn_width(dims),
        "peaks": peaks_mod.peaks("TPU v5 lite" if rehearse
                                 else dev.device_kind),
        "device_events": first, "host_spans": spans,
        "traced_s": (t1 - t0) * 1e-9, "busy_s": busy,
        "trace_wall": (window["trace_t0"], window["trace_t1"]),
        "results": results,
    }
    cell_name = cell["name"]
    e2e = e2e_names(bench, cell_name)
    values = {}
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell_name not in m["workloads"]:
                continue
        elif m["moves"] not in e2e:
            continue
        reader = load_reader(m["name"])
        v = reader(ctx)
        if v is not None:
            values[m["name"]] = (float(v), m["unit"])
    breakdown = {
        "device_ops": [[n, s] for n, s in tr.top_ops(first)],
        "idle_gaps": [[n, s] for n, s in tr.top_gaps(first, spans, t0, t1)],
    }
    return {"metrics": values, "busy_s": busy, "window_s": (t1 - t0) * 1e-9,
            "breakdown": breakdown,
            "planes": {k: len(v) for k, v in devices.items()}}


def e2e_names(bench: dict, cell_name: str) -> List[str]:
    return [m["name"] for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def load_reader(name: str):
    from bench import reference
    return reference.load_file(BENCH / "metrics" / f"{name}.py",
                               f"bench_metric_{name}").read


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------

def check_sample(results, gen, seed: int) -> list:
    """Finished requests to compare, drawn from the seed: the one with the
    longest prompt and answer, then others until ``CHECK_TOKENS`` served
    tokens or ``CHECK_REQUESTS`` requests."""
    docs = gen.prompts
    ok = [r for r in results if not r.truncated and r.answer]
    if not ok:
        return []
    size = {r.req_id: len(docs[r.context_key]) + len(r.answer) for r in ok}
    longest = max(ok, key=lambda r: (size[r.req_id], -r.req_id))
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 3])
    rest = [ok[i] for i in rng.permutation(len(ok))
            if ok[i].req_id != longest.req_id]
    pick, n_tok = [longest], len(longest.answer)
    for r in rest:
        if n_tok >= CHECK_TOKENS or len(pick) >= CHECK_REQUESTS:
            break
        pick.append(r)
        n_tok += len(r.answer)
    return [(r, docs[r.context_key]) for r in pick]


@contextlib.contextmanager
def reference_clock():
    """Wall seconds the reference took, in a one-element list."""
    box = [0.0]
    t0 = time.perf_counter()
    try:
        yield box
    finally:
        box[0] = time.perf_counter() - t0


def passes(checks: List[dict]) -> bool:
    """The verdict: every number compared within its limit."""
    return all(c["value"] <= c["limit"] for c in checks)


def check(sample, gen, arch, dims, seed, mix, out,
          gap_fn=None) -> List[dict]:
    """The numbers compared, each beside its limit. ``gap_fn`` gives a
    request's served-token gaps against ``arch``'s reference
    (``reference.served_gaps``, the program's tokens); ``control.py``
    passes ``reference.control_gaps`` to put the fp8 control's tokens in
    the program's place, which the benchmark's own runs never do."""
    from bench import reference
    gap_fn = gap_fn or reference.served_gaps
    limits = mix["limits"]
    checks = []
    if sample:
        flat = reference.make_flat(arch, dims, seed)
        gaps = []
        pad = gen.longest_sequence()
        with reference_clock() as rt:
            for r, ctx_tokens in sample:
                prompt = np.concatenate([ctx_tokens,
                                         gen.asked[r.req_id].question])
                gaps.append(gap_fn(arch, flat, dims, prompt, r.answer,
                                   pad_to=pad))
        del flat
        gap = reference.widest(gaps)
        served = int(sum(len(r.answer) for r, _ in sample))
        note = (f"{len(sample)} requests, {served} served tokens, "
                f"reference {rt[0]:.1f} s")
    else:
        gap, note = float("inf"), "no finished request"
    checks.append({"name": "logit_gap", "value": gap,
                   "limit": float(limits["logit_gap"]), "note": note})
    checks.append({"name": "failed", "value": float(out["failed"]),
                   "limit": 0.0, "note": f"of {out['attempted']} attempted"})
    return checks
