"""The served path's own profiler spans (``repro.runtime.spans``): they
land in a ``jax.profiler`` trace with their names, arguments and nesting,
on the clock of the device operations, and a traced run serves exactly
what an untraced one does."""
import dataclasses

import jax
import numpy as np
import pytest

from bench import program_trace, trace as tr
from repro.configs import get_config
from repro.core.compression import KIVICompression, NoCompression
from repro.core.compression.base import StoredKV
from repro.models import build_model
from repro.runtime.spans import PREFIX, mark, span
from repro.serving.baselines import build_engine
from repro.serving.chunking import split_kv, tail_kv
from repro.serving.runner import ModelRunner
from repro.serving.scheduler import ContinuousBatcher
from repro.serving.timemodel import A100, TimeModel
from repro.serving.workload import (
    Request, make_prefix_sharing_contexts, round_robin_requests,
)

FULL = "adaptcache-8b"
N_ACTIVE = 8_030_000_000


@pytest.fixture(scope="module")
def runner():
    cfg = get_config(FULL, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return ModelRunner(model, params, capacity=256)


def _traced(tmp_path, fn):
    """Run ``fn`` under a profiler session; (its result, program spans,
    device operations of the host taken for the device)."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    spans, ops = program_trace.load_program(tr.find_xplane(str(tmp_path)),
                                            host_as_device=True)
    return out, spans, [o for v in ops.values() for o in v]


def _inside(inner, outer) -> bool:
    return (outer[1] <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2])


def test_spans_and_marks_land_in_the_trace(tmp_path):
    def work():
        with span("outer", req_id=7, nbytes=123):
            with span("inner", kind="tick"):
                pass
            mark("point", req_id=7)
    _, spans, _ = _traced(tmp_path, work)
    by = {s[0]: s for s in spans}
    assert set(by) == {"outer", "inner", "point"}
    assert by["outer"][3] == {"req_id": 7, "nbytes": 123}
    assert by["inner"][3] == {"kind": "tick"}
    assert by["point"][3] == {"req_id": 7}
    assert _inside(by["inner"], by["outer"])
    assert _inside(by["point"], by["outer"])
    assert by["inner"][1] + by["inner"][2] <= by["point"][1]
    assert PREFIX == "adaptcache/"


def _batcher(runner, n_slots=2):
    tm = TimeModel(get_config(FULL), A100, N_ACTIVE)
    return ContinuousBatcher(runner.model, runner.params, tm,
                             n_slots=n_slots, capacity=runner.capacity)


def test_decode_tick_and_its_device_ops_share_one_clock(runner, tmp_path):
    b = _batcher(runner)
    rng = np.random.RandomState(3)
    tokens = rng.randint(0, runner.model.cfg.vocab_size, 48)
    kv = runner.prefill_entry(tokens)
    b.admit(0, Request(1, "c", np.array([5, 6]), 0.0, "qa", 3), kv, 48, 0.0)
    b.tick(0.0)                               # compiles the step

    def ticks():
        for k in range(3):
            b.tick(float(k))
    _, spans, ops = _traced(tmp_path, ticks)
    decode = [o for o in ops if "decode_step" in o[3] and o[2] > 0]
    ticks_ = [s for s in spans if s[0] == "decode_tick"]
    assert len(ticks_) == 3 and decode
    for _, s, d, args in ticks_:
        assert args["lanes"] == 1 and args["positions"] >= 48
        mine = [o for o in decode if s <= o[1] <= s + d]
        assert mine, "no decode_step operation starts inside its tick"
    # every decode operation belongs to one of the ticks
    assert all(any(s <= o[1] <= s + d for _, s, d, _ in ticks_)
               for o in decode)


def test_lane_write_counts_the_bytes_it_copies(runner, tmp_path):
    b = _batcher(runner)
    rng = np.random.RandomState(4)
    tokens = rng.randint(0, runner.model.cfg.vocab_size, 40)
    kv = runner.prefill_entry(tokens)
    req = Request(9, "c", np.array([1, 2, 3]), 0.0, "qa", 2)
    _, spans, _ = _traced(tmp_path, lambda: b.admit(1, req, kv, 40, 0.0))
    names = [s[0] for s in spans]
    assert names == ["admit", "lane_write", "admitted"]
    admit, write, admitted = spans
    assert admit[3] == {"req_id": 9} and admitted[3] == {"req_id": 9}
    assert _inside(write, admit) and admitted[1] >= admit[1] + admit[2]
    cache_dtype = jax.tree.leaves(b.cache)[0].dtype
    written = sum(np.asarray(kv[n], cache_dtype).nbytes for n in ("k", "v"))
    assert written > 0
    assert write[3] == {"h2d_bytes": written, "pages": 0, "host_pieces": 1}

    # fetched pages cross as stored (KIVI codes, scales and zeros; lossless
    # float32 rows), never their positions; a prefilled tail as host rows
    # cast to the cache dtype
    pages, _ = split_kv(kv, 16)
    run = [StoredKV(KIVICompression().compress(pages[0], 0.0, bits=4),
                    KIVICompression()),
           StoredKV(NoCompression().compress(pages[1], 1.0),
                    NoCompression()),
           tail_kv(kv, 32)]
    _, spans, _ = _traced(tmp_path / "pages",
                          lambda: b.admit(0, req, run, 40, 0.0))
    write = next(s for s in spans if s[0] == "lane_write")
    stored = sum(a.nbytes for p in run[:2] for n, a in p.entry.arrays.items()
                 if n != "positions")
    tail = sum(np.asarray(run[2][n], cache_dtype).nbytes for n in ("k", "v"))
    assert write[3] == {"h2d_bytes": stored + tail, "pages": 2,
                        "host_pieces": 1}


def _served(runner, tmp, reqs, contexts):
    rig = build_engine(runner, contexts, get_config(FULL), N_ACTIVE,
                       policy=("kivi", 0.16), dram_entries=40.0,
                       ssd_entries=100.0, n_lanes=2, ssd_root=str(tmp),
                       page_tokens=64, chunk_tokens=32)
    res = rig.engine.process(reqs, skip_quality=True)
    return ([dataclasses.astuple(r) for r in res], rig.engine.last_trace)


def test_traced_process_is_bit_identical(runner, tmp_path):
    rng = np.random.RandomState(21)
    contexts = make_prefix_sharing_contexts(
        rng, runner.model.cfg.vocab_size, n_docs=2, n_variants=2,
        prefix_len=128, suffix_len=64, n_probes=2)
    reqs = round_robin_requests(contexts, 8, 0.05, max_new_tokens=4)
    plain = _served(runner, tmp_path / "plain", reqs, contexts)
    traced, spans, _ = _traced(
        tmp_path / "trace",
        lambda: _served(runner, tmp_path / "traced", reqs, contexts))
    assert traced == plain
    # the run went through every layer that carries a span
    names = {s[0] for s in spans}
    assert {"event", "arrival", "dispatch", "prefix_match", "page_fetch",
            "tier_get", "decompress", "kivi_dequantize",
            "insert", "tier_put", "kivi_quantize", "prefill", "admit",
            "lane_write", "admitted", "decode_tick",
            "first_token"} <= names
    firsts = [s[3]["req_id"] for s in spans if s[0] == "first_token"]
    assert sorted(firsts) == sorted(r.req_id for r in reqs)


def test_lane_wait_marks_the_requests_no_lane_took(runner, tmp_path):
    """Eight requests 1 ms apart on two lanes: a request whose arrival
    finds no free lane is marked ``lane_wait`` in its arrival event, and
    dispatched in a later event; one that found a lane is dispatched in
    its arrival event and has no mark."""
    rng = np.random.RandomState(5)
    contexts = make_prefix_sharing_contexts(
        rng, runner.model.cfg.vocab_size, n_docs=2, n_variants=2,
        prefix_len=128, suffix_len=64, n_probes=2)
    reqs = round_robin_requests(contexts, 8, 0.001, max_new_tokens=4)
    _, spans, _ = _traced(
        tmp_path / "trace",
        lambda: _served(runner, tmp_path / "run", reqs, contexts))
    events = [s for s in spans if s[0] == "event"]

    def first(name, rid):
        return next(s for s in spans if s[0] == name
                    and s[3].get("req_id") == rid)

    waited = set()
    for r in reqs:
        arrival, dispatch = first("arrival", r.req_id), \
            first("dispatch", r.req_id)
        ev = next(e for e in events if _inside(arrival, e))
        marks = [s for s in spans if s[0] == "lane_wait"
                 and s[3]["req_id"] == r.req_id]
        assert len(marks) == (0 if _inside(dispatch, ev) else 1)
        if marks:
            waited.add(r.req_id)
            assert _inside(marks[0], ev) and marks[0][1] < dispatch[1]
    assert 0 < len(waited) < len(reqs)
