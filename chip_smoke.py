"""On-chip smoke run of the served path: qwen3-1.7b at its published widths
(28 layers, d_model 2048, vocab 151936, bf16, weights drawn from a seed)
through ``repro.launch.serve`` on one TPU chip.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --four-chips   # 4 one-chip replicas vs 1 replica
    JAX_PLATFORMS=cpu python chip_smoke.py --arch qwen3-1.7b-smoke
                                        # CPU rehearsal: every phase runs,
                                        # the final device check fails

One chip runs these phases in one process:

  kivi_kernel     the compiled Pallas KIVI quantize->dequantize against
                  kernels/kivi/ref.py, K-style and V-style, bits 8/4/2
  fetch_logits    decode logits after a lossless ("none") insert and
                  fetch through the cache tiers against the full forward
                  pass's last-position logits
  serve_kivi      a seeded workload served with --policy kivi:<rate>, so
                  the compiled KIVI kernels run on every insert and fetch
  serve_adaptive  the same workload with --policy adaptive --paged
                  --chunk-tokens

``--four-chips`` runs only the replica phase: four one-chip replicas
behind the router (--split-dram --affinity), lossless policy, against one
replica on one device serving the same requests, plus a fixed decode on
every device whose logits must agree.

Each phase prints its host wall time, the XLA compile time spent in it,
persistent-cache hits, the requests it served and the device's
``peak_bytes_in_use``. TTFT figures come from the engine's TimeModel,
which prices A100 constants; they are printed under that label and are
not chip measurements. The last line of stdout is one JSON object; it
says ``"ok": true`` only on a TPU with every check passed. Any failed
check raises, and the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.compression.kivi import BITS_LADDER  # noqa: E402
from repro.kernels.kivi import ops as kivi_ops  # noqa: E402
from repro.kernels.kivi import ref as kivi_ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.serving.baselines import build_engine  # noqa: E402
from repro.serving.runner import kvdata_to_cache  # noqa: E402
from repro.serving.workload import make_contexts  # noqa: E402

# Decode after a lossless fetch recomputes the last position from cached
# K/V, while the forward pass computes it with the whole sequence in one
# program. In bf16 the two orders round differently in every layer, so
# the logits agree to a few bf16 ulps (2^-8 each) of the largest logit,
# not bit for bit. At qwen3-1.7b widths on the CPU backend the gap is
# 8e-3 at 6 layers and 1.2e-2 at 14, growing about as sqrt(depth), so
# ~1.7e-2 is expected at 28; the limit leaves a factor of 3 for the
# TPU's different fusion. float32 models agree to ~1e-6.
FETCH_LOGIT_RTOL = {"bfloat16": 5e-2, "float32": 1e-4}
# Every device runs the same compiled program on the same inputs, so
# replica logits should agree to one bf16 ulp of the largest logit.
REPLICA_LOGIT_RTOL = 2.0 ** -7

# 10 requests over 3 contexts (748, 572 and 251 tokens at seed 0)
WORKLOAD = ["--contexts-per-task", "1", "--rate", "2", "--duration", "6",
            "--lanes", "2"]
KIVI_POLICY = "kivi:0.16"          # 4-bit KIVI on float32 entries
CHUNK_TOKENS = "64"
# a burst: 15 requests within 4 us, so every replica gets work
FOUR_CHIP_WORKLOAD = ["--contexts-per-task", "2", "--rate", "4000000",
                      "--duration", "0.0000035", "--lanes", "2",
                      "--split-dram", "--affinity", "--policy", "none"]


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class CompileMeter:
    """Sums XLA backend compile time and persistent-cache hits from
    JAX's monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(device) -> str:
    stats = device.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def run_phase(name: str, meter: CompileMeter, fn):
    """Run one phase and print its host wall time, compile time, cache
    hits, requests served and peak device bytes."""
    c0, h0 = meter.compile_s, meter.cache_hits
    t0 = time.perf_counter()
    served = fn()
    wall = time.perf_counter() - t0
    print(f"[phase {name}] host_wall_s={wall:.3f} "
          f"compile_s={meter.compile_s - c0:.3f} "
          f"persistent_cache_hits={meter.cache_hits - h0} "
          f"requests_served={served} "
          f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}", flush=True)


def serve_args(arch: str, seed: int, extra) -> argparse.Namespace:
    return serve.parse_args(["--arch", arch, "--full-width", "--seed",
                             str(seed), *extra])


def serve_pass(runner, args, label: str):
    """Serve the seeded workload; every request must complete."""
    rig, requests, results, summary = serve.serve(args, runner)
    check(len(requests) > 0, f"{label}: empty workload")
    check(sorted(r.req_id for r in results)
          == sorted(r.req_id for r in requests),
          f"{label}: {len(results)} of {len(requests)} requests completed")
    check(not any(r.truncated for r in results),
          f"{label}: a lane ran out of cache capacity")
    check(all(len(r.answer) == req.max_new_tokens
              for r, req in zip(sorted(results, key=lambda r: r.req_id),
                                sorted(requests, key=lambda r: r.req_id))),
          f"{label}: an answer is shorter than max_new_tokens")
    print(f"  {label}: {len(results)} requests, hit_rate "
          f"{summary['hit_rate']:.3f}, quality_mean "
          f"{summary['quality_mean']:.3f}")
    print(f"  {label}: simulated by TimeModel on A100 constants, not "
          f"measured: ttft_mean_s {summary['ttft_mean_s']:.6f}")
    return rig, requests, results


def phase_kivi_kernel(seed: int) -> int:
    """Pallas KIVI quantize->dequantize against the jnp reference at
    qwen3-1.7b K/V widths (28 layers x 256 tokens, 8 KV heads x 128)."""
    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(28 * 256, 1024).astype(np.float32))
    for bits in BITS_LADDER:
        for axis in (0, 1):
            hlo = kivi_ops.quantize.lower(x, bits, 64, axis).as_text()
            if on_tpu:
                check("tpu_custom_call" in hlo,
                      "kivi quantize did not lower to the Pallas kernel")
            got = kivi_ops.quantize(x, bits, 64, axis)
            want = kivi_ref.quantize_ref(x, bits, 64, axis)
            d_got = np.asarray(kivi_ops.dequantize(got))
            d_want = np.asarray(kivi_ref.dequantize_ref(want))
            # a code may round the other way at a step boundary: allow
            # one quantization step of the element's group
            step = np.asarray(want.scale)
            step = (np.repeat(step, 64, axis=0) if axis == 0
                    else np.repeat(step, 64, axis=1))
            err = np.abs(d_got - d_want)
            same = float(np.mean(np.asarray(got.packed)
                                 == np.asarray(want.packed)))
            print(f"  kivi bits={bits} axis={axis}: max_err "
                  f"{err.max():.3e}, packed bytes equal {same:.5f}, "
                  f"kernel={'pallas' if 'tpu_custom_call' in hlo else 'ref'}")
            check(bool(np.all(err <= step * (1 + 1e-5) + 1e-6)),
                  f"kivi bits={bits} axis={axis}: dequantized values "
                  f"differ from the reference by more than one step")
            check(same > 0.999, f"kivi bits={bits} axis={axis}: "
                  f"{1 - same:.4%} of packed bytes differ from the ref")
    return 0


def phase_fetch_logits(runner, args) -> int:
    """Decode after a lossless insert+fetch through the cache tiers
    against the forward pass over context + question token."""
    cfg = runner.model.cfg
    ctx = make_contexts(np.random.RandomState(args.seed), cfg.vocab_size,
                        1, n_probes=1, tasks=("qa",))[0]
    q = int(ctx.probes[0][0])
    rig = build_engine(runner, [ctx], cfg,
                       runner.model.active_param_count(),
                       policy=("none", 1.0))
    rig.controller.insert(ctx.key, runner.prefill_entry(ctx.tokens), "qa")
    fetched = rig.controller.fetch(ctx.key)
    check(fetched is not None and fetched.method == "none",
          "lossless fetch missed")
    t = len(ctx.tokens)
    cache, n_kept = kvdata_to_cache(fetched.kv, cfg, runner.model,
                                    runner.capacity)
    check(n_kept == t, "fetched entry lost tokens")
    logits, _ = runner._decode(runner.params, cache, jnp.int32(t),
                               jnp.asarray([[q]], jnp.int32), jnp.int32(t))
    dec = np.asarray(logits[0, -1], np.float32)
    tokens = jnp.asarray(np.append(ctx.tokens, q), jnp.int32)[None]
    full = jax.jit(runner.model.forward)(runner.params, {"tokens": tokens})
    ref = np.asarray(full[0, -1], np.float32)
    rel = float(np.abs(dec - ref).max() / np.abs(ref).max())
    rtol = FETCH_LOGIT_RTOL[cfg.dtype]
    print(f"  fetch_logits: {t} context tokens, max|dlogit|/max|logit| "
          f"{rel:.3e} (limit {rtol:g}), argmax decode {int(dec.argmax())} "
          f"forward {int(ref.argmax())}")
    check(rel <= rtol, f"decode-after-fetch logits differ from the "
          f"forward pass: {rel:.3e} > {rtol:g}")
    return 0


def phase_replicas(runner, args, n_dev: int) -> int:
    """Four one-chip replicas vs one replica on the same requests, and a
    fixed decode on every device."""
    four = serve_args(args.arch, args.seed,
                      FOUR_CHIP_WORKLOAD + ["--replicas", str(n_dev)])
    one = serve_args(args.arch, args.seed,
                     FOUR_CHIP_WORKLOAD + ["--replicas", "1"])
    _, requests, res4 = serve_pass(runner, four, f"{n_dev}_replicas")
    counts = [sum(r.replica == i for r in res4) for i in range(n_dev)]
    print(f"  requests per device: "
          + ", ".join(f"{jax.devices()[i]}: {c}"
                      for i, c in enumerate(counts)))
    check(all(c > 0 for c in counts), "a device served no request")
    _, _, res1 = serve_pass(runner, one, "1_replica")
    ans4 = {r.req_id: r.answer for r in res4}
    same = sum(ans4[r.req_id] == r.answer for r in res1)
    print(f"  answers equal between {n_dev} replicas and 1 replica: "
          f"{same}/{len(res1)}")

    cfg = runner.model.cfg
    ctx = make_contexts(np.random.RandomState(args.seed), cfg.vocab_size,
                        1, n_probes=1, tasks=("qa",))[0]
    kv = runner.prefill_entry(ctx.tokens)
    t = len(ctx.tokens)
    logits = []
    for dev in jax.devices()[:n_dev]:
        cache, _ = kvdata_to_cache(kv, cfg, runner.model, runner.capacity)
        out, _ = runner._decode(
            jax.device_put(runner.params, dev), jax.device_put(cache, dev),
            jnp.int32(t), jnp.asarray([[int(ctx.probes[0][0])]], jnp.int32),
            jnp.int32(t))
        check(out.devices() == {dev}, f"decode did not run on {dev}")
        logits.append(np.asarray(out[0, -1], np.float32))
    scale = np.abs(logits[0]).max()
    for dev, lg in zip(jax.devices()[1:n_dev], logits[1:]):
        rel = float(np.abs(lg - logits[0]).max() / scale)
        print(f"  fixed decode {dev} vs {jax.devices()[0]}: "
              f"max|dlogit|/max|logit| {rel:.3e}")
        check(rel <= REPLICA_LOGIT_RTOL,
              f"decode logits on {dev} disagree with device 0")
    return len(res4) + len(res1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica phase")
    args = ap.parse_args(argv)

    dev0 = jax.devices()[0]
    on_tpu = dev0.platform == "tpu"
    if not on_tpu and not args.arch.endswith("-smoke"):
        print(f"chip_smoke: no TPU (platform {dev0.platform}); full-width "
              f"serving needs the chip. Rehearse with --arch "
              f"{args.arch}-smoke.", file=sys.stderr)
        return 2
    n_dev = 4 if args.four_chips else 1
    if len(jax.devices()) < n_dev:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    print(f"device: {dev0.platform} {dev0.device_kind} x "
          f"{len(jax.devices())}; compile cache "
          f"{serve.enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    kivi = serve_args(args.arch, args.seed,
                      WORKLOAD + ["--policy", KIVI_POLICY])
    adaptive = serve_args(args.arch, args.seed,
                          WORKLOAD + ["--policy", "adaptive", "--paged",
                                      "--chunk-tokens", CHUNK_TOKENS])
    holder = {}

    def setup() -> int:
        holder["runner"] = serve.load_runner(kivi)
        jax.block_until_ready(holder["runner"].params)
        return 0

    # SSD tier spool files land in a directory removed on exit
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as spool:
        tempfile.tempdir = spool
        run_phase("setup", meter, setup)
        runner = holder["runner"]
        if args.four_chips:
            run_phase("replicas", meter,
                      lambda: phase_replicas(runner, kivi, n_dev))
        else:
            run_phase("kivi_kernel", meter,
                      lambda: phase_kivi_kernel(args.seed))
            run_phase("fetch_logits", meter,
                      lambda: phase_fetch_logits(runner, kivi))
            run_phase("serve_kivi", meter,
                      lambda: len(serve_pass(runner, kivi,
                                             "serve_kivi")[2]))
            run_phase("serve_adaptive", meter,
                      lambda: len(serve_pass(runner, adaptive,
                                             "serve_adaptive")[2]))
        tempfile.tempdir = None

    if not on_tpu:
        print(f"chip_smoke: every phase ran on {dev0.platform}, but this "
              f"is not a TPU: no result.", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
