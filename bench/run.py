"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``; its last key,
``checks``, gives each number compared with the plain reference beside its
limit, and the same lines end standard error.

Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits 2. A run that prints its result exits 0, whatever
``correct`` says. ``--rehearse`` (for the CPU tests) runs the whole cell
at a tiny size on whatever JAX finds, prints the result line with
``"correct": false`` and exits 1: a rehearsal never reports success.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def result_line(out: dict, dev, trace: bool, rehearse: bool) -> dict:
    from bench import harness
    ok = harness.passes(out["checks"])
    metrics = out["per_layer"]["metrics"] if trace else out["e2e"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": out["cell"]["chips"],
              "memory_peak_bytes": out["peak"],
              "memory_held_bytes": out["held"]}
    line = {"correct": bool(ok and dev.platform == "tpu" and not rehearse),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: {"value": v, "unit": u}
                        for k, (v, u) in metrics.items()},
            "device": device}
    if trace:
        device["busy_s"] = out["per_layer"]["busy_s"]
        device["window_s"] = out["per_layer"]["window_s"]
        line["breakdown"] = out["per_layer"]["breakdown"]
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in out["checks"]}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    import jax
    from bench import harness
    _, cell, _, _ = harness.load_cell(args.workload)
    devs = jax.devices()
    if not args.rehearse:
        if devs[0].platform != "tpu":
            print(f"bench: no TPU (platform {devs[0].platform}); no result",
                  file=sys.stderr)
            return 2
        if len(devs) < cell["chips"]:
            print(f"bench: {args.workload} needs {cell['chips']} chips, "
                  f"found {len(devs)}; no result", file=sys.stderr)
            return 2
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START, rehearse=args.rehearse)
    line = result_line(out, devs[0], bool(args.trace), args.rehearse)
    if args.trace:
        print(f"trace planes: {out['per_layer']['planes']}", file=sys.stderr)
    for c in out["checks"]:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"({c['note']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 1 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
