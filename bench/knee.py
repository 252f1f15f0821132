"""Find a cell's simulated knee: the highest arrival rate at which the
engine's simulated schedule keeps up.

    JAX_PLATFORMS=cpu python bench/knee.py --workload <cell> [--rates 4,8,16]

The engine prices every event with its ``TimeModel`` (TPU v5e constants
at the cell's full widths), so the simulated schedule does not depend on
the device or on the weights. This sweep therefore serves the cell's
traffic through the engine on the CPU with a tiny model behind the full
configuration's time model, at the mix's real prompt lengths, lanes and
tiers, and reports per rate the mean simulated wait for a lane
(``queue_s``) of the first and the last segment against the mean
simulated time a request holds its lane (arrival to last token, less the
wait). A rate is sustained while the last segment's mean wait stays under
5% of that time: past the knee every lane is busy and the wait grows with
each segment. Lanes over holding time bounds the knee from above. A cell's
``rate_hz`` is set to about four fifths of the knee.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def sweep(workload: str, rates, segments: int, seed: int = 1,
          segment_requests: int = 0):
    import tempfile
    import numpy as np
    from bench import harness, reference, traffic
    from repro.models import build_model
    from repro.serving.runner import ModelRunner
    _, cell, cfg_json, mix = harness.load_cell(workload)
    arch, full = harness.architecture(cfg_json, False)
    _, small = harness.architecture(cfg_json, True)
    full_cfg = arch.program_config(cell["config"], full)
    n_active = arch.param_count(full)
    model = build_model(arch.program_config(cell["config"], small))
    runner = ModelRunner(model, arch.program_layout(
        reference.make_flat(arch, small, seed)),
        capacity=mix["engine"]["capacity"])
    rows = []
    with tempfile.TemporaryDirectory() as spool:
        for rate in rates:
            m = harness.merge(mix, {"rate_hz": rate})
            if segment_requests:
                m["segment_requests"] = segment_requests
            gen = traffic.Traffic(m, seed, small["vocab"])
            rig = None
            if gen.kind == "documents":
                rig = harness.make_rig(runner, gen.docs, m, full_cfg,
                                       n_active, f"{spool}/{rate}")
                rig.engine.process(harness.to_requests(
                    gen.fill_requests(1), m["task"]), skip_quality=True)
            first_q, last_q, service = None, None, []
            for k in range(segments):
                if gen.kind == "unique":       # a fresh engine per segment
                    docs, reqs = gen.segment(k, 0.0)
                    rig = harness.make_rig(runner, docs, m, full_cfg,
                                           n_active, f"{spool}/{rate}-{k}")
                else:
                    docs, reqs = gen.segment(k, rig.clock.now + 1.0)
                res = rig.engine.process(harness.to_requests(reqs, m["task"]),
                                         skip_quality=True)
                q = float(np.mean([r.queue_s for r in res]))
                first_q = q if first_q is None else first_q
                last_q = q
                service += [r.finish_s - r.arrival_s - r.queue_s
                            for r in res]
            svc = float(np.mean(service))
            ok = last_q <= 0.05 * svc
            rows.append((rate, svc, first_q, last_q, ok))
            lanes = m["engine"]["lanes"]
            print(f"rate {rate:8.3f} req/s: mean lane occupancy {svc:.4f} s "
                  f"(lanes / occupancy = {lanes / svc:.2f} req/s), mean "
                  f"lane wait first {first_q:.4f} s last {last_q:.4f} s "
                  f"-> {'sustained' if ok else 'not sustained'}", flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="2,4,8,16,24,32,48")
    ap.add_argument("--segments", type=int, default=4)
    ap.add_argument("--segment-requests", type=int, default=0,
                    help="requests per segment (default: the mix's); a "
                         "mix whose segments hold fewer requests than it "
                         "has lanes never queues, so sweep such a cell "
                         "with longer segments")
    args = ap.parse_args(argv)
    rows = sweep(args.workload, [float(r) for r in args.rates.split(",")],
                 args.segments, segment_requests=args.segment_requests)
    ok = [r for r, _, _, _, good in rows if good]
    print(f"knee: {max(ok) if ok else 'below the lowest rate'} req/s "
          f"(simulated)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
