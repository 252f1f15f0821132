"""Readings that set a cell's limit, and the proof that the limit fails
the fp8 control: the program's widest served-logit gap and the control's,
on several seeds in one process, both judged by the harness's own check.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 8]

Each seed runs the cell as the benchmark does (set-up, a short window at
the cell's own load, the check), then puts the control's tokens in the
program's place: at the same prompts and served tokens, the token that
the fp8 reference puts first, scored against the float32 reference by the
same ``harness.check`` and judged by the same ``harness.passes``. One
line per seed; a last JSON line with all readings. Exits 1 where the
control comes out correct or the program does not. The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from bench import harness
    rows, sound = [], True
    for s in args.seeds.split(","):
        t = time.perf_counter()
        out = harness.run(args.workload, int(s), args.seconds, False, t,
                          rehearse=args.rehearse, control=True)
        prog, ctrl = out["checks"], out["control_checks"]
        row = {"seed": int(s),
               "program_correct": harness.passes(prog),
               "control_correct": harness.passes(ctrl),
               "program": {c["name"]: c["value"] for c in prog},
               "control": {c["name"]: c["value"] for c in ctrl},
               "limits": {c["name"]: c["limit"] for c in prog},
               "note": prog[0]["note"]}
        sound &= row["program_correct"] and not row["control_correct"]
        rows.append(row)
        print(f"seed {s}: program {row['program']} correct "
              f"{row['program_correct']}; control {row['control']} correct "
              f"{row['control_correct']}; limits {row['limits']} "
              f"({row['note']}); {time.perf_counter() - t:.1f} s",
              flush=True)
    print(json.dumps({"workload": args.workload, "sound": sound,
                      "readings": rows}))
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
