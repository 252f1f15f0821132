"""The readers of the program's own spans on synthetic events: each
reads only the traced segment, and each finds nothing, without raising,
in a trace of a program that has no spans. A traced rehearsal finds the
spans in the run's own trace."""
import json

import pytest

from bench import harness, program_trace, run

NEW = ["first_token_wait_ms", "fetch_idle_share", "admit_h2d_mb",
       "decode_device_ms"]

T0, T1 = 100.0, 1100.0          # the segment, in ns
DEV = [("fusion.1", 150.0, 50.0),           # busy 150-200
       ("fusion.2", 500.0, 100.0),          # busy 500-600
       ("fusion.3", 1000.0, 200.0)]         # busy 1000-1200, clipped
OPS = [("while.2", 510.0, 30.0, "jit_decode_step"),
       ("copy.2", 540.0, 40.0, "jit_decode_step/decode_step/copy"),
       ("fusion.2", 500.0, 5.0, "jit_scatter"),
       ("while.2", 1010.0, 60.0, "jit_decode_step"),
       ("while.2", 1150.0, 20.0, "jit_decode_step"),   # after its tick
       ("while.2", 20.0, 60.0, "jit_decode_step")]     # before the segment
SPANS = [
    # outside the segment: none of these count
    ("admitted", 50.0, 0.0, {"req_id": 1}),
    ("admit", 40.0, 5.0, {"req_id": 1}),
    ("lane_write", 41.0, 3.0, {"h2d_bytes": 9_000_000}),
    ("prefix_match", 0.0, 400.0, {}),
    ("decode_tick", 10.0, 80.0, {"lanes": 1, "positions": 5}),
    # inside
    ("first_token", 120.0, 0.0, {"req_id": 1}),
    ("admit", 200.0, 30.0, {"req_id": 2}),
    ("lane_write", 205.0, 20.0, {"h2d_bytes": 3_000_000}),
    ("admitted", 230.0, 0.0, {"req_id": 2}),
    ("admit", 300.0, 30.0, {"req_id": 3}),
    ("lane_write", 305.0, 20.0, {"h2d_bytes": 5_000_000}),
    ("admitted", 330.0, 0.0, {"req_id": 3}),
    ("decode_tick", 500.0, 100.0, {"lanes": 2, "positions": 9}),
    ("first_token", 590.0, 0.0, {"req_id": 2}),
    ("prefix_match", 650.0, 250.0, {}),
    ("decode_tick", 1000.0, 120.0, {"lanes": 1, "positions": 4}),
    ("first_token", 1110.0, 0.0, {"req_id": 3}),    # after the segment
]


def ctx(spans=SPANS, ops=OPS):
    from bench import trace
    return {"device_events": trace.clip(DEV, T0, T1),
            "program": {"spans": spans, "ops": ops, "t0": T0, "t1": T1}}


def read(name, c):
    return harness.load_reader(name)(c)


def test_first_token_wait_counts_requests_admitted_in_the_segment():
    # request 1 was admitted before the segment, request 3's first token
    # came after it: only request 2 counts, 590 - 230 ns
    assert read("first_token_wait_ms", ctx()) == pytest.approx(360e-6)


def test_fetch_idle_share_clips_matches_to_the_segment():
    # the early match is clipped to 100-400: idle there 100-150, 200-400;
    # the late one 650-900 is idle throughout
    idle = 50 + 200 + 250
    assert read("fetch_idle_share", ctx()) == pytest.approx(
        100.0 * idle / (T1 - T0))


def test_admit_h2d_is_the_mean_over_admissions_in_the_segment():
    assert read("admit_h2d_mb", ctx()) == pytest.approx(4.0)


def test_decode_device_time_per_tick():
    # tick at 500: 510-540 and 540-580 (the scatter is not the step's);
    # tick at 1000-1120: 1010-1070; the 1150 op starts after it ends
    assert read("decode_device_ms", ctx()) == pytest.approx(
        1e-6 * (70 + 60) / 2)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_reads_nothing(name):
    assert read(name, ctx(spans=[])) is None


@pytest.mark.parametrize("name", NEW)
def test_no_trace_outside_a_traced_run(name):
    # neither handed over nor found up the stack: nothing, and no raise
    c = ctx()
    del c["program"]
    c["host_spans"] = [("segment", T0, T1 - T0)]
    assert program_trace.program(c) is None
    assert read(name, c) is None


def test_program_spans_match_the_hooks(capsys, monkeypatch):
    """In a traced rehearsal the readers find the program's spans in the
    run's trace, and the spans of the segment count the calls the
    harness's hooks count there."""
    seen = {}
    load = harness.load_reader

    def spy(name):
        reader = load(name)

        def read(c):
            seen.setdefault("ctx", c)
            return reader(c)
        return read

    monkeypatch.setattr(harness, "load_reader", spy)
    rc = run.main(["--workload", "qwen3-1.7b.doc_reuse", "--seed",
                   str(2 ** 31 + 11), "--seconds", "1", "--trace", "1",
                   "--rehearse"])
    assert rc == 1
    out, err = capsys.readouterr()
    assert "idle gaps by program span: " in err
    line = json.loads(out.strip().splitlines()[-1])
    assert set(NEW) <= set(line["metrics"])
    c = seen["ctx"]
    p, hooks = c["program"], c["hooks"]
    a, b = c["trace_wall"]
    mine = [s[0] for s in p["spans"] if p["t0"] <= s[1] <= p["t1"]]
    kivi = [k[1] for k in hooks.kivi if a <= k[0] <= b]
    for hook, name in [("tick", "decode_tick"), ("admit", "admit"),
                       ("fetch", "page_fetch"), ("match", "prefix_match"),
                       ("prefill", "prefill")]:
        assert mine.count(name) == len(hooks.durations(hook, a, b)), name
    assert mine.count("kivi_quantize") == kivi.count("q")
    assert mine.count("kivi_dequantize") == kivi.count("d")
    assert mine.count("decode_tick") > 0 and mine.count("page_fetch") > 0
    assert mine.count("kivi_dequantize") > 0
    assert any("decode_step" in o[3] for o in p["ops"])
