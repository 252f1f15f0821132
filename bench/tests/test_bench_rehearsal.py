"""CPU rehearsal of each traffic mix at a tiny size: the whole run goes
through, the last line carries every key, and no run reports success off
the TPU."""
import json
import sys

import pytest

from bench import harness, run

CELLS = [w["name"] for w in
         harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]]
# every mix under traffic/ is rehearsed, also one no cell uses yet: it
# is run as a cell of the first configuration
MIXES = sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json"))


@pytest.fixture
def every_mix(monkeypatch):
    """BENCHMARK.json with a cell for each mix that has none."""
    load = harness.load_json

    def with_mixes(path):
        out = load(path)
        if path.name == "BENCHMARK.json":
            used = {w["traffic"] for w in out["workloads"]}
            conf = out["configs"][0]["name"]
            out["workloads"] += [
                {"name": f"{conf}.{m}", "config": conf, "traffic": m,
                 "chips": 1, "why": "rehearsal"}
                for m in MIXES if m not in used]
        return out

    monkeypatch.setattr(harness, "load_json", with_mixes)


@pytest.mark.parametrize("mix", MIXES)
def test_rehearsal_line_has_every_key(mix, every_mix, capsys):
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = next(w["name"] for w in bench["workloads"] if w["traffic"] == mix)
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 7),
                   "--seconds", "1", "--trace", "0", "--rehearse"])
    assert rc == 1                       # a rehearsal never succeeds
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] > 0
    bench, c, _, _ = harness.load_cell(cell)
    assert set(line["metrics"]) == set(harness.e2e_names(bench, cell))
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    # the reference agrees with the program: every check holds
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name


def test_traced_rehearsal_reads_per_layer_metrics(capsys):
    cell = CELLS[0]
    rc = run.main(["--workload", cell, "--seed", "11", "--seconds", "1",
                   "--trace", "1", "--rehearse"])
    assert rc == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["busy_s"] > 0
    # the host-side readers; the kernel's roofline needs the chip's trace
    assert {"tick_ms", "admit_ms", "fetch_ms", "dram_hit_share", "mfu",
            "device_idle"} <= set(line["metrics"])
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_no_result_off_the_tpu(capsys, monkeypatch):
    monkeypatch.setattr(sys, "argv", ["run.py"])
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""
