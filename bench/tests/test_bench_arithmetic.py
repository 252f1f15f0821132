"""End-to-end arithmetic, KIVI byte counts, the peaks table, the traffic
generator's fixed work, and the benchmark file's own rules."""
import json
import math
import pathlib
import re

import numpy as np
import pytest

from bench import kivi_cost, peaks, stats, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_percentiles_are_over_all_requests():
    ttft = [10.0, 20.0, 30.0, 40.0, 1000.0]
    assert stats.percentile(ttft, 50) == 30.0
    assert stats.percentile(ttft, 90) == pytest.approx(40 + 0.6 * 960)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_all_work_over_all_time():
    assert stats.rate(300, 12.0) == 25.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_kivi_bytes():
    # qwen3-1.7b K page: 28 layers x 64 tokens by 8 heads x 128
    t, f = 28 * 64, 1024
    b, ops = kivi_cost.quantize_cost(t, f, 4, 64)
    assert b == 4 * t * f + t * f // 2 + 8 * t * f // 64
    assert ops == kivi_cost.QUANT_OPS_PER_ELEM * t * f
    b2, _ = kivi_cost.dequantize_cost(t, f, 4, 64)
    assert b2 == b
    assert kivi_cost.dequantize_cost(t, f, 2, 64)[0] < b2
    least = kivi_cost.least_time_s(b, ops, 197e12, 819e9)
    assert least == pytest.approx(b / 819e9)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bps"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("mix", ["doc_reuse", "short_unique"])
def test_every_seed_gets_the_same_work(mix):
    m = _mix(mix)

    def work(seed):
        g = traffic.Traffic(m, seed, 1000)
        out = []
        for k in range(2):
            docs, reqs = g.segment(k, 0.0)
            lens = {d.key: len(d.tokens) for d in g.docs + docs}
            out.append(([lens[r.doc_key] for r in reqs],
                        [r.doc_key for r in reqs],
                        [len(r.question) for r in reqs],
                        [r.answer_tokens for r in reqs],
                        [round(r.arrival_s, 9) for r in reqs]))
        return out, [d.tokens.tolist() for d in g.docs[:1]]

    (w1, t1), (w2, t2) = work(1), work(2 ** 31 + 11)
    assert w1 == w2
    assert t1 != t2 or not t1


def test_document_lengths_are_fixed_and_page_aligned():
    m = _mix("doc_reuse")
    g = traffic.Traffic(m, 3, 1000)
    lens = [len(d.tokens) for d in g.docs]
    assert len(lens) == m["documents"]["count"]
    assert all(n % m["engine"]["page_tokens"] == 0 for n in lens)
    assert min(lens) >= 1024 and max(lens) <= 3072
    toks = np.concatenate([d.tokens for d in g.docs])
    assert toks.min() >= 0 and toks.max() < 1000


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_keeps_its_rules():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for w in b["workloads"]:
        assert len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for c in b["configs"]:
        assert (ROOT / c["file"]).exists()
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert not math.isnan(b["run_seconds"])
