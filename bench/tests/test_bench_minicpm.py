"""MiniCPM-2B as a benchmark configuration: its file loads with its lanes,
its reference (``arch/minicpm.py``) computes the program's function with
the three muP scalings and not without any one of them, a file without a
scaling is refused before a weight is drawn, its cell rehearses on the
CPU with every check holding while the fp8 control fails them, and the
``lane_wait_ms`` reader on synthetic spans."""
import dataclasses
import json
import time

import numpy as np
import pytest

from bench import harness, reference, run

MINICPM = harness.ROOT / "bench" / "configs" / "minicpm-2b.json"
CELL = "minicpm-2b.doc_reuse"
SCALINGS = ("scale_emb", "scale_depth", "dim_model_base")


def arch_and_dims():
    cfg = harness.load_json(MINICPM)
    return harness.architecture(cfg, True)


def test_committed_file_loads_with_its_lanes():
    _, cell, cfg, mix = harness.load_cell(CELL)
    traffic = harness.load_json(harness.BENCH / "traffic" / "doc_reuse.json")
    lanes = cfg["engine"]["lanes"]
    assert cfg["reference"] == "minicpm" and cell["chips"] == 1
    assert 0 < lanes < traffic["engine"]["lanes"]
    assert mix["engine"] == dict(traffic["engine"], lanes=lanes)
    assert (cfg["scale_emb"], cfg["scale_depth"], cfg["dim_model_base"]) \
        == (12, 1.4, 256)


def test_published_sizes():
    """At the published widths: 2.725e9 parameters with the tied
    embedding once, KV pages of (40, 2304), 36 x 64 x 40 attention
    width, and the program's config carries the scalings."""
    arch = reference.load("minicpm")
    dims = arch.dims_from_config(harness.load_json(MINICPM))
    assert arch.param_count(dims) == 2_724_880_896
    assert arch.kv_plane(dims) == (40, 2304)
    assert arch.attn_width(dims) == 36 * 64 * 40
    cfg = arch.program_config("minicpm-2b", dims)
    assert (cfg.scale_emb, cfg.scale_depth, cfg.dim_model_base) == \
        (12.0, 1.4, 256)
    assert cfg.kv_bytes_per_token() == 368_640
    assert cfg.head_divisor == 9.0


@pytest.mark.parametrize("off", (None,) + SCALINGS)
def test_reference_is_the_program_with_every_scaling(off):
    """In float32 the reference computes the program's function (to
    rounding, 1e-4 of the largest logit), and a program with any one
    scaling off is far from it."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    arch, dims = arch_and_dims()
    flat = reference.make_flat(arch, dims, 5)
    toks = np.random.default_rng(1).integers(8, 1000, 40).astype(np.int32)
    ref = arch.logits_at(flat, dims, toks, list(range(40)))
    cfg = dataclasses.replace(arch.program_config("t", dims),
                              dtype="float32", param_dtype="float32")
    if off is not None:
        cfg = dataclasses.replace(cfg, **{off: None})
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          arch.program_layout(flat))
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(build_model(cfg).forward(
            params, {"tokens": toks[None]}))[0]
    gap = np.abs(prog - ref).max()
    tol = 1e-4 * np.abs(ref).max()
    assert (gap <= tol) if off is None else (gap > 100 * tol)


def test_logits_are_not_the_input_token():
    """The drawn model is not degenerate: its best token at a position is
    not the token there (the tied head's own row, x12 at the input, would
    win everywhere at a branch gain of 1)."""
    arch, dims = arch_and_dims()
    flat = reference.make_flat(arch, dims, 9)
    toks = np.random.default_rng(2).integers(8, 1000, 64).astype(np.int32)
    lg = arch.logits_at(flat, dims, toks, list(range(64)))
    assert (lg.argmax(-1) == toks).mean() < 0.1


@pytest.fixture
def minicpm_copy(monkeypatch, tmp_path):
    """Enter a copy of minicpm-2b's file with ``changes`` (None leaves
    the key out) as the configuration of a ``doc_reuse`` cell in a
    patched ``BENCHMARK.json``; returns the cell's name."""
    load = harness.load_json

    def add(**changes):
        cfg = dict(json.loads(MINICPM.read_text()), **changes)
        cfg = {k: v for k, v in cfg.items() if changes.get(k, 0) is not None}
        path = tmp_path / "copy.json"
        path.write_text(json.dumps(cfg))

        def with_copy(p):
            out = load(p)
            if p.name == "BENCHMARK.json":
                out["configs"].append({"name": "copy", "source": "test",
                                       "file": str(path), "reduced": [],
                                       "why": "test"})
                out["workloads"].append({"name": "copy.doc_reuse",
                                         "config": "copy",
                                         "traffic": "doc_reuse", "chips": 1,
                                         "why": "test"})
            return out
        monkeypatch.setattr(harness, "load_json", with_copy)
        return "copy.doc_reuse"
    return add


@pytest.mark.parametrize("changes, key", [
    ({"scale_emb": None}, "scale_emb"),
    ({"scale_depth": None}, "scale_depth"),
    ({"dim_model_base": None}, "dim_model_base"),
    ({"num_key_value_heads": 4}, "num_key_value_heads"),
    ({"qk_norm": True}, "qk_norm"),
    ({"tie_word_embeddings": None}, "tie_word_embeddings"),
])
def test_unmodelled_copy_is_refused(changes, key, minicpm_copy,
                                    monkeypatch):
    """Refused, naming the key, before any weight is drawn."""
    cell = minicpm_copy(**changes)

    def no_draw(*a, **k):
        raise AssertionError("weights drawn for a refused configuration")
    monkeypatch.setattr(reference, "make_flat", no_draw)
    with pytest.raises(ValueError, match=key):
        run.main(["--workload", cell, "--seed", "1", "--seconds", "0",
                  "--trace", "0", "--rehearse"])


def test_traced_rehearsal_holds_and_reads_lane_wait(capsys):
    """The whole cell at the rehearsal's sizes, traced: every check
    against the MiniCPM reference holds, and the line carries the
    per-layer metrics the cell lists, ``lane_wait_ms`` among them."""
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 13),
                   "--seconds", "1", "--trace", "1", "--rehearse"])
    assert rc == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    assert {"lane_wait_ms", "first_token_wait_ms", "admit_h2d_mb",
            "fetch_idle_share", "decode_device_ms", "mfu",
            "dram_hit_share", "fetch_ms"} <= set(line["metrics"])
    assert line["metrics"]["lane_wait_ms"]["value"] >= 0


def test_fp8_control_fails_the_check():
    """At the mix's rehearsal limit the fp8 control comes out not
    correct where the program, on the same requests, comes out correct."""
    out = harness.run(CELL, 2 ** 31 + 3, 0.0, False, time.perf_counter(),
                      rehearse=True, control=True)
    assert harness.passes(out["checks"])
    assert not harness.passes(out["control_checks"])


# ---------------------------------------------------------------------------
# the lane_wait_ms reader on synthetic spans
# ---------------------------------------------------------------------------

T0, T1 = 100.0, 1100.0          # the segment, in ns
SPANS = [
    ("arrival", 50.0, 0.0, {"req_id": 1}),          # before the segment
    ("lane_wait", 50.0, 0.0, {"req_id": 1}),
    ("dispatch", 150.0, 5.0, {"req_id": 1}),
    ("arrival", 200.0, 0.0, {"req_id": 2}),         # found a lane: 0
    ("dispatch", 200.0, 5.0, {"req_id": 2}),
    ("arrival", 300.0, 0.0, {"req_id": 3}),         # waited 400 ns
    ("lane_wait", 300.0, 0.0, {"req_id": 3}),
    ("dispatch", 700.0, 5.0, {"req_id": 3}),
    ("arrival", 900.0, 0.0, {"req_id": 4}),         # still waiting at 1100
    ("lane_wait", 900.0, 0.0, {"req_id": 4}),
]


def lane_wait(spans):
    return harness.load_reader("lane_wait_ms")(
        {"program": {"spans": spans, "ops": [], "t0": T0, "t1": T1}})


def test_lane_wait_is_the_mean_over_the_segments_arrivals():
    # requests 2, 3 and 4 arrived in the segment: 0, 400 and 200 ns
    assert lane_wait(SPANS) == pytest.approx(1e-6 * (0 + 400 + 200) / 3)


def test_lane_wait_reads_zero_where_no_request_waited():
    spans = [s for s in SPANS if s[0] != "lane_wait"]
    assert lane_wait(spans) == 0.0


def test_lane_wait_reads_nothing_without_arrivals():
    assert lane_wait([s for s in SPANS if s[0] != "arrival"]) is None
    assert lane_wait([]) is None
