"""The seam between the harness and an architecture: a second
architecture (``arch_untied.py``, dense GQA with an untied head) runs
through a whole CPU rehearsal as new files only; a configuration that
says what its module does not model is refused before a weight is drawn;
a configuration's ``engine`` sizes the lanes."""
import dataclasses
import json

import numpy as np
import pytest

from bench import harness, reference, run

QWEN = harness.ROOT / "bench" / "configs" / "qwen3-1.7b.json"
DIMS = harness.architecture(harness.load_json(QWEN), True)[1]


def untied_arch():
    """``arch_untied.py``, as the module of ``"reference": "dense_untied"``."""
    return reference.load("dense_untied",
                          harness.BENCH / "tests" / "arch_untied.py")


DROP = object()         # a change that leaves the key out of the copy


@pytest.fixture
def add_config(monkeypatch, tmp_path):
    """Enter a configuration, as a copy of qwen3-1.7b's file with
    ``changes`` made to it (``DROP`` leaves a key out), and its
    ``doc_reuse`` cell in a patched ``BENCHMARK.json``; returns the cell's
    name."""
    load = harness.load_json

    def add(name, **changes):
        cfg = dict(json.loads(QWEN.read_text()), **changes)
        cfg = {k: v for k, v in cfg.items() if v is not DROP}
        path = tmp_path / f"{name}.json"
        path.write_text(json.dumps(cfg))

        def with_config(p):
            out = load(p)
            if p.name == "BENCHMARK.json":
                out["configs"].append({"name": name, "source": cfg["source"],
                                       "file": str(path), "reduced": [],
                                       "why": "test"})
                out["workloads"].append({"name": f"{name}.doc_reuse",
                                         "config": name,
                                         "traffic": "doc_reuse", "chips": 1,
                                         "why": "test"})
            return out
        monkeypatch.setattr(harness, "load_json", with_config)
        return f"{name}.doc_reuse"
    return add


def program_logits(arch, flat, toks, tie=None):
    """The program's float32 forward pass over ``toks`` with ``arch``'s
    weights and program config (``tie`` overrides whether its head is
    tied)."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    cfg = dataclasses.replace(arch.program_config("t", DIMS),
                              dtype="float32", param_dtype="float32")
    if tie is not None:
        cfg = dataclasses.replace(cfg, tie_embeddings=tie)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          arch.program_layout(flat))
    with jax.default_matmul_precision("highest"):
        return np.asarray(build_model(cfg).forward(
            params, {"tokens": toks[None]}))[0]


@pytest.mark.parametrize("tie, agrees", [(None, True), (True, False)])
def test_untied_reference_against_the_program(tie, agrees):
    """The untied reference computes the function of the program config
    its module gives (float32, to rounding), and not that of a program
    with a tied head over the same weights."""
    arch = untied_arch()
    flat = reference.make_flat(arch, DIMS, 3)
    assert "lm_head" in flat
    toks = np.random.default_rng(0).integers(8, 1000, 40).astype(np.int32)
    ref = arch.logits_at(flat, DIMS, toks, list(range(40)))
    gap = np.abs(program_logits(arch, flat, toks, tie) - ref).max()
    assert bool(gap <= 1e-4 * np.abs(ref).max()) == agrees


def test_second_architecture_rehearses(add_config, capsys, monkeypatch):
    """A configuration naming the untied module runs the whole cell on the
    CPU, its weights drawn by that module, and every check against its
    reference holds."""
    untied_arch()
    cell = add_config("toy-untied", reference="dense_untied",
                      tie_word_embeddings=False)
    seen = []
    draw = reference.make_flat

    def spy(arch, dims, seed):
        seen.append(arch)
        return draw(arch, dims, seed)
    monkeypatch.setattr(reference, "make_flat", spy)
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 5),
                   "--seconds", "0", "--trace", "0", "--rehearse"])
    assert rc == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert seen and all(a is untied_arch() for a in seen)
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name


@pytest.mark.parametrize("changes, key", [
    ({"scale_emb": 12}, "scale_emb"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"rope_scaling": {"type": "linear", "factor": 2.0}}, "rope_scaling"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"tie_word_embeddings": DROP}, "tie_word_embeddings"),
    ({"hidden_act": DROP}, "hidden_act"),
    ({"engine": {"lanes": 4, "dram_share": 0.5}}, "dram_share"),
    ({"engine": {"capacity": 2048}}, "capacity"),
    ({"engine": {"lanes": 0}}, "lanes"),
    ({"max_position_embeddings": 2048}, "max_position_embeddings"),
])
def test_unmodelled_configuration_is_refused(changes, key, add_config,
                                             monkeypatch):
    """Refused at load, naming the key, before any weight is drawn."""
    cell = add_config("refused", **changes)

    def no_draw(*a, **k):
        raise AssertionError("weights drawn for a refused configuration")
    monkeypatch.setattr(reference, "make_flat", no_draw)
    with pytest.raises(ValueError, match=key):
        run.main(["--workload", cell, "--seed", "1", "--seconds", "0",
                  "--trace", "0", "--rehearse"])


# what Qwen3-1.7B's published config.json holds beyond the keys of
# ``configs/qwen3-1.7b.json``
PUBLISHED = {"attention_dropout": 0.0, "bos_token_id": 151643,
             "eos_token_id": 151645, "initializer_range": 0.02,
             "max_window_layers": 28, "model_type": "qwen3",
             "rope_scaling": None, "sliding_window": None,
             "transformers_version": "4.51.0", "use_cache": True,
             "use_sliding_window": False}


def test_published_bookkeeping_loads(add_config):
    """A configuration may carry every key of its source's config.json:
    the bookkeeping is ignored, the features that are off are checked."""
    cell = add_config("published", **PUBLISHED)
    _, _, cfg, _ = harness.load_cell(cell)
    assert set(PUBLISHED) <= set(cfg)


def test_committed_configurations_load():
    """Every committed cell loads, its mix's engine with the
    configuration's ``engine`` merged over it; qwen3-1.7b states none."""
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        _, _, cfg, mix = harness.load_cell(w["name"])
        traffic = harness.load_json(
            harness.BENCH / "traffic" / f"{w['traffic']}.json")
        assert mix["engine"] == dict(traffic["engine"],
                                     **cfg.get("engine", {}))
    assert "engine" not in harness.load_json(QWEN)


def test_configuration_engine_sizes_the_lanes(add_config, monkeypatch):
    """A configuration's ``engine.lanes`` replaces the mix's, and the
    mix's other engine settings stay. A rehearsal's own sizes win over it;
    where the rehearsal sets no lane count, the configuration's reaches
    the engine's batcher."""
    from repro.serving import scheduler
    cell = add_config("four-lanes", engine={"lanes": 4})
    _, _, _, mix = harness.load_cell(cell)
    traffic = harness.load_json(harness.BENCH / "traffic" / "doc_reuse.json")
    assert mix["engine"] == dict(traffic["engine"], lanes=4)
    _, _, _, small = harness.load_cell(cell, rehearse=True)
    assert small["engine"] == dict(traffic["engine"],
                                   **traffic["rehearsal"]["engine"])

    load = harness.load_json

    def no_rehearsal_lanes(p):
        out = load(p)
        if p.name == "doc_reuse.json":
            del out["rehearsal"]["engine"]["lanes"]
        return out
    monkeypatch.setattr(harness, "load_json", no_rehearsal_lanes)
    _, _, _, small = harness.load_cell(cell, rehearse=True)
    assert small["engine"]["lanes"] == 4
    assert small["engine"]["capacity"] == 384        # the rehearsal's
    lanes = []
    init = scheduler.ContinuousBatcher.__init__

    def spy(self, *a, **k):
        init(self, *a, **k)
        lanes.append(self.n_slots)
    monkeypatch.setattr(scheduler.ContinuousBatcher, "__init__", spy)
    import time
    out = harness.run(cell, 7, 0.0, False, time.perf_counter(),
                      rehearse=True)
    assert lanes and set(lanes) == {4}
    assert harness.passes(out["checks"])
