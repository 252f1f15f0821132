"""The check catches a broken timed path: with a token altered where the
decode tick produces it, the served-logit gap fails its limit. And the
fp8 control, at a size a test can hold, lies far above what the program
reads, which is what lets one limit sit between them."""
import numpy as np

from bench import harness, reference

DENSE = reference.load("dense_gqa")


def test_altered_token_fails_the_check(monkeypatch):
    from repro.serving import scheduler
    orig = scheduler.ContinuousBatcher.tick

    def broken(self, now):
        done, dt = orig(self, now)
        for s in self.slots:
            if s.active and s.generated and not s.pending:
                s.generated[-1] = (s.generated[-1] + 7) % \
                    self.model.cfg.vocab_size
        for r in done:
            r.tokens[-1] = (r.tokens[-1] + 7) % self.model.cfg.vocab_size
        return done, dt

    monkeypatch.setattr(scheduler.ContinuousBatcher, "tick", broken)
    import time
    out = harness.run("qwen3-1.7b.doc_reuse", 5, 1.0, False,
                      time.perf_counter(), rehearse=True)
    gap = next(c for c in out["checks"] if c["name"] == "logit_gap")
    assert gap["value"] > gap["limit"]
    assert scheduler.ContinuousBatcher.tick is broken   # hooks restored


def test_fp8_control_fails_the_check():
    """The control, put in the program's place through the harness's own
    check, comes out not correct at the mix's limit, while the program
    on the same requests comes out correct."""
    import time
    out = harness.run("qwen3-1.7b.doc_reuse", 2 ** 31 + 3, 0.0, False,
                      time.perf_counter(), rehearse=True, control=True)
    assert harness.passes(out["checks"])
    assert not harness.passes(out["control_checks"])
    prog, ctrl = (next(c for c in cs if c["name"] == "logit_gap")
                  for cs in (out["checks"], out["control_checks"]))
    assert ctrl["limit"] == prog["limit"] and ctrl["value"] > ctrl["limit"]


# qwen3-1.7b's configuration at the rehearsal's sizes
DIMS = harness.architecture(harness.load_json(
    harness.BENCH / "configs" / "qwen3-1.7b.json"), True)[1]


def test_reference_matches_the_program_in_float32():
    """The plain reference computes the program's function: with float32
    weights and activations the two agree to rounding."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    flat = reference.make_flat(DENSE, DIMS, 3)
    cfg = dataclasses.replace(DENSE.program_config("t", DIMS),
                              dtype="float32", param_dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          DENSE.program_layout(flat))
    toks = np.random.default_rng(0).integers(8, 1000, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(build_model(cfg).forward(params,
                                                   {"tokens": toks[None]}))
    ref = DENSE.logits_at(flat, DIMS, toks, list(range(40)))
    assert np.abs(prog[0] - ref).max() <= 1e-4 * np.abs(ref).max()


def test_fp8_control_lies_far_above_bf16():
    """At a test's size the fp8 control's widest gap is several times the
    bf16 program's (the ratio the cells' limits rely on, read at full size
    on the chip)."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    flat = reference.make_flat(DENSE, DIMS, 4)
    cfg = DENSE.program_config("t", DIMS)
    params = DENSE.program_layout(flat)
    model = build_model(cfg)
    fwd = jax.jit(lambda t: model.forward(params, {"tokens": t}))
    rng = np.random.default_rng(1)
    prog_gaps, ctrl_gaps = [], []
    n_prompt, n_new, width = 48, 16, 64
    for _ in range(4):
        seq = list(rng.integers(8, 1000, n_prompt))
        for _ in range(n_new):          # bf16 greedy decoding
            pad = np.zeros((1, width), np.int32)
            pad[0, :len(seq)] = seq
            lg = np.asarray(fwd(jnp.asarray(pad)), np.float32)
            seq.append(int(lg[0, len(seq) - 1].argmax()))
        prompt, served = np.asarray(seq[:n_prompt]), seq[n_prompt:]
        prog_gaps.append(reference.served_gaps(DENSE, flat, DIMS, prompt,
                                               served))
        ctrl_gaps.append(reference.control_gaps(DENSE, flat, DIMS, prompt,
                                                served))
    assert reference.widest(ctrl_gaps) > 3 * reference.widest(prog_gaps)
