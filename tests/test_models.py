"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
output shapes + no NaNs; prefill+decode bit-consistency vs full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config
from repro.models import build_model
from repro.models import transformer


def make_batch(cfg, B=2, S=12, seed=2, labels=True):
    batch = {"tokens": jax.random.randint(jax.random.key(seed), (B, S), 0,
                                          cfg.vocab_size)}
    if labels:
        batch["labels"] = jax.random.randint(jax.random.key(seed + 1),
                                             (B, S + (cfg.n_patches or 0)),
                                             0, cfg.vocab_size)
    if cfg.n_patches:
        batch["patch_embeds"] = jax.random.normal(
            jax.random.key(3), (B, cfg.n_patches, cfg.d_model)) * 0.1
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            jax.random.key(4), (B, cfg.n_frames, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("name", ASSIGNED)
def test_forward_and_loss_smoke(name):
    cfg = get_config(name, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    B, S = 2, 12
    batch = make_batch(cfg, B, S)
    logits = m.forward(params, batch)
    total = S + (cfg.n_patches or 0)
    assert logits.shape == (B, total, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    loss = m.loss(params, batch)
    assert jnp.isfinite(loss)


@pytest.mark.parametrize("name", ASSIGNED)
def test_train_step_no_nans(name):
    from repro.training.optimizer import AdamWConfig
    from repro.training.train_step import init_train_state, make_train_step
    cfg = get_config(name, smoke=True)
    m = build_model(cfg)
    opt = AdamWConfig(lr=1e-3)
    state = init_train_state(m, jax.random.key(0), opt)
    step = make_train_step(m, opt)
    batch = make_batch(cfg, 2, 12)
    state, metrics = jax.jit(step)(state, batch)
    assert jnp.isfinite(metrics["loss"])
    for leaf in jax.tree.leaves(state.params):
        assert not bool(jnp.isnan(leaf.astype(jnp.float32)).any())


@pytest.mark.parametrize("name", ASSIGNED)
def test_prefill_decode_matches_forward(name):
    cfg = get_config(name, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(1))
    B, S = 2, 12
    batch = make_batch(cfg, B, S, labels=False)
    toks = batch["tokens"]
    full, _ = transformer.forward_train(params, cfg, batch,
                                        moe_dropless=True)
    bp = dict(batch)
    bp["tokens"] = toks[:, : S - 1]
    cap = S + (cfg.n_patches or 0) + 4
    logits_p, cache = m.prefill(params, bp, capacity=cap)
    idx = jnp.int32((S - 1) + (cfg.n_patches or 0))
    logits_d, _ = m.decode_step(params, cache, idx, toks[:, S - 1: S])
    ref = np.asarray(full[:, -1], np.float32)
    np.testing.assert_allclose(np.asarray(logits_d[:, 0], np.float32), ref,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(logits_p[:, 0], np.float32),
                               np.asarray(full[:, -2], np.float32),
                               rtol=2e-3, atol=2e-3)


def test_ragged_decode_matches_scalar():
    """Per-lane cur_index (continuous batching) == aligned scalar decode."""
    cfg = get_config("qwen3-1.7b", smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    B, S = 3, 10
    toks = jax.random.randint(jax.random.key(5), (B, S), 0, cfg.vocab_size)
    _, cache = m.prefill(params, {"tokens": toks}, capacity=S + 4)
    nxt = toks[:, -1:]
    lg_scalar, _ = m.decode_step(params, cache, jnp.int32(S), nxt)
    lg_vec, _ = m.decode_step(params, cache,
                              jnp.full((B,), S, jnp.int32), nxt)
    np.testing.assert_allclose(np.asarray(lg_vec), np.asarray(lg_scalar),
                               rtol=1e-5, atol=1e-5)


def test_decode_position_override():
    """Token-dropped caches: write slot != rope position must be exact."""
    cfg = get_config("smollm-135m", smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    B, S = 1, 9
    toks = jax.random.randint(jax.random.key(6), (B, S), 0, cfg.vocab_size)
    _, cache = m.prefill(params, {"tokens": toks}, capacity=S + 4)
    # same slot, explicit position equal to slot -> identical logits
    a, _ = m.decode_step(params, cache, jnp.int32(S), toks[:, :1])
    b, _ = m.decode_step(params, cache, jnp.int32(S), toks[:, :1],
                         position=jnp.int32(S))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # different position -> different logits (rope actually applied)
    c, _ = m.decode_step(params, cache, jnp.int32(S), toks[:, :1],
                         position=jnp.int32(S + 7))
    assert float(jnp.abs(a - c).max()) > 0


def test_quantized_decode_tracks_exact():
    """serve_step_quantized: 8-bit packed KV reproduces exact decode
    (argmax-equal); lower bit-widths degrade monotonically."""
    cfg = get_config("qwen3-1.7b", smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(1))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
    _, cache = m.prefill(params, {"tokens": toks[:, : S - 1]},
                         capacity=S + 4)
    exact, _ = m.decode_step(params, cache, jnp.int32(S - 1),
                             toks[:, S - 1: S])
    errs = []
    for bits in (8, 4, 2):
        qc = m.init_cache(batch=B, capacity=S + 4, kv_bits=bits)
        lg = None
        for t in range(S):
            lg, qc = m.decode_step(params, qc, jnp.int32(t),
                                   toks[:, t: t + 1])
        errs.append(float(jnp.abs(lg - exact).max()
                          / (jnp.abs(exact).max() + 1e-9)))
    assert errs[0] < 0.02                    # 8-bit ~exact
    assert errs[0] <= errs[1] <= errs[2]     # monotone in bits
    # cache really is packed uint8
    qc = m.init_cache(batch=B, capacity=8, kv_bits=4)
    leaf = qc["stack"][0]["self"]["k_packed"]
    assert leaf.dtype == jnp.uint8
    assert leaf.shape[-1] == cfg.resolved_head_dim // 2


def test_chunked_loss_matches_plain():
    from repro.models.layers import cross_entropy_loss
    cfg = get_config("smollm-135m", smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch = make_batch(cfg, 2, 24)
    logits, aux = transformer.forward_train(params, cfg, batch)
    plain = cross_entropy_loss(logits, batch["labels"]) + 0.01 * aux
    chunked = transformer.loss_fn(params, cfg, batch, loss_chunk=7)
    assert abs(float(plain) - float(chunked)) < 1e-4


def test_flash_chunked_attention_matches_dense():
    """The >=FLASH_THRESHOLD path must agree with the dense path."""
    from repro.models import attention as A
    cfg = get_config("qwen3-1.7b", smoke=True)
    p = A.init_attention(jax.random.key(0), cfg, jnp.float32)
    B, S = 2, 64
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model)) * 0.3
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    dense, _ = A.attention_fwd(p, cfg, x, pos)
    old = A.FLASH_THRESHOLD
    try:
        A.FLASH_THRESHOLD = 32
        chunked, _ = A.attention_fwd(p, cfg, x, pos)
    finally:
        A.FLASH_THRESHOLD = old
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# MiniCPM's muP scalings
# ---------------------------------------------------------------------------

SCALINGS = ("scale_emb", "scale_depth", "dim_model_base")


def _minicpm_small():
    """MiniCPM's smoke config at 4 layers: at the smoke's 2 the depth
    scaling 1.4/sqrt(2) is within 1% of none."""
    import dataclasses
    return dataclasses.replace(get_config("minicpm-2b", smoke=True),
                               n_layers=4)


def _minicpm_logits(cfg, params, toks):
    with jax.default_matmul_precision("highest"):
        full, _ = transformer.forward_train(params, cfg, {"tokens": toks})
    return np.asarray(full, np.float32)


def test_minicpm_prefill_then_decode_matches_forward():
    """With the three scalings on, prefill and then three decode steps
    through the cache give the full forward pass's logits at every
    position, in float32. The tolerance is float32 rounding over a few
    layers (1e-4 of the largest logit); a scaling applied in prefill but
    not in decode's own embedding lookup or head would be off by a factor
    of 12 or 1/4 there."""
    cfg = _minicpm_small()
    assert cfg.dtype == "float32"
    m = build_model(cfg)
    params = m.init(jax.random.key(3))
    B, S, P = 2, 12, 9
    toks = jax.random.randint(jax.random.key(4), (B, S), 0, cfg.vocab_size)
    ref = _minicpm_logits(cfg, params, toks)
    tol = 1e-4 * np.abs(ref).max()
    with jax.default_matmul_precision("highest"):
        lp, cache = m.prefill(params, {"tokens": toks[:, :P]}, capacity=S)
        np.testing.assert_allclose(np.asarray(lp[:, 0]), ref[:, P - 1],
                                   rtol=0, atol=tol)
        for t in range(P, S):
            ld, cache = m.decode_step(params, cache,
                                      jnp.full((B,), t, jnp.int32),
                                      toks[:, t: t + 1])
            np.testing.assert_allclose(np.asarray(ld[:, 0]), ref[:, t],
                                       rtol=0, atol=tol)


@pytest.mark.parametrize("off", SCALINGS)
def test_minicpm_each_scaling_matters(off):
    """Turning off any one scaling moves the logits far beyond the
    tolerance prefill and decode are held to above."""
    import dataclasses
    cfg = _minicpm_small()
    params = build_model(cfg).init(jax.random.key(3))
    toks = jax.random.randint(jax.random.key(4), (2, 12), 0, cfg.vocab_size)
    ref = _minicpm_logits(cfg, params, toks)
    without = _minicpm_logits(dataclasses.replace(cfg, **{off: None}),
                              params, toks)
    assert np.abs(without - ref).max() > 100 * 1e-4 * np.abs(ref).max()


def test_minicpm_chunked_loss_divides_the_head():
    """The chunked loss reads the head as the logits do: divided by
    d_model / dim_model_base."""
    from repro.models.layers import cross_entropy_loss
    cfg = _minicpm_small()
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch = make_batch(cfg, 2, 24)
    logits, aux = transformer.forward_train(params, cfg, batch)
    plain = cross_entropy_loss(logits, batch["labels"]) + 0.01 * aux
    chunked = transformer.loss_fn(params, cfg, batch, loss_chunk=7)
    assert abs(float(plain) - float(chunked)) < 1e-4


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs (scan and loop
    bodies, jit calls, branches) included, in order."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = []
    for e in jaxpr.eqns:
        out.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, ClosedJaxpr):
                    out += _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    out += _eqns(sub)
    return out


def _is_subsequence(short, long) -> bool:
    it = iter(long)
    return all(any(x == y for y in it) for x in short)


def _traced_eqns(cfg):
    """The equations of the loss, prefill and decode step of ``cfg``."""
    m = build_model(cfg)
    params = m.init_shapes()
    toks = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    cache = jax.eval_shape(lambda: m.init_cache(2, 12))
    entries = {
        "loss": (lambda p, t: m.loss(p, {"tokens": t, "labels": t}),
                 (params, toks)),
        "prefill": (lambda p, t: m.prefill(p, {"tokens": t}, 12),
                    (params, toks)),
        "decode_step": (lambda p, c, t: m.decode_step(
            p, c, jnp.full((2,), 8, jnp.int32), t),
            (params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32))),
    }
    return {n: _eqns(jax.make_jaxpr(f)(*a).jaxpr)
            for n, (f, a) in entries.items()}


@pytest.mark.parametrize("field,value", [("scale_emb", 11.3),
                                         ("scale_depth", 3.7),
                                         ("dim_model_base", 37)])
def test_default_config_traces_the_same_jaxpr(field, value):
    """An off scaling adds no operation. With one scaling on, each entry
    point's trace is the default trace plus exactly the scaling's own
    operations (a multiply or divide by the scaling's constant): the
    default trace is what remains when those are taken out, so it holds
    no operation of the scaling, not even a multiply by 1. The values are
    odd ones, so that no other constant of the trace matches them."""
    from jax.extend.core import Literal
    import dataclasses
    cfg = get_config("qwen3-1.7b", smoke=True)
    assert all(getattr(cfg, f) is None for f in SCALINGS)
    on = dataclasses.replace(cfg, **{field: value})
    const = {"scale_emb": on.scale_emb, "scale_depth": on.residual_scale,
             "dim_model_base": on.head_divisor}[field]

    def scaling(e) -> bool:
        return e.primitive.name in ("mul", "div") and any(
            isinstance(v, Literal) and np.ndim(v.val) == 0
            and np.isclose(float(v.val), const, rtol=1e-3)
            for v in e.invars)

    off_eqns, on_eqns = _traced_eqns(cfg), _traced_eqns(on)
    for name in off_eqns:
        prims = [e.primitive.name for e in off_eqns[name]]
        ops = [e for e in on_eqns[name] if scaling(e)]
        assert ops, (name, field)
        assert not any(scaling(e) for e in off_eqns[name]), (name, field)
        assert len(on_eqns[name]) == len(prims) + len(ops), (name, field)
        assert _is_subsequence(
            prims, [e.primitive.name for e in on_eqns[name]
                    if not scaling(e)]), (name, field)
