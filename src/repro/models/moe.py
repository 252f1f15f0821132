"""Mixture-of-Experts FFN: top-k routing, shared experts, capacity dispatch.

Dispatch is SORT-based (linear in tokens), not GShard dense-einsum dispatch
(quadratic in tokens): tokens' (token, expert) assignments are argsorted by
expert id, packed into an (E, C, d) buffer with per-expert capacity
C = ceil(T·k/E · capacity_factor); overflow tokens are dropped (standard
capacity dropping). Expert FFNs run vmapped over E; the buffer shards over
the "model" mesh axis → expert parallelism, with XLA inserting the
token<->expert all-to-all at the scatter/gather boundaries.

Router: softmax over logits, take top-k, renormalize the top-k weights
(olmoe/mixtral convention; deepseek scores are softmax-then-topk as well).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.launch.sharding import constrain
from repro.models.layers import Params, dense_init, init_mlp, mlp_fwd

CAPACITY_FACTOR = 1.25


def init_moe(rng, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    ks = jax.random.split(rng, 3 + m.n_shared_experts)
    ek = jax.random.split(ks[0], 3)
    p: Params = {
        "router": dense_init(ks[1], cfg.d_model, m.n_routed_experts, dtype,
                             scale=cfg.d_model ** -0.5),
        # stacked expert weights: (E, d, ff) / (E, ff, d)
        "experts": {
            "wi_gate": _expert_init(ek[0], m.n_routed_experts, cfg.d_model,
                                    m.expert_d_ff, dtype),
            "wi_up": _expert_init(ek[1], m.n_routed_experts, cfg.d_model,
                                  m.expert_d_ff, dtype),
            "wo": _expert_init(ek[2], m.n_routed_experts, m.expert_d_ff,
                               cfg.d_model, dtype),
        },
    }
    if m.n_shared_experts:
        p["shared"] = init_mlp(ks[2], cfg.d_model,
                               m.expert_d_ff * m.n_shared_experts, dtype)
    return p


def _expert_init(rng, e, d_in, d_out, dtype):
    return (jax.random.normal(rng, (e, d_in, d_out), dtype=jnp.float32)
            * d_in ** -0.5).astype(dtype)


def router_topk(logits: jax.Array, top_k: int) -> Tuple[jax.Array, jax.Array]:
    """(T, E) -> weights (T, k) renormalized, indices (T, k)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-9)
    return w, idx


def moe_fwd_ep(p: Params, cfg: ModelConfig, x: jax.Array,
               capacity_factor: float = CAPACITY_FACTOR,
               dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Explicit expert-parallel MoE via shard_map (§Perf iteration A2).

    Key structural fact: within a TP block the token activations are
    REPLICATED across the "model" axis, and experts are sharded across it —
    so dispatch needs NO cross-device token movement at all: every model
    rank filters its own experts' tokens out of its local (replicated)
    block, computes them, and a single bf16 psum over "model" combines the
    per-expert partial outputs. XLA's gather/scatter SPMD partitioner is
    never consulted (it lowers data<->model-sharded gathers to
    replicate+all-reduce of (T·k, d) tensors — iteration A1's 41 s floor).

    Per-layer collective cost: psum of (t_loc, d) activations (+ FSDP
    weight all-gathers), matching dense-TP blocks.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch import sharding as shlib

    rules = shlib._rules()
    mesh = rules["mesh"]
    amap = rules["map"]
    d_ax, m_ax = amap.get("data"), amap.get("model")
    m = cfg.moe
    e, k = m.n_routed_experts, m.top_k
    b, s, d = x.shape
    mp = mesh.shape[m_ax] if not isinstance(m_ax, tuple) else 0
    dp = (mesh.shape[d_ax] if not isinstance(d_ax, tuple)
          else int(np_prod([mesh.shape[a] for a in d_ax])))
    if mp == 0 or e % mp != 0 or (b * s) % dp != 0 or d % dp != 0:
        return moe_fwd(p, cfg, x, capacity_factor, dropless)
    e_loc = e // mp
    t_loc = (b * s) // dp
    cap = t_loc if dropless else int(max(1, -(-t_loc * k * capacity_factor
                                              // e)))

    def body(x_blk, router, wi_g, wi_u, wo):
        # x_blk (b_loc, s, d) replicated over model.
        # weights arrive d-replicated (in_specs): for FSDP-trained params
        # jit inserts the ZeRO-3 all-gather at the shard_map boundary; for
        # TP-only serving params there is NO collective — an in-body
        # explicit gather would re-gather every decode step (§Perf fix for
        # deepseek/jamba decode cells).
        xf = x_blk.reshape(-1, d)

        # routing in f32 THROUGH AN EXPLICIT CAST: the astype's vjp converts
        # the f32 router cotangent back to bf16 before it joins the residual
        # stream — without it the f32 poisons every upstream activation
        # all-reduce, doubling backward collective bytes (§Perf B3).
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        w, idx = router_topk(logits, k)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(axis=1)
        aux = e * jnp.sum((onehot.mean(axis=0) / k) * probs.mean(axis=0))
        aux = jax.lax.pmean(aux, d_ax)

        mi = jax.lax.axis_index(m_ax)
        flat_e = idx.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(t_loc), k)
        flat_w = w.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, st_, sw_ = flat_e[order], flat_tok[order], flat_w[order]
        first = jnp.searchsorted(se, se, side="left")
        pos = jnp.arange(t_loc * k) - first
        my_e = se - mi * e_loc
        mine = (my_e >= 0) & (my_e < e_loc) & (pos < cap)
        target = jnp.where(mine, my_e * cap + pos, e_loc * cap)

        buf = jnp.zeros((e_loc * cap + 1, d), x_blk.dtype)
        buf = buf.at[target].set(xf[st_], mode="drop")
        buf = buf[:-1].reshape(e_loc, cap, d)

        def expert(g, u, o, h):
            return (jax.nn.silu(h @ g) * (h @ u)) @ o

        out_buf = jax.vmap(expert)(wi_g, wi_u, wo, buf).reshape(-1, d)
        gathered = jnp.where(mine[:, None],
                             out_buf[jnp.clip(target, 0, e_loc * cap - 1)],
                             0)
        contrib = gathered * sw_[:, None].astype(x_blk.dtype)
        part = jax.ops.segment_sum(contrib, st_, num_segments=t_loc)
        # combine across experts in the RESIDUAL dtype (bf16 on TPU): the
        # wire cost halves and the sum over <= mp partials is benign.
        out = jax.lax.psum(part.astype(x_blk.dtype), m_ax)
        return out.reshape(x_blk.shape), aux

    d_spec = d_ax
    wrapped = shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P(d_spec, None, None),        # x: batch over data
                  P(None, None),                # router: replicated
                  P(m_ax, None, None),          # wi_gate (E, d, ff): EP only
                  P(m_ax, None, None),          # wi_up
                  P(m_ax, None, None)),         # wo (E, ff, d)
        out_specs=(P(d_spec, None, None), P()))
    out, aux = wrapped(
        x, p["router"], p["experts"]["wi_gate"], p["experts"]["wi_up"],
        p["experts"]["wo"])
    if "shared" in p:
        out = out + mlp_fwd(p["shared"], x)
    return out, aux


def np_prod(xs):
    r = 1
    for v in xs:
        r *= v
    return r


def moe_fwd(p: Params, cfg: ModelConfig, x: jax.Array,
            capacity_factor: float = CAPACITY_FACTOR,
            dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss). Load-balance aux loss is returned for
    the training objective (Switch-style: E * mean(frac_tokens * frac_probs)).

    dropless=True sets per-expert capacity to T (serving paths: no token is
    ever dropped, outputs are exactly causal). Training uses the standard
    capacity factor (overflow drop) for bounded, shardable buffers.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = m.top_k
    e = m.n_routed_experts
    xf = x.reshape(t, d)

    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    w, idx = router_topk(logits, k)                            # (T,k)

    # ---- aux load-balance loss ----
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(axis=1)   # (T, E)
    frac_tokens = onehot.mean(axis=0) / k
    frac_probs = probs.mean(axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    # ---- sort-based dispatch ----
    if dropless:
        cap = t
    else:
        cap = int(max(1, -(-t * k * capacity_factor // e)))    # ceil
    flat_e = idx.reshape(-1)                                   # (T*k,)
    flat_tok = jnp.repeat(jnp.arange(t), k)                    # token id per slot
    flat_w = w.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_tok[order], flat_w[order]
    # position of each entry within its expert group
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(t * k) - first
    keep = pos < cap
    target = jnp.where(keep, se * cap + pos, e * cap)          # overflow -> dropped row

    xs_sorted = constrain(xf[st], ("data", None))              # (T*k, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[target].set(xs_sorted, mode="drop")
    buf = buf[:-1].reshape(e, cap, d)
    # experts over "model" (EP), capacity over "data": dispatch/combine
    # gathers then partition as all-to-all instead of replicate+all-reduce
    # of (T*k, d) tensors (§Perf iteration A1).
    buf = constrain(buf, ("model", "data", None))

    # ---- expert compute (vmapped over E) ----
    def expert(wi_g, wi_u, wo, h):
        return (jax.nn.silu(h @ wi_g) * (h @ wi_u)) @ wo

    out_buf = jax.vmap(expert)(p["experts"]["wi_gate"], p["experts"]["wi_up"],
                               p["experts"]["wo"], buf)        # (E, C, d)
    out_buf = constrain(out_buf, ("model", "data", None))

    # ---- combine: gather back and weight ----
    flat_out = out_buf.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], flat_out[jnp.clip(target, 0, e * cap - 1)], 0)
    gathered = constrain(gathered, ("data", None))
    contrib = gathered * sw[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(contrib, st, num_segments=t)     # (T, d)
    out = constrain(out, ("data", None))

    if "shared" in p:
        out = out + mlp_fwd(p["shared"], x).reshape(t, d)
    return out.reshape(b, s, d).astype(x.dtype), aux
