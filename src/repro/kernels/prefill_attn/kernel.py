"""Pallas TPU kernel: tiled causal flash attention (prefill hot path).

Grid (P, S/Qb, S/Kb): planes and query-blocks parallel, key-block dim
sequential with flash (m, l, acc) scratch carried across K-steps. Causal
structure: K-blocks strictly above the diagonal contribute nothing — their
scores are fully masked; the kernel still visits them (simple variant) but
@pl.when skips the FLOPs for fully-masked blocks, so compiled cost is the
~triangular half. Qb=Kb=128/256 keep the (Qb, hd) x (hd, Kb) matmuls
MXU-aligned and the VMEM working set ≈ Qb*hd + Kb*hd + Qb*Kb floats ≈ 0.4 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
                  qb: int, kb: int, hd: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks fully above the causal diagonal are skipped entirely
    @pl.when(ki * kb <= qi * qb + (qb - 1))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (Qb, hd)
        k = k_ref[0].astype(jnp.float32)                  # (Kb, hd)
        v = v_ref[0].astype(jnp.float32)
        scores = (q @ k.T) * (hd ** -0.5)                 # (Qb, Kb)
        qpos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        kpos = ki * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        scores = jnp.where(kpos <= qpos, scores, NEG_INF)

        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + p @ v
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def flash_attention(q, k, v, *, qb: int = 256, kb: int = 256,
                    interpret: bool = True) -> jax.Array:
    """q/k/v: (P, S, hd) plane-major; returns (P, S, hd) f32, causal."""
    p_dim, s, hd = q.shape
    qb, kb = min(qb, s), min(kb, s)
    assert s % qb == 0 and s % kb == 0, (s, qb, kb)
    grid = (p_dim, s // qb, s // kb)
    kern = functools.partial(_flash_kernel, qb=qb, kb=kb, hd=hd)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, qb, hd), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, kb, hd), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, kb, hd), lambda i, j, t: (i, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, hd), lambda i, j, t: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((p_dim, s, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
