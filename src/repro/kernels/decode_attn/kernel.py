"""Pallas TPU kernel: fused KIVI-dequant + flash-decode attention.

The paper's data plane decompresses KV on the serving device before
attention; a GPU implementation launches a dequant kernel that materializes
bf16 KV in device memory. TPU-native adaptation (DESIGN.md §4): decode
attention is HBM-bandwidth-bound on reading the KV cache, so we stream the
*packed* uint8 KV HBM->VMEM (up to 8x fewer bytes at 2-bit than bf16),
dequantize in VREGs, and feed the MXU — dequantized KV never exists in HBM.

Layout, one (batch*kv_head) plane per grid row:
  q        (P, Gq, hd)       Gq = query heads per kv head (sublane-padded)
  k_packed (P, T/cpb, hd)    K codes packed along tokens
  k_scale  (P, T/gs, hd)     per-channel scale per token-group
  k_zero   (P, T/gs, hd)
  v_packed (P, T, hd/cpb)    V codes packed along channels
  v_scale  (P, T, hd/gv)     per-token scale per channel-group
  v_zero   (P, T, hd/gv)
  cur_len  (P, 1) int32      valid cache length (mask >= cur_len)
  out      (P, Gq, hd)

Grid: (P, T/Tb); token dim is sequential ("arbitrary") with the flash
running max / sum / accumulator carried in VMEM scratch across T-steps.
VMEM per step at Tb=256, hd=128, 2-bit: ~0.3 MB. Tb and hd are 128-aligned
for clean (sublane, lane) tiling; scores hit the MXU as (Gq, hd)x(hd, Tb).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TB = 256
NEG_INF = -1e30


def _unpack_rows(packed, bits, n_rows):
    """(R/cpb, C) uint8 -> (R, C) f32 codes, unpacking along rows (axis 0)."""
    cpb = 8 // bits
    if cpb == 1:
        return packed.astype(jnp.float32)
    p = packed.astype(jnp.uint32)
    mask = jnp.uint32(2 ** bits - 1)
    rows = [(p >> jnp.uint32(j * bits)) & mask for j in range(cpb)]
    q = jnp.stack(rows, axis=1)                    # (R/cpb, cpb, C)
    return q.reshape(p.shape[0] * cpb, p.shape[1]).astype(jnp.float32)


def _unpack_cols(packed, bits, n_cols):
    """(R, C/cpb) uint8 -> (R, C) f32 codes, unpacking along columns."""
    cpb = 8 // bits
    if cpb == 1:
        return packed.astype(jnp.float32)
    p = packed.astype(jnp.uint32)
    mask = jnp.uint32(2 ** bits - 1)
    cols = [(p >> jnp.uint32(j * bits)) & mask for j in range(cpb)]
    q = jnp.stack(cols, axis=2)                    # (R, C/cpb, cpb)
    return q.reshape(p.shape[0], p.shape[1] * cpb).astype(jnp.float32)


def _expand_groups_rows(s, group_size, n_rows):
    """(G, C) per-group values -> (R, C) repeated group_size times along rows."""
    return jnp.repeat(s, group_size, axis=0, total_repeat_length=n_rows)


def _expand_groups_cols(s, group_size, n_cols):
    return jnp.repeat(s, group_size, axis=1, total_repeat_length=n_cols)


def _decode_kernel(cur_len_ref, q_ref, kp_ref, ks_ref, kz_ref,
                   vp_ref, vs_ref, vz_ref, out_ref,
                   m_ref, l_ref, acc_ref, *,
                   bits: int, k_group: int, v_group: int, tb: int, hd: int):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (Gq, hd)
    # --- dequantize K block: (Tb, hd) ---
    k_codes = _unpack_rows(kp_ref[0], bits, tb)
    k_scale = _expand_groups_rows(ks_ref[0], k_group, tb)
    k_zero = _expand_groups_rows(kz_ref[0], k_group, tb)
    k = k_codes * k_scale + k_zero
    # --- dequantize V block ---
    v_codes = _unpack_cols(vp_ref[0], bits, hd)
    v_scale = _expand_groups_cols(vs_ref[0], v_group, hd)
    v_zero = _expand_groups_cols(vz_ref[0], v_group, hd)
    v = v_codes * v_scale + v_zero                 # (Tb, hd)

    scores = (q @ k.T) * (hd ** -0.5)              # (Gq, Tb) -> MXU
    token0 = t_idx * tb
    tok = token0 + jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
    valid = tok < cur_len_ref[0, 0]
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)         # (Gq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * alpha + p @ v
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(t_idx == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def fused_decode_attention(q, k_packed, k_scale, k_zero,
                           v_packed, v_scale, v_zero, cur_len, *,
                           bits: int, k_group: int, v_group: int,
                           tb: int = DEFAULT_TB, interpret: bool = True):
    p_dim, gq, hd = q.shape
    t = v_packed.shape[1]
    assert t % tb == 0 and tb % k_group == 0, (t, tb, k_group)
    cpb = 8 // bits
    grid = (p_dim, t // tb)
    kern = functools.partial(_decode_kernel, bits=bits, k_group=k_group,
                             v_group=v_group, tb=tb, hd=hd)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),                 # cur_len
            pl.BlockSpec((1, gq, hd), lambda i, j: (i, 0, 0)),         # q
            pl.BlockSpec((1, tb // cpb, hd), lambda i, j: (i, j, 0)),  # kp
            pl.BlockSpec((1, tb // k_group, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tb // k_group, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tb, hd // cpb), lambda i, j: (i, j, 0)),  # vp
            pl.BlockSpec((1, tb, hd // v_group), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tb, hd // v_group), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, gq, hd), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p_dim, gq, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((gq, 1), jnp.float32),     # running max
            pltpu.VMEM((gq, 1), jnp.float32),     # running denom
            pltpu.VMEM((gq, hd), jnp.float32),    # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cur_len, q, k_packed, k_scale, k_zero, v_packed, v_scale, v_zero)
