"""Pallas TPU kernel: fused KIVI-dequant + chunk-prefill flash attention.

Chunked prefill's dominant read is the chunk-vs-prefix cross-attention:
every chunk streams the WHOLE cached prefix KV out of HBM once. When the
prefix is KIVI-quantized the serving stack used to dequantize it into
bf16 HBM first and then attend — paying full-precision bytes on the
bandwidth-bound term plus a separate decompress pass. This kernel streams
the *packed* uint8 prefix HBM->VMEM (up to 8x fewer bytes at 2-bit),
dequantizes each K-block in VREGs, and feeds the MXU; dequantized prefix
KV never exists in HBM. The chunk's own bf16 K/V ride along so one launch
produces the full causal chunk output.

Layout, one (batch*kv_head) plane per grid row (decode_attn's packing):
  q        (P, C, hd)        C chunk queries (sublane-padded)
  k_packed (P, T/cpb, hd)    prefix K codes packed along tokens
  k_scale  (P, T/gs, hd)     per-channel scale per token-group
  k_zero   (P, T/gs, hd)
  v_packed (P, T, hd/cpb)    prefix V codes packed along channels
  v_scale  (P, T, hd/gv)     per-token scale per channel-group
  v_zero   (P, T, hd/gv)
  k_chunk  (P, C, hd)        the chunk's own keys (full precision)
  v_chunk  (P, C, hd)
  cur_len  (P, 1) int32      valid prefix length (mask >= cur_len)
  out      (P, C, hd)

Grid: (P, T/Tb); the prefix-token dim is sequential ("arbitrary") with
the flash running max / sum / accumulator carried in VMEM scratch across
T-steps. Prefix columns are fully visible to every chunk row (all prefix
positions precede the chunk), so no causal test is needed until the LAST
step, which folds in the chunk's own (C, C) causally-masked scores and
finalizes. VMEM per step at Tb=256, hd=128, C=128, 2-bit: ~0.4 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attn.kernel import (
    _expand_groups_cols, _expand_groups_rows, _unpack_cols, _unpack_rows,
)

DEFAULT_TB = 256
NEG_INF = -1e30


def _fused_chunk_kernel(cur_len_ref, q_ref, kp_ref, ks_ref, kz_ref,
                        vp_ref, vs_ref, vz_ref, kc_ref, vc_ref, out_ref,
                        m_ref, l_ref, acc_ref, *,
                        bits: int, k_group: int, v_group: int,
                        tb: int, c: int, hd: int):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (C, hd)

    def _update(scores, v):
        """One flash step: fold (C, Kb) scores and (Kb, hd) values into
        the running (m, l, acc) scratch."""
        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)        # (C, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + p @ v
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    # --- dequantize the packed prefix K/V block in VREGs: (Tb, hd) ---
    k_codes = _unpack_rows(kp_ref[0], bits, tb)
    k_scale = _expand_groups_rows(ks_ref[0], k_group, tb)
    k_zero = _expand_groups_rows(kz_ref[0], k_group, tb)
    k = k_codes * k_scale + k_zero
    v_codes = _unpack_cols(vp_ref[0], bits, hd)
    v_scale = _expand_groups_cols(vs_ref[0], v_group, hd)
    v_zero = _expand_groups_cols(vz_ref[0], v_group, hd)
    v = v_codes * v_scale + v_zero                 # (Tb, hd)

    scores = (q @ k.T) * (hd ** -0.5)              # (C, Tb) -> MXU
    token0 = t_idx * tb
    tok = token0 + jax.lax.broadcasted_iota(jnp.int32, (1, tb), 1)
    valid = tok < cur_len_ref[0, 0]                # resident prefix only
    _update(jnp.where(valid, scores, NEG_INF), v)

    @pl.when(t_idx == pl.num_programs(1) - 1)
    def _chunk_self_and_finalize():
        # the chunk's own keys: causal (C, C) block, then normalize
        kc = kc_ref[0].astype(jnp.float32)
        vc = vc_ref[0].astype(jnp.float32)
        sc = (q @ kc.T) * (hd ** -0.5)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
        _update(jnp.where(kpos <= qpos, sc, NEG_INF), vc)
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def fused_chunk_prefill(q, k_packed, k_scale, k_zero,
                        v_packed, v_scale, v_zero,
                        k_chunk, v_chunk, cur_len, *,
                        bits: int, k_group: int, v_group: int,
                        tb: int = DEFAULT_TB, interpret: bool = True):
    """q/k_chunk/v_chunk: (P, C, hd); packed prefix per module doc;
    cur_len: (P, 1) int32. Returns (P, C, hd) f32."""
    p_dim, c, hd = q.shape
    t = v_packed.shape[1]
    tb = min(tb, t)
    assert t % tb == 0 and tb % k_group == 0, (t, tb, k_group)
    cpb = 8 // bits
    grid = (p_dim, t // tb)
    kern = functools.partial(_fused_chunk_kernel, bits=bits,
                             k_group=k_group, v_group=v_group,
                             tb=tb, c=c, hd=hd)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),                 # cur_len
            pl.BlockSpec((1, c, hd), lambda i, j: (i, 0, 0)),          # q
            pl.BlockSpec((1, tb // cpb, hd), lambda i, j: (i, j, 0)),  # kp
            pl.BlockSpec((1, tb // k_group, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tb // k_group, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tb, hd // cpb), lambda i, j: (i, j, 0)),  # vp
            pl.BlockSpec((1, tb, hd // v_group), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tb, hd // v_group), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, c, hd), lambda i, j: (i, 0, 0)),          # kc
            pl.BlockSpec((1, c, hd), lambda i, j: (i, 0, 0)),          # vc
        ],
        out_specs=pl.BlockSpec((1, c, hd), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p_dim, c, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((c, 1), jnp.float32),      # running max
            pltpu.VMEM((c, 1), jnp.float32),      # running denom
            pltpu.VMEM((c, hd), jnp.float32),     # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cur_len, q, k_packed, k_scale, k_zero,
      v_packed, v_scale, v_zero, k_chunk, v_chunk)
