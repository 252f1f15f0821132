"""Pallas TPU kernel: KIVI group-wise asymmetric quantization + bit-packing.

TPU mapping: quantization is pure VPU elementwise work over (sublane,
lane) = (tokens, channels) tiles. The (T, F) input is viewed as
(T / group, group, F), so one grid step sees one quant GROUP of rows and
the min/max reduction is a sublane reduce. Every block's last two dims
are either the full array dims (group, group/codes_per_byte, 1) or a
multiple of 128 lanes, which is what Mosaic's tiling requires for any
group size ``KIVICompression`` can pick.

Grid: (T / group_size, F / 128). The packed codes interleave cpb rows
per byte row; each interleave is a sublane-strided ref load or store,
which Mosaic supports on a 128-lane block. VMEM working set per step is
group_size * 128 * 4 B of input plus the outputs — 32 KB at group 64.

The V-style (per-token) variant transposes at the ops.py layer and reuses
this kernel — one kernel body, both KIVI modes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Mosaic's strided sublane loads and stores need a 128-lane base block
LANE_BLOCK = 128


def _quant_pack_kernel(x_ref, packed_ref, scale_ref, zero_ref, *,
                       bits: int, group_size: int):
    cpb = 8 // bits
    x = x_ref[...].astype(jnp.float32)            # (group_size, LB)
    zero = jnp.min(x, axis=0, keepdims=True)      # (1, LB)
    scale = (jnp.max(x, axis=0, keepdims=True) - zero) / (2 ** bits - 1)
    safe = jnp.where(scale > 0, scale, 1.0)
    # packed row r holds the codes of rows r*cpb + j, j < cpb
    acc = None
    for j in range(cpb):
        xj = x_ref[pl.ds(j, group_size // cpb, stride=cpb), :]
        q = jnp.clip(jnp.round((xj.astype(jnp.float32) - zero) / safe),
                     0, 2 ** bits - 1).astype(jnp.int32)
        q = q << (j * bits)
        acc = q if acc is None else acc | q
    packed_ref[...] = acc.astype(jnp.uint8)
    scale_ref[...] = scale
    zero_ref[...] = zero


def _dequant_kernel(packed_ref, scale_ref, zero_ref, out_ref, *,
                    bits: int, group_size: int, out_dtype):
    cpb = 8 // bits
    packed = packed_ref[...].astype(jnp.int32)    # (group/cpb, LB)
    mask = 2 ** bits - 1
    scale, zero = scale_ref[...], zero_ref[...]
    for j in range(cpb):
        q = ((packed >> (j * bits)) & mask).astype(jnp.float32)
        out_ref[pl.ds(j, group_size // cpb, stride=cpb), :] = (
            q * scale + zero).astype(out_dtype)


def quantize_pallas(x: jax.Array, bits: int, group_size: int,
                    interpret: bool = True):
    """x: (T, F) grouped along axis 0 (K-style). Returns (packed, scale, zero)."""
    t, f = x.shape
    assert t % group_size == 0 and f % LANE_BLOCK == 0, (x.shape, group_size)
    lb = LANE_BLOCK
    cpb = 8 // bits
    g = t // group_size
    kernel = functools.partial(_quant_pack_kernel, bits=bits,
                               group_size=group_size)
    packed, scale, zero = pl.pallas_call(
        kernel,
        grid=(g, f // lb),
        in_specs=[pl.BlockSpec((None, group_size, lb),
                               lambda i, j: (i, 0, j))],
        out_specs=[
            pl.BlockSpec((None, group_size // cpb, lb),
                         lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, lb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, lb), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, group_size // cpb, f), jnp.uint8),
            jax.ShapeDtypeStruct((g, 1, f), jnp.float32),
            jax.ShapeDtypeStruct((g, 1, f), jnp.float32),
        ],
        interpret=interpret,
    )(x.reshape(g, group_size, f))
    return (packed.reshape(t // cpb, f), scale.reshape(g, f),
            zero.reshape(g, f))


def dequantize_pallas(packed: jax.Array, scale: jax.Array, zero: jax.Array,
                      bits: int, group_size: int, out_dtype=jnp.float32,
                      interpret: bool = True) -> jax.Array:
    tp, f = packed.shape
    cpb = 8 // bits
    t = tp * cpb
    lb = LANE_BLOCK
    g = t // group_size
    kernel = functools.partial(_dequant_kernel, bits=bits,
                               group_size=group_size, out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(g, f // lb),
        in_specs=[
            pl.BlockSpec((None, group_size // cpb, lb),
                         lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, lb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, lb), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, group_size, lb),
                               lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((g, group_size, f), out_dtype),
        interpret=interpret,
    )(packed.reshape(g, group_size // cpb, f), scale.reshape(g, 1, f),
      zero.reshape(g, 1, f))
    return out.reshape(t, f)
