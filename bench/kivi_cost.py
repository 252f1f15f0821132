"""Bytes and operations of one KIVI kernel call, from its shapes.

The Pallas kernels (``kernels/kivi``) quantize a (T, F) float32 matrix in
groups of ``group`` rows to ``bits``-bit codes packed 8/bits to a byte,
with one float32 scale and one zero per group and column, and dequantize
back to float32. Each kernel reads its inputs once and writes its outputs
once, so:

    quantize:   read 4*T*F,                  write T*F*bits/8 + 8*T*F/group
    dequantize: read T*F*bits/8 + 8*T*F/group, write 4*T*F

Operations: per element about 8 vector operations to quantize (subtract,
divide, round, clip, shift, or, and the min/max reductions) and 4 to
dequantize (shift, mask, multiply, add). The kernels are bound by bytes
on any TPU, so the count of operations only has to be of the right order.
"""
from __future__ import annotations

QUANT_OPS_PER_ELEM = 8
DEQUANT_OPS_PER_ELEM = 4


def quantize_cost(t: int, f: int, bits: int, group: int):
    """(bytes, ops) of quantizing a (t, f) float32 matrix."""
    n = t * f
    out = n * bits // 8 + 2 * 4 * n // group
    return 4 * n + out, QUANT_OPS_PER_ELEM * n


def dequantize_cost(t: int, f: int, bits: int, group: int):
    """(bytes, ops) of dequantizing back to a (t, f) float32 matrix."""
    n = t * f
    inp = n * bits // 8 + 2 * 4 * n // group
    return inp + 4 * n, DEQUANT_OPS_PER_ELEM * n


def least_time_s(nbytes: float, ops: float, peak_flops: float,
                 hbm_bps: float) -> float:
    """Roofline: the least time the chip could take for the call."""
    return max(nbytes / hbm_bps, ops / peak_flops)
