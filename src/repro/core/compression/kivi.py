"""KIVI compression method (quantization arm of AdaptCache).

Wraps repro.kernels.kivi: K per-channel / V per-token asymmetric group
quantization at 8/4/2 bits. Rate ladder is analytic:
    r(bits) = bits/(8*itemsize) + 2*4/(group*itemsize)   (codes + scale/zero)
SSM entries (no token axis) are quantized per-row-group — quant-only archs
(falcon-mamba) use this arm; token dropping is inapplicable (DESIGN.md §6).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression.base import (
    CompressedEntry, CompressionMethod, KVData, kv_nbytes,
)
from repro.kernels.kivi import ops as kivi_ops
from repro.runtime.spans import span

BITS_LADDER = (8, 4, 2)


class KIVICompression(CompressionMethod):
    name = "kivi"

    def __init__(self, group_size: int = 64):
        self.group_size = group_size

    # -- rate bookkeeping ----------------------------------------------------
    def _rate_for_bits(self, kv: KVData, bits: int) -> float:
        return self.estimate_quantized_nbytes(kv, bits) / max(kv_nbytes(kv), 1)

    def _bits_for_rate(self, kv: KVData, rate: float) -> int:
        pairs = [(abs(self._rate_for_bits(kv, b) - rate), b) for b in BITS_LADDER]
        return min(pairs)[1]

    def rates(self, kv: Optional[KVData] = None) -> Sequence[float]:
        if kv is None:
            # nominal fp32 entry rates
            return tuple((b / 32) + 8 / (self.group_size * 4) for b in BITS_LADDER)
        return tuple(self._rate_for_bits(kv, b) for b in BITS_LADDER)

    def estimate_quantized_nbytes(self, kv: KVData, bits: int) -> int:
        total = 0
        for name, a in kv.items():
            if name == "positions":
                total += a.nbytes
                continue
            rows = int(np.prod(a.shape[:-1], dtype=np.int64))
            f = a.shape[-1]
            axis = _axis_for(name)
            g = _round_group(min(self.group_size, rows if axis == 0 else f),
                             bits)
            if axis == 0:
                rows_p = -(-rows // g) * g
                codes = rows_p * f * bits // 8
                n_groups = (rows_p // g) * f
            else:
                f_p = -(-f // g) * g
                codes = rows * f_p * bits // 8
                n_groups = rows * (f_p // g)
            total += codes + n_groups * 2 * 4
        return int(total)

    def estimate_nbytes(self, kv: KVData, rate: float) -> int:
        return self.estimate_quantized_nbytes(kv, self._bits_for_rate(kv, rate))

    # -- compress / decompress ------------------------------------------------
    def compress(self, kv: KVData, rate: float,
                 bits: Optional[int] = None) -> CompressedEntry:
        bits = bits if bits is not None else self._bits_for_rate(kv, rate)
        arrays: Dict[str, np.ndarray] = {}
        meta = {"bits": bits, "group": {}, "shape": {}, "axis": {},
                "dtype": {}}
        for name, a in kv.items():
            if name == "positions":
                arrays[name] = np.asarray(a)
                continue
            axis = _axis_for(name)
            mat, lead_shape = _to_2d(a)
            g = _round_group(min(self.group_size, mat.shape[axis]), bits)
            # pad the grouped axis to a multiple of the group size
            dim = mat.shape[axis]
            pad = (-dim) % g
            if pad:
                widths = [(0, pad), (0, 0)] if axis == 0 else [(0, 0), (0, pad)]
                mat = np.pad(mat, widths)
            rows, cols = mat.shape if axis == 0 else mat.shape[::-1]
            with span("kivi_quantize", rows=rows, cols=cols, bits=bits,
                      group=g):
                qt = kivi_ops.quantize(jnp.asarray(mat), bits, g, axis)
                arrays[f"{name}.packed"] = np.asarray(qt.packed)
                arrays[f"{name}.scale"] = np.asarray(qt.scale)
                arrays[f"{name}.zero"] = np.asarray(qt.zero)
            meta["group"][name] = g
            meta["shape"][name] = a.shape
            meta["axis"][name] = axis
            meta["dtype"][name] = str(a.dtype)
        true_rate = sum(v.nbytes for v in arrays.values()) / max(kv_nbytes(kv), 1)
        return CompressedEntry(self.name, true_rate, arrays, meta)

    def decompress(self, entry: CompressedEntry) -> KVData:
        out: KVData = {
            name: np.asarray(mat).reshape(entry.meta["shape"][name])
            .astype(entry.meta["dtype"][name])
            for name, mat in self.decompress_device(entry,
                                                    jnp.asarray).items()}
        if "positions" in entry.arrays:
            out["positions"] = entry.arrays["positions"]
        return out

    def decompress_device(self, entry: CompressedEntry, put) -> Dict:
        """The codes, scales and zeros go to the device; the dequantized
        float32 stays there."""
        out = {}
        for name, shape in entry.meta["shape"].items():
            rows = int(np.prod(shape[:-1], dtype=np.int64))
            mat = self._dequantize(entry, name, put)
            if mat.shape != (rows, shape[-1]):      # strip padding
                mat = _strip(mat, rows, shape[-1])
            out[name] = mat
        return out

    def _dequantize(self, entry: CompressedEntry, name: str, put):
        """One array's float32 matrix on the device, with the padding
        ``compress`` added; ``put`` sends each stored array there."""
        from repro.kernels.kivi.ref import Quantized
        shape = entry.meta["shape"][name]
        axis = entry.meta["axis"][name]
        bits = entry.meta["bits"]
        g = _round_group(entry.meta["group"][name], bits)
        packed = entry.arrays[f"{name}.packed"]
        # padded dims as stored
        if axis == 0:
            rows = int(np.prod(shape[:-1], dtype=np.int64))
            padded_dim = -(-rows // g) * g
        else:
            padded_dim = -(-shape[-1] // g) * g
        # the kernel's (rows, cols) of unpacked codes
        p_rows, p_cols = packed.shape if axis == 0 else packed.shape[::-1]
        with span("kivi_dequantize", rows=p_rows * (8 // bits),
                  cols=p_cols, bits=bits, group=g):
            qt = Quantized(put(packed), put(entry.arrays[f"{name}.scale"]),
                           put(entry.arrays[f"{name}.zero"]),
                           bits, g, axis, padded_dim)
            return kivi_ops.dequantize(qt)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _strip(mat: jax.Array, rows: int, cols: int) -> jax.Array:
    return mat[:rows, :cols]


def _axis_for(name: str) -> int:
    """KIVI: K per-channel (grouped along tokens, axis 0); V and state
    tensors per-row (grouped along the feature axis)."""
    return 0 if name == "k" else 1


def _to_2d(a: np.ndarray):
    """(L, T, F) -> (L*T, F); already-2d stays."""
    if a.ndim == 2:
        return a, a.shape
    return a.reshape(-1, a.shape[-1]), a.shape


def _round_group(g: int, bits: int) -> int:
    """group size must be a positive multiple of codes-per-byte (packing
    keeps each group's codes byte-aligned)."""
    cpb = 8 // bits
    return max(cpb, (g // cpb) * cpb)
