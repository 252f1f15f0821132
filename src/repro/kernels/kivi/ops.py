"""jit'd public wrappers for KIVI quantization.

Dispatch policy:
  * TPU backend      -> compiled Pallas kernel
  * CPU + REPRO_FORCE_PALLAS=1 -> Pallas interpret mode (kernel-path tests)
  * CPU otherwise    -> jnp reference (fast path for the serving engine)

All entry points accept (T, F) arrays; K-style grouping (axis=0) runs the
kernel directly, V-style (axis=1) transposes around the same kernel.
Each runs under a ``named_scope`` (``kivi_quantize``/``kivi_dequantize``)
so that a device trace attributes its operations.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels.kivi import kernel as _k
from repro.kernels.kivi import ref as _r
from repro.kernels.kivi.ref import Quantized, compressed_nbytes  # noqa: F401


def _use_pallas() -> bool:
    if jax.default_backend() == "tpu":
        return True
    return os.environ.get("REPRO_FORCE_PALLAS", "") == "1"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "axis"))
def quantize(x: jax.Array, bits: int, group_size: int, axis: int) -> Quantized:
    with jax.named_scope("kivi_quantize"):
        if not _use_pallas():
            return _r.quantize_ref(x, bits, group_size, axis)
        xx = x.T if axis == 1 else x
        t, f = xx.shape
        padded_f = (-f) % 128
        if padded_f:
            xx = jnp.pad(xx, ((0, 0), (0, padded_f)))
        packed, scale, zero = _k.quantize_pallas(xx, bits, group_size,
                                                 interpret=_interpret())
        if padded_f:
            packed, scale, zero = packed[:, :f], scale[:, :f], zero[:, :f]
        if axis == 1:
            packed, scale, zero = packed.T, scale.T, zero.T
        return Quantized(packed, scale, zero, bits, group_size, axis, t)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def dequantize(qt: Quantized, out_dtype=jnp.float32) -> jax.Array:
    with jax.named_scope("kivi_dequantize"):
        if not _use_pallas():
            return _r.dequantize_ref(qt, out_dtype)
        packed, scale, zero = qt.packed, qt.scale, qt.zero
        if qt.axis == 1:
            packed, scale, zero = packed.T, scale.T, zero.T
        f = packed.shape[1]
        padded_f = (-f) % 128
        if padded_f:
            packed = jnp.pad(packed, ((0, 0), (0, padded_f)))
            scale = jnp.pad(scale, ((0, 0), (0, padded_f)))
            zero = jnp.pad(zero, ((0, 0), (0, padded_f)))
        x = _k.dequantize_pallas(packed, scale, zero, qt.bits, qt.group_size,
                                 out_dtype, interpret=_interpret())
        if padded_f:
            x = x[:, :f]
        return x.T if qt.axis == 1 else x


def quantize_kv(k: jax.Array, v: jax.Array, bits: int, group_size: int = 64):
    """KIVI convention: K per-channel (axis 0), V per-token (axis 1)."""
    return (quantize(k, bits, group_size, 0),
            quantize(v, bits, min(group_size, v.shape[1]), 1))
