"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). JAX reports a v5e chip's
``device_kind`` as "TPU v5 lite". A device that is not in the table is an
error, never a default.
"""
from __future__ import annotations

V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bps": 819e9,
       "hbm_bytes": 16e9, "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
