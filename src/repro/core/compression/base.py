"""Compression method interface for KV cache entries.

A *KV entry* is the cacheable artifact of one context chunk:
  attention archs: {"k": (L, T, F), "v": (L, T, F)}  (+ "positions": (T,))
  ssm archs:       {"ssm": (L, D, N), "conv": (L, C, D)}  (fixed-size state)

Methods expose a discrete ladder of compression RATES (r = compressed
bytes / original bytes); the AdaptCache policy optimizer picks (method,
rate) per entry via marginal utility (core/policy.py). ``estimate_nbytes``
is analytic — the policy never has to compress to evaluate a candidate.
"""
from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.runtime.spans import span

KVData = Dict[str, np.ndarray]


def kv_nbytes(kv: KVData) -> int:
    return int(sum(a.nbytes for a in kv.values()))


def shape_proxy(kv: KVData) -> KVData:
    """Zero-stride stand-in with the same shapes and dtypes, for size
    estimates: every element aliases one zero, so it holds no storage, but
    any fancy index or copy of it builds a full-size array."""
    return {k: np.broadcast_to(np.zeros((), a.dtype), a.shape)
            for k, a in kv.items()}


def kv_num_tokens(kv: KVData) -> int:
    if "k" in kv:
        return int(kv["k"].shape[1])
    return 0  # ssm state: no token axis


@dataclasses.dataclass
class CompressedEntry:
    method: str
    rate: float                       # nominal compressed/original byte ratio
    arrays: Dict[str, np.ndarray]     # method-specific payload
    meta: Dict[str, Any]              # method-specific (bits, kept idx, ...)

    @property
    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.arrays.values()))

    def tobytes(self) -> bytes:
        """Serialized page payload for the SSD tier."""
        import io
        buf = io.BytesIO()
        np.savez(buf, **self.arrays)
        return buf.getvalue()

    @classmethod
    def frombytes(cls, raw: bytes, method: str, rate: float,
                  meta: Dict[str, Any]) -> "CompressedEntry":
        import io
        with np.load(io.BytesIO(raw)) as z:
            arrays = {k: z[k] for k in z.files}
        return cls(method, rate, arrays, meta)


class CompressionMethod(abc.ABC):
    name: str = "base"

    @abc.abstractmethod
    def rates(self, kv: Optional[KVData] = None) -> Sequence[float]:
        """Supported rate ladder, descending (1.0 first if lossless point)."""

    @abc.abstractmethod
    def compress(self, kv: KVData, rate: float) -> CompressedEntry:
        ...

    @abc.abstractmethod
    def decompress(self, entry: CompressedEntry) -> KVData:
        ...

    def decompress_device(self, entry: CompressedEntry,
                          put: Callable[[np.ndarray], Any]) -> Dict[str, Any]:
        """``decompress`` on the device: each array but ``positions`` as
        its float32 ``(rows, features)`` matrix (an ``(L, T, F)`` array
        as ``(L*T, F)``), with ``put`` sending host arrays to the device.
        This default sends the host-decompressed arrays."""
        return {name: put(a.reshape(-1, a.shape[-1]))
                for name, a in self.decompress(entry).items()
                if name != "positions"}

    @abc.abstractmethod
    def estimate_nbytes(self, kv: KVData, rate: float) -> int:
        """Analytic compressed size — no compression performed."""

    def applicable(self, kv: KVData) -> bool:
        return True

    def closest_rate(self, kv: KVData, rate: float) -> float:
        ladder = list(self.rates(kv))
        return min(ladder, key=lambda r: abs(r - rate))


class NoCompression(CompressionMethod):
    """Identity 'method' — the paper's Without-Compression arm."""
    name = "none"

    def rates(self, kv=None):
        return (1.0,)

    def compress(self, kv: KVData, rate: float) -> CompressedEntry:
        return CompressedEntry("none", 1.0, dict(kv), {})

    def decompress(self, entry: CompressedEntry) -> KVData:
        return dict(entry.arrays)

    def estimate_nbytes(self, kv: KVData, rate: float) -> int:
        return kv_nbytes(kv)


def decompressed_view(entry: CompressedEntry) -> KVData:
    """Shapes of the entry after decompression, without decompressing.

    For drop-based methods the kept-token count lives in the stored
    arrays themselves; we reconstruct shape-only views cheaply."""
    if entry.method in ("none", "streaming_llm"):
        return dict(entry.arrays)
    # kivi / drop_kivi: meta["shape"] holds decompressed shapes
    meta_shape = entry.meta["kivi"]["shape"] if "kivi" in entry.meta \
        else entry.meta["shape"]
    out = {k: np.broadcast_to(np.zeros((), np.float32), s)
           for k, s in meta_shape.items()}
    if "positions" in entry.arrays:
        out["positions"] = entry.arrays["positions"]
    return out


@dataclasses.dataclass(eq=False)
class StoredKV:
    """A fetched entry in its stored form, with the method that reads it.
    The served path writes it into a lane from this form
    (``decompress_device``); ``kv`` decompresses it on the host for every
    other reader, once."""
    entry: CompressedEntry
    codec: CompressionMethod

    @functools.cached_property
    def kv(self) -> KVData:
        with span("decompress", method=self.entry.method):
            return self.codec.decompress(self.entry)

    @property
    def n_tokens(self) -> int:
        """Token rows the entry keeps (0 for state alone)."""
        view = decompressed_view(self.entry)
        for name in ("k", "ckv"):
            if name in view:
                return int(view[name].shape[1])
        return 0

    @property
    def device_nbytes(self) -> int:
        """Bytes ``decompress_device`` sends for the lane: every stored
        array but ``positions``, which stays on the host."""
        return sum(a.nbytes for name, a in self.entry.arrays.items()
                   if name != "positions")
