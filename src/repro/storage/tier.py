"""Storage tiers for the KV cache hierarchy.

Two concrete tiers matching the paper's evaluation (DRAM + SSD) plus the
device spec abstraction so the same policy runs with TPU-host constants
(DESIGN.md §4). Realism requirements honored:

  * DRAMTier holds real numpy buffers (bytes are resident);
  * SSDTier serializes entries to real files (zstd-framed, CRC-checked)
    under a spool directory — bytes genuinely leave memory;
  * delay accounting is a calibrated model (default: the paper's 1 GB/s
    disk; DRAM->device 16 GB/s PCIe-class) so benchmark numbers are
    host-independent; the real I/O shows in a profile as the
    ``tier_get``/``tier_put`` spans.
"""
from __future__ import annotations

import dataclasses
import os
import struct
import tempfile
import zlib
from typing import Any, Dict, Iterable, Optional, Tuple

import numpy as np
import zstandard

from repro.core.compression.base import CompressedEntry
from repro.runtime.spans import span


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    name: str
    capacity_bytes: int
    read_bps: float          # bytes/s toward the accelerator
    write_bps: float
    latency_s: float = 0.0


# Paper constants: 100 GB DRAM, 400 GB SSD @ 1 GB/s (A100 box, §3).
PAPER_DRAM = DeviceSpec("dram", 100 << 30, 16e9, 16e9, 20e-6)
PAPER_SSD = DeviceSpec("ssd", 400 << 30, 1e9, 1e9, 100e-6)


class Tier:
    """Base tier: capacity accounting + load/store delay models.

    ``load_delay_s`` prices the read path (fetch toward the accelerator);
    ``store_delay_s`` prices the write path and is the service time the
    event engine books on the tier's write ``IOChannel`` for insert
    write-back, MCKP demotions, and prefetch promotions — writes queue
    and contend in simulated time instead of landing instantly.
    ``written_bytes`` counts every byte that entered the tier via
    ``put`` (write-traffic accounting — under a half-duplex topology
    these writes share the read direction's bandwidth budget).

    Tier identity is ``(level, replica)``: ``name`` follows the
    ``StorageTopology`` convention (``dram`` / ``dram:<r>`` / ``ssd``),
    so a per-replica DRAM tier knows which replica owns it and the
    shared SSD has no owner.
    """

    def __init__(self, spec: DeviceSpec, name: Optional[str] = None):
        self.spec = spec
        self.name = spec.name if name is None else name
        self.used_bytes = 0
        self.written_bytes = 0
        self._meta: Dict[str, Dict[str, Any]] = {}

    @property
    def identity(self) -> "Tuple[int, Optional[int]]":
        """``(level, replica)`` per the StorageTopology naming scheme."""
        from repro.storage.topology import StorageTopology
        return StorageTopology.ident(self.name)

    @property
    def replica(self) -> Optional[int]:
        return self.identity[1]

    # -- delay model --------------------------------------------------------
    def load_delay_s(self, nbytes: int) -> float:
        return self.spec.latency_s + nbytes / self.spec.read_bps

    def store_delay_s(self, nbytes: int) -> float:
        return self.spec.latency_s + nbytes / self.spec.write_bps

    # -- inventory ----------------------------------------------------------
    def has(self, key: str) -> bool:
        return key in self._meta

    def keys(self) -> Iterable[str]:
        return self._meta.keys()

    def entry_nbytes(self, key: str) -> int:
        return self._meta[key]["nbytes"]

    def entry_info(self, key: str) -> Dict[str, Any]:
        return self._meta[key]

    @property
    def free_bytes(self) -> int:
        return self.spec.capacity_bytes - self.used_bytes

    def __len__(self) -> int:
        return len(self._meta)


class DRAMTier(Tier):
    def __init__(self, spec: DeviceSpec = PAPER_DRAM,
                 name: Optional[str] = None):
        super().__init__(spec, name=name)
        self._store: Dict[str, CompressedEntry] = {}

    def put(self, key: str, entry: CompressedEntry) -> int:
        if key in self._store:
            self.evict(key)
        nb = entry.nbytes
        with span("tier_put", tier=self.name, nbytes=nb):
            self._store[key] = entry
        self._meta[key] = {"nbytes": nb, "method": entry.method,
                           "rate": entry.rate}
        self.used_bytes += nb
        self.written_bytes += nb
        return nb

    def get(self, key: str) -> CompressedEntry:
        with span("tier_get", tier=self.name,
                  nbytes=self._meta[key]["nbytes"]):
            return self._store[key]

    def evict(self, key: str) -> None:
        self.used_bytes -= self._meta.pop(key)["nbytes"]
        del self._store[key]


_MAGIC = b"ADKV"
_HEADER = struct.Struct("<IQ")           # CRC32(raw), raw length


class SSDTier(Tier):
    """File-backed tier: one zstd-framed, CRC-checked file per entry."""

    def __init__(self, spec: DeviceSpec = PAPER_SSD,
                 root: Optional[str] = None,
                 name: Optional[str] = None):
        super().__init__(spec, name=name)
        self.root = root or tempfile.mkdtemp(prefix="adaptcache_ssd_")
        self._cctx = zstandard.ZstdCompressor(level=1)
        self._dctx = zstandard.ZstdDecompressor()
        os.makedirs(self.root, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key.replace("/", "_") + ".kv")

    def put(self, key: str, entry: CompressedEntry) -> int:
        if key in self._meta:
            self.evict(key)
        # capacity accounting uses the LOGICAL entry size (policy view);
        # frame compression is transparent transport compression.
        nb = entry.nbytes
        with span("tier_put", tier=self.name, nbytes=nb):
            raw = entry.tobytes()
            framed = self._cctx.compress(raw)
            crc = zlib.crc32(raw)
            path = self._path(key)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write(_HEADER.pack(crc, len(raw)))
                f.write(framed)
            os.replace(tmp, path)                   # atomic
        self._meta[key] = {"nbytes": nb, "method": entry.method,
                           "rate": entry.rate, "meta": entry.meta,
                           "disk_bytes": len(framed) + 4 + _HEADER.size,
                           "path": path}
        self.used_bytes += nb
        self.written_bytes += nb
        return nb

    def get(self, key: str) -> CompressedEntry:
        info = self._meta[key]
        with span("tier_get", tier=self.name, nbytes=info["nbytes"]):
            with open(info["path"], "rb") as f:
                assert f.read(4) == _MAGIC, f"corrupt frame for {key}"
                crc, orig_len = _HEADER.unpack(f.read(_HEADER.size))
                raw = self._dctx.decompress(f.read(),
                                            max_output_size=orig_len)
            if zlib.crc32(raw) != crc:
                raise IOError(
                    f"CRC mismatch for entry {key} — corrupt SSD page")
            return CompressedEntry.frombytes(raw, info["method"],
                                             info["rate"], info["meta"])

    def evict(self, key: str) -> None:
        info = self._meta.pop(key)
        self.used_bytes -= info["nbytes"]
        try:
            os.unlink(info["path"])
        except FileNotFoundError:
            pass
