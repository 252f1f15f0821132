"""Reduction of a ``jax.profiler`` trace to device busy time, idle gaps
by host span, kernel time and the costliest device operations.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
returns two lists of ``(name, start_ns, duration_ns)``: the operations
that ran on each TPU (the "XLA Ops" line of every ``/device:TPU:<n>``
plane) and the benchmark's own host spans (``TraceAnnotation`` events
whose name starts with ``bench:``). The rest works on such lists, so the
arithmetic is tested on synthetic events.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns
SPAN_PREFIX = "bench:"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_as_device: bool = False
         ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device events by device plane, host spans) from one xplane file.

    ``host_as_device`` (CPU rehearsals only) takes the host's XLA worker
    threads for the device, so that the reduction runs without a chip."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            evs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs.extend((e.name, float(e.start_ns), float(e.duration_ns))
                               for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if host_as_device and line.name.startswith("tf_XLA"):
                    devices.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
                    continue
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      float(e.start_ns), float(e.duration_ns)))
    return devices, spans


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to the window [t0, t1]; those outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Union of the events' intervals, sorted and merged."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[float]] = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in intervals(events))


def gaps(events: Iterable[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of the device inside [t0, t1]."""
    out, cur = [], t0
    for a, b in intervals(events):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def label_gap(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """What the host was doing in an idle gap: the innermost (shortest)
    host span that covers at least half of it; "host" when none does."""
    a, b = gap
    best: Optional[Tuple[float, str]] = None
    for name, s, d in spans:
        cover = min(b, s + d) - max(a, s)
        if cover < 0.5 * (b - a) or cover <= 0:
            continue
        if best is None or (d, name) < best:
            best = (d, name)
    return best[1] if best else "host"


def op_name(name: str) -> str:
    """An operation's short name: the HLO instruction before its text
    (``%fusion.12 = bf16[...] ...`` -> ``%fusion.12``)."""
    return name.split(" = ", 1)[0]


def top_ops(events: Iterable[Event], n: int = 10) -> List[Tuple[str, float]]:
    """Device operations by total time, in seconds, largest first."""
    tot: Dict[str, float] = {}
    for name, _, d in events:
        k = op_name(name)
        tot[k] = tot.get(k, 0.0) + d
    return [(k, v * 1e-9) for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(events: Sequence[Event], spans: Sequence[Event], t0: float,
             t1: float, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each labelled by its host span."""
    gs = sorted(gaps(events, t0, t1), key=lambda g: g[0] - g[1])[:n]
    return [(label_gap(g, spans), (g[1] - g[0]) * 1e-9) for g in gs]


def kernel_ns(events: Iterable[Event], names: Sequence[str]) -> float:
    """Total device time of the events whose name contains any of
    ``names``."""
    return sum(d for name, _, d in events if any(k in name for k in names))
