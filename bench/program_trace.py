"""The program's own side of a traced run's xplane file: its
``adaptcache/`` spans (``repro.runtime.spans``) with their arguments, and
the device operations, each with the program that owns it.

The readers of the program's spans get them from ``program(ctx)``. A
harness may hand them over as ``ctx["program"]``; where it does not, the
traced segment's file is found as the ``trace_dir`` of the
``harness.per_layer`` call that is reading the metrics, read once, and
kept in ``ctx`` for the readers after. A trace of a program without spans
gives an empty list, so its readers return None.
"""
from __future__ import annotations

import bisect
import sys
from typing import Dict, List, Optional, Tuple

from bench import trace

PROGRAM_PREFIX = "adaptcache/"
# name, start_ns, duration_ns, arguments
Span = Tuple[str, float, float, Dict[str, object]]
# name, start_ns, duration_ns, owner: "<module>" or "<module>/<scope>"
Op = Tuple[str, float, float, str]


def load_program(path: str, host_as_device: bool = False
                 ) -> Tuple[List[Span], Dict[str, List[Op]]]:
    """(program spans, device operations by device plane) from one xplane
    file.

    A span is an ``adaptcache/`` ``TraceAnnotation`` of any host thread,
    its name without the prefix, with its arguments. An operation's owner
    is its XLA module (the ``hlo_module`` statistic, or else the event of
    the plane's "XLA Modules" line that holds the operation's start) and,
    where the trace gives it, the ``named_scope`` path of the operation
    (the ``tf_op`` statistic). ``host_as_device`` as in ``trace.load``."""
    import jax

    def op(e, modules) -> Op:
        stats = dict(e.stats)
        module = str(stats.get("hlo_module", ""))
        k = bisect.bisect_right(modules, (e.start_ns, float("inf"))) - 1
        if not module and k >= 0 and e.start_ns < modules[k][1]:
            module = modules[k][2]
        scope = str(stats.get("tf_op", ""))
        return (e.name, float(e.start_ns), float(e.duration_ns),
                f"{module}/{scope}" if scope else module)

    data = jax.profiler.ProfileData.from_file(path)
    spans: List[Span] = []
    ops: Dict[str, List[Op]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {line.name: list(line.events) for line in plane.lines}
            # (start, end, name) of each module run, by start
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in lines.get("XLA Modules", []))
            ops[plane.name] = [op(e, modules)
                               for e in lines.get("XLA Ops", [])]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if host_as_device and line.name.startswith("tf_XLA"):
                    ops.setdefault(plane.name, []).extend(
                        op(e, []) for e in line.events)
                    continue
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name[len(PROGRAM_PREFIX):],
                                      float(e.start_ns),
                                      float(e.duration_ns), dict(e.stats)))
    spans.sort(key=lambda sp: sp[1])
    return spans, ops


def _traced_run() -> Optional[Tuple[str, bool]]:
    """(trace directory, rehearsal) of the ``per_layer`` call up the
    stack, or None outside one."""
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "per_layer" and "trace_dir" in f.f_locals:
            return f.f_locals["trace_dir"], bool(f.f_locals.get("rehearse"))
        f = f.f_back
    return None


def program(ctx: dict) -> Optional[dict]:
    """``{"spans", "ops", "t0", "t1"}``: the program's spans, the device
    operations of the plane the harness reads, and the traced segment in
    the trace's ns; None where no trace is found.

    On first reading it logs on stderr the segment's ten longest idle
    gaps, each labelled by the innermost program span open over it (an
    ``event`` span by its ``kind``)."""
    if "program" in ctx:
        return ctx["program"]
    run = _traced_run()
    seg = [s for s in ctx.get("host_spans", []) if s[0] == "segment"]
    if run is None or not seg:
        return None
    trace_dir, rehearse = run
    spans, ops = load_program(trace.find_xplane(trace_dir),
                              host_as_device=rehearse)
    t0, t1 = seg[0][1], seg[0][1] + seg[0][2]
    # the first plane with an operation in the segment, as the harness takes
    plane = next((v for v in ops.values()
                  if trace.clip([o[:3] for o in v], t0, t1)), [])
    ctx["program"] = {"spans": spans, "ops": plane, "t0": t0, "t1": t1}
    labels = [(f"{n}:{a.get('kind')}" if n == "event" else n, s, d)
              for n, s, d, a in spans]
    print("idle gaps by program span: " + ", ".join(
        f"{n} {g:.6f} s" for n, g in trace.top_gaps(
            ctx["device_events"], labels, t0, t1)),
        file=sys.stderr, flush=True)
    return ctx["program"]
