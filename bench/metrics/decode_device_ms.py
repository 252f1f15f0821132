"""Median, over the traced segment's ``decode_tick`` spans, of the device
time of the operations of that tick's decode step, in milliseconds: the
busy union of the operations owned by the ``decode_step`` module or
scope (``Model.decode_step`` runs under ``jax.named_scope("decode_step")``
and is jitted as ``jit_decode_step``) that start inside the tick's host
span. Ticks with no such operation are left out; nothing is returned
when none has one."""
import bisect
import statistics

from bench import program_trace, trace


def read(ctx):
    p = program_trace.program(ctx)
    if p is None:
        return None
    ops = sorted((o for o in p["ops"] if "decode_step" in o[3]),
                 key=lambda o: o[1])
    starts = [o[1] for o in ops]
    per_tick = []
    for name, s, d, _ in p["spans"]:
        if name != "decode_tick" or not p["t0"] <= s <= p["t1"]:
            continue
        lo = bisect.bisect_left(starts, s)
        hi = bisect.bisect_right(starts, s + d)
        mine = [(n, a, b) for n, a, b, _ in ops[lo:hi]]
        if mine:
            per_tick.append(trace.busy_ns(mine))
    return 1e-6 * statistics.median(per_tick) if per_tick else None

