"""Median, over the requests admitted in the traced segment, of the time
from a request's ``admitted`` mark (the end of its lane admission) to its
``first_token`` mark (the decode tick that put its first answer token on
the host), in milliseconds: its own question ticks and every other
request's host work that the engine serves meanwhile on its one thread.
Both marks are the program's own (``repro.runtime.spans``), on the
profiler's clock."""
import statistics

from bench import program_trace


def read(ctx):
    p = program_trace.program(ctx)
    if p is None:
        return None
    admitted, first = {}, {}
    for name, s, _, args in p["spans"]:
        if not p["t0"] <= s <= p["t1"]:
            continue
        if name == "admitted":
            admitted.setdefault(args["req_id"], s)
        elif name == "first_token":
            first.setdefault(args["req_id"], s)
    waits = [first[r] - a for r, a in admitted.items()
             if r in first and first[r] >= a]
    return 1e-6 * statistics.median(waits) if waits else None
