"""Mean, over the requests that arrived in the traced segment, of the
time each waited for a lane, in milliseconds: from its ``lane_wait`` mark
(the engine found no free lane when it arrived; ``serving/engine.py``) to
the start of its ``dispatch`` span (a lane was reserved and its load or
prefill booked). A request that found a lane has no mark and counts 0.
Nothing is returned where the segment holds no ``arrival`` mark."""
from bench import program_trace


def read(ctx):
    p = program_trace.program(ctx)
    if p is None:
        return None
    arrived, marked, dispatched = [], {}, {}
    for name, s, _, args in p["spans"]:
        if name == "arrival" and p["t0"] <= s <= p["t1"]:
            arrived.append(args["req_id"])
        elif name == "lane_wait":
            marked.setdefault(args["req_id"], s)
        elif name == "dispatch":
            dispatched.setdefault(args["req_id"], []).append(s)
    if not arrived:
        return None
    waits = []
    for r in arrived:
        if r not in marked:
            waits.append(0.0)
            continue
        # its first dispatch after the mark; the segment's end bounds a
        # request still waiting there
        later = [d for d in dispatched.get(r, []) if d >= marked[r]]
        waits.append(min(later, default=p["t1"]) - marked[r])
    return 1e-6 * sum(waits) / len(waits)
