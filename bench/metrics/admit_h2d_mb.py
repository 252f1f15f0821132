"""Mean bytes copied from host to device per lane admission in the traced
segment, in 10^6 B: the ``h2d_bytes`` the program counts on its
``lane_write`` spans (each host array written into the lane, cast on the
host to the cache dtype), over the segment's ``admit`` spans."""
from bench import program_trace


def read(ctx):
    p = program_trace.program(ctx)
    if p is None:
        return None
    seg = [(n, a) for n, s, _, a in p["spans"]
           if p["t0"] <= s <= p["t1"]]
    admits = sum(1 for n, _ in seg if n == "admit")
    if not admits:
        return None
    h2d = sum(a["h2d_bytes"] for n, a in seg if n == "lane_write")
    return 1e-6 * h2d / admits
