"""Share of the traced segment in which the device was idle while the
program's ``prefix_match`` span (``PagedPrefixCache.match_prefix``: the
page fetches, their tier reads and KIVI decompression, and the join of
the pages) was open on the host, in %. Nothing is returned when the
segment holds no prefix match."""
from bench import program_trace, trace


def read(ctx):
    p = program_trace.program(ctx)
    if p is None:
        return None
    t0, t1 = p["t0"], p["t1"]
    match = trace.intervals(trace.clip(
        [(n, s, d) for n, s, d, _ in p["spans"] if n == "prefix_match"],
        t0, t1))
    if not match or t1 <= t0:
        return None
    idle = trace.gaps(ctx["device_events"], t0, t1)
    both, i, j = 0.0, 0, 0
    while i < len(idle) and j < len(match):     # both sorted and disjoint
        both += max(0.0, min(idle[i][1], match[j][1])
                    - max(idle[i][0], match[j][0]))
        if idle[i][1] < match[j][1]:
            i += 1
        else:
            j += 1
    return 100.0 * both / (t1 - t0)
