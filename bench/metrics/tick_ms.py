"""Median host wall time of one decode tick (``ContinuousBatcher.tick``:
one batched decode step over every active lane, ending in the host copy
of the argmax), over the whole window, in milliseconds."""
import statistics


def read(ctx):
    w = ctx["window"]
    d = ctx["hooks"].durations("tick", w["t0"], w["t1"])
    return 1e3 * statistics.median(d) if d else None
