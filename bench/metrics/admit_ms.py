"""Median host wall time of one lane admission
(``ContinuousBatcher.admit``: the eager per-layer writes of a context's
K/V into its lane of the batched cache), over the window, in
milliseconds."""
import statistics


def read(ctx):
    w = ctx["window"]
    d = ctx["hooks"].durations("admit", w["t0"], w["t1"])
    return 1e3 * statistics.median(d) if d else None
