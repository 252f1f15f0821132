"""Median host wall time of one real prefill
(``ModelRunner.prefill_entry``: the eager forward pass over a prompt and
the copy of its K/V to the host as float32), over the window, in
milliseconds."""
import statistics


def read(ctx):
    w = ctx["window"]
    d = ctx["hooks"].durations("prefill", w["t0"], w["t1"])
    return 1e3 * statistics.median(d) if d else None
