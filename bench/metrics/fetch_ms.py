"""Median host wall time of one controller fetch
(``AdaptCacheController.fetch``: the tier read — DRAM dict or SSD spool
file with zstd and CRC — and the KIVI decompress of one page), over the
window, in milliseconds."""
import statistics


def read(ctx):
    w = ctx["window"]
    d = ctx["hooks"].durations("fetch", w["t0"], w["t1"])
    return 1e3 * statistics.median(d) if d else None
