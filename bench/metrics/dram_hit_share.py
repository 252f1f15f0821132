"""Share of the window's fetch attempts that the DRAM tier served, in %,
from the controller's own counters (``hit_<tier>``, ``hits``,
``misses``; pages count one each in paged serving), taken as the
difference between window start and end."""


def read(ctx):
    w = ctx["window"]
    c0, c1 = w.get("counters0"), w.get("counters1")
    if not c0 or not c1:
        return None
    dram = sum(c1[k] - c0.get(k, 0) for k in c1
               if k.startswith("hit_dram"))
    tries = (c1["hits"] - c0["hits"]) + (c1["misses"] - c0["misses"])
    return 100.0 * dram / tries if tries else None
