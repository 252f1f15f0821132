"""Share of the traced segment in which no operation ran on the device,
in %: 1 - (union of the device's operation intervals) / (segment)."""


def read(ctx):
    if ctx["traced_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["traced_s"])
