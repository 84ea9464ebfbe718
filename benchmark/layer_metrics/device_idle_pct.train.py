"""Share of the traced stretch in which no operation ran on the device:
1 - (union of the device's operation intervals) / (traced stretch)."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"] if ctx["trace"] else None
