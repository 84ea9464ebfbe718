"""Calls of sparse attention that the program traced on a plain path, which
holds the [heads, T, T] scores, forward or backward, by the time the window
closed (the program's reason-tagged ``sparse_attention.fallbacks`` counter,
summed; it counts at trace time, and nothing traces after set-up). 0 is the
number to expect. A program that traced no sparse call
(``sparse_attention.calls``), or has no such counter, has nothing to
read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not telemetry.value("sparse_attention.calls"):
        return None
    return telemetry.value("sparse_attention.fallbacks")
