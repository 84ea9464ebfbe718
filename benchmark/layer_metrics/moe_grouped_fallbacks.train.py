"""Grouped products of the routed expert layers that the program traced on
XLA's ``ragged_dot`` and not on the Pallas grouped-matmul kernel, by the
time the window closed (the program's reason-tagged ``moe.grouped_mm.xla``
counter, summed; it counts at trace time, and nothing traces after
set-up). 0 is the number to expect. A program that traced no expert layer
(``moe.layers``), or one from before the kernel (no ``moe.grouped_mm.pallas``
and no ``moe.grouped_mm.xla``), has nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not telemetry.value("moe.layers"):
        return None
    left = telemetry.value("moe.grouped_mm.xla")
    if not left and not telemetry.value("moe.grouped_mm.pallas"):
        return None
    return left
