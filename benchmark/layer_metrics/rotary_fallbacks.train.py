"""Calls of the grouped operators' rotary (q or k of a layer, turned and
laid heads first) that the program traced on the plain function under XLA
and not on the Pallas pair ``rotary_turn`` / ``rotary_unturn``, by the time
the window closed (the program's reason-tagged ``rotary.xla`` counter,
summed; it counts at trace time, and nothing traces after set-up). 0 is the
number to expect where heads are 128 wide. A program that turned nothing
so, or one from before the kernels (no ``rotary.calls``), has nothing to
read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not telemetry.value("rotary.calls"):
        return None
    return telemetry.value("rotary.xla")
