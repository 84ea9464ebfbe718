"""Calls of Gated DeltaNet's recurrence that the program traced on the
plain path (the same chunks under ``vmap`` and a scan, no kernel), by the
time the window closed (the program's reason-tagged
``gated_delta.fallbacks`` counter, summed; it counts at trace time, and
nothing traces after set-up). 0 is the number to expect. A program that
traced no such call (``gated_delta.calls``), or has no such counter, has
nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not telemetry.value("gated_delta.calls"):
        return None
    return telemetry.value("gated_delta.fallbacks")
