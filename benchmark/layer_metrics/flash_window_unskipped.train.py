"""Calls of flash attention with a sliding window that the program traced
on a path that visits the block pairs left of the window (the program's
``pallas_flash.window_unskipped`` counter, at trace time: the XLA path, a
refusal, a fallback of the backward; its Pallas kernels skip those pairs
and fetch nothing for them). 0 is the number to expect. A program that
traced no windowed call (``pallas_flash.windowed``), or has no such
counter, has nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not telemetry.value("pallas_flash.windowed"):
        return None
    return telemetry.value("pallas_flash.window_unskipped")
