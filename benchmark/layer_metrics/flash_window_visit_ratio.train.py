"""(query, key) pairs in the blocks the traced windowed flash calls visit
over the pairs their windows see (the program's
``pallas_flash.window_pairs_visited`` / ``.window_pairs_seen`` counters, at
trace time, from the shapes: a head's pairs of every windowed forward
traced; the backward walks the same pairs in blocks of its own). 1.0 is
the floor, a form that touches the window's pairs alone; blocks of 1,024
read 1.25 under a window of 4,096 over 16,384 positions and 3.94 under one
of 512; a call on the plain path visits the whole square. A program that
traced no windowed call, or has no such counters, has nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    seen = telemetry.value("pallas_flash.window_pairs_seen")
    if not seen:
        return None
    return telemetry.value("pallas_flash.window_pairs_visited") / seen
