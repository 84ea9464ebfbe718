"""Calls of flash attention that the program traced on a path other than
its Pallas kernels, forward and backward, by the time the window closed
(the program's ``pallas_flash.xla`` + ``.fallback`` + ``.bwd_xla`` +
``.bwd_fallback`` counters; they count at trace time, and nothing traces
after set-up). 0 is the number to expect. A program that never counted a
forward kernel either has no such counters: nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    names = ("xla", "fallback", "bwd_xla", "bwd_fallback")
    if not any(telemetry.value("pallas_flash." + n)
               for n in names + ("pallas",)):
        return None
    return sum(telemetry.value("pallas_flash." + n) for n in names)
