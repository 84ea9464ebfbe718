"""Passes of a Kimi-Delta-Attention layer's short filter (the value, or the
two gradients) that the program traced on the plain function under XLA and
not on the Pallas pair ``kda_conv_fwd`` / ``kda_conv_bwd``, by the time the
window closed (the program's reason-tagged ``kda_conv.xla`` counter,
summed; it counts at trace time, and nothing traces after set-up). 0 is
the number to expect. A program that traced no such filter, or one from
before the kernels (no ``kda_conv.calls``), has nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not telemetry.value("kda_conv.calls"):
        return None
    return telemetry.value("kda_conv.xla")
