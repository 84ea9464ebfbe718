"""Expert layers the program traced on the path that multiplies every
token by every expert held, chosen or not (its ``moe.grouped_mm.dense``
counter, at trace time). 0 is the number to expect. A program that traced
no expert layer (``moe.layers``) has nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not telemetry.value("moe.layers"):
        return None
    return telemetry.value("moe.grouped_mm.dense")
