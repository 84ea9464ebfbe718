"""Calls of flash attention with grouped heads (fewer key/value heads than
query heads) that the program traced on a path that repeats K or V to the
query heads: a [B, H_q, T, D] copy of each, which its Pallas kernels never
make (the program's ``pallas_flash.kv_repeated`` counter, at trace time;
the XLA and refusal paths count there). 0 is the number to expect. A
program that traced no grouped call (``pallas_flash.grouped``), or has no
such counter, has nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not telemetry.value("pallas_flash.grouped"):
        return None
    return telemetry.value("pallas_flash.kv_repeated")
