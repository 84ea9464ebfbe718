"""Decoder blocks the step recomputes in its backward from their inputs
(the program's ``train_step.blocks_recomputed`` counter, at trace time: one
for each block traced under ``jax.checkpoint``). The cell's seven is the
number to expect; a tree that lost the recomputation reads 0 (and would
not fit). A program without the counter, or a model built without
``recompute``, has nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    return telemetry.value("train_step.blocks_recomputed") or None
