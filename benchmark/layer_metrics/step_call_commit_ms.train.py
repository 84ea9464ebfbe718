"""Median of ``train_step.commit``: writing the new parameters back into
the block, wrapping the loss, and releasing the step's donated inputs
(some hundreds of arrays whose last reference is dropped there)."""
from benchmark import span_ring


def read(ctx):
    return span_ring.call_ms(ctx, "train_step.commit")
