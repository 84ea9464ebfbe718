"""Median of ``train_step.rng``: the eager split of the global PRNG key,
two small device programs a call."""
from benchmark import span_ring


def read(ctx):
    return span_ring.call_ms(ctx, "train_step.rng")
