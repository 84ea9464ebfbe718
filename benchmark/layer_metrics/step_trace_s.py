"""Seconds of Python tracing (``jax.trace``) inside the step's first
build: the part of set-up no compile cache removes."""
from benchmark import span_ring


def read(ctx):
    return span_ring.first_build_s(ctx, "jax.trace")
