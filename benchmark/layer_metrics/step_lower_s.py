"""Seconds of lowering to MLIR (``jax.lower``) inside the step's first
build."""
from benchmark import span_ring


def read(ctx):
    return span_ring.first_build_s(ctx, "jax.lower")
