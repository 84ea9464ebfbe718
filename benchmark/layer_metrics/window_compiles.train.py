"""Python traces plus backend compiles JAX reported between the first and
the last call of the measured window (``jax.trace`` and
``jax.backend_compile`` events of the program): nothing may compile there,
so the number to expect is 0."""
from benchmark import span_ring


def read(ctx):
    return span_ring.window_compiles(ctx)
