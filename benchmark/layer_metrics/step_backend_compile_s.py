"""Seconds of backend compile (``jax.backend_compile``) inside the step's
first build. A load from JAX's persistent cache is inside this event; the
program's ``compile.xla_cache_hits{train_step.build}`` says which it was."""
from benchmark import span_ring


def read(ctx):
    return span_ring.first_build_s(ctx, "jax.backend_compile")
