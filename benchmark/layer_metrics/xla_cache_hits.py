"""Backend compiles of set-up that JAX's persistent cache served instead
(``jax.monitoring`` cache-hit events, counted until set-up ends)."""


def read(ctx):
    return ctx["compile_clock"].cache_hits
