"""Seconds JAX spent tracing, lowering and compiling during set-up
(``jax.monitoring`` durations, summed by ``benchmark/compile_clock.py`` and
read when set-up ends: what the reference compiles after the window is no
part of ``setup_s`` and is left out here too)."""


def read(ctx):
    return ctx["compile_clock"].seconds
