"""Seconds of tracing, lowering and backend compile (or cache load) of the
program's small eager programs in set-up: the union of the ``jax.trace`` /
``jax.lower`` / ``jax.backend_compile`` events before the window inside a
program span other than ``train_step.build``. It cuts across the phases;
the harness's own programs (seeded weights and batches) are under no span
of the program and are left out."""
from benchmark import setup_ring


def read(ctx):
    found = setup_ring.eager_compiles(ctx)
    return None if found is None else found[0]
