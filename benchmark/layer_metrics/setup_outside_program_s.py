"""``setup_s`` less the union of every span of the program before the
window: what no change to the program can shorten (the harness's imports,
JAX's among them, the backend, seeded weights and batches, the first
gradient fetched), plus any program code that still runs under no span."""
from benchmark import setup_ring


def read(ctx):
    return setup_ring.outside_program_s(ctx)
