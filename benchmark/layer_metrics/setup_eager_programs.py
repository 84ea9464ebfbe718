"""How many programs ``setup_eager_compile_s`` is about: the
``jax.backend_compile`` events of that same set, one a program whether it
was compiled or loaded from the cache."""
from benchmark import setup_ring


def read(ctx):
    found = setup_ring.eager_compiles(ctx)
    return None if found is None else found[1]
