"""Seconds of the program's own import (``mxtpu.import``: the first line of
``mxtpu/__init__.py`` to its last; JAX's import is outside, the harness
imported it before)."""
from benchmark import setup_ring


def read(ctx):
    return setup_ring.phase_s(ctx, "import")
