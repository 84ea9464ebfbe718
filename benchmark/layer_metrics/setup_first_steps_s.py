"""Seconds of the first steps dispatched and waited for in set-up: the union
of the ``train_step`` roots and ``ndarray.asnumpy`` spans before the window,
less the first ``train_step.build`` (the three ``step_*_s`` split that). The
executable's load and its first run are among it."""
from benchmark import setup_ring


def read(ctx):
    return setup_ring.phase_s(ctx, "first_steps")
