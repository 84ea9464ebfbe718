"""Seconds of weights going into the block before the window: the union of
``gluon.param.set_data`` (one a leaf), ``gluon.param.init`` and
``gluon.cast``, outside ``train_step.init`` and the first steps."""
from benchmark import setup_ring


def read(ctx):
    return setup_ring.phase_s(ctx, "param_load")
