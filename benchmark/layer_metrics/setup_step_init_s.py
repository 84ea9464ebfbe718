"""Seconds of the first ``train_step.init`` (``ShardedTrainStep.__init__``):
every leaf placed, the optimizer's state made for each trainable leaf (an
eager program a distinct shape) and placed."""
from benchmark import setup_ring


def read(ctx):
    return setup_ring.phase_s(ctx, "step_init")
