"""Median of ``train_step.launch``: the call of the compiled step, the
dispatch of its some hundreds of arrays."""
from benchmark import span_ring


def read(ctx):
    return span_ring.call_ms(ctx, "train_step.launch")
