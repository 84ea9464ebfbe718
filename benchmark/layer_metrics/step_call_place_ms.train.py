"""Median of ``train_step.place``: flattening the batch, the policy key
and input signature, the placement of each input and the two scalar
hyper-parameters put on the device, per call."""
from benchmark import span_ring


def read(ctx):
    return span_ring.call_ms(ctx, "train_step.place")
