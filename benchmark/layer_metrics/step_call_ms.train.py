"""Median host time of one ``ShardedTrainStep.__call__`` as the program
times it itself (its ``train_step`` root span), over the calls of the
window made before the profiler may start: ``dispatch_ms.train`` from the
inside, the same calls on the same clock, so the two can be compared."""
from benchmark import span_ring


def read(ctx):
    return span_ring.call_ms(ctx, "train_step")
