"""Device milliseconds a step, over forward and backward, in the network's
batch norms (the operations whose innermost block is a ``batchnorm*``: the
statistics, the scaling and their backward); ``benchmark/step_scopes.py``
(``conv_kind_of``). The rest of the step is in the split by transform. A
program without the operation table, or a run without a trace, has nothing
to read."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.kind_ms(ctx, "batchnorm", group="by_conv_kind")
