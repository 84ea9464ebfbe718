"""Device milliseconds a step, over forward, recomputed forward and backward,
in a layer's own: its two norms and its residual sums. One of the six layer
kinds of ``benchmark/step_scopes.py`` (``kind_of`` has the path rules); with
``optimizer_ms.train`` and the unattributed share they account for the whole
step. A program without the operation table, or a run without a trace, has
nothing to read."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.kind_ms(ctx, "layer_glue")
