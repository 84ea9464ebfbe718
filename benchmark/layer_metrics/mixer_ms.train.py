"""Device milliseconds a step, over forward, recomputed forward and backward,
in a layer's token mixer, whole: the block that is neither a norm, an MLP
nor the routed layer (attention of every kind, KDA, Gated DeltaNet, the
short convolution) with its projections, inner norms, rotary turns, gates,
filters, selection and kernels. One of the six layer kinds of
``benchmark/step_scopes.py`` (``kind_of`` has the path rules); with
``optimizer_ms.train`` and the unattributed share they account for the whole
step. A program without the operation table, or a run without a trace, has
nothing to read."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.kind_ms(ctx, "mixer")
