"""Device milliseconds a step, over forward, recomputed forward and backward,
in what lies outside the stack of layers: the embeddings, the final norm,
the vocabulary head and the loss (``softmax_ce``). One of the six layer
kinds of ``benchmark/step_scopes.py`` (``kind_of`` has the path rules); with
``optimizer_ms.train`` and the unattributed share they account for the whole
step. A program without the operation table, or a run without a trace, has
nothing to read."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.kind_ms(ctx, "head_loss")
