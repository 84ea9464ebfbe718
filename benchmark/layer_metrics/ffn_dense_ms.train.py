"""Device milliseconds a step, over forward, recomputed forward and backward,
in the dense feed-forward blocks: a layer's gated or plain MLP (``mlp*``)
and the routed layer's shared expert with its gate (``moe.shared``,
``moe.shared_gate``). One of the six layer kinds of
``benchmark/step_scopes.py`` (``kind_of`` has the path rules); with
``optimizer_ms.train`` and the unattributed share they account for the whole
step. A program without the operation table, or a run without a trace, has
nothing to read."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.kind_ms(ctx, "ffn_dense")
