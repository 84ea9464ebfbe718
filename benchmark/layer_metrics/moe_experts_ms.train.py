"""Device milliseconds a step, over forward, recomputed forward and backward,
in the routed experts (``moe.dispatch``, ``moe.experts``, ``moe.combine``
and what else the routed layer runs of its own): the switches' self time and
what runs inside them; 0.0 in a cell without routed layers. One of the six
layer kinds of ``benchmark/step_scopes.py`` (``kind_of`` has the path
rules); with ``optimizer_ms.train`` and the unattributed share they account
for the whole step. A program without the operation table, or a run without
a trace, has nothing to read."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.kind_ms(ctx, "moe_experts")
