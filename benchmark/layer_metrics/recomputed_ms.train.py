"""Device milliseconds a step in a recomputed block's second forward: every
operation traced under ``rematted_computation`` (``jax.checkpoint``'s); 0.0
in a step that recomputes nothing (``benchmark/step_scopes.py``: the trace's
seconds by instruction joined with the operation table of the executable the
program runs, a switch or a loop by its self time). With the other three
transforms and ``unattributed_step_pct.train`` it accounts for the whole
step. A program without the table, or a run without a trace, has nothing to
read."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.transform_ms(ctx, "recomputed")
