"""Device milliseconds a step in the backward pass: every operation traced
under ``transpose(`` and not under ``rematted_computation``
(``benchmark/step_scopes.py``: the trace's seconds by instruction joined
with the operation table of the executable the program runs, a switch or a
loop by its self time). With the other three transforms and
``unattributed_step_pct.train`` it accounts for the whole step. A program
without the table, or a run without a trace, has nothing to read."""
from benchmark import step_scopes


def read(ctx):
    return step_scopes.transform_ms(ctx, "backward")
