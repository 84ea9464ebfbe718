"""The share of the step's device seconds in operations that the program's
operation table does not hold, or that carry no name (``op_name``) of their
own nor run inside a switch or a loop that has one: the copies the compiler
puts in, mostly. What the four transforms' milliseconds leave of the step
(``benchmark/step_scopes.py``). A program without the table, or a run
without a trace, has nothing to read."""
from benchmark import step_scopes


def read(ctx):
    found = step_scopes.matrix(ctx)
    return None if found is None else found["unattributed_pct"]
