"""The trace reduction's arithmetic (``benchmark/tests/
test_trace_reduce.py``) in the tier-1 run, in a file of their own so that
the run, which hands out work by file, can give them to another worker than
``tests/test_benchmark_suite.py``'s."""
from benchmark.tests.test_trace_reduce import *        # noqa: F401,F403
