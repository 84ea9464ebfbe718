"""The readers of the step's device time by the program's own names
(``benchmark/tests/test_step_scopes.py``) in the tier-1 run, in a file of
their own so that the run, which hands out work by file, can give them to
another worker than ``tests/test_benchmark_suite.py``'s. The star import
brings the fixtures too."""
from benchmark.tests.test_step_scopes import *        # noqa: F401,F403
