"""The readers of the window's spans (``benchmark/tests/
test_span_readers.py``) in the tier-1 run, in a file of their own so that
the run, which hands out work by file, can give them to another worker than
``tests/test_benchmark_suite.py``'s. The star import brings the fixtures
too."""
from benchmark.tests.test_span_readers import *        # noqa: F401,F403
