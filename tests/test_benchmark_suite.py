"""The benchmark's own tests, in the tier-1 run: the record against the
harness (every metric of ``BENCHMARK.json`` has its reader, every cell its
files), the trace reduction's arithmetic, and the readers of the program's
spans, the window's and the restart's. ``benchmark/tests/test_rehearse.py`` drives every cell end to end
and takes five minutes: it stays a by-hand run
(``benchmark/tests/__init__.py``). The star imports bring the fixtures too.
"""
from benchmark.tests.test_benchmark_json import *      # noqa: F401,F403
from benchmark.tests.test_setup_readers import *       # noqa: F401,F403
from benchmark.tests.test_span_readers import *        # noqa: F401,F403
from benchmark.tests.test_trace_reduce import *        # noqa: F401,F403
