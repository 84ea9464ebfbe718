"""The keye_vl2_30b_a3b cell's own cases in the tier-1 run: whole rehearsal
runs on the CPU, a sound one, the fp8 control, and one fault at a time
planted in the program (``benchmark/tests/test_keye_vl2_30b_a3b.py``). A
file of their own beside ``tests/test_benchmark_suite.py``, not one more
star import there: each case is a whole run of some twenty seconds, the
tier-1 run hands out work by file, and that file already holds six minutes
of one worker's time."""
from benchmark.tests.test_keye_vl2_30b_a3b import *      # noqa: F401,F403
