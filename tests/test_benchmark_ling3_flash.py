"""The ling3_flash cell's own cases in the tier-1 run: whole rehearsal runs
on the CPU, a sound one and one fault at a time planted
in the program's new operator (``benchmark/tests/test_ling3_flash.py``). A
file of their own beside ``tests/test_benchmark_suite.py``: each case is a
whole run of some twenty seconds, and the tier-1 run hands out work by
file. The faults planted beside the operator are in
``tests/test_benchmark_ling3_flash_beside.py``, for another worker."""
from benchmark.tests.test_ling3_flash import *      # noqa: F401,F403

# the fp8 control (one more whole run, twice the reference) stays a by-hand
# case of benchmark/tests: the tier-1 run has no minute to spare for it
del test_a_fault_beside_the_operator_is_not_correct    # noqa: F821
del test_control_fails_the_rehearsal_limits            # noqa: F821
