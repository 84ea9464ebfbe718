"""The rest of the ling3_flash cell's planted faults, whole rehearsal runs
with the group limit ignored, the head gate dropped, one expert zeroed
(``benchmark/tests/test_ling3_flash.py``): apart from
``tests/test_benchmark_ling3_flash.py`` so that the tier-1 run, which
hands out work by file, can give them to another worker."""
from benchmark.tests.test_ling3_flash import (      # noqa: F401
    one_chip, served, test_a_fault_beside_the_operator_is_not_correct)
