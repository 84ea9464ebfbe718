"""The rest of the ling3_flash cell's planted faults, whole rehearsal runs
with the group limit ignored, the head gate dropped, one expert zeroed
(``benchmark/tests/test_ling3_flash.py``), all three marked ``slow`` since
PR 45 (the tier-1 run stood on its time limit; one sound run and one fault a
cell stay, in ``tests/test_benchmark_ling3_flash.py``; ROADMAP D11): the
by-hand run of ``benchmark/tests`` keeps them."""
import pytest

from benchmark.tests.test_ling3_flash import (      # noqa: F401
    one_chip, served, test_a_fault_beside_the_operator_is_not_correct)

test_a_fault_beside_the_operator_is_not_correct = pytest.mark.slow(
    test_a_fault_beside_the_operator_is_not_correct)
