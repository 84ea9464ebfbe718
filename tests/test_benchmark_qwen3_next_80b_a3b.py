"""The qwen3_next_80b_a3b cell's own cases in the tier-1 run
(``benchmark/tests/test_qwen3_next_80b_a3b.py``), in files of their own
beside ``tests/test_benchmark_suite.py`` (the tier-1 run hands out work by
file). Here: ONE whole rehearsal run that is sound, ONE whole run with a
fault planted in the program (the decay dropped from the recurrence), and
the new readers' arithmetic. The other nine planted faults, as the check's
own numbers of one eager step, are
``tests/test_benchmark_qwen3_next_80b_a3b_faults.py``. The by-hand run of
``benchmark/tests`` keeps what is marked ``slow`` here: the nine faults as
whole runs, the three fp8 controls, and the whole step on the Pallas
kernels under the interpreter (``tests/test_gated_delta_rule.py`` holds the
kernel pair to the token-by-token recurrence at the operator)."""
import pytest

from benchmark.tests import test_qwen3_next_80b_a3b as _cases
from benchmark.tests.test_qwen3_next_80b_a3b import (      # noqa: F401
    one_chip, served, test_a_sound_run_is_correct_and_counts_what_it_traced,
    test_the_roofline_readers_read_the_new_kernels)

test_control_fails_the_rehearsal_limits = pytest.mark.slow(
    _cases.test_control_fails_the_rehearsal_limits)
test_the_kernels_run_the_rehearsal = pytest.mark.slow(
    _cases.test_the_kernels_run_the_rehearsal)


@pytest.mark.parametrize("fault", [
    pytest.param(f, id=f.__name__.strip("_"),
                 marks=() if f is _cases.FAULTS[0] else pytest.mark.slow)
    for f in _cases.FAULTS])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    _cases.test_a_planted_fault_is_not_correct(monkeypatch, fault)
