"""The qwen3_next_80b_a3b cell's planted faults in the tier-1 run, as the
check's own numbers of one eager step (``benchmark/tests/
test_qwen3_next_80b_a3b.py``; a dozen seconds each after the reference's
one compile): a sound step inside the rehearsal limits, and nine faults in
the PROGRAM only that each move a number over its limit: beta taken as 1,
the state reset at each chunk, value head ``j`` reading key head ``j %
Hk``, the filter acausal by a tap, the elementwise gate dropped, rotary
over the whole head, ``w`` for ``1 + w`` in one norm, the shared expert's
gate dropped, the weights' renormalisation dropped. The tenth, the decay
dropped, is a whole run in ``tests/test_benchmark_qwen3_next_80b_a3b.py``
(its quick form is marked ``slow`` here); a file of its own, because the
tier-1 run hands out work by file."""
import pytest

from benchmark.tests import test_qwen3_next_80b_a3b as _cases
from benchmark.tests.test_qwen3_next_80b_a3b import (      # noqa: F401
    one_chip, served, test_a_sound_step_is_inside_the_rehearsal_limits)


@pytest.mark.parametrize("fault", [
    pytest.param(f, id=f.__name__.strip("_"),
                 marks=pytest.mark.slow if f is _cases.FAULTS[0] else ())
    for f in _cases.FAULTS])
def test_a_planted_fault_moves_the_checks_numbers(monkeypatch, fault):
    _cases.test_a_planted_fault_moves_the_checks_numbers(monkeypatch, fault)
