"""The ling3_flash cell's own cases in the tier-1 run: whole rehearsal runs
on the CPU (``benchmark/tests/test_ling3_flash.py``). A file of their own
beside ``tests/test_benchmark_suite.py``: each case is a whole run of some
twenty seconds, and the tier-1 run hands out work by file.

One sound run and one planted fault (the decay dropped from the operator)
stay in tier-1, with the roofline readers' arithmetic. Marked ``slow`` (PR
45: the tier-1 run stood on its time limit; ROADMAP D11), and kept by the
by-hand run of ``benchmark/tests``: the other three faults in the operator,
and the three planted beside it
(``tests/test_benchmark_ling3_flash_beside.py``). The fp8 control (twice
the reference) was a by-hand case already."""
import pytest

from benchmark.tests import test_ling3_flash as _cases
from benchmark.tests.test_ling3_flash import *      # noqa: F401,F403

del test_a_fault_beside_the_operator_is_not_correct    # noqa: F821
del test_control_fails_the_rehearsal_limits            # noqa: F821


@pytest.mark.parametrize("fault", [
    pytest.param(fault, id=fault.__name__.strip("_"),
                 marks=() if fault is _cases._decay_dropped
                 else pytest.mark.slow)
    for fault in (_cases._decay_dropped, _cases._beta_one,
                  _cases._state_reset, _cases._filter_acausal)])
def test_a_fault_in_the_operator_is_not_correct(monkeypatch, fault):  # noqa: F811,E501
    _cases.test_a_fault_in_the_operator_is_not_correct(monkeypatch, fault)
