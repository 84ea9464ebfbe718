"""The laguna_s_2_1 cell's own cases in the tier-1 run
(``benchmark/tests/test_laguna_s_2_1.py``), in a file of their own beside
``tests/test_benchmark_suite.py`` (the tier-1 run hands out work by file):
ONE whole rehearsal run that is sound, ONE whole run with a fault planted in
the program (the window one key wider), and the other six planted faults as
the check's own numbers of one eager step, seconds each. The by-hand run of
``benchmark/tests`` keeps what is marked ``slow`` here: the six faults as
whole runs, the three fp8 controls, and the whole step on the Pallas kernels
under the interpreter (``tests/test_laguna_s_2_1.py`` holds both kinds of
layer's kernels to the plain path at the operator)."""
import pytest

from benchmark.tests import test_laguna_s_2_1 as _cases
from benchmark.tests.test_laguna_s_2_1 import *      # noqa: F401,F403

for _name in ("test_control_fails_the_rehearsal_limits",
              "test_the_kernels_run_the_rehearsal"):
    globals()[_name] = pytest.mark.slow(getattr(_cases, _name))


def _params(faults, whole):
    return [pytest.param(f, id=f.__name__.strip("_"),
                         marks=() if (f is _cases.FAULTS[0]) == whole
                         else pytest.mark.slow) for f in faults]


@pytest.mark.parametrize("fault", _params(_cases.FAULTS, True))
def test_a_planted_fault_is_not_correct(monkeypatch, fault):  # noqa: F811
    _cases.test_a_planted_fault_is_not_correct(monkeypatch, fault)


@pytest.mark.parametrize("fault", _params(_cases.FAULTS, False))
def test_a_planted_fault_moves_the_checks_numbers(  # noqa: F811
        monkeypatch, fault):
    _cases.test_a_planted_fault_moves_the_checks_numbers(monkeypatch, fault)
