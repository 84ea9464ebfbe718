"""The keye_vl2_30b_a3b cell's own cases in the tier-1 run: whole rehearsal
runs on the CPU (``benchmark/tests/test_keye_vl2_30b_a3b.py``). A file of
their own beside ``tests/test_benchmark_suite.py``: each case is a whole run
of forty seconds to a minute, and the tier-1 run hands out work by file.

One sound run and one planted fault (a set off by one key) stay in tier-1.
Marked ``slow`` (PR 45: the tier-1 run stood on its time limit, and this
file was ten minutes of one worker; ROADMAP D11), and kept by the by-hand
run of ``benchmark/tests``: the three fp8 controls, the sparse kernels under
the interpreter in the whole step (``tests/test_keye_vl2.py`` holds the
kernels to the plain path at the operator), and nine more planted faults."""
import pytest

from benchmark.tests import test_keye_vl2_30b_a3b as _cases
from benchmark.tests.test_keye_vl2_30b_a3b import *      # noqa: F401,F403

for _name in ("test_control_fails_the_rehearsal_limits",
              "test_the_sparse_kernels_run_the_rehearsal",
              "test_a_key_ahead_admitted_is_not_correct",
              "test_the_relu_dropped_is_not_correct",
              "test_the_index_weights_ignored_is_not_correct",
              "test_a_set_a_head_group_is_not_correct",
              "test_a_gradient_let_into_the_indexer_is_not_correct",
              "test_one_expert_zeroed_is_not_correct"):
    globals()[_name] = pytest.mark.slow(getattr(_cases, _name))


@pytest.mark.parametrize("by", [1, pytest.param(-1, marks=pytest.mark.slow)])
def test_a_set_off_by_one_key_is_not_correct(monkeypatch, by):  # noqa: F811
    _cases.test_a_set_off_by_one_key_is_not_correct(monkeypatch, by)
