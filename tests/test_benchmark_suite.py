"""The benchmark's own tests, in the tier-1 run: the record against the
harness (every metric of ``BENCHMARK.json`` has its reader, every cell its
files) and the reference's own cases (``benchmark/tests/
test_benchmark_json.py``, which imports ``reference_checks.py``). The tier-1
run hands out work by file, so each of the benchmark's test files has a file
of its own here: the readers of the restart's spans in
``tests/test_benchmark_setup_readers.py``, of the window's in
``tests/test_benchmark_span_readers.py``, the trace reduction's arithmetic
in ``tests/test_benchmark_trace_reduce.py``, and each language cell's whole
rehearsal runs in ``tests/test_benchmark_<cell>.py``.
``benchmark/tests/test_rehearse.py`` drives every cell end to end and takes
five minutes: it stays a by-hand run (``benchmark/tests/__init__.py``), and
the by-hand run keeps every case that is marked ``slow`` here. The star
import brings the fixtures too.

Marked ``slow`` (PR 45: the tier-1 run stood on its time limit, and these
were 9.5 minutes of one worker; ROADMAP D11): the made-up four-chip cell's
whole run, the two cases of the reference over four devices (ResNet's
compiles at rehearsal sizes, two to three minutes each), and the
two-program reference in every cell but two (one convolutional step and one
routed language step stay)."""
import pytest

from benchmark.tests import test_benchmark_json as _cases
from benchmark.tests.test_benchmark_json import *      # noqa: F401,F403

for _name in ("test_a_made_up_cell_on_four_chips_runs_through_the_check",
              "test_the_reference_over_four_devices_is_the_one_device_"
              "reference",
              "test_a_batch_statistic_taken_by_shard_is_found"):
    globals()[_name] = pytest.mark.slow(getattr(_cases, _name))

_TWO_PROGRAMS_IN_TIER_1 = ("bert_base.train_b16_s512",
                           "kanana2_30b_a3b.train_b1_s8192")


@pytest.mark.parametrize("name", [
    pytest.param(name, marks=() if name in _TWO_PROGRAMS_IN_TIER_1
                 else pytest.mark.slow) for name in _cases.CELLS])
def test_the_reference_in_two_programs_follows_the_one_program_form(  # noqa: F811,E501
        name):
    _cases.test_the_reference_in_two_programs_follows_the_one_program_form(
        name)
