"""Every cell end to end at its rehearsal sizes on the CPU, through the
same ``run_cell`` a chip run goes through (only the harness's look for a
chip is skipped); the control, which has to come out NOT correct; and runs
with the timed path broken underneath, which have to come out NOT correct
too."""
import jax
import jax.numpy as jnp
import pytest

from benchmark import control, run

from benchmark.tests import SPEC, cell

CELLS = [w["name"] for w in SPEC["workloads"]]
TRAIN = [c for c in CELLS if cell(c).traffic["runner"] == "train_steps"]


def _rehearse(name, seed=7, trace=0):
    lines = []
    result = run.run_cell(cell(name, rehearse=True), seed, 1.0, trace,
                          out=lines.append)
    return result, lines


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_is_correct_and_measures_nothing(name):
    result, lines = _rehearse(name, trace=1)
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # every number compared is printed beside its limit
    assert sum(line.startswith("check ") for line in lines) >= 2
    # the parts of the memory reading, the untraced dispatch time and what
    # the reference cost after the window are on note lines of every run
    for note in ("memory.", "dispatch_ms_median =", "setup_compile_s =",
                 "reference_s =", "reference_compile_s ="):
        assert any(line.startswith("note " + note) for line in lines), note


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_is_not_correct(name, seed):
    rows = control.control(cell(name, rehearse=True), seed)
    assert any(not value <= limit for _n, value, limit in rows), rows


class _Frozen:
    """A step that computes its loss and returns its state unchanged."""

    def __init__(self, step):
        self.step = step

    def __getattr__(self, name):
        return getattr(self.step, name)

    def __call__(self, *batch):
        params = [jnp.copy(x) for x in self.step._param_datas]
        states = jax.tree_util.tree_map(jnp.copy, self.step._opt_states)
        loss = self.step(*batch)
        self.step._param_datas, self.step._opt_states = params, states
        for p, d in zip(self.step._params, params):
            p.data()._set_data(d)
        return loss


@pytest.mark.parametrize("name", TRAIN)
def test_a_step_that_keeps_its_state_is_not_correct(name, monkeypatch):
    from benchmark.models import common
    whole = common.whole_step
    monkeypatch.setattr(common, "whole_step",
                        lambda *a, **k: _Frozen(whole(*a, **k)))
    result, lines = _rehearse(name)
    assert result["correct"] is False, lines


def _fed_the_first_part(monkeypatch, name, parts):
    """Every batch of the cell's program made of its first ``1 / parts``,
    repeated: the rows where there are as many, else (one sequence a step)
    the positions of each row."""
    model = cell(name, rehearse=True).module("models")
    batch = model.batch

    def cut(cfg, x, y):
        axis = 0 if x.shape[0] >= parts else 1
        keep = x.shape[axis] // parts
        return batch(cfg, *(jnp.concatenate(
            [jax.lax.slice_in_dim(a, 0, keep, axis=axis)] * parts, axis)
            for a in (x, y)))

    monkeypatch.setattr(model, "batch", cut)
    return _rehearse(name)


@pytest.mark.parametrize("name", TRAIN)
def test_a_part_of_the_batch_left_out_is_not_correct(name, monkeypatch):
    """The second half of every batch replaced by its first half."""
    result, lines = _fed_the_first_part(monkeypatch, name, 2)
    assert result["correct"] is False, lines


@pytest.mark.parametrize("name", [c for c in TRAIN if cell(c).chips > 1])
def test_the_exchange_between_chips_left_out_is_not_correct(name,
                                                            monkeypatch):
    """What a data-parallel step without its exchange computes on chip 0:
    the step of chip 0's rows alone. Every chip is given those rows."""
    result, lines = _fed_the_first_part(monkeypatch, name, cell(name).chips)
    assert result["correct"] is False, lines
