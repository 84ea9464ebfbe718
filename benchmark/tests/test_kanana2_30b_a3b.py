"""The kanana2_30b_a3b cell at its rehearsal sizes on the CPU: the fp8
control has to fail the cell's rehearsal limits, a run whose expert layer
is broken (one held expert of one layer answers zero) has to come out NOT
correct, and a sound run correct, with the share of moved selections on a
note line."""
import pytest

from benchmark import control, run
from benchmark.tests import cell

CELL = "kanana2_30b_a3b.train_b1_s8192"


def _rehearse(seed):
    lines = []
    result = run.run_cell(cell(CELL, rehearse=True), seed, 0.5, 0,
                          out=lines.append)
    return result, lines


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_the_rehearsal_limits(seed):
    rows = control.control(cell(CELL, rehearse=True), seed)
    failed = [n for n, value, limit in rows if not value <= limit]
    assert "first_grad_distance" in failed, rows


def test_a_sound_run_is_correct_and_counts_moved_selections(capsys):
    result, lines = _rehearse(21)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    notes = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("note moe_selection_flip_share_")]
    assert len(notes) == 1 and 0.0 <= float(notes[0].split(" = ")[1]) < 0.05


@pytest.mark.parametrize("layer", [1, 2])
def test_a_zeroed_expert_is_not_correct(layer, monkeypatch):
    """One held expert's down projection zeroed in the PROGRAM only: its
    tokens lose that expert's part, which the reference still adds."""
    import mxtpu as mx
    model = cell(CELL, rehearse=True).module("models")
    build = model.build

    def broken(cfg, specs, leaves):
        net = build(cfg, specs, leaves)
        down = [p for name, p in net.collect_params().items()
                if name.endswith("moe_w_down")][layer - 1]
        down.set_data(mx.nd.NDArray(down.data()._data.at[0].set(0)))
        return net

    monkeypatch.setattr(model, "build", broken)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines
