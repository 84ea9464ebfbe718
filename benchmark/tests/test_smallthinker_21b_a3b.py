"""The smallthinker_21b_a3b cell at its rehearsal sizes on the CPU: a sound
run is correct (with the share of moved selections on a note line and the
new counters read), the fp8 control has to fail the cell's rehearsal
limits, and a whole run whose timed path is broken in the PROGRAM only has
to come out NOT correct: the window off by one key either way, the window
dropped from one layer, rotary applied to the global layer, the router fed
``norm2(h)``, ``silu`` in the experts' place, one expert zeroed, key/value
heads mis-grouped (on the plain path, and in the windowed Pallas kernels'
own index map under the interpreter)."""
import importlib

import pytest

from benchmark import control, run
from benchmark.tests import cell

CELL = "smallthinker_21b_a3b.train_b1_s16384"


def _rehearse(seed):
    lines = []
    result = run.run_cell(cell(CELL, rehearse=True), seed, 0.3, 0,
                          out=lines.append)
    return result, lines


def _failed(lines):
    return {line.split()[1] for line in lines
            if line.startswith("check ") and "NOT CORRECT" in line}


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_the_rehearsal_limits(seed):
    rows = control.control(cell(CELL, rehearse=True), seed)
    failed = [n for n, value, limit in rows if not value <= limit]
    assert "first_grad_distance" in failed, rows


def test_a_sound_run_is_correct_and_counts_what_it_traced(capsys):
    from mxtpu import telemetry
    names = ("pallas_flash.windowed", "pallas_flash.window_unskipped",
             "moe.router_ahead", "moe.score.softmax")
    for name in names:
        telemetry.reset_metric(name)
    result, lines = _rehearse(21)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    notes = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("note moe_selection_flip_share_")]
    assert len(notes) == 1 and 0.0 <= float(notes[0].split(" = ")[1]) < 0.05
    assert [telemetry.value(n) for n in names[:1] + names[2:]] == [3, 4, 4]
    # the new per-layer metric's reader: three windowed calls were traced;
    # on the CPU they ran the plain path, which skips nothing, and say so
    read = run.reader("flash_window_unskipped.train")
    assert read({"window": {"attempted": 1}}) == 3
    assert read({"window": {"attempted": 0}}) is None
    telemetry.reset_metric("pallas_flash.windowed")
    assert read({"window": {"attempted": 1}}) is None
    # the windowed backward's share reads nothing without a trace
    assert run.reader("flash_window_bwd_mxu_pct.train")(
        {"trace": None, "peak": None}) is None


def test_the_windowed_kernels_run_the_rehearsal(monkeypatch):
    """Both windowed Pallas kernels (the interpreter, as tier-1 runs them)
    in the cell's own step: correct, three windowed calls, none of them on
    a path that visits the pairs left of the window."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    fa.reset_dispatch_stats()
    result, lines = _rehearse(25)
    assert result["correct"] is True, lines
    stats = dict(fa.DISPATCH_STATS.items())
    assert stats["pallas"] == 4 and stats["bwd_pallas"] == 4, stats
    assert stats["windowed"] == 3 and stats["window_unskipped"] == 0, stats
    assert stats["xla"] == 0 and stats["kv_repeated"] == 0, stats
    assert run.reader("flash_window_unskipped.train")(
        {"window": {"attempted": 1}}) == 0


def _planted(monkeypatch, change):
    """The cell's model with ``change(net)`` applied to the program's block
    after its leaves are loaded: the reference knows nothing of it."""
    model = cell(CELL, rehearse=True).module("models")
    build = model.build

    def broken(cfg, specs, leaves):
        net = build(cfg, specs, leaves)
        change(net)
        return net

    monkeypatch.setattr(model, "build", broken)


def _windowed(net):
    return [blk.op for blk in net.blocks if blk.op._attrs["window"]]


@pytest.mark.parametrize("by", [1, -1])
def test_a_window_off_by_one_key_is_not_correct(monkeypatch, by):
    """``i - W - 1 < j`` (or ``i - W + 1 < j``) in the three windowed
    layers: one key of 72 more or fewer. The loss does not see it; the
    first gradient's distance reads three times the sound runs'."""
    _planted(monkeypatch, lambda net: [
        op._attrs.update(window=op._attrs["window"] + by)
        for op in _windowed(net)])
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines
    assert "first_grad_distance" in _failed(lines), lines


def test_a_window_dropped_from_one_layer_is_not_correct(monkeypatch):
    _planted(monkeypatch,
             lambda net: _windowed(net)[1]._attrs.update(window=0))
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_rotary_on_the_global_layer_is_not_correct(monkeypatch):
    """The position-free layer given the windowed layers' rotary."""
    def turn(net):
        assert net.blocks[0].op._attrs["rope"] is False
        net.blocks[0].op._attrs.update(rope=True)

    _planted(monkeypatch, turn)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


@pytest.mark.parametrize("layers", [slice(None), slice(1, 2)])
def test_a_router_fed_the_experts_input_is_not_correct(monkeypatch, layers):
    """The router reading ``norm2(h)``, as every other routed model in the
    benchmark has it, in every layer or in one."""
    def behind(net):
        for blk in list(net.blocks)[layers]:
            assert blk._router_ahead is True
            blk._router_ahead = False

    _planted(monkeypatch, behind)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


@pytest.mark.parametrize("layers", [slice(None), slice(3, 4)])
def test_silu_in_the_experts_place_is_not_correct(monkeypatch, layers):
    _planted(monkeypatch, lambda net: [
        blk.ffn._attrs.update(activation="silu")
        for blk in list(net.blocks)[layers]])
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_one_expert_zeroed_is_not_correct(monkeypatch):
    import mxtpu as mx

    def zero(net):
        down = [p for name, p in net.collect_params().items()
                if name.endswith("moe_w_down")][1]
        down.set_data(mx.nd.NDArray(down.data()._data.at[0].set(0)))

    _planted(monkeypatch, zero)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_mis_grouped_heads_are_not_correct(monkeypatch):
    """Query head j reads key/value head j % H_kv where the model says j //
    7: the heads are there, wired to the wrong queries."""
    import jax.numpy as jnp
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")

    def wrong(q, k, v):
        group = q.shape[1] // k.shape[1]
        return jnp.tile(k, (1, group, 1, 1)), jnp.tile(v, (1, group, 1, 1))

    monkeypatch.setattr(fa, "_repeat_kv", wrong)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_a_wrong_head_map_in_the_kernels_is_not_correct(monkeypatch):
    """The fault in what the chip runs: the Pallas kernels (the
    interpreter) with the index maps naming key/value head ``(j // 7) ^ 1``
    for query head ``j``; no plain path, no repeated K or V."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_kv_head_map",
                        lambda group: lambda b_: (b_ // group) ^ 1)
    fa.reset_dispatch_stats()
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines
    stats = dict(fa.DISPATCH_STATS.items())
    assert stats["pallas"] >= 1 and stats["bwd_pallas"] >= 1, stats
    assert stats["xla"] == 0 and stats["kv_repeated"] == 0, stats
