"""The lfm2_8b_a1b cell at its rehearsal sizes on the CPU: a sound run is
correct (with the share of moved selections on a note line and both new
counters read), the fp8 control has to fail the cell's rehearsal limits,
and a whole run whose timed path is broken in the PROGRAM only (key/value
heads mis-grouped; one tap of the convolution zeroed) has to come out NOT
correct."""
import importlib

import pytest

from benchmark import control, run
from benchmark.tests import cell

CELL = "lfm2_8b_a1b.train_b2_s8192"


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


def test_a_sound_run_is_correct_and_counts_what_it_traced(capsys):
    from mxtpu import telemetry
    for name in ("pallas_flash.grouped", "pallas_flash.kv_repeated"):
        telemetry.reset_metric(name)
    result, lines = _rehearse(21)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    notes = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("note moe_selection_flip_share_")]
    assert len(notes) == 1 and 0.0 <= float(notes[0].split(" = ")[1]) < 0.05
    # the new per-layer metric's reader: a grouped call was traced; on the
    # CPU it ran the plain path, which repeats K and V and counts it
    read = run.reader("flash_kv_repeats.train")
    assert read({"window": {"attempted": 1}}) >= 1
    assert read({"window": {"attempted": 0}}) is None
    telemetry.reset_metric("pallas_flash.grouped")
    assert read({"window": {"attempted": 1}}) is None


def test_mis_grouped_heads_are_not_correct(monkeypatch):
    """Query head j reads key/value head j % H_kv where the model says j //
    group: the heads are there, wired to the wrong queries."""
    import jax.numpy as jnp
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")

    def wrong(q, k, v):
        group = q.shape[1] // k.shape[1]
        return jnp.tile(k, (1, group, 1, 1)), jnp.tile(v, (1, group, 1, 1))

    monkeypatch.setattr(fa, "_repeat_kv", wrong)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_a_wrong_head_map_in_the_kernel_is_not_correct(monkeypatch):
    """The fault in what the chip runs: both Pallas kernels (the
    interpreter, as tier-1 runs them) with the forward's index map naming
    key/value head ``(j // group) ^ 1`` for query head ``j``; no plain
    path, no repeated K or V."""
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


def test_labels_moved_by_one_fail_the_loss_limit(monkeypatch):
    """The fault the loss limit is held against: the program trained on
    each position's own token (the labels rolled by one), which the tied
    head makes an easier target; the reference keeps the next token."""
    import jax.numpy as jnp
    model = cell(CELL, rehearse=True).module("models")
    batch = model.batch
    monkeypatch.setattr(
        model, "batch", lambda cfg, x, y: batch(cfg, x, jnp.roll(y, 1, 1)))
    result, lines = _rehearse(24)
    assert result["correct"] is False, lines
    assert any(line.startswith("check loss_rel_gap") and "NOT CORRECT" in line
               for line in lines), lines


def test_a_zeroed_tap_is_not_correct(monkeypatch):
    """The middle tap of one conv layer's filter zeroed in the program
    only: z[t-1] no longer reaches c[t], and that tap gets no gradient."""
    import mxtpu as mx
    model = cell(CELL, rehearse=True).module("models")
    build = model.build

    def broken(cfg, specs, leaves):
        net = build(cfg, specs, leaves)
        taps = [p for name, p in net.collect_params().items()
                if name.endswith("conv_weight")][1]
        taps.set_data(mx.nd.NDArray(taps.data()._data.at[:, 1].set(0)))
        return net

    monkeypatch.setattr(model, "build", broken)
    result, lines = _rehearse(23)
    assert result["correct"] is False, lines
