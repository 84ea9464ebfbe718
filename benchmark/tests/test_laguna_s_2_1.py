"""The laguna_s_2_1 cell at its rehearsal sizes on the CPU: a sound run is
correct (with the rows its experts draw and the share of moved selections
on note lines, and the new counters read), the fp8 control has to fail the
cell's rehearsal limits, both kinds of layer run their Pallas kernels (the
interpreter) inside the cell's own step, and a whole run whose timed path
is broken in the PROGRAM only has to come out NOT correct: the window one
key wider, the gate dropped in one layer, YaRN's factor dropped, the whole
head turned on a full layer, the attention factor left out, the 72-head
layers grouped as the 48-head ones are, the routed scale left out.

Each fault twice: as a whole run (``test_a_planted_fault_is_not_correct``),
and as the check's own numbers (the loss's gap, the first gradient's
distance overall and of the worst leaf, against the reference's) of one
eager step of the same model at the same sizes, in seconds
(``test_a_planted_fault_moves_the_checks_numbers``). The tier-1 run takes
the sound run, the first fault as a whole run and the quick form of the
other six (``tests/test_benchmark_laguna_s_2_1.py``)."""
import importlib

import numpy as np
import pytest

from benchmark import control, run
from benchmark.tests import cell

CELL = "laguna_s_2_1.train_b1_s16384"


# the cell has one chip and a batch of one, and its whole runs compile the
# same reference, pool and check programs: the ling3 cell's two fixtures (the
# program's mesh on the first device; JAX's cache in the checkout)
from benchmark.tests.test_ling3_flash import one_chip, served  # noqa: E402,F401


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
    names = ("pallas_flash.windowed", "pallas_flash.window_pairs_seen",
             "pallas_flash.window_pairs_visited", "attention.head_gated",
             "rotary.scaled", "train_step.blocks_recomputed")
    for name in names:
        telemetry.reset_metric(name)
    result, lines = _rehearse(21)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    notes = {line.split(" = ")[0]: line.split(" = ")[1]
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("note moe_")}
    assert 0.0 <= float(
        notes["note moe_selection_flip_share_bfloat16_vs_float32"]) < 0.05
    # four expert layers' rows on the two experts held, an even share 32
    rows = notes["note moe_rows_held_by_layer"]
    assert rows.endswith("(an even share: 32.0)") and rows.count(",") == 3
    # three windowed calls and two full ones a pass, each traced twice (the
    # blocks are recomputed); five gates; q and k of two layers by YaRN
    c = cell(CELL, rehearse=True).cfg
    t, w = c["seq_len"], c["sliding_window"]
    assert [telemetry.value(n) for n in names] == [
        6, 6 * (w * t - w * (w - 1) // 2), 6 * t * t, 5, 4, 5]
    # the new per-layer metric's reader: on the CPU the windowed calls ran
    # the plain path, which visits the square
    read = run.reader("flash_window_visit_ratio.train")
    assert read({"window": {"attempted": 1}}) == pytest.approx(
        t * t / (w * t - w * (w - 1) // 2))
    assert read({"window": {"attempted": 0}}) is None
    assert run.reader("blocks_recomputed.train")(
        {"window": {"attempted": 1}}) == 5
    telemetry.reset_metric("pallas_flash.window_pairs_seen")
    assert read({"window": {"attempted": 1}}) is None


def test_the_kernels_run_the_rehearsal(monkeypatch):
    """Both Pallas kernels (the interpreter, as tier-1 runs them) in the
    cell's own step, at 9 and at 6 query heads a key/value head: correct,
    six windowed calls (three layers, traced twice), none of them on a path
    that visits the pairs left of the window."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    fa.reset_dispatch_stats()
    result, lines = _rehearse(25)
    assert result["correct"] is True, lines
    stats = dict(fa.DISPATCH_STATS.items())
    assert stats["pallas"] == 10 and stats["bwd_pallas"] == 5, stats
    assert stats["windowed"] == 6 and stats["window_unskipped"] == 0, stats
    assert stats["xla"] == 0 and stats["kv_repeated"] == 0, stats
    # 64 positions are one block: the window's 24 x 64 - 24 x 23 / 2 pairs
    # of the square
    assert stats["window_pairs_visited"] == 6 * 64 * 64
    assert run.reader("flash_window_unskipped.train")(
        {"window": {"attempted": 1}}) == 0


# --------------------------------------------------------- planted faults
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


def _layers(net, windowed):
    return [blk.op for blk in net.blocks
            if bool(blk.op._attrs["window"]) == windowed]


def _window_one_key_wider(monkeypatch):
    """``i - W - 1 < j`` in the three windowed layers: one key of 24 more."""
    _planted(monkeypatch, lambda net: [
        op._attrs.update(window=op._attrs["window"] + 1)
        for op in _layers(net, True)])


def _gate_dropped_in_one_layer(monkeypatch):
    """The second windowed layer's heads go to ``Wo`` ungated."""
    from mxtpu.gluon.model_zoo import hybrid_lm
    gated = hybrid_lm.gate_heads

    def drop(net):
        dropped = net.blocks[2].op.gate
        assert dropped is not None
        monkeypatch.setattr(
            hybrid_lm, "gate_heads", lambda F, out, gate, x, head_dim:
            out if gate is dropped else gated(F, out, gate, x, head_dim))

    _planted(monkeypatch, drop)


def _scaling(net, **changed):
    for op in _layers(net, False):
        assert op._attrs["rope_scaling"] is not None
        op._attrs["rope_scaling"] = dict(op._attrs["rope_scaling"], **changed)


def _yarn_factor_dropped(monkeypatch):
    """The full layers' table left at ``theta^(-2i/R)``: factor 1, the
    attention factor kept."""
    _planted(monkeypatch, lambda net: _scaling(net, factor=1))


def _whole_head_turned_on_a_full_layer(monkeypatch):
    """``partial_rotary_factor`` 1 on the last layer: all of a head turns."""
    def whole(net):
        assert net.blocks[4].op._attrs["rotary_dim"] > 0
        net.blocks[4].op._attrs["rotary_dim"] = 0

    _planted(monkeypatch, whole)


def _attention_factor_left_out(monkeypatch):
    """cos and sin of the full layers' turned entries unscaled."""
    _planted(monkeypatch, lambda net: _scaling(net, attention_factor=1.0))


def _windowed_heads_grouped_as_full(monkeypatch):
    """Query head j of a windowed layer reads key/value head ``j // 6``
    (wrapped) where the layer's own 18 heads over 2 say ``j // 9``: the
    full layers' group in the windowed layers' place. On the plain path,
    where the CPU's rehearsal runs."""
    import jax.numpy as jnp
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    repeat = fa._repeat_kv
    c = cell(CELL, rehearse=True).cfg
    full, windowed = sorted(set(c["num_attention_heads_per_layer"]))
    hk = c["num_key_value_heads"]

    def wrong(q, k, v):
        if q.shape[1] != windowed:
            return repeat(q, k, v)
        idx = (jnp.arange(windowed) // (full // hk)) % hk
        return jnp.take(k, idx, axis=1), jnp.take(v, idx, axis=1)

    monkeypatch.setattr(fa, "_repeat_kv", wrong)


def _routed_scale_left_out(monkeypatch):
    """``moe_routed_scaling_factor`` 2.5 read as 1 in every expert layer."""
    _planted(monkeypatch, lambda net: [
        blk.ffn._attrs.update(scale=1.0) for blk in list(net.blocks)[1:]])


FAULTS = [_window_one_key_wider, _gate_dropped_in_one_layer,
          _yarn_factor_dropped, _whole_head_turned_on_a_full_layer,
          _attention_factor_left_out, _windowed_heads_grouped_as_full,
          _routed_scale_left_out]


def _names(faults):
    return {"argvalues": faults,
            "ids": [f.__name__.strip("_") for f in faults]}


@pytest.mark.parametrize("fault", **_names(FAULTS))
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


# ---------------------------------------- the check's numbers of one step
_WANT = {}      # the reference's loss and first gradient, followed once


def _one_step(seed=22):
    """The check's first three numbers of ONE eager step of the cell's model
    (as ``model.build`` gives it now) against the reference's, at the
    rehearsal sizes in the cell's dtype: ``{number: value}``. The whole
    run's check reads the gradient out of Adam's state after a compiled
    step; here it is the program's eager autograd, which needs no compile.
    """
    import jax
    import mxtpu as mx
    from mxtpu import autograd, gluon
    from benchmark.reference import common as rc
    from benchmark.runners import train_steps
    c = cell(CELL, rehearse=True)
    ref, model = c.module("reference"), c.module("models")
    specs = ref.param_specs(c.cfg)
    x, y = train_steps._pool(c, seed)[0]
    t_idx = [i for i, s in enumerate(specs) if s[3]]
    if seed not in _WANT:
        leaves = rc.init_params(specs, seed)
        loss_fn = ref.forward_loss(c.cfg)

        def of(train):
            full = list(leaves)
            for i, w in zip(t_idx, train):
                full[i] = w
            return loss_fn(full, x, y, "float32")[0]

        loss, grads = jax.jit(jax.value_and_grad(of))(
            [leaves[i] for i in t_idx])
        _WANT[seed] = float(loss), [np.asarray(g, np.float32) for g in grads]
    want_loss, want = _WANT[seed]
    net = model.build(c.cfg, specs, rc.init_params(specs, seed))
    model._FIRST.clear()
    cross_entropy = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = cross_entropy(
            net(mx.nd.NDArray(x)).reshape((-1, c.cfg["vocab_size"])),
            mx.nd.NDArray(y).reshape((-1,))).mean()
    loss.backward()
    got = [np.asarray(p.grad().asnumpy(), np.float32)
           for p in net.collect_params().values() if p.grad_req != "null"]
    per_leaf, overall = rc.leaf_distances(got, want)
    return {"loss_rel_gap": abs(float(loss.asnumpy()) - want_loss)
            / abs(want_loss),
            "first_grad_distance": overall,
            "first_grad_distance_worst_leaf": float(np.max(per_leaf))}


def _over(numbers):
    limits = cell(CELL, rehearse=True).limits
    return sorted(n for n, v in numbers.items() if not v <= limits[n])


def test_a_sound_step_is_inside_the_rehearsal_limits():
    numbers = _one_step()
    assert _over(numbers) == [], numbers


@pytest.mark.parametrize("fault", **_names(FAULTS))
def test_a_planted_fault_moves_the_checks_numbers(monkeypatch, fault):
    fault(monkeypatch)
    numbers = _one_step()
    assert _over(numbers), numbers
