"""The keye_vl2_30b_a3b cell at its rehearsal sizes on the CPU: a sound run
is correct (with both shares of moved selections on note lines and the new
counters and readers read), the fp8 control has to fail the cell's
rehearsal limits, and a whole run whose timed path is broken in the PROGRAM
only has to come out NOT correct: a set one key larger or smaller than
``topk``, a key ahead of the query admitted, the indexer's ``relu``
dropped, its weights ``w`` ignored, a set a key/value head in the place of
one a query, a gradient let into the indexer, one expert zeroed; and the
same sound run through both sparse Pallas kernels under the interpreter."""
import importlib

import pytest

from benchmark import control, run
from benchmark.tests import cell

CELL = "keye_vl2_30b_a3b.train_b1_s16384"


@pytest.fixture(autouse=True)
def one_chip(monkeypatch):
    """The cell has one chip and a batch of one: where the run has more
    devices (the tier-1 run has eight on the host), the program's mesh is
    the first, as the reference's is (``train_steps._devices``)."""
    import jax
    from benchmark.models import common
    from mxtpu.parallel import data_parallel_mesh
    monkeypatch.setattr(common, "data_parallel_mesh",
                        lambda: data_parallel_mesh(jax.devices()[:1]))


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
    names = ("sparse_attention.calls", "sparse_attention.fallbacks",
             "sparse_attention.pairs_selected",
             "sparse_attention.pairs_visited",
             "moe.score.softmax")
    for name in names:
        telemetry.reset_metric(name)
    result, lines = _rehearse(21)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    out = capsys.readouterr().out.splitlines()
    for kind in ("moe", "sparse"):
        notes = [line for line in out if line.startswith(
            "note %s_selection_flip_share_" % kind)]
        assert len(notes) == 1
        assert 0.0 <= float(notes[0].split(" = ")[1]) < 0.05
    # four sparse layers were traced once; on the CPU each took the plain
    # path, which visits every pair of the square, and the readers say so
    assert [telemetry.value(n) for n in names[:2] + names[4:]] == [4, 4, 4]
    c = cell(CELL, rehearse=True).cfg
    t, k = c["seq_len"], c["sa_config"]["topk"]
    window = {"window": {"attempted": 1}}
    assert run.reader("sparse_attn_fallbacks.train")(window) == 4
    assert run.reader("sparse_attn_visit_ratio.train")(window) \
        == t * t / (k * t - k * (k - 1) // 2)
    for metric in ("sparse_attn_fallbacks.train",
                   "sparse_attn_visit_ratio.train"):
        assert run.reader(metric)({"window": {"attempted": 0}}) is None
    for name in names:
        telemetry.reset_metric(name)
    assert run.reader("sparse_attn_fallbacks.train")(window) is None
    assert run.reader("sparse_attn_visit_ratio.train")(window) is None
    # the kernels' shares read nothing without a trace
    for metric in ("sparse_attn_fwd_mxu_pct.train",
                   "sparse_attn_bwd_mxu_pct.train"):
        assert run.reader(metric)({"trace": None, "peak": None}) is None


def test_the_sparse_kernels_run_the_rehearsal(monkeypatch):
    """Both sparse Pallas kernels (the interpreter, as tier-1 runs them) in
    the cell's own step: correct, four calls, none on the plain path, and
    the masked form's ratio of visited to selected pairs."""
    from mxtpu import telemetry
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    fa.reset_dispatch_stats()
    for name in ("calls", "fallbacks", "bwd_pallas", "pairs_selected",
                 "pairs_visited"):
        telemetry.reset_metric("sparse_attention." + name)
    result, lines = _rehearse(25)
    assert result["correct"] is True, lines
    assert [telemetry.value("sparse_attention." + n)
            for n in ("calls", "fallbacks", "bwd_pallas")] == [4, 0, 4]
    stats = dict(fa.DISPATCH_STATS.items())
    assert stats["pallas"] == 0 and stats["xla"] == 0, stats
    assert stats["kv_repeated"] == 0, stats
    window = {"window": {"attempted": 1}}
    assert run.reader("sparse_attn_fallbacks.train")(window) == 0
    # 256 positions are one block pair: the whole square
    assert run.reader("sparse_attn_visit_ratio.train")(window) > 1.0


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


@pytest.mark.parametrize("by", [1, -1])
def test_a_set_off_by_one_key_is_not_correct(monkeypatch, by):
    """``topk`` + 1 or - 1 keys a query in every layer: one key of 32."""
    def resize(net):
        for blk in net.blocks:
            blk.op._topk += by
            blk.op.indexer._attrs["topk"] += by

    _planted(monkeypatch, resize)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def _selection(monkeypatch, name, wrong):
    """``mxtpu.ops.nn.<name>`` replaced in the program's selection."""
    nn = importlib.import_module("mxtpu.ops.nn")
    right = getattr(nn, name)
    monkeypatch.setattr(nn, name, lambda *a: wrong(right, *a))


def test_a_key_ahead_admitted_is_not_correct(monkeypatch):
    """Every query also attends to the key after it."""
    import jax.numpy as jnp

    def ahead(right, q_idx, w_idx, k_idx, at, topk):
        sets = right(q_idx, w_idx, k_idx, at, topk)
        tk, rows = sets.shape[1:]
        nxt = jnp.arange(tk)[:, None] == at + jnp.arange(rows)[None, :] + 1
        return sets | nxt[None].astype(sets.dtype)

    _selection(monkeypatch, "_select_block", ahead)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_the_relu_dropped_is_not_correct(monkeypatch):
    """``I[t, s] = sum_j w[t, j] (qI[t, j] . kI[s])``."""
    import jax.numpy as jnp

    def linear(right, q_idx, w_idx, k_idx):
        return jnp.einsum("bsd,bqjd,bqj->bsq", k_idx, q_idx, w_idx,
                          precision="highest")

    _selection(monkeypatch, "_index_score", linear)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_the_index_weights_ignored_is_not_correct(monkeypatch):
    """``I[t, s] = sum_j relu(qI[t, j] . kI[s])``."""
    import jax.numpy as jnp
    _selection(monkeypatch, "_index_score",
               lambda right, q_idx, w_idx, k_idx: right(
                   q_idx, jnp.ones_like(w_idx), k_idx))
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_a_set_a_head_group_is_not_correct(monkeypatch):
    """Each key/value head's query heads attend to a set of their own,
    scored by that group's share of the index heads, where the model has
    one set a query for all heads."""
    import mxtpu as mx
    from mxtpu.gluon.model_zoo import hybrid_lm

    def by_group(self, F, x):
        heads = (0, 0, -1, self._head_dim)
        q = self.q_norm(F.reshape(self.q(x), shape=heads))
        k = self.k_norm(F.reshape(self.k(x), shape=heads))
        v = F.reshape(self.v(x), shape=heads)
        idx = self.indexer
        hk, hi = k.shape[2], idx._attrs["num_heads"]
        group, share = q.shape[2] // hk, hi // hk
        wq, wk, ww = (p.data() for p in (idx.q_weight, idx.k_weight,
                                         idx.w_weight))
        di = wq.shape[0] // hi
        outs = []
        for g in range(hk):
            sets = F._contrib_index_select(
                x, wq[g * share * di:(g + 1) * share * di], wk,
                ww[g * share:(g + 1) * share], num_heads=share,
                topk=self._topk)
            outs.append(F._contrib_sparse_attention(
                q[:, :, g * group:(g + 1) * group], k[:, :, g:g + 1],
                F.reshape(v[:, :, g:g + 1], shape=(0, 0, -1)), sets,
                rope_theta=self._attrs["rope_theta"], topk=self._topk))
        return self.proj(mx.nd.concat(*outs, dim=-1))

    monkeypatch.setattr(hybrid_lm.GroupedQueryAttention, "hybrid_forward",
                        by_group)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


def test_a_gradient_let_into_the_indexer_is_not_correct(monkeypatch):
    """The indexer's leaves marked trainable and a path from the loss to
    one of them small enough to leave the forward where it was (a thousandth
    of the index keys' mean added to the layer's output): Adam moves a leaf
    by its step whatever the gradient's size, and the reference's leaves
    stand still."""
    from mxtpu.gluon.model_zoo import hybrid_lm
    forward = hybrid_lm.GroupedQueryAttention.hybrid_forward

    def leaky(self, F, x):
        keys = F.FullyConnected(x, self.indexer.k_weight.data(), no_bias=True,
                                num_hidden=self.indexer.k_weight.shape[0],
                                flatten=False)
        return forward(self, F, x) + 1e-3 * F.mean(keys, axis=-1,
                                                   keepdims=True)

    def train(net):
        for blk in net.blocks:
            for p in blk.op.indexer.collect_params().values():
                p.grad_req = "write"

    monkeypatch.setattr(hybrid_lm.GroupedQueryAttention, "hybrid_forward",
                        leaky)
    _planted(monkeypatch, train)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines
    assert "param_change_norm_gap" in _failed(lines), lines


def test_one_expert_zeroed_is_not_correct(monkeypatch):
    import mxtpu as mx

    def zero(net):
        down = [p for name, p in net.collect_params().items()
                if name.endswith("moe_w_down")][1]
        down.set_data(mx.nd.NDArray(down.data()._data.at[0].set(0)))

    _planted(monkeypatch, zero)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines
