"""``HybridLM`` as the language model of Keye-VL-2.0-30B-A3B (grouped
attention, 8 query heads a key/value head, over the keys a learned indexer
picks query by query; a softmax router over SiLU-gated experts; an untied
head) against the plain reference of its cell, ``benchmark/reference/
keye_vl2_30b_a3b.py``, at the configuration's rehearsal size on seeded
weights: leaves, logits, loss, every leaf's gradient (the indexer's three
reading exactly zero), three Adam steps under ``ShardedTrainStep``; the
selection's count, causality and tie rule; both sparse kernels under the
Pallas interpreter against the plain path; ``topk >= T`` equal to full
attention; the shares of 1, 2, 4 and 8 holders adding up to the uncut
layer; the counters; and the older cells' models unmoved."""
import importlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import autograd, gluon, telemetry
from mxtpu.gluon.model_zoo import hybrid_lm
from mxtpu.ops.registry import get_op
from mxtpu.parallel import ShardedTrainStep

from benchmark.flops import keye_vl2_30b_a3b as flops
from benchmark.models import keye_vl2_30b_a3b as model
from benchmark.reference import common as ref_common
from benchmark.reference import keye_vl2_30b_a3b as ref

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
ops_nn = importlib.import_module("mxtpu.ops.nn")
index_select = get_op("_contrib_index_select").fn


@pytest.fixture
def block_of_64(monkeypatch):
    """The selection by blocks of 64 queries: several blocks at 192."""
    monkeypatch.setattr(ops_nn, "_SELECT_BLOCK", 64)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


PUBLISHED = _config("keye_vl2_30b_a3b")
CFG = dict(PUBLISHED)
CFG.update(CFG["rehearsal"], dtype="float32")
SPECS = ref.param_specs(CFG)
TRAINABLE = [s[0] for s in SPECS if s[3]]
INDEXER = [s[0] for s in SPECS if "indexer" in s[0]]
ADAM = {"name": "adam", "learning_rate": 1e-3}
SPARSE = ("sparse_attention.calls", "sparse_attention.pairs_selected",
          "sparse_attention.pairs_visited", "sparse_attention.fallbacks",
          "sparse_attention.bwd_pallas")


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def _leaf_names(net):
    return [re.sub(r"^h_decoderblock(\d+)_", r"h\1_", k[len(net.prefix):])
            for k in net.collect_params().keys()]


def _loss_fn():
    blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(block, tokens, labels):
        return blk(block(tokens).reshape((-1, CFG["vocab_size"])),
                   labels.reshape((-1,)))
    return forward


def _reset():
    for name in SPARSE:
        telemetry.reset_metric(name)


@pytest.fixture(scope="module")
def case():
    """The model with the reference's seeded leaves, two sequences, and the
    reference's logits, loss and gradients on them, the gradient of EVERY
    leaf, the indexer's among them."""
    leaves = ref_common.init_params(SPECS, 5)
    x, y = ref.sample_inputs(CFG, jax.random.PRNGKey(9), 2)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    loss_fn = ref.forward_loss(CFG)
    loss, grads = jax.value_and_grad(
        lambda full: loss_fn(full, x, y, "float32")[0])(list(leaves))
    return {"net": net, "leaves": leaves, "x": x, "y": y,
            "logits": ref.forward(CFG, leaves, x)[0], "loss": float(loss),
            "grads": dict(zip([s[0] for s in SPECS], grads))}


@pytest.fixture(scope="module")
def program_grads(case):
    """The program's loss and gradients by its eager autograd."""
    x, y = mx.nd.NDArray(case["x"]), mx.nd.NDArray(case["y"])
    with autograd.record():
        loss = _loss_fn()(case["net"], x, y).mean()
    loss.backward()
    params = [p for p in case["net"].collect_params().values()
              if p.grad_req != "null"]
    return float(loss.asnumpy()), {
        n: p.grad().asnumpy() for n, p in zip(TRAINABLE, params)}


# ------------------------------------------------------ the configuration
def test_the_rehearsal_has_what_the_cell_has():
    """A ``topk`` well under the sequence, so that most rows select and
    some (the first ``topk``) keep all they see; 8 query heads a key/value
    head; several index heads over one index key head; 2 of 16 experts
    held and not from expert 0."""
    sa = CFG["sa_config"]
    assert 0 < sa["topk"] * 4 <= CFG["seq_len"]
    assert CFG["num_attention_heads"] == 8 * CFG["num_key_value_heads"]
    assert sa["indexer_num_heads"] > 1 and sa["indexer_num_kv_heads"] == 1
    assert (CFG["num_experts"], CFG["num_experts_held"]) == (16, 2)
    assert CFG["first_expert_held"] != 0


def test_published_sizes_are_the_sources():
    """Every number of the catalog's row is in the file under its own key;
    what is cut is listed with the published value beside it."""
    c = PUBLISHED
    row = {"attention_bias": False, "decoder_sparse_step": 1,
           "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 6144, "max_position_embeddings": 262144,
           "max_window_layers": 48, "mlp_only_layers": [],
           "model_type": "KeyeVL2", "moe_intermediate_size": 768,
           "norm_topk_prob": True, "num_attention_heads": 32,
           "num_experts": 128, "num_experts_per_tok": 8,
           "num_hidden_layers": 48, "num_key_value_heads": 4,
           "num_local_experts": 128, "rms_norm_eps": 1e-06,
           "rope_scaling": {"mrope_section": [16, 24, 24],
                            "rope_type": "default", "type": "default"},
           "rope_theta": 10000000,
           "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                         "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                         "q_chunk_size": 512, "topk": 2048},
           "sliding_window": None, "tie_word_embeddings": False,
           "use_sliding_window": False, "vocab_size": 151936}
    differs = sorted(k for k, v in row.items() if c[k] != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert c["reduced"] == ["num_hidden_layers", "num_experts_held",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                              "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts_held"]) == (4, 16)
    assert c["vocab_size"] * 8 == 151936
    for key in ("qk_norm", "positions", "indexer", "selection",
                "indexer_precision", "weights", "seq_len", "optimizer"):
        assert c["assumed"][key]
    assert any("held fixed" in d for d in c["departs"])
    assert any("vision tower" in d for d in c["departs"])


def test_operations_are_the_issues_count():
    """MAC = 2 at the published widths: the selected and the causal pairs,
    each kernel's call over the SELECTED pairs, the index score, a step."""
    c = PUBLISHED
    assert flops.causal_pairs(c) == 134225920
    assert flops.selected_pairs(c) == 2048 * 16384 - 2048 * 2047 // 2 \
        == 31458304
    assert flops.sparse_attn_fwd_flops(c) == 2 * 31458304 * 32 * 2 * 128
    assert flops.sparse_attn_bwd_flops(c) * 2 \
        == flops.sparse_attn_fwd_flops(c) * 5
    assert flops.index_score_flops(c) == 2 * 134225920 * 16 * 64
    assert abs(flops.train_flops_per_sample(c) - 2.08e13) < 0.01e13
    # a topk as long as the sequence selects the causal pairs
    whole = dict(c, sa_config=dict(c["sa_config"], topk=16384))
    assert flops.selected_pairs(whole) == flops.causal_pairs(c)
    # parameters: 96.9 M a layer, 465 M in all
    n = sum(int(np.prod(s[1])) for s in ref.param_specs(c))
    assert abs(n - 465.4e6) < 0.5e6


# ------------------------------------------------- program and reference
def test_leaves_are_the_references(case):
    params = case["net"].collect_params()
    assert [tuple(p.shape) for p in params.values()] == [
        tuple(s[1]) for s in SPECS]
    assert [p.grad_req != "null" for p in params.values()] == [
        s[3] for s in SPECS]
    assert _leaf_names(case["net"]) == [s[0] for s in SPECS]
    # three indexer leaves a layer, none of them trained
    assert len(INDEXER) == 3 * CFG["num_hidden_layers"]
    assert not set(INDEXER) & set(TRAINABLE)
    assert case["net"].head.weight is not case["net"].embed.weight


def test_logits_match_the_reference(case):
    got = case["net"](mx.nd.NDArray(case["x"])).asnumpy()
    assert got.shape == (2, CFG["seq_len"], CFG["vocab_size"])
    assert _gap(got, case["logits"]) <= 1e-5


def test_loss_matches_the_reference(case, program_grads):
    assert abs(program_grads[0] - case["loss"]) <= 1e-5 * case["loss"]


@pytest.mark.parametrize("leaf", TRAINABLE)
def test_gradient_matches_the_reference(case, program_grads, leaf):
    assert _gap(program_grads[1][leaf], case["grads"][leaf]) <= 2e-4


@pytest.mark.parametrize("leaf", INDEXER)
def test_the_indexers_gradient_is_zero_unaided(case, leaf):
    """The reference's autodiff, asked for every leaf: a set is made of
    comparisons, so the language loss's gradient with respect to the
    indexer's three weights is exactly zero (the router's selection bias
    reads zero the same way), while the leaves beside them read more."""
    assert not np.any(np.asarray(case["grads"][leaf]))
    beside = leaf.replace("indexer_q_", "q_").replace(
        "indexer_k_", "k_").replace("indexer_w_weight", "v_weight")
    assert np.any(np.asarray(case["grads"][beside]))


def test_three_adam_steps_match_the_reference():
    """``ShardedTrainStep`` on one device against the reference's own
    training loop: each step's loss and every leaf after three steps; the
    indexer's leaves do not move; what the step counted while it was
    traced, and its named scopes."""
    leaves = ref_common.init_params(SPECS, 6)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    counters = SPARSE + ("moe.score.softmax", "moe.layers",
                         "moe.router_ahead", "pallas_flash.pallas",
                         "pallas_flash.xla")
    for name in counters:
        telemetry.reset_metric(name)
    step = ShardedTrainStep(net, None, mesh, optimizer="adam",
                            optimizer_params={"learning_rate": 1e-3},
                            forward=_loss_fn())
    batches = [ref.sample_inputs(CFG, jax.random.PRNGKey(k), 2)
               for k in (1, 2, 3)]
    start = [np.asarray(w) for w in leaves]
    losses = [float(step(mx.nd.NDArray(x), mx.nd.NDArray(y)).asnumpy())
              for x, y in batches]
    want = ref_common.train_reference(ref.forward_loss(CFG), SPECS, ADAM, 6,
                                      batches, "float32")
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-5)
    got = ref_common.delta_norms(
        [p.data()._data for p in net.collect_params().values()], start)
    gaps = ref_common.leaf_gaps(np.asarray(got), want["delta_norms"])
    assert float(np.max(gaps)) <= 2e-3, gaps
    # the indexer's leaves and the selection bias are where they were
    frozen = [i for i, s in enumerate(SPECS) if not s[3]]
    assert len(frozen) == 4 * CFG["num_hidden_layers"]
    assert all(np.asarray(got)[i] == 0.0 for i in frozen)
    # four sparse calls a pass; off the chip they take the plain path,
    # which holds [H, T, T], and say so; no call of the dense kernels
    t, k = CFG["seq_len"], CFG["sa_config"]["topk"]
    got = {name: telemetry.value(name) for name in counters}
    assert got == {
        "sparse_attention.calls": 4,
        "sparse_attention.pairs_selected": 4 * 2 * (k * t - k * (k - 1) // 2),
        "sparse_attention.pairs_visited": 4 * 2 * t * t,
        "sparse_attention.fallbacks": 4, "sparse_attention.bwd_pallas": 0,
        "moe.score.softmax": 4,
        "moe.layers": 4, "moe.router_ahead": 0, "pallas_flash.pallas": 0,
        "pallas_flash.xla": 0}
    assert telemetry.tagged("sparse_attention.fallbacks") == {
        "platform is not tpu": 4}
    text = step.compiled().as_text()
    for scope in ("sparse_attention", "index_select", "moe.route",
                  "moe.experts"):
        assert "/%s/" % scope in text, scope
    spans = [e for e in telemetry.events()
             if e[0] == "sparse_attention.trace"]
    assert len(spans) >= 4


# ------------------------------------------------------------ the selection
def _indexer(seed, b=2, t=192, d=32, hi=4, di=8, round_to=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = jax.random.normal
    parts = [n(ks[0], (b, t, d)), 0.2 * n(ks[1], (hi * di, d)),
             0.2 * n(ks[2], (di, d)), 0.2 * n(ks[3], (hi, d))]
    if round_to:        # few distinct scores: many keys level at the edge
        parts = [jnp.round(p * s) for p, s in zip(parts, round_to)]
    return parts, hi


def _sets_by_top_k(parts, hi, topk):
    """``jax.lax.top_k`` itself on the whole [T, T] score, one index at a
    time into a boolean array: queries first."""
    data, wq, wk, ww = parts
    b, t, _ = data.shape
    proj = lambda w: jnp.einsum("btd,od->bto", data, w, precision="highest")
    s = jnp.einsum("bthd,bsd->bhts", proj(wq).reshape(b, t, hi, -1),
                   proj(wk), precision="highest")
    score = jnp.sum(proj(ww).transpose(0, 2, 1)[..., None]
                    * jax.nn.relu(s), 1)
    score = jnp.where(jnp.tril(jnp.ones((t, t), bool)), score, -jnp.inf)
    _, idx = jax.lax.top_k(score, min(topk, t))
    want = np.zeros((b, t, t), bool)
    for bi, ti in np.ndindex(b, t):
        picked = np.asarray(idx[bi, ti])
        want[bi, ti, picked[picked <= ti]] = True
    return want, np.asarray(score)


@pytest.mark.parametrize("topk,block", [(32, 64), (32, 2048), (100, 64),
                                        (17, 96), (500, 64)])
def test_the_selection_is_top_ks(monkeypatch, topk, block):
    """Exactly ``min(t + 1, topk)`` keys a query, none ahead of it, and
    they are ``jax.lax.top_k``'s, whatever the block of queries."""
    monkeypatch.setattr(ops_nn, "_SELECT_BLOCK", block)
    parts, hi = _indexer(1)
    got = np.asarray(index_select(*parts, num_heads=hi, topk=topk))
    t = got.shape[1]
    assert got.dtype == np.int8 and got.shape == (2, t, t)
    sets = got.transpose(0, 2, 1).astype(bool)       # queries first
    assert np.array_equal(sets.sum(-1), np.broadcast_to(
        np.minimum(np.arange(t) + 1, topk), (2, t)))
    assert not np.any(np.triu(sets, 1))
    assert np.array_equal(sets, _sets_by_top_k(parts, hi, topk)[0])


@pytest.mark.parametrize("topk", [16, 33])
def test_of_equal_scores_the_lower_key_wins(block_of_64, topk):
    """Scores built to tie (every operand a small integer): the edge of
    many a set is a level shared by keys inside and outside it, and the
    set takes the lowest positions of the level, as ``top_k`` does."""
    parts, hi = _indexer(2, round_to=(1, 3, 3, 3))
    want, score = _sets_by_top_k(parts, hi, topk)
    tied = 0
    for bi, ti in np.ndindex(score.shape[:2]):
        row, sel = score[bi, ti, :ti + 1], want[bi, ti, :ti + 1]
        if sel.sum() == topk and np.any(row[~sel] == row[sel].min()):
            tied += 1
            level = np.flatnonzero(row == row[sel].min())
            n_in = int(sel[level].sum())
            assert np.array_equal(np.flatnonzero(sel[level]),
                                  np.arange(n_in))
    assert tied > 20        # the case is what it says it is
    got = np.asarray(index_select(*parts, num_heads=hi, topk=topk))
    assert np.array_equal(got.transpose(0, 2, 1).astype(bool), want)


def test_a_key_ahead_is_never_chosen_whatever_its_score(block_of_64):
    """Index keys that grow along the sequence: every key ahead of a query
    scores above every key it may see."""
    (data, wq, wk, ww), hi = _indexer(3)
    t = data.shape[1]
    data = jnp.abs(data[:, :1]) * (1.0 + jnp.arange(t))[None, :, None]
    wq, wk, ww = jnp.abs(wq), jnp.abs(wk), jnp.abs(ww)
    got = np.asarray(index_select(data, wq, wk, ww, num_heads=hi,
                                  topk=16)).transpose(0, 2, 1)
    assert not np.any(np.triu(got, 1))
    assert np.array_equal(got.sum(-1)[0], np.minimum(np.arange(t) + 1, 16))
    # and the sets are the LAST sixteen keys a query sees
    assert np.all(got[0, 100, 85:101]) and not np.any(got[0, 100, :85])


def test_the_selection_takes_no_gradient():
    parts, hi = _indexer(4)
    grads = jax.grad(lambda *p: jnp.sum(index_select(
        *p, num_heads=hi, topk=16).astype(jnp.float32)),
        argnums=(0, 1, 2, 3))(*parts)
    assert all(not np.any(np.asarray(g)) for g in grads)


# -------------------------------------------------------------- the kernels
def _heads(seed, b=2, h=8, hk=2, t=256, d=16, keep=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, g = (jax.random.normal(k, (b, h, t, d)) for k in (ks[0], ks[4]))
    k, v = (jax.random.normal(k_, (b, hk, t, d)) for k_ in ks[1:3])
    score = jnp.where(jnp.tril(jnp.ones((t, t), bool)),
                      jax.random.normal(ks[3], (b, t, t)), -jnp.inf)
    edge = jnp.sort(score, -1)[..., -keep][..., None]
    sets = (score >= edge) & jnp.tril(jnp.ones((t, t), bool))
    return q, k, v, g, jnp.swapaxes(sets, 1, 2).astype(jnp.int8)


def _plain(q, k, v, mask_t):
    """K and V repeated, the set's mask position by position."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    seen = jnp.swapaxes(mask_t, 1, 2)[:, None] != 0
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (256, 256)])
def test_both_sparse_kernels_match_the_plain_path(monkeypatch, blocks):
    """The Pallas kernels (the interpreter) over several q and k blocks, a
    set of 32 keys a query: forward, and dq, dk, dv at the key/value heads;
    the counters say what the masked form visits."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g, mask_t = _heads(7)
    _reset()
    sparse = lambda q, k, v: fa.sparse_attention(q, k, v, mask_t, None,
                                                 *blocks, 32)
    assert _gap(sparse(q, k, v), _plain(q, k, v, mask_t)) <= 1e-6
    got = jax.grad(lambda *a: jnp.sum(sparse(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_plain(*a, mask_t) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape and _gap(a, b) <= 1e-6
    t, (bq, bk) = q.shape[2], blocks
    live = sum(1 for i in range(t // bq) for j in range(t // bk)
               if j * bk <= i * bq + bq - 1)
    assert telemetry.value("sparse_attention.calls") == 2
    assert telemetry.value("sparse_attention.bwd_pallas") == 1
    assert telemetry.value("sparse_attention.fallbacks") == 0
    assert telemetry.value("sparse_attention.pairs_visited") \
        == 2 * 2 * live * bq * bk
    assert telemetry.value("sparse_attention.pairs_selected") \
        == 2 * 2 * (32 * t - 32 * 31 // 2)


def test_the_plain_backward_reads_the_same_set(monkeypatch):
    """The blockwise path a refused backward takes, given the forward's
    log-sum-exp and the forward's set."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g, mask_t = _heads(8)
    mask, scale = fa.Mask(True, selected=True), q.shape[-1] ** -0.5
    out, _, res = fa._forward(q, k, v, mask_t, mask, scale, 128, 128, 32)
    got = fa._fa_backward_blockwise(q, k, v, out, res[4].reshape(q.shape[:3]),
                                    g, mask, scale, 64, selection=mask_t)
    want = jax.grad(lambda *a: jnp.sum(_plain(*a, mask_t) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert _gap(a, b) <= 1e-6
    assert res[5] is mask_t         # the backward's set IS the forward's


def test_a_whole_set_is_causal_attention(monkeypatch):
    """A set that holds every key a query sees: the sparse kernels give
    the causal kernels' result, forward and backward."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    q, k, v, g, _ = _heads(9)
    t = q.shape[2]
    whole = jnp.broadcast_to(jnp.triu(jnp.ones((t, t), jnp.int8)),
                             (2, t, t))
    fns = (lambda *a: fa.sparse_attention(*a, whole, None, 128, 128, t),
           lambda *a: fa.flash_attention(*a, True, None, 128, 128))
    outs = [fn(q, k, v) for fn in fns]
    assert _gap(outs[0], outs[1]) <= 1e-6
    grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * g), (0, 1, 2))(q, k, v)
             for fn in fns]
    for a, b in zip(*grads):
        assert _gap(a, b) <= 1e-6


@pytest.mark.parametrize("topk", [24, 200, 4096])
def test_topk_of_the_sequence_or_more_is_full_attention(topk):
    """The op after its projections and norms: with ``topk >= T`` the call
    IS ``grouped_attention``'s causal call, to the bit, and counts where
    that one does; below it differs."""
    rng = np.random.RandomState(3)
    b, t, h, hk, d = 2, 24, 8, 1, 8
    q, k, v = (mx.nd.NDArray(jnp.asarray(rng.randn(b, t, n, d), jnp.float32))
               for n in (h, hk, hk))
    v = v.reshape((b, t, hk * d))
    (data, wq, wk, ww), hi = _indexer(5, t=t)
    sets = mx.nd.NDArray(index_select(data, wq, wk, ww, num_heads=hi,
                                      topk=min(topk, 8)))
    _reset()
    telemetry.reset_metric("pallas_flash.xla")
    got = mx.nd.contrib.sparse_attention(q, k, v, sets, rope_theta=100.0,
                                         topk=topk).asnumpy()
    dense = telemetry.value("pallas_flash.xla")
    full = mx.nd.grouped_attention(q, k, v, rope_theta=100.0).asnumpy()
    if topk >= t:
        assert np.array_equal(got, full)
        assert dense == 1
        assert telemetry.value("sparse_attention.calls") == 0
    else:
        assert _gap(got, full) > 1e-2
        assert telemetry.value("sparse_attention.calls") == 1


def test_the_block_with_a_topk_past_the_sequence_is_full_attention():
    """``GroupedQueryAttention(topk=...)`` against the same leaves without
    an indexer."""
    kw = {"num_heads": 8, "num_kv_heads": 2, "head_dim": 8}
    sparse = hybrid_lm.GroupedQueryAttention(32, topk=64, index_heads=2,
                                             index_head_dim=4, prefix="s_",
                                             **kw)
    full = hybrid_lm.GroupedQueryAttention(32, prefix="f_", **kw)
    sparse.initialize()
    full.initialize()
    x = mx.nd.NDArray(jax.random.normal(jax.random.PRNGKey(0), (2, 48, 32)))
    sparse(x)
    full(x)
    theirs = sparse.collect_params()
    assert [n for n in theirs.keys()][6:] == [
        "s_indexer_q_weight", "s_indexer_k_weight", "s_indexer_w_weight"]
    assert [tuple(p.shape) for p in theirs.values()][6:] == [
        (8, 32), (4, 32), (2, 32)]
    assert all(p.grad_req == "null" for p in list(theirs.values())[6:])
    for mine, other in zip(full.collect_params().values(), theirs.values()):
        mine.set_data(other.data())
    assert np.array_equal(sparse(x).asnumpy(), full(x).asnumpy())


def test_operators_of_the_sparse_kind():
    kinds = hybrid_lm.OPERATORS
    assert set(kinds) == {"conv", "full_attention", "window_attention",
                          "sparse_attention", "latent_attention", "kda",
                          "gated_delta_net"}
    # the grouped block under a name of its own, as the windowed kind is:
    # a stack's keyword arguments for the kind carry the ``topk``
    assert kinds["sparse_attention"] == kinds["window_attention"] == (
        hybrid_lm.GroupedQueryAttention, "attn_")
    with pytest.raises(ValueError, match="without a window"):
        hybrid_lm.GroupedQueryAttention(32, 4, 2, topk=8, window=4)
    with pytest.raises(ValueError, match="without a window"):
        hybrid_lm.GroupedQueryAttention(32, 4, 2, topk=8, rope=False)


# --------------------------------------------------------- the share's tie
E, K, D, F_ = 16, 4, 32, 12          # experts, choices a token, widths


def _layer(seed, t=48):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = jax.random.normal
    return n(ks[0], (t, D), jnp.float32), [
        0.3 * n(ks[1], (E, D)), jnp.zeros((E,), jnp.float32),
        0.2 * n(ks[2], (E, D, F_)), 0.2 * n(ks[3], (E, D, F_)),
        0.2 * n(ks[4], (E, F_, D))]


def _layer_cfg(held=E, first=0):
    return dict(CFG, num_experts=E, num_experts_per_tok=K,
                num_experts_held=held, first_expert_held=first)


@pytest.mark.parametrize("holders", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(holders):
    """Expert parallelism ``holders`` ways: the parts the holders' experts
    give (the program's layer, each told which experts it holds) add up to
    what the uncut reference gives for the whole layer; what every chip
    computes alike (attention, the indexer, the router, the norms) is
    counted once, being outside this sum."""
    from mxtpu.parallel import moe
    m, leaves = _layer(11)
    router, bias, eg, eu, ed = leaves
    want = ref.expert_layer(_layer_cfg(), m[None], leaves)[0]
    held = E // holders
    total = 0.0
    for first in range(0, E, held):
        part = slice(first, first + held)
        mine = moe.routed_ffn(m, router, bias, eg[part], eu[part], ed[part],
                              top_k=K, first_expert=first, score="softmax",
                              activation="silu")
        theirs = ref.expert_layer(
            _layer_cfg(held, first), m[None],
            [router, bias, eg[part], eu[part], ed[part]])[0]
        assert _gap(mine, theirs) <= 1e-5
        total = total + mine
    assert _gap(total, want) <= 1e-5


# ------------------------------------- the older cells beside the new kind
@pytest.mark.parametrize("name", ["lfm2_8b_a1b", "smallthinker_21b_a3b"])
def test_older_hybrid_models_take_none_of_the_sparse_paths(name):
    """lfm2's and smallthinker's models, built as their cells build them:
    the same leaves in the reference's order, the reference's logits, no
    indexer leaf and no sparse call."""
    older = importlib.import_module("benchmark.models." + name)
    older_ref = importlib.import_module("benchmark.reference." + name)
    cfg = _config(name)
    cfg.update(cfg["rehearsal"], dtype="float32")
    specs = older_ref.param_specs(cfg)
    leaves = ref_common.init_params(specs, 5)
    _reset()
    net = older.build(cfg, specs, leaves)
    older._FIRST.clear()
    assert _leaf_names(net) == [s[0] for s in specs]
    assert not any("indexer" in k for k in net.collect_params().keys())
    x, _ = older_ref.sample_inputs(cfg, jax.random.PRNGKey(9), 2)
    got = net(mx.nd.NDArray(x)).asnumpy()
    assert _gap(got, older_ref.forward(cfg, leaves, x)[0]) <= 1e-5
    assert [telemetry.value(c) for c in SPARSE] == [0] * len(SPARSE)
