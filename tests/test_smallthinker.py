"""``HybridLM`` as SmallThinker-21BA3B-Instruct's stack (a global attention
layer without position encoding, three with rotary and a sliding window,
seven query heads a key/value head, a softmax router that reads the layer's
input ahead of attention over ReLU-gated experts, an untied head) against
the plain reference of its cell, ``benchmark/reference/
smallthinker_21b_a3b.py``, at the configuration's rehearsal size on seeded
weights: leaves, logits, loss, every leaf's gradient, three Adam steps
under ``ShardedTrainStep``; the router's gradient reaching the layer's
input; the hand-written ReLU backward; the shares of 1, 2, 4 and 8 holders
adding up to the uncut layer; and the older cells' models unmoved by the
new defaults."""
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
from mxtpu.base import MXNetError
from mxtpu.gluon.model_zoo import hybrid_lm
from mxtpu.parallel import ShardedTrainStep
from mxtpu.parallel import moe

from benchmark.flops import smallthinker_21b_a3b as flops
from benchmark.models import smallthinker_21b_a3b as model
from benchmark.reference import common as ref_common
from benchmark.reference import smallthinker_21b_a3b as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


PUBLISHED = _config("smallthinker_21b_a3b")
CFG = dict(PUBLISHED)
CFG.update(CFG["rehearsal"], dtype="float32")
SPECS = ref.param_specs(CFG)
TRAINABLE = [s[0] for s in SPECS if s[3]]
ADAM = {"name": "adam", "learning_rate": 1e-3}


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def _leaf_names(net):
    """The program's leaves under the reference's names: ``h<i>_`` for
    layer i's block."""
    return [re.sub(r"^h_decoderblock(\d+)_", r"h\1_", k[len(net.prefix):])
            for k in net.collect_params().keys()]


def _loss_fn():
    blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(block, tokens, labels):
        return blk(block(tokens).reshape((-1, CFG["vocab_size"])),
                   labels.reshape((-1,)))
    return forward


@pytest.fixture(scope="module")
def case():
    """The model with the reference's seeded leaves, two sequences, and the
    reference's logits, loss and gradients on them."""
    leaves = ref_common.init_params(SPECS, 5)
    x, y = ref.sample_inputs(CFG, jax.random.PRNGKey(9), 2)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    t_idx = [i for i, s in enumerate(SPECS) if s[3]]
    loss_fn = ref.forward_loss(CFG)

    def of(train):
        full = list(leaves)
        for i, w in zip(t_idx, train):
            full[i] = w
        return loss_fn(full, x, y, "float32")[0]

    loss, grads = jax.value_and_grad(of)([leaves[i] for i in t_idx])
    return {"net": net, "leaves": leaves, "x": x, "y": y,
            "logits": ref.forward(CFG, leaves, x)[0], "loss": float(loss),
            "grads": dict(zip(TRAINABLE, grads))}


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


def test_the_rehearsal_has_what_the_cell_has():
    """The issue's floor for the rehearsal size: a window shorter than the
    sequence and not a multiple of the kernels' 128-row granule, a whole
    period of one global and three windowed layers, 7 query heads a
    key/value head, 2 of 16 experts held and not from expert 0."""
    assert ref._layout(CFG) == [0, 1, 1, 1]
    assert 0 < CFG["sliding_window_size"] < CFG["seq_len"]
    assert CFG["sliding_window_size"] % 128
    assert CFG["num_attention_heads"] == 7 * CFG["num_key_value_heads"]
    assert CFG["num_attention_heads"] * CFG["head_dim"] != CFG["hidden_size"]
    assert (CFG["moe_num_primary_experts"],
            CFG["moe_num_primary_experts_held"]) == (16, 2)
    assert CFG["first_expert_held"] != 0


def test_published_sizes_are_the_sources():
    """Every width, the router's 64 outputs and its 6 experts a token are
    as published; what is cut is listed with the published value beside
    it; the kept layers are one whole period."""
    c = PUBLISHED
    assert (c["hidden_size"], c["head_dim"], c["moe_ffn_hidden_size"]) == (
        2560, 128, 768)
    assert (c["num_attention_heads"], c["num_key_value_heads"]) == (28, 4)
    assert (c["moe_num_primary_experts"],
            c["moe_num_active_primary_experts"]) == (64, 6)
    assert (c["sliding_window_size"], c["rope_theta"], c["rms_norm_eps"],
            c["max_position_embeddings"]) == (4096, 1500000, 1e-6, 16384)
    assert c["sliding_window_layout"] == c["rope_layout"] == [0, 1, 1, 1] * 13
    assert c["moe_primary_router_apply_softmax"] and c["norm_topk_prob"]
    assert not c["tie_word_embeddings"]
    assert c["reduced"] == ["num_hidden_layers",
                            "moe_num_primary_experts_held", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 52,
                              "moe_num_primary_experts": 64,
                              "vocab_size": 151936}
    assert c["vocab_size"] * 8 == 151936 and c["seq_len"] == 16384
    assert ref._layout(c) == [0, 1, 1, 1]
    n = sum(int(np.prod(s[1])) for s in ref.param_specs(c))
    assert abs(n - 370.5e6) < 0.2e6


def test_operations_are_the_issues_count():
    """MAC = 2 at the published widths: the causal and the windowed pairs,
    each kernel's call, a token's forward outside attention, a step."""
    c = PUBLISHED
    assert flops.causal_pairs(c) == 134225920
    assert flops.window_pairs(c) == 58722304
    assert abs(flops.flash_fwd_flops(c) - 1.924e12) < 1e9
    assert abs(flops.flash_window_fwd_flops(c) - 0.842e12) < 1e9
    assert flops.flash_bwd_flops(c) * 2 == flops.flash_fwd_flops(c) * 5
    assert flops.flash_window_bwd_flops(c) * 2 \
        == flops.flash_window_fwd_flops(c) * 5
    assert abs(flops.train_flops_per_sample(c) - 28.2e12) < 0.1e12
    # a window as long as the sequence sees the causal pairs
    assert flops.window_pairs(dict(c, sliding_window_size=16384)) \
        == flops.causal_pairs(c)


def test_leaves_are_the_references(case):
    params = case["net"].collect_params()
    assert [tuple(p.shape) for p in params.values()] == [
        tuple(s[1]) for s in SPECS]
    assert [p.grad_req != "null" for p in params.values()] == [
        s[3] for s in SPECS]
    assert _leaf_names(case["net"]) == [s[0] for s in SPECS]
    # no per-head norm in this attention; the head is a leaf of its own
    assert not any("qnorm" in k or "knorm" in k for k in params.keys())
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


def test_three_adam_steps_match_the_reference():
    """``ShardedTrainStep`` on one device against the reference's own
    training loop: each step's loss and every leaf after three steps; what
    the step counted while it was traced, and its named scopes."""
    leaves = ref_common.init_params(SPECS, 6)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    counters = ("pallas_flash.windowed", "pallas_flash.window_unskipped",
                "pallas_flash.grouped", "moe.router_ahead",
                "moe.score.softmax", "moe.layers")
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
    # the selection bias is held at zero
    frozen = [i for i, s in enumerate(SPECS) if not s[3]]
    assert len(frozen) == 4 and all(
        np.asarray(got)[i] == 0.0 and not np.any(start[i]) for i in frozen)
    # three windowed calls a pass and one without a window; off the chip
    # they take the plain path, which visits the pairs left of the window
    got = {name: telemetry.value(name) for name in counters}
    assert got == {"pallas_flash.windowed": 3,
                   "pallas_flash.window_unskipped": 3,
                   "pallas_flash.grouped": 4, "moe.router_ahead": 4,
                   "moe.score.softmax": 4, "moe.layers": 4}
    text = step.compiled().as_text()
    for scope in ("window_attention", "gqa_attention", "moe.route",
                  "moe.experts"):
        assert "/%s/" % scope in text, scope


# ----------------------------------------------------------- the attention
def _plain_attention(q, k, v, window):
    """[B, T, H, D] heads, K and V repeated, masked position by position."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
    seen = (j <= i) & (j > i - window) if window else j <= i
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(q.shape[0], t, -1)


@pytest.mark.parametrize("window", [0, 5, 12, 40])
@pytest.mark.parametrize("rope", [True, False])
def test_grouped_attention_op_takes_a_window_and_no_rotary(window, rope):
    """The op after its projections: with ``rope=False`` nothing turns (a
    global layer carries no position encoding: its only order is the
    mask's), and a window of W keys holds the query's own."""
    rng = np.random.RandomState(3)
    b, t, h, hk, d = 2, 24, 7, 1, 8
    q, k, v = (jnp.asarray(rng.randn(b, t, n, d), jnp.float32)
               for n in (h, hk, hk))
    got = mx.nd.grouped_attention(
        mx.nd.NDArray(q), mx.nd.NDArray(k),
        mx.nd.NDArray(v.reshape(b, t, hk * d)), rope_theta=100.0,
        window=window, rope=rope).asnumpy()
    if rope:
        q, k = ref._rotary(q, 100.0), ref._rotary(k, 100.0)
    assert _gap(got, _plain_attention(q, k, v, window)) <= 1e-5


def test_attention_blocks_of_the_two_kinds():
    """``window_attention`` is ``full_attention``'s block under its own
    name; without ``qk_norm`` it has no per-head scales; ``head_dim`` need
    not be ``dim / num_heads``; LFM2's defaults keep its six leaves."""
    kinds = hybrid_lm.OPERATORS
    assert kinds["window_attention"] == kinds["full_attention"]
    heads = {"num_heads": 7, "num_kv_heads": 1, "head_dim": 16,
             "qk_norm": False}
    blk = hybrid_lm.DecoderBlock(
        64, ("window_attention", dict(heads, window=72, rope_theta=1.5e6)),
        moe={"hidden": 24, "num_experts": 16, "top_k": 6, "score": "softmax",
             "activation": "relu"}, router_ahead=True, prefix="w_")
    assert [n for n in blk.collect_params().keys()] == [
        "w_norm1_gamma", "w_attn_q_weight", "w_attn_k_weight",
        "w_attn_v_weight", "w_attn_proj_weight", "w_norm2_gamma",
        "w_moe_router_weight", "w_moe_score_bias", "w_moe_w_gate",
        "w_moe_w_up", "w_moe_w_down"]
    whole = {"rotary_dim": 0, "rope_scaling": None}     # the plain table
    assert blk.op._attrs == dict(whole, rope_theta=1.5e6, window=72,
                                 rope=True)
    lfm2 = hybrid_lm.GroupedQueryAttention(16, 4, 2, prefix="a_")
    assert lfm2._attrs == dict(whole, rope_theta=10000.0, window=0, rope=True)
    assert [n for n in lfm2.collect_params().keys()] == [
        "a_q_weight", "a_k_weight", "a_v_weight", "a_qnorm_gamma",
        "a_knorm_gamma", "a_proj_weight"]


def test_a_router_ahead_needs_routed_experts():
    """A dense block has no router to place: the flag changes nothing."""
    blk = hybrid_lm.DecoderBlock(16, ("conv", {"kernel_size": 2}),
                                 dense_hidden=8, router_ahead=True)
    assert blk._router_ahead is False
    net = hybrid_lm.HybridLM(32, 16, ["full_attention"], {
        "full_attention": {"num_heads": 2, "num_kv_heads": 1}},
        dense_hidden=0, dense_layers=0, router_ahead=True,
        moe={"hidden": 8, "num_experts": 4, "top_k": 2})
    assert net.blocks[0]._router_ahead is True
    assert not any("mlp_" in k for k in net.collect_params().keys())


# ------------------------------------------------------- the expert layer
E, K, D, F_ = 64, 6, 32, 12          # experts, choices a token, widths


def _layer(seed, t=48):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = jax.random.normal
    return n(ks[0], (t, D), jnp.float32), n(ks[6], (t, D), jnp.float32), [
        0.3 * n(ks[1], (E, D)),                                # router
        jnp.zeros((E,), jnp.float32),
        0.2 * n(ks[3], (E, D, F_)), 0.2 * n(ks[4], (E, D, F_)),
        0.2 * n(ks[5], (E, F_, D))]


def _layer_cfg(held=E, first=0):
    return dict(CFG, moe_num_primary_experts=E,
                moe_num_active_primary_experts=K,
                moe_num_primary_experts_held=held, first_expert_held=first)


def _routed(m, router_x, leaves, first=0, held=E, **kwargs):
    router, bias, eg, eu, ed = leaves
    part = slice(first, first + held)
    return moe.routed_ffn(m, router, bias, eg[part], eu[part], ed[part],
                          top_k=K, first_expert=first, router_x=router_x,
                          score="softmax", activation="relu", **kwargs)


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_shares_add_up_to_the_whole_layer(shares):
    """model-configs §4 at this layer's counts (top-6 of 64, no shared
    expert, the router reading another tensor than the experts): the
    routed parts that ``shares`` holders of ``64 / shares`` experts give add
    up to the uncut layer's output and to the reference's over all
    experts; 8 shares of 8 is the cell's deployment."""
    m, x, leaves = _layer(3)
    held = E // shares
    parts = sum(_routed(m, x, leaves, first=i * held, held=held)
                for i in range(shares))
    assert _gap(parts, _routed(m, x, leaves)) <= 1e-5
    assert _gap(parts, ref.expert_layer(_layer_cfg(), m, x, leaves)) <= 1e-5
    # and the plain form of the layer (every expert on every token)
    assert _gap(parts, _routed(m, x, leaves, grouped=False)) <= 1e-5


@pytest.mark.parametrize("first", [0, 8, 56])
def test_a_share_is_the_references_share(first):
    m, x, leaves = _layer(4)
    part = slice(first, first + 8)
    want = ref.expert_layer(_layer_cfg(8, first), m, x,
                            leaves[:2] + [w[part] for w in leaves[2:]])
    assert _gap(_routed(m, x, leaves, first=first, held=8), want) <= 1e-5


def test_the_routers_gradient_reaches_the_layers_input():
    """With ``router_x`` the weights' cotangent goes through the router to
    that tensor and to the router's weight; the experts' input gets the
    experts' part alone. Against autodiff of the plain reference, and
    against the same layer with its weights cut off the gradient."""
    m, x, leaves = _layer(5)
    g = jax.random.normal(jax.random.PRNGKey(17), m.shape, jnp.float32)

    def program(m_, x_, router):
        return jnp.sum(g * _routed(m_, x_, [router] + leaves[1:],
                                   first=8, held=8))

    def plain(m_, x_, router):
        part = slice(8, 16)
        return jnp.sum(g * ref.expert_layer(
            _layer_cfg(8, 8), m_, x_,
            [router, leaves[1]] + [w[part] for w in leaves[2:]]))

    got = jax.grad(program, argnums=(0, 1, 2))(m, x, leaves[0])
    want = jax.grad(plain, argnums=(0, 1, 2))(m, x, leaves[0])
    for name, a, b in zip(("m", "router_x", "router_weight"), got, want):
        assert float(jnp.linalg.norm(b)) > 0 and _gap(a, b) <= 2e-5, name
    # the experts' input sees no router: its gradient is that of the
    # layer with the router's tensor held constant
    held_still = jax.grad(lambda m_: program(
        m_, jax.lax.stop_gradient(x), leaves[0]))(m)
    assert _gap(got[0], held_still) <= 1e-6
    # a router that reads the experts' own input sends its part there
    same = jax.grad(lambda m_: jnp.sum(g * _routed(
        m_, None, leaves, first=8, held=8)))(m)
    both = jax.grad(lambda m_: program(m_, m_, leaves[0]))(m)
    assert _gap(same, both) <= 1e-6 and _gap(same, got[0]) > 1e-3


@pytest.mark.parametrize("bias", [False, True])
def test_softmax_then_top_k_is_the_softmax_over_the_chosen(bias):
    """``score="softmax"``: the softmax over all logits in float32, the
    ``top_k`` largest renormalised, equals the softmax over the chosen
    logits alone (``norm_topk_prob``); a selection bias moves the choice
    and not the weights; ``sigmoid`` stays the default."""
    x, _, leaves = _layer(6)
    sel = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (E,)) if bias \
        else leaves[1]
    idx, w = moe.route_top_k(x, leaves[0], sel, K, score="softmax")
    logits = jnp.einsum("td,ed->te", x, leaves[0], precision="highest")
    _, want_idx = jax.lax.top_k(jax.nn.softmax(logits, -1) + sel, K)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    want = jax.nn.softmax(jnp.take_along_axis(logits, idx, -1), -1)
    assert _gap(w, want) <= 1e-6
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0, rtol=1e-6)
    if not bias:    # without one the chosen are the largest logits
        assert np.array_equal(np.asarray(idx),
                              np.asarray(jax.lax.top_k(logits, K)[1]))
    s_idx, s_w = moe.route_top_k(x, leaves[0], sel, K)
    d_idx, d_w = moe.route_top_k(x, leaves[0], sel, K, score="sigmoid")
    assert np.array_equal(np.asarray(s_idx), np.asarray(d_idx))
    assert np.array_equal(np.asarray(s_w), np.asarray(d_w))
    with pytest.raises(MXNetError, match="neither"):
        moe.route_top_k(x, leaves[0], sel, K, score="tanh")
    with pytest.raises(MXNetError, match="activation"):
        moe.routed_ffn(x, *leaves, top_k=K, activation="gelu")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("activation", ["relu", "silu"])
@pytest.mark.parametrize("rows", [128, 48 * K])
def test_the_hand_written_backward_is_the_transpose(activation, dtype, rows):
    """``_held_rows_bwd`` over the two products the forward kept, against
    ``jax.vjp`` of ``_held_rows`` at the same rung, for both gates: ``d
    gate = dh * up * (gate > 0)`` and ``d up = dh * relu(gate)`` under
    ReLU."""
    m, x, leaves = _layer(8)
    m = m.astype(dtype)
    first, held = 8, 8
    idx, w = moe.route_top_k(x, leaves[0], leaves[1], K, score="softmax")
    plan = moe.piece_plan(idx, first, held, E)
    assert int(plan.n_live) <= 128
    experts = tuple(a[first:first + held].astype(dtype) for a in leaves[2:])
    diff = (m, w) + experts
    g = jax.random.normal(jax.random.PRNGKey(13), m.shape, jnp.float32)
    out, gate, up = moe._held_rows(rows, K, True, *diff, plan.order,
                                   plan.sizes, activation=activation)
    want_out, transpose = jax.vjp(
        lambda *a: moe._held_rows(rows, K, False, *a, plan.order, plan.sizes,
                                  activation=activation), *diff)
    assert _gap(out, want_out) <= 1e-6
    got = moe._held_rows_bwd(rows, K, g, gate, up, *diff, plan.order,
                             plan.sizes, activation=activation)
    tol = 1e-5 if dtype == "float32" else 3e-2
    for a, b, operand in zip(got, transpose(g), diff):
        assert a.shape == operand.shape and a.dtype == operand.dtype
        assert float(jnp.linalg.norm(b.astype(jnp.float32))) > 0
        assert _gap(a.astype(jnp.float32), b.astype(jnp.float32)) <= tol


def test_relu_and_silu_layers_differ_and_default_is_silu():
    m, x, leaves = _layer(9)
    relu = _routed(m, x, leaves)
    silu = moe.routed_ffn(m, *leaves, top_k=K, router_x=x, score="softmax")
    named = moe.routed_ffn(m, *leaves, top_k=K, router_x=x, score="softmax",
                           activation="silu")
    assert np.array_equal(np.asarray(silu), np.asarray(named))
    assert _gap(relu, silu) > 1e-2


# ------------------------------------- the older cells under the new defaults
@pytest.mark.parametrize("name", ["lfm2_8b_a1b", "kanana2_30b_a3b"])
def test_older_language_models_keep_leaves_order_and_outputs(name):
    """lfm2's and kanana's models, built as their cells build them: the
    same leaves in the reference's order, the reference's logits, and none
    of this PR's paths taken (no window, no router ahead, no softmax)."""
    older = importlib.import_module("benchmark.models." + name)
    older_ref = importlib.import_module("benchmark.reference." + name)
    cfg = _config(name)
    cfg.update(cfg["rehearsal"], dtype="float32")
    specs = older_ref.param_specs(cfg)
    leaves = ref_common.init_params(specs, 5)
    counters = ("pallas_flash.windowed", "moe.router_ahead",
                "moe.score.softmax")
    for counter in counters:
        telemetry.reset_metric(counter)
    net = older.build(cfg, specs, leaves)
    older._FIRST.clear()
    assert _leaf_names(net) == [s[0] for s in specs]
    assert [tuple(p.shape) for p in net.collect_params().values()] == [
        tuple(s[1]) for s in specs]
    x, _ = older_ref.sample_inputs(cfg, jax.random.PRNGKey(9), 2)
    got = net(mx.nd.NDArray(x)).asnumpy()
    assert _gap(got, older_ref.forward(cfg, leaves, x)[0]) <= 1e-5
    assert [telemetry.value(c) for c in counters] == [0, 0, 0]
