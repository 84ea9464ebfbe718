"""``HybridLM`` as Laguna-S-2.1's stack (a leading dense layer under full
attention, then windowed, windowed, windowed, full over routed experts; 6
query heads a key/value head on a full layer and 9 on a windowed one, a
head-wise output gate on all; YaRN's table on half a head beside the
plain one over a whole head; sigmoid top-k experts with a scale and a
shared expert) against the plain reference of its cell,
``benchmark/reference/laguna_s_2_1.py``, at the configuration's rehearsal
size on seeded weights: leaves, logits, loss, every leaf's gradient, three
Adam steps under ``ShardedTrainStep``; YaRN's table against numbers worked
by hand; the partial rotary's unturned half; the gate written out; both
kinds of layer through the Pallas kernels (the interpreter) under a window
narrower than a block; the eight shares adding up to the uncut layer."""
import importlib
import json
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import autograd, gluon, telemetry
from mxtpu.gluon.model_zoo import hybrid_lm
from mxtpu.ops import nn as ops_nn
from mxtpu.parallel import ShardedTrainStep
from mxtpu.parallel import moe

from benchmark.flops import laguna_s_2_1 as flops
from benchmark.models import laguna_s_2_1 as model
from benchmark.reference import common as ref_common
from benchmark.reference import laguna_s_2_1 as ref

from _jaxpr_count import calls, differentiated, router_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")

with open(os.path.join(ROOT, "benchmark", "configs",
                       "laguna_s_2_1.json")) as f:
    PUBLISHED = json.load(f)
CFG = dict(PUBLISHED)
CFG.update(CFG["rehearsal"], dtype="float32")
SPECS = ref.param_specs(CFG)
TRAINABLE = [s[0] for s in SPECS if s[3]]
ADAM = {"name": "adam", "learning_rate": 1e-3}
YARN = PUBLISHED["rope_parameters"]["full_attention"]


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


# ------------------------------------------------------ the configuration
def test_the_rehearsal_has_what_the_cell_has():
    """A leading dense layer and a whole period; 6 and 9 query heads a
    key/value head; a window shorter than the sequence; a sequence past
    the context YaRN's table was stretched from; half a head turned; 2 of
    16 experts held and not from expert 0; a shared expert."""
    assert ref.layers(CFG) == [(False, 12, True), (True, 18, False),
                               (True, 18, False), (True, 18, False),
                               (False, 12, False)]
    assert ref.layers(PUBLISHED) == [(False, 48, True), (True, 72, False),
                                     (True, 72, False), (True, 72, False),
                                     (False, 48, False)]
    assert 0 < CFG["sliding_window"] < CFG["seq_len"]
    rope = CFG["rope_parameters"]["full_attention"]
    assert CFG["seq_len"] > rope["original_max_position_embeddings"]
    assert rope["partial_rotary_factor"] == 0.5 and rope["factor"] > 1
    inv_freq, factor, turned = ref.rope_table(rope, CFG["head_dim"])
    assert turned == CFG["head_dim"] // 2 and factor > 1
    # the rehearsal's table mixes kept and interpolated pairs
    plain = rope["rope_theta"] ** (-np.arange(turned // 2) * 2.0 / turned)
    assert inv_freq[0] == plain[0] and np.all(inv_freq[1:] < plain[1:] / 2)
    assert (CFG["num_experts"], CFG["num_experts_held"]) == (16, 2)
    assert CFG["first_expert_held"] != 0
    assert CFG["shared_expert_intermediate_size"] > 0


def test_published_sizes_are_the_sources():
    """Every width, the router's 256 outputs and its 10 experts a token
    are as the catalog's row gives them; what is cut is listed with the
    published value beside it."""
    c = PUBLISHED
    assert (c["hidden_size"], c["head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"],
            c["shared_expert_intermediate_size"]) == (3072, 128, 12288,
                                                      1024, 1024)
    assert (c["num_attention_heads"], c["num_key_value_heads"]) == (48, 8)
    assert c["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert c["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + (["full_attention"] + [
            "sliding_attention"] * 3) * 11
    assert (c["num_experts"], c["num_experts_per_tok"],
            c["moe_routed_scaling_factor"]) == (256, 10, 2.5)
    assert (c["sliding_window"], c["rms_norm_eps"],
            c["max_position_embeddings"]) == (512, 1e-6, 1048576)
    assert c["mlp_only_layers"] == [0] and not c["tie_word_embeddings"]
    assert c["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    assert c["reduced"] == ["num_hidden_layers", "num_experts_held",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                              "vocab_size": 100352}
    assert (c["num_hidden_layers"], c["num_experts_held"],
            c["vocab_size"]) == (5, 8, 100352 // 8)
    assert c["recompute"] is True and "16.32 GiB" in c["assumed"]["recompute"]


def test_operations_are_the_issues_count():
    """MAC = 2 at the published widths: 811 M parameters held, the causal
    and the window's pairs, each kernel's call at its own kind's heads,
    the products and the whole step."""
    c = PUBLISHED
    held = sum(int(np.prod(s[1])) for s in ref.param_specs(c) if s[3])
    assert abs(held - 811.0e6) < 0.1e6
    assert flops.causal_pairs(c) == 134225920
    assert flops.window_pairs(c) == 512 * 16384 - 512 * 511 // 2 == 8257792
    # a full layer's kernels at 48 heads, a windowed layer's at 72
    assert flops.flash_fwd_flops(c) == 2 * 134225920 * 48 * 2 * 128
    assert flops.flash_window_fwd_flops(c) == 2 * 8257792 * 72 * 2 * 128
    assert flops.flash_bwd_flops(c) * 2 == flops.flash_fwd_flops(c) * 5
    assert flops.flash_window_bwd_flops(c) * 2 \
        == flops.flash_window_fwd_flops(c) * 5
    # the two full layers' kernels: 23.1 TFLOP a step; the three windowed
    # layers' 3.2; the products 47.4 (482 M active parameters a token x 6)
    full = 2 * (flops.flash_fwd_flops(c) + flops.flash_bwd_flops(c))
    windowed = 3 * (flops.flash_window_fwd_flops(c)
                    + flops.flash_window_bwd_flops(c))
    assert abs(full - 23.1e12) < 0.05e12 and abs(windowed - 3.2e12) < 0.05e12
    # the step is 3 x forward, 69.9 TFLOP: the backward's second s = k q^T
    # is the kernel's own (5 products of its 7 a pair) and no part of what
    # the algorithm needs; with it the issue's 73.7, the full kernels 31%
    step = flops.train_flops_per_sample(c)
    assert step == 3 * flops.forward_flops(c)
    assert abs(step - 69.9e12) < 0.1e12
    assert abs(step - (full + windowed) * 6 / 7 - 47.4e12) < 0.05e12
    as_run = step + (full + windowed) / 7
    assert abs(as_run - 73.7e12) < 0.3e12 and 0.30 < full / as_run < 0.33
    # a window as long as the sequence sees the causal pairs
    assert flops.window_pairs(dict(c, sliding_window=16384)) \
        == flops.causal_pairs(c)


# ------------------------------------------------- the model, end to end
def test_leaves_are_the_references(case):
    params = case["net"].collect_params()
    assert [tuple(p.shape) for p in params.values()] == [
        tuple(s[1]) for s in SPECS]
    assert [p.grad_req != "null" for p in params.values()] == [
        s[3] for s in SPECS]
    assert _leaf_names(case["net"]) == [s[0] for s in SPECS]
    # 12 query heads on the full layers, 18 on the windowed: two sets of
    # keyword arguments, one block class
    kinds = [(type(b.op).__name__, b.op.q.weight.shape[0] // CFG["head_dim"],
              b.op._attrs["window"], b.op._attrs["rotary_dim"],
              b.op._attrs["rope_scaling"] is not None, b.op.gate is not None)
             for b in case["net"].blocks]
    full = ("GroupedQueryAttention", 12, 0, 8, True, True)
    windowed = ("GroupedQueryAttention", 18, 24, 0, False, True)
    assert kinds == [full, windowed, windowed, windowed, full]
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
    the step counted while it was traced, and its named scopes. Built as
    the cell builds it, every block recomputed in the backward."""
    leaves = ref_common.init_params(SPECS, 6)
    assert CFG["recompute"] is True
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    counters = ("pallas_flash.windowed", "pallas_flash.window_unskipped",
                "pallas_flash.window_pairs_seen",
                "pallas_flash.window_pairs_visited", "pallas_flash.grouped",
                "attention.head_gated", "rotary.scaled", "moe.layers",
                "train_step.blocks_recomputed")
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
    # three windowed calls a pass and two without a window, each traced
    # twice (the forward and its recomputation); off the chip they take
    # the plain path, which visits the whole square; five gates; q and k
    # of the two full layers turned by the scaled table
    t, w = CFG["seq_len"], CFG["sliding_window"]
    got = {name: telemetry.value(name) for name in counters}
    assert got == {"pallas_flash.windowed": 6,
                   "pallas_flash.window_unskipped": 6,
                   "pallas_flash.window_pairs_seen":
                       6 * (w * t - w * (w - 1) // 2),
                   "pallas_flash.window_pairs_visited": 6 * t * t,
                   "pallas_flash.grouped": 10, "attention.head_gated": 5,
                   "rotary.scaled": 4, "moe.layers": 4,
                   "train_step.blocks_recomputed": 5}
    read = importlib.import_module("benchmark.run").reader(
        "flash_window_visit_ratio.train")
    assert read({"window": {"attempted": 1}}) == pytest.approx(
        t * t / (w * t - w * (w - 1) // 2))
    assert read({"window": {"attempted": 0}}) is None
    text = step.compiled().as_text()
    for scope in ("window_attention", "gqa_attention", "rotary_yarn",
                  "rotary", "head_gate", "moe.route", "moe.shared"):
        assert "/%s/" % scope in text, scope
    telemetry.reset_metric("pallas_flash.window_pairs_seen")
    assert read({"window": {"attempted": 1}}) is None


def test_a_recomputed_block_runs_its_kernels_forward_once(monkeypatch):
    """The model as the cell builds it, differentiated and not run (the
    interpreter, at 128 positions: the flash pair wants its keys in 128s):
    five blocks recomputed, and the two full layers' forward kernel stands
    in the jaxpr once for each backward: their second forward holds none
    (``hybrid_lm.kept_policy``; under a bare checkpoint each stood twice,
    as the three windowed layers' still does: their outputs are let go,
    0.92 GB the cell's set-up has no room for). The four routed layers'
    routers stand once each (``moe.KEPT_NAMES``)."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    cfg = dict(CFG, seq_len=128, items_per_sample=128)
    net = model.build(cfg, SPECS, ref_common.init_params(SPECS, 5))
    model._FIRST.clear()
    telemetry.reset_metric("train_step.blocks_recomputed")
    x, y = ref.sample_inputs(cfg, jax.random.PRNGKey(9), 2)
    closed = differentiated(net, _loss_fn(), x, y)
    got = calls(closed)
    assert telemetry.value("train_step.blocks_recomputed") == 5
    assert got["flash_attention_fwd"] == got["flash_attention_bwd"] == 2
    assert (got["flash_window_fwd"], got["flash_window_bwd"]) == (6, 3)
    # and it routes once: one choice over all the experts, one sort, one
    # router product and one gather of the picked scores for each routed
    # layer, none in the second forward
    routed = router_ops(closed, cfg["num_experts"])
    n_routed = sum(s[0].endswith("_moe_router_weight") for s in SPECS)
    assert n_routed > 0 and routed == {
        "top_k.full": n_routed, "sort": n_routed, "score": n_routed,
        "picked": n_routed, "top_k": n_routed}, routed


# --------------------------------------------------------------- rotary
def test_yarn_table_is_the_formula_worked_by_hand():
    """At the published sizes (64 turned entries, theta 500,000, factor 128
    from 8,192 positions, beta 32 and 1): ``c(32) = 9.04``, ``c(1) =
    17.49``, so the ramp runs from pair 9 to pair 18; pair i is ``e_i =
    500000^(-i/32)`` below it, ``e_i / 128`` above it, and between them
    ``e_i (1 - r_i + r_i / 128)`` with ``r_i = (i - 9) / 9``."""
    def c(b):
        return 64 * math.log(8192 / (2 * math.pi * b)) / (
            2 * math.log(500000))

    assert abs(c(32) - 9.04) < 0.005 and abs(c(1) - 17.49) < 0.005
    assert (math.floor(c(32)), math.ceil(c(1))) == (9, 18)
    got = ops_nn.yarn_inv_freq(YARN["rope_theta"], 64, YARN["factor"],
                               YARN["original_max_position_embeddings"],
                               YARN["beta_fast"], YARN["beta_slow"])
    assert got.shape == (32,) and got.dtype == np.float32
    by_hand = {0: 1.0,                                   # kept
               9: 2.4955e-2,                             # the ramp's foot
               13: 4.8394e-3 * (5 / 9 + 4 / 9 / 128),    # r = 4/9
               18: 6.2265e-4 / 128,                      # the ramp's head
               31: 3.0139e-6 / 128}
    for i, want in by_hand.items():
        assert got[i] == pytest.approx(want, rel=2e-4), i
    assert np.all(np.diff(got) < 0)
    # the reference's own table, written apart, is the same
    theirs, factor, turned = ref.rope_table(YARN, 128)
    np.testing.assert_allclose(got, theirs, rtol=1e-6)
    assert turned == 64
    assert factor == YARN["attention_factor"] == pytest.approx(
        0.1 * math.log(128) + 1, rel=1e-12)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_partial_rotary_turns_half_a_head_and_leaves_the_rest(dtype):
    """``width=64`` of 128 with YaRN's table: the last 64 entries leave bit
    for bit as they came; the first 64 are what the whole-head rotary
    gives a head of 64 with the same table; cos and sin carry the
    attention factor: at position 0 the turned entries are the input times
    1.4852."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 3, 128),
                          jnp.float32).astype(dtype)
    out = ops_nn.rotary(x, YARN["rope_theta"], width=64, scaling=YARN)
    assert out.dtype == x.dtype and out.shape == x.shape
    assert np.array_equal(np.asarray(out[..., 64:], np.float32),
                          np.asarray(x[..., 64:], np.float32))
    alone = ops_nn.rotary(x[..., :64], YARN["rope_theta"], scaling=YARN)
    assert np.array_equal(np.asarray(out[..., :64], np.float32),
                          np.asarray(alone, np.float32))
    want = x[:, 0, :, :64].astype(jnp.float32) * YARN["attention_factor"]
    assert _gap(out[:, 0, :, :64].astype(jnp.float32), want) <= (
        1e-6 if dtype == "float32" else 4e-3)
    # against the reference's rotary (its own table, halves written out)
    theirs = ref._rotary(x.astype(jnp.float32), YARN)
    assert _gap(out.astype(jnp.float32), theirs) <= (
        1e-5 if dtype == "float32" else 4e-3)
    # the plain table over the whole head is the call without arguments
    plain = PUBLISHED["rope_parameters"]["sliding_attention"]
    assert _gap(ops_nn.rotary(x.astype(jnp.float32), plain["rope_theta"]),
                ref._rotary(x.astype(jnp.float32), plain)) <= 1e-5
    # a scale of 1 with the factor it implies is the plain table
    same = ops_nn.rotary(x, 10000.0, scaling={
        "factor": 1.0, "original_max_position_embeddings": 8192})
    assert _gap(same.astype(jnp.float32),
                ops_nn.rotary(x, 10000.0).astype(jnp.float32)) <= (
        1e-6 if dtype == "float32" else 4e-3)


# ----------------------------------------------------------- the head gate
def _attention_pair(**kwargs):
    gated = hybrid_lm.GroupedQueryAttention(32, 6, 2, head_dim=8,
                                            head_gate=True, **kwargs)
    plain = hybrid_lm.GroupedQueryAttention(32, 6, 2, head_dim=8, **kwargs)
    gated.initialize()
    plain.initialize()
    x = mx.nd.NDArray(jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32)))
    gated(x), plain(x)                 # the deferred shapes
    for name, p in plain.collect_params().items():
        p.set_data(gated.collect_params()[
            gated.prefix + name[len(plain.prefix):]].data())
    return gated, plain, x


def test_the_head_gate_is_sigmoid_of_the_input_by_head():
    """``o_h * sigmoid(x Wg)_h`` written out: the gated block's output is
    the ungated block's heads, each scaled, through the same projection;
    the leaf sits between the norms and the projection."""
    gated, plain, x = _attention_pair(window=5)
    names = [k[len(gated.prefix):] for k in gated.collect_params().keys()]
    assert names == ["q_weight", "k_weight", "v_weight", "qnorm_gamma",
                     "knorm_gamma", "gate_weight", "proj_weight"]
    assert "gate_weight" not in "".join(plain.collect_params().keys())
    wg = jax.random.normal(jax.random.PRNGKey(1), (6, 32)) * 0.5
    gated.gate.weight.set_data(mx.nd.NDArray(wg))
    wo = plain.proj.weight.data()._data                      # [32, 48]
    # the ungated block's heads, before its projection
    q = plain.q_norm(mx.nd.reshape(plain.q(x), shape=(0, 0, -1, 8)))
    k = plain.k_norm(mx.nd.reshape(plain.k(x), shape=(0, 0, -1, 8)))
    heads = mx.nd._contrib_grouped_attention(
        q, k, plain.v(x), **plain._attrs)._data.reshape(2, 12, 6, 8)
    assert _gap(plain(x).asnumpy(), jnp.einsum(
        "bto,do->btd", heads.reshape(2, 12, 48), wo)) <= 1e-5
    g = jax.nn.sigmoid(jnp.einsum("btd,hd->bth", x._data, wg))
    want = jnp.einsum("bto,do->btd",
                      (heads * g[..., None]).reshape(2, 12, 48), wo)
    assert _gap(gated(x).asnumpy(), want) <= 1e-5
    # a gate of zero weight halves every head
    gated.gate.weight.set_data(mx.nd.zeros((6, 32)))
    half = jnp.einsum("bto,do->btd", heads.reshape(2, 12, 48), wo) * 0.5
    assert _gap(gated(x).asnumpy(), half) <= 1e-5


def test_both_blocks_share_the_gate():
    """One spelling: the latent block and the grouped block call the one
    function, and each call is counted."""
    from mxtpu.gluon.model_zoo import latent_moe
    assert latent_moe.gate_heads is hybrid_lm.gate_heads
    telemetry.reset_metric("attention.head_gated")
    gated, _, x = _attention_pair()
    telemetry.reset_metric("attention.head_gated")
    gated(x)
    latent = latent_moe.MultiHeadLatentAttention(
        32, num_heads=2, kv_rank=8, nope_dim=8, rope_dim=4, v_dim=8,
        head_gate=True)
    latent.initialize()
    latent(x)
    assert telemetry.value("attention.head_gated") == 2


def test_sparse_attention_refuses_the_new_rotary():
    for extra in ({"rotary_dim": 8}, {"rope_scaling": YARN}):
        with pytest.raises(ValueError, match="plain rotary"):
            hybrid_lm.GroupedQueryAttention(32, 4, 2, topk=4, **extra)


# ------------------------------------------------------------ the kernels
def _plain_attention(q, k, v, window):
    """[B, H, T, D] heads, K and V repeated, masked position by position."""
    t, group = q.shape[2], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
    seen = (j <= i) & (j > i - window) if window else j <= i
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("heads,window", [(18, 40), (12, 0)],
                         ids=["windowed-9-a-head", "full-6-a-head"])
def test_both_kinds_of_layer_run_the_kernels(monkeypatch, heads, window):
    """Groups of 9 and of 6 query heads a key/value head through both
    Pallas kernels (the interpreter), blocks of 128 over 384 positions,
    the window (40) narrower than a block: output and all three gradients
    against the plain attention; nothing falls back, K and V are never
    repeated, and the windowed call counts the pairs it visits."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    t = 384
    ks = jax.random.split(jax.random.PRNGKey(heads), 4)
    q = jax.random.normal(ks[0], (1, heads, t, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, t, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, t, 16), jnp.float32)
    g = jax.random.normal(ks[3], (1, heads, t, 16), jnp.float32)
    fa.reset_dispatch_stats()

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, True, block_q=128, block_k=128,
                                  window=window)

    out, vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(lambda *a: _plain_attention(*a, window), q, k, v)
    assert _gap(out, want) <= 1e-5
    for got, ref_grad in zip(vjp(g), want_vjp(g)):
        assert _gap(got, ref_grad) <= 1e-5
    stats = dict(fa.DISPATCH_STATS.items())
    assert (stats["pallas"], stats["bwd_pallas"]) == (1, 1), stats
    assert stats["xla"] == 0 and stats["kv_repeated"] == 0, stats
    assert stats["grouped"] == 1 and stats["window_unskipped"] == 0
    if window:
        # q block i visits k blocks i - 1 and i: 5 of 9 block pairs for
        # the 40 x 384 - 40 x 39 / 2 pairs the window sees
        assert stats["windowed"] == 1
        assert stats["window_pairs_seen"] == 40 * 384 - 40 * 39 // 2
        assert stats["window_pairs_visited"] == 5 * 128 * 128
    else:
        assert stats["windowed"] == 0 and stats["window_pairs_seen"] == 0


# ------------------------------------------------------- the expert layer
E, K, D, F_ = 16, 4, 32, 12          # experts, choices a token, widths


def _layer(seed, t=48):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = jax.random.normal
    return n(ks[0], (t, D), jnp.float32), [
        0.3 * n(ks[1], (E, D)),                                # router
        jnp.zeros((E,), jnp.float32),
        0.2 * n(ks[3], (E, D, F_)), 0.2 * n(ks[4], (E, D, F_)),
        0.2 * n(ks[5], (E, F_, D)),
        0.2 * n(ks[6], (F_, D)), 0.2 * n(ks[7], (F_, D)),      # shared
        0.2 * n(ks[8], (D, F_))]


def _layer_cfg(held=E, first=0):
    return dict(CFG, num_experts=E, num_experts_per_tok=K,
                num_experts_held=held, first_expert_held=first)


def _routed(m, leaves, first=0, held=E, **kwargs):
    router, bias, eg, eu, ed = leaves[:5]
    part = slice(first, first + held)
    return moe.routed_ffn(m, router, bias, eg[part], eu[part], ed[part],
                          top_k=K, first_expert=first,
                          scale=CFG["moe_routed_scaling_factor"], **kwargs)


def _shared(m, leaves):
    sg, su, sd = leaves[5:]
    return (jax.nn.silu(m @ sg.T) * (m @ su.T)) @ sd.T


def test_shares_add_up_to_the_whole_layer():
    """model-configs §4 at this layer's kind (sigmoid scores normalised
    over the chosen, times 2.5, a shared expert): at 16 experts, 2 held,
    the routed parts the eight shares give plus the shared expert COUNTED
    ONCE add up to the uncut reference's layer over all sixteen."""
    m, leaves = _layer(3)
    parts = sum(_routed(m, leaves, first=2 * i, held=2) for i in range(8))
    whole = ref.expert_layer(_layer_cfg(), m, leaves)
    assert _gap(parts + _shared(m, leaves), whole) <= 1e-5
    # a share alone is the reference's share, shared expert and all
    for first in (0, 4, 14):
        part = slice(first, first + 2)
        want = ref.expert_layer(
            _layer_cfg(2, first), m,
            leaves[:2] + [w[part] for w in leaves[2:5]] + leaves[5:])
        got = _routed(m, leaves, first=first, held=2) + _shared(m, leaves)
        assert _gap(got, want) <= 1e-5
    # counting the shared expert in every share would be eight of it
    assert _gap(parts + 8 * _shared(m, leaves), whole) > 0.1
    # the routed weights sum to 2.5 a token
    idx, w = ref.route(_layer_cfg(), m, leaves[0], leaves[1])
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
