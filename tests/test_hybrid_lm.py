"""``gluon.model_zoo.hybrid_lm.HybridLM`` (layers that differ in kind: the
gated short convolution or grouped-query attention, a dense MLP or routed
experts, a tied head) against the plain reference of the LFM2-8B-A1B cell,
``benchmark/reference/lfm2_8b_a1b.py``, at the configuration's rehearsal
size on seeded weights: logits, loss, every leaf's gradient, three Adam
steps under ``ShardedTrainStep``; the four shares' routed parts adding up
to the uncut layer; and ``LatentMoELM`` (now the same model with latent
attention in every layer) unchanged by the refactor."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import autograd, gluon, telemetry
from mxtpu.gluon.model_zoo import hybrid_lm, latent_moe
from mxtpu.parallel import ShardedTrainStep
from mxtpu.parallel import moe

from benchmark.models import lfm2_8b_a1b as model
from benchmark.reference import common as ref_common
from benchmark.reference import lfm2_8b_a1b as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2_8b_a1b.json")) as f:
    PUBLISHED = json.load(f)
CFG = dict(PUBLISHED)
CFG.update(CFG["rehearsal"], dtype="float32")
SPECS = ref.param_specs(CFG)
TRAINABLE = [s[0] for s in SPECS if s[3]]
ADAM = {"name": "adam", "learning_rate": 1e-3}


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


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


def test_the_rehearsal_has_every_kind_of_sub_layer():
    """The issue's floor for the rehearsal size: both operators, both
    feed-forwards, at least 2 key/value heads under 4 query heads each, 4
    of 16 experts held and not from expert 0."""
    kinds = ref._kinds(CFG)
    assert kinds == ["conv", "full_attention", "conv", "conv", "conv"]
    assert 0 < CFG["num_dense_layers"] < len(kinds)
    assert CFG["num_key_value_heads"] >= 2
    assert CFG["num_attention_heads"] >= 4 * CFG["num_key_value_heads"]
    assert (CFG["num_experts"], CFG["num_experts_held"]) == (16, 4)
    assert CFG["first_expert_held"] != 0


def test_published_sizes_are_the_sources():
    """Every width, the router's 32 outputs and its 4 experts a token are
    as published; what is cut is listed with the published value beside
    it; the kept layers are one dense conv layer and a whole period."""
    c = PUBLISHED
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"]) == (2048, 7168, 1792)
    assert (c["num_attention_heads"], c["num_key_value_heads"]) == (32, 8)
    assert (c["num_experts"], c["num_experts_per_tok"]) == (32, 4)
    assert c["conv_L_cache"] == 3 and len(c["layer_types"]) == 24
    assert sorted(c["reduced"]) == ["num_dense_layers", "num_experts_held",
                                    "num_hidden_layers", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 24, "num_dense_layers": 2,
                              "num_experts": 32, "vocab_size": 65536}
    assert ref._kinds(c) == ["conv", "full_attention", "conv", "conv", "conv"]
    n = sum(int(np.prod(s[1])) for s in ref.param_specs(c))
    assert abs(n - 507.8e6) < 0.3e6


def test_leaves_are_the_references(case):
    params = list(case["net"].collect_params().values())
    assert [tuple(p.shape) for p in params] == [tuple(s[1]) for s in SPECS]
    assert [p.grad_req != "null" for p in params] == [s[3] for s in SPECS]
    # the tied head adds no leaf: the embedding's weight is the head's
    assert case["net"].head.weight is case["net"].embed.weight
    assert not any("head" in p.name for p in params)


def test_logits_match_the_reference(case):
    got = case["net"](mx.nd.NDArray(case["x"])).asnumpy()
    assert got.shape == (2, CFG["seq_len"], CFG["vocab_size"])
    assert _gap(got, case["logits"]) <= 1e-5


def test_loss_matches_the_reference(case, program_grads):
    assert abs(program_grads[0] - case["loss"]) <= 1e-5 * case["loss"]


@pytest.mark.parametrize("leaf", TRAINABLE)
def test_gradient_matches_the_reference(case, program_grads, leaf):
    assert _gap(program_grads[1][leaf], case["grads"][leaf]) <= 2e-4


def test_tied_leafs_gradient_is_the_sum_of_both_uses(case, program_grads):
    """The embedding's weight is read by the lookup and by the head: its
    gradient is the lookup's plus the head's, each taken alone in the
    reference by cutting the other use off the gradient."""
    leaves, x, y = case["leaves"], case["x"], case["y"]
    loss_fn = ref.forward_loss(CFG)

    def head_only(w):       # the lookup reads a copy that takes no gradient
        hid, _ = ref.hidden(CFG, [jax.lax.stop_gradient(w)] + leaves[1:], x)
        logp = jax.nn.log_softmax(hid @ w.T, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, y.astype(jnp.int32)[..., None], -1))

    def lookup_only(w):
        hid, _ = ref.hidden(CFG, [w] + leaves[1:], x)
        logp = jax.nn.log_softmax(hid @ jax.lax.stop_gradient(w).T, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, y.astype(jnp.int32)[..., None], -1))

    with jax.default_matmul_precision("highest"):
        parts = jax.grad(head_only)(leaves[0]), jax.grad(lookup_only)(
            leaves[0])
    assert float(jnp.linalg.norm(parts[0])) > 0
    assert float(jnp.linalg.norm(parts[1])) > 0
    assert _gap(program_grads[1]["wte_weight"], parts[0] + parts[1]) <= 2e-4
    assert _gap(program_grads[1]["wte_weight"], parts[0]) > 1e-2
    assert abs(float(loss_fn(leaves, x, y, "float32")[0])
               - float(head_only(leaves[0]))) <= 1e-5


def test_three_adam_steps_match_the_reference():
    """``ShardedTrainStep`` on one device against the reference's own
    training loop: each step's loss and every leaf after three steps."""
    leaves = ref_common.init_params(SPECS, 6)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    for name in ("short_conv.layers", "pallas_flash.grouped"):
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
    # the selection bias is held fixed
    frozen = [i for i, s in enumerate(SPECS) if not s[3]]
    assert frozen and all(np.asarray(got)[i] == 0.0 for i in frozen)
    # what the step counted while it was traced, and its named scopes
    assert telemetry.value("short_conv.layers") == 4
    assert telemetry.value("pallas_flash.grouped") == 1
    text = step.compiled().as_text()
    for scope in ("short_conv", "gqa_attention", "moe.experts"):
        assert "/%s/" % scope in text, scope


# ------------------------------------------------------- the expert layer
E, K, D, F_ = 32, 4, 32, 12          # experts, choices a token, widths


def _layer(seed, t=48):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = jax.random.normal
    return n(ks[0], (t, D), jnp.float32), [
        0.3 * n(ks[1], (E, D)),                                # router
        jax.random.uniform(ks[2], (E,), jnp.float32, -0.01, 0.01),
        0.2 * n(ks[3], (E, D, F_)), 0.2 * n(ks[4], (E, D, F_)),
        0.2 * n(ks[5], (E, F_, D))]


def _layer_cfg(held=E, first=0):
    return dict(CFG, num_experts_per_tok=K, routed_scaling_factor=1,
                num_experts_held=held, first_expert_held=first,
                router_epsilon=PUBLISHED["router_epsilon"])


def _routed(x, leaves, first=0, held=E):
    router, bias, eg, eu, ed = leaves
    part = slice(first, first + held)
    return moe.routed_ffn(x, router, bias, eg[part], eu[part], ed[part],
                          top_k=K, first_expert=first, scale=1.0)


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_shares_add_up_to_the_whole_layer(shares):
    """model-configs §4 at this layer's counts (top-4 of 32, no shared
    expert): the routed parts that ``shares`` holders of ``32 / shares``
    experts give add up to the uncut layer's output and to the reference's
    over all experts; 4 shares of 8 is the cell's deployment."""
    x, leaves = _layer(3)
    held = E // shares
    parts = sum(_routed(x, leaves, first=i * held, held=held)
                for i in range(shares))
    assert _gap(parts, _routed(x, leaves)) <= 1e-5
    assert _gap(parts, ref.expert_layer(_layer_cfg(), x, leaves)) <= 1e-5


@pytest.mark.parametrize("first", [0, 8, 24])
def test_a_share_is_the_references_share(first):
    x, leaves = _layer(4)
    part = slice(first, first + 8)
    want = ref.expert_layer(_layer_cfg(8, first), x,
                            leaves[:2] + [w[part] for w in leaves[2:]])
    assert _gap(_routed(x, leaves, first=first, held=8), want) <= 1e-5


def test_the_cells_ladder_of_row_counts():
    """8 of 32 experts held over 65,536 (token, slot) pairs: two rungs, a
    third over the even share of 16,384 rows, then all; kanana's ladder
    (16 of 128 over 49,152) is what its cell was measured with."""
    assert moe._rungs(65536, 8, 32) == (22016, 65536)
    assert moe._rungs(49152, 16, 128) == (8192, 16384, 49152)


# ------------------------------------------------------------ the blocks
def test_grouped_attention_op_is_attention_over_repeated_heads():
    """The op after its projections and norms: rotary on q and k, then
    causal attention with K and V repeated the plain way."""
    rng = np.random.RandomState(2)
    b, t, h, hk, d = 2, 12, 4, 2, 8
    q, k = rng.randn(b, t, h, d), rng.randn(b, t, hk, d)
    v = rng.randn(b, t, hk * d)
    got = mx.nd.grouped_attention(*(mx.nd.array(a.astype(np.float32))
                                    for a in (q, k, v)),
                                  rope_theta=100.0).asnumpy()
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    qr, kr = ref._rotary(f32(q), 100.0), ref._rotary(f32(k), 100.0)
    kr = jnp.repeat(kr, h // hk, axis=2)
    vr = jnp.repeat(f32(v).reshape(b, t, hk, d), h // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qr, kr) / np.sqrt(d)
    s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None], s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vr)
    assert _gap(got, want.reshape(b, t, h * d)) <= 1e-5


def test_operator_kinds_and_their_prefixes():
    """One decoder block, its operator a child named by kind; an unknown
    kind is refused when the block is built."""
    blk = hybrid_lm.DecoderBlock(16, ("conv", {"kernel_size": 2}),
                                 dense_hidden=8, prefix="b_")
    assert isinstance(blk.op, gluon.nn.ShortConv)
    assert [n for n in blk.collect_params().keys()] == [
        "b_norm1_gamma", "b_conv_weight", "b_conv_in_weight",
        "b_conv_out_weight", "b_norm2_gamma", "b_mlp_gate_weight",
        "b_mlp_up_weight", "b_mlp_down_weight"]
    attn = hybrid_lm.DecoderBlock(
        16, ("full_attention", {"num_heads": 4, "num_kv_heads": 2}),
        dense_hidden=8, prefix="a_")
    assert isinstance(attn.op, hybrid_lm.GroupedQueryAttention)
    assert "a_attn_qnorm_gamma" in attn.collect_params().keys()
    with pytest.raises(KeyError):
        hybrid_lm.DecoderBlock(16, ("window", {}), dense_hidden=8)
    with pytest.raises(ValueError, match="do not divide"):
        hybrid_lm.GroupedQueryAttention(16, num_heads=4, num_kv_heads=3)


def test_untied_head_is_a_leaf_of_its_own():
    net = hybrid_lm.HybridLM(32, 16, ["conv"], {}, dense_hidden=8, moe=None,
                             tie_head=False, prefix="m_")
    names = list(net.collect_params().keys())
    assert names[0] == "m_wte_weight" and names[-1] == "m_head_weight"
    assert net.head.weight is not net.embed.weight


# ------------------------------------------- LatentMoELM after the refactor
_LATENT = dict(vocab_size=64, dim=32, num_layers=3, dense_hidden=48,
               attention={"num_heads": 2, "kv_rank": 16, "nope_dim": 8,
                          "rope_dim": 4, "v_dim": 8},
               moe={"hidden": 16, "num_experts": 4, "top_k": 2,
                    "shared_hidden": 16})
# what ``collect_params()`` listed before LatentMoELM became a HybridLM, but
# for the block's class in each name (``latentmoeblock`` then); the trainer
# and ``save_parameters`` read the order and the attributes, not these names
_LATENT_LEAVES = (
    ["wte_weight"]
    + ["h_decoderblock%d_%s" % (i, n) for i in range(3) for n in (
        ["norm1_gamma", "attn_q_weight", "attn_kva_weight",
         "attn_kvnorm_gamma", "attn_kvb_weight", "attn_proj_weight",
         "norm2_gamma"]
        + (["mlp_gate_weight", "mlp_up_weight", "mlp_down_weight"] if i < 1
           else ["moe_router_weight", "moe_score_bias", "moe_w_gate",
                 "moe_w_up", "moe_w_down", "moe_shared_gate_weight",
                 "moe_shared_up_weight", "moe_shared_down_weight"]))]
    + ["normf_gamma", "head_weight"])


def test_latent_moe_lm_keeps_its_leaves_and_their_order():
    net = latent_moe.LatentMoELM(prefix="lm_", **_LATENT)
    assert isinstance(net, hybrid_lm.HybridLM)
    assert list(net.collect_params().keys()) == [
        "lm_" + n for n in _LATENT_LEAVES]
    block = net.blocks[0]
    assert type(block) is hybrid_lm.DecoderBlock
    assert isinstance(block.op, latent_moe.MultiHeadLatentAttention)
    # the head is its own leaf, with its shape left to the first forward
    assert net.head.weight is not net.embed.weight
    assert tuple(net.head.weight.shape) == (64, 0)


def test_latent_moe_lm_output_is_its_layers_written_out():
    """The refactored model's logits against its own blocks applied by
    hand in the order the old class applied them."""
    net = latent_moe.LatentMoELM(prefix="lo_", **_LATENT)
    net.initialize(mx.init.Normal(0.05))
    tokens = mx.nd.array(np.random.RandomState(0).randint(0, 64, (2, 16)))
    got = net(tokens).asnumpy()
    x = net.embed(tokens)
    for blk in net.blocks:
        x = x + blk.op(blk.norm1(x))
        x = x + blk.ffn(blk.norm2(x))
    want = net.head(net.norm_f(x)).asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert got.shape == (2, 16, 64) and np.isfinite(got).all()
