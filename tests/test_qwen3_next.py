"""``HybridLM`` as Qwen3-Next's stack (three Gated-DeltaNet layers to one
gated softmax layer, every norm but the DeltaNet's head norm a ``1 + w``
scale, softmax top-k experts with a sigmoid-gated shared one, an untied
head, blocks recomputed) against the plain reference of its cell,
``benchmark/reference/qwen3_next_80b_a3b.py``, at the configuration's
rehearsal size on seeded weights: leaves, loss, every leaf's gradient; the
counters and scopes a traced step leaves; the kept names under
recomputation; the shares of the experts' holders adding up to the uncut
layer. The operator's own cases (the chunked rule against the
token-by-token recurrence, the gates, the norm, the flash backward's second
layout) are ``tests/test_gated_delta_rule.py``: two files, because the
tier-1 run hands out work by file."""
import importlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import gluon, telemetry
from mxtpu.gluon.model_zoo import hybrid_lm
from mxtpu.parallel import moe

from benchmark.flops import qwen3_next_80b_a3b as flops
from benchmark.models import qwen3_next_80b_a3b as model
from benchmark.reference import common as ref_common
from benchmark.reference import qwen3_next_80b_a3b as ref

from _jaxpr_count import calls, differentiated, router_ops, traced_loss

kda = importlib.import_module("mxtpu.ops.pallas.kda")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


PUBLISHED = _config("qwen3_next_80b_a3b")
CFG = dict(PUBLISHED)
CFG.update(CFG["rehearsal"], dtype="float32")
SPECS = ref.param_specs(CFG)
TRAINABLE = [s[0] for s in SPECS if s[3]]
COUNTERS = ("gated_delta.calls", "gated_delta.fallbacks",
            "gated_delta.chunks", "train_step.blocks_recomputed",
            "attention.element_gated", "moe.layers")


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def _leaf_names(net):
    return [re.sub(r"^h_decoderblock(\d+)_", r"h\1_", k[len(net.prefix):])
            for k in net.collect_params().keys()]


def _loss_fn(vocab=CFG["vocab_size"]):
    blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(block, tokens, labels):
        return blk(block(tokens).reshape((-1, vocab)),
                   labels.reshape((-1,)))
    return forward


@pytest.fixture(scope="module")
def case():
    """The model with the reference's seeded leaves, two sequences, and the
    reference's loss and gradients on them."""
    leaves = ref_common.init_params(SPECS, 5)
    x, y = ref.sample_inputs(CFG, jax.random.PRNGKey(9), 2)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    loss_fn = ref.forward_loss(CFG)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda full: loss_fn(full, x, y, "float32")[0]))(list(leaves))
    return {"net": net, "leaves": leaves, "x": x, "y": y,
            "loss": float(loss),
            "grads": dict(zip([s[0] for s in SPECS], grads))}


@pytest.fixture(scope="module")
def program_grads(case):
    """The program's loss and gradients through the traced step (the
    whole-step trainer's own forward, differentiated by jax), every block
    recomputed."""
    for name in COUNTERS:
        telemetry.reset_metric(name)
    loss_of, datas = traced_loss(case["net"], _loss_fn(), case["x"],
                                 case["y"])
    step = jax.jit(jax.value_and_grad(loss_of))
    text = step.lower(datas).as_text(debug_info=True)
    counted = {name: telemetry.value(name) for name in COUNTERS}
    loss, grads = step(datas)
    trainable = [g for g, s in zip(grads, SPECS) if s[3]]
    return float(loss), dict(zip(TRAINABLE, trainable)), text, counted


# ------------------------------------------------------ the configuration
def test_the_rehearsal_has_what_the_cell_has():
    """One whole period, DeltaNet x 3 then attention, every layer over
    routed experts; two value heads a key head; a sequence of several
    chunks that is no multiple of the chunk; part of a head turned; 2 of 16
    experts held and not from expert 0; blocks recomputed."""
    for c in (PUBLISHED, CFG):
        assert ref.kinds(c) == ["gated_delta_net"] * 3 + ["full_attention"]
        assert c["linear_num_value_heads"] == 2 * c["linear_num_key_heads"]
        assert c["num_attention_heads"] > c["num_key_value_heads"]
        assert 0 < c["partial_rotary_factor"] < 1
        assert c["recompute"] is True
    assert CFG["seq_len"] >= 4 * CFG["gdn_chunk"]
    assert CFG["seq_len"] % CFG["gdn_chunk"]
    assert (CFG["num_experts"], CFG["num_experts_held"]) == (16, 2)
    assert CFG["first_expert_held"] != 0


def test_published_sizes_are_the_sources():
    """Every number of the catalog's row is in the file under its own key;
    what is cut is listed with the published value beside it; every
    assumption and departure is written down."""
    c = PUBLISHED
    row = {"decoder_sparse_step": 1, "full_attention_interval": 4,
           "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
           "linear_key_head_dim": 128, "linear_num_key_heads": 16,
           "linear_num_value_heads": 32, "linear_value_head_dim": 128,
           "max_position_embeddings": 262144, "mlp_only_layers": [],
           "model_type": "qwen3_next", "moe_intermediate_size": 512,
           "norm_topk_prob": True, "num_attention_heads": 16,
           "num_experts": 512, "num_experts_per_tok": 10,
           "num_hidden_layers": 48, "num_key_value_heads": 2,
           "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 10000000,
           "shared_expert_intermediate_size": 512,
           "tie_word_embeddings": False, "use_sliding_window": False,
           "vocab_size": 151936}
    differs = sorted(k for k, v in row.items() if c[k] != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert c["reduced"] == ["num_hidden_layers", "num_experts_held",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts_held"]) == (4, 32)
    assert c["vocab_size"] * 8 == 151936
    assert "16 chips share each layer" in c["deployment"]
    for key in ("projections", "decay_gate", "norms", "rotary", "gate",
                "router", "shared_expert", "weights", "recompute", "seq_len",
                "gdn_chunk", "optimizer"):
        assert c["assumed"][key], key
    for word in ("multi-token-prediction", "float32", "no dropout",
                 "packing"):
        assert any(word in d for d in c["departs"]), word


def test_operations_are_the_issues_count():
    """MAC = 2 at the published widths: the recurrence a call (``7 K V`` a
    token and value head), the bytes any form must move (q, k at 16 heads,
    v and o at 32, the decay and beta in float32), a step; and the
    parameters the leaves give."""
    c = PUBLISHED
    assert flops.gdn_fwd_flops(c) == 16384 * 32 * 7 * 128 * 128
    assert flops.gdn_bwd_flops(c) == 2 * flops.gdn_fwd_flops(c)
    assert flops.gdn_fwd_bytes(c) == 16384 * (
        2 * (2 * 2048 + 2 * 4096) + 2 * 4 * 32)
    assert flops.gdn_bwd_bytes(c) == 16384 * (
        2 * (2 * 2048 + 2 * 4096) + 2 * (2 * 2048 + 4096) + 4 * 4 * 32)
    # the bytes bound both: no form can read over 100%
    for f, b in ((flops.gdn_fwd_flops, flops.gdn_fwd_bytes),
                 (flops.gdn_bwd_flops, flops.gdn_bwd_bytes)):
        assert f(c) / 197e12 < b(c) / 819e9
    assert flops.flash_fwd_flops(c) == 2 * (16384 * 16385 // 2) * 16 * 512
    assert 2 * flops.flash_bwd_flops(c) == 5 * flops.flash_fwd_flops(c)
    n = sum(int(np.prod(s[1])) for s in ref.param_specs(c))
    assert abs(n - 625.7e6) < 0.1e6          # the issue's count


# ------------------------------------------------- program and reference
def test_leaves_are_the_references(case):
    params = case["net"].collect_params()
    assert [tuple(p.shape) for p in params.values()] == [
        tuple(s[1]) for s in SPECS]
    assert [p.grad_req != "null" for p in params.values()] == [
        s[3] for s in SPECS]
    assert _leaf_names(case["net"]) == [s[0] for s in SPECS]
    assert case["net"].head.weight is not case["net"].embed.weight


def test_loss_matches_the_reference(case, program_grads):
    assert abs(program_grads[0] - case["loss"]) <= 1e-5 * case["loss"]


@pytest.mark.parametrize("leaf", TRAINABLE)
def test_gradient_matches_the_reference(case, program_grads, leaf):
    assert _gap(program_grads[1][leaf], case["grads"][leaf]) <= 2e-4


def test_the_step_counts_what_it_traced(program_grads):
    """Three calls of the rule (the recomputed forward keeps the first
    one's outputs, but is traced), each counted with its chunks and on the
    plain path off the TPU; four blocks recomputed; one elementwise gate."""
    text, counted = program_grads[2:]
    chunks = -(-CFG["seq_len"] // CFG["gdn_chunk"])
    assert counted["gated_delta.calls"] == counted["gated_delta.fallbacks"]
    assert counted["gated_delta.calls"] in (3, 6)
    assert counted["gated_delta.chunks"] \
        == counted["gated_delta.calls"] * chunks
    assert counted["train_step.blocks_recomputed"] == 4
    assert counted["attention.element_gated"] in (1, 2)
    for scope in ("gated_delta_rule", "gdn_gate", "gated_norm",
                  "element_gate", "moe.shared_gate"):
        assert scope in text, scope
    assert telemetry.tagged("gated_delta.fallbacks") == {
        "platform is not tpu": counted["gated_delta.fallbacks"]}


# ------------------------------------------------------------ recomputing
def test_a_recomputed_block_runs_the_rule_forward_once(monkeypatch):
    """Under ``recompute`` the kept names (read from the kernel file) hold
    the rule's output and chunk states: the differentiated step has three
    ``gdn_fwd`` for its three layers, not six; and (from
    ``moe.KEPT_NAMES``) what the four routers decided: four ``top_k``s,
    four sorts and four router products, not eight."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    leaves = ref_common.init_params(SPECS, 5)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    x, y = ref.sample_inputs(CFG, jax.random.PRNGKey(9), 1)
    closed, cfg = differentiated(net, _loss_fn(), x, y), CFG
    found = calls(closed)
    assert found["gdn_fwd"] == 3 and found["gdn_bwd"] == 3, found
    assert kda.GDN_KEPT_NAMES == ("gdn_o", "gdn_states")
    # and it routes once: one choice over all the experts, one sort, one
    # router product and one gather of the picked scores for each routed
    # layer, none in the second forward
    routed = router_ops(closed, cfg["num_experts"])
    n_routed = sum(s[0].endswith("_moe_router_weight") for s in SPECS)
    assert n_routed > 0 and routed == {
        "top_k.full": n_routed, "sort": n_routed, "score": n_routed,
        "picked": n_routed, "top_k": n_routed}, routed


# --------------------------------------------------------- the share's tie
E, K, D, F_ = 16, 4, 32, 12          # experts, choices a token, widths


def _layer(seed, t=48):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = jax.random.normal
    return n(ks[0], (t, D), jnp.float32), [
        0.3 * n(ks[1], (E, D)), jnp.zeros((E,)),
        0.2 * n(ks[2], (E, D, F_)), 0.2 * n(ks[3], (E, D, F_)),
        0.2 * n(ks[4], (E, F_, D))], [
        0.3 * n(ks[8], (1, D)),
        0.2 * n(ks[6], (F_, D)), 0.2 * n(ks[7], (F_, D)),
        0.2 * n(ks[5], (D, F_))]


def _layer_cfg(held=E, first=0):
    return dict(CFG, num_experts=E, num_experts_per_tok=K,
                num_experts_held=held, first_expert_held=first)


@pytest.mark.parametrize("holders", [1, 2, 16])
def test_the_shares_add_up_to_the_uncut_layer(holders):
    """Expert parallelism ``holders`` ways (16: the deployment's): the
    parts the holders' experts give (the program's layer, each told which
    experts it holds) add up to what the uncut reference gives for the
    routed part of the whole layer; what every chip computes alike (the
    gated shared expert) is counted once."""
    m, leaves, shared = _layer(11)
    router, bias, eg, eu, ed = leaves
    zero = [jnp.zeros_like(w) for w in shared]
    want = ref.expert_layer(_layer_cfg(), m[None], leaves + zero)[0]
    held = E // holders
    total = 0.0
    for first in range(0, E, held):
        part = slice(first, first + held)
        mine = moe.routed_ffn(m, router, bias, eg[part], eu[part], ed[part],
                              top_k=K, first_expert=first, score="softmax")
        theirs = ref.expert_layer(
            _layer_cfg(held, first), m[None],
            [router, bias, eg[part], eu[part], ed[part]] + zero)[0]
        assert _gap(mine, theirs) <= 1e-5
        total = total + mine
    assert _gap(total, want) <= 1e-5
    # and the shared expert behind its gate, once
    with_shared = ref.expert_layer(_layer_cfg(), m[None], leaves + shared)[0]
    wsg, sg, su, sd = shared
    alone = jax.nn.sigmoid(m @ wsg.T) * (
        (jax.nn.silu(m @ sg.T) * (m @ su.T)) @ sd.T)
    assert _gap(total + alone, with_shared) <= 1e-5


def test_operators_of_the_gated_delta_kind():
    make, prefix = hybrid_lm.OPERATORS["gated_delta_net"]
    assert make is hybrid_lm.GatedDeltaNet and prefix == "gdn_"
    blk = make(32, num_heads=4, num_key_heads=2, head_dim=16, prefix=prefix)
    names = [k[len(blk.prefix):] for k in blk.collect_params().keys()]
    assert names == ["q_conv_weight", "k_conv_weight", "v_conv_weight",
                     "a_log", "dt_bias", "a_weight", "q_weight", "k_weight",
                     "v_weight", "z_weight", "b_weight", "onorm_gamma",
                     "proj_weight"]
    assert blk.q_conv.shape == (32, 4) and blk.v_conv.shape == (64, 4)
    assert blk.a_weight.shape == (4, 32) and blk.a_log.shape == (4,)
    # no model's name in the program, no variable of the environment read
    # by what this operator added
    for path in (hybrid_lm.__file__, kda.__file__):
        text = open(path).read().lower()
        assert "qwen" not in text
    assert "os.environ" not in open(kda.__file__).read()
