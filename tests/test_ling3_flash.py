"""``HybridLM`` as Ling-3.0-flash's stack (Kimi-Delta-Attention layers to
one head-gated latent-attention layer, a group-limited sigmoid router over
SiLU-gated experts with a shared one, an untied head, blocks recomputed)
against the plain reference of its cell,
``benchmark/reference/ling3_flash.py``, at the configuration's rehearsal
size on seeded weights: leaves, loss, every leaf's gradient; the
chunked operator against the token-by-token recurrence at the decay's two
ends, on the plain path and through both Pallas kernels under the
interpreter; a sequence that is no multiple of the chunk; the group limit
on a hand-built case; the head gate; recomputation on and off; the shares of the experts' holders adding up to the uncut layer; the
counters; and the older cells' models unmoved."""
import functools
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
from mxtpu.gluon.model_zoo import hybrid_lm, latent_moe
from mxtpu.ops.registry import get_op
from mxtpu.parallel import moe

from benchmark.flops import ling3_flash as flops
from benchmark.models import ling3_flash as model
from benchmark.reference import common as ref_common
from benchmark.reference import ling3_flash as ref

from _jaxpr_count import calls, differentiated, router_ops, traced_loss

kda = importlib.import_module("mxtpu.ops.pallas.kda")
short_filter = importlib.import_module("mxtpu.ops.pallas.short_filter")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


PUBLISHED = _config("ling3_flash")
CFG = dict(PUBLISHED)
CFG.update(CFG["rehearsal"], dtype="float32")
SPECS = ref.param_specs(CFG)
TRAINABLE = [s[0] for s in SPECS if s[3]]
COUNTERS = ("kda_attention.calls", "kda_attention.fallbacks",
            "kda_attention.chunks", "train_step.blocks_recomputed",
            "moe.group_limited", "moe.layers")


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


def _grads(net, x, y, vocab):
    """Loss and every trainable leaf's gradient through a traced step (the
    whole-step trainer's own forward, differentiated by jax)."""
    loss_of, datas = traced_loss(net, _loss_fn(vocab), x, y)
    return jax.jit(jax.value_and_grad(loss_of)), datas


@pytest.fixture(scope="module")
def case():
    """The model with the reference's seeded leaves, two sequences, and the
    reference's loss and gradients on them."""
    leaves = ref_common.init_params(SPECS, 5)
    x, y = ref.sample_inputs(CFG, jax.random.PRNGKey(9), 2)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    loss_fn = ref.forward_loss(CFG)
    loss, grads = jax.value_and_grad(
        lambda full: loss_fn(full, x, y, "float32")[0])(list(leaves))
    return {"net": net, "leaves": leaves, "x": x, "y": y,
            "loss": float(loss),
            "grads": dict(zip([s[0] for s in SPECS], grads))}


@pytest.fixture(scope="module")
def program_grads(case):
    """The program's loss and gradients through the traced step, every
    block recomputed (the eager tape runs this model an operation at a
    time, half a minute here; other models' tests keep that path)."""
    for name in COUNTERS:
        telemetry.reset_metric(name)
    step, datas = _grads(case["net"], case["x"], case["y"],
                         CFG["vocab_size"])
    text = step.lower(datas).as_text(debug_info=True)
    counted = {name: telemetry.value(name) for name in COUNTERS}
    loss, grads = step(datas)
    trainable = [g for g, s in zip(grads, SPECS) if s[3]]
    return float(loss), dict(zip(TRAINABLE, trainable)), text, counted


# ------------------------------------------------------ the configuration
def test_the_rehearsal_has_what_the_cell_has():
    """A leading dense KDA layer, a latent layer over routed experts,
    several chunks a sequence, the group limit with fewer groups kept than
    there are, 2 of 16 experts held and not from expert 0."""
    assert ref.kinds(PUBLISHED) == ["kda"] * 4 + ["latent_attention"] \
        + ["kda"] * 2
    ops = ref.kinds(CFG)
    assert ops[0] == "kda" and "latent_attention" in ops[1:]
    assert CFG["first_k_dense_replace"] == 1
    assert CFG["seq_len"] >= 4 * CFG["kda_chunk"]
    assert 1 < CFG["topk_group"] < CFG["n_group"]
    assert (CFG["num_experts"], CFG["num_experts_held"]) == (16, 2)
    assert CFG["first_expert_held"] != 0 and CFG["recompute"] is True


def test_published_sizes_are_the_sources():
    """Every number of the catalog's row is in the file under its own key;
    what is cut is listed with the published value beside it; every
    assumption and departure of the issue is written down."""
    c = PUBLISHED
    row = {"first_k_dense_replace": 2, "group_norm_size": 1, "head_dim": 128,
           "hidden_size": 2560, "intermediate_size": 6144,
           "kda_lower_bound": -5, "kda_safe_gate": True, "kv_lora_rank": 512,
           "layer_group_size": 6, "linear_silu": True,
           "moe_intermediate_size": 768,
           "moe_shared_expert_intermediate_size": 768, "n_group": 8,
           "no_kda_lora": True, "num_attention_heads": 32,
           "num_experts": 512, "num_experts_per_tok": 8,
           "num_hidden_layers": 42, "num_key_value_heads": 32,
           "num_nextn_predict_layers": 1, "num_shared_experts": 1,
           "qk_head_dim": 192, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
           "rope_interleave": True, "rope_theta": 6000000, "rotary_dim": 64,
           "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
           "short_conv_kernel_size": 4, "tie_word_embeddings": False,
           "topk_group": 4, "topk_method": "noaux_tc", "use_qk_norm": True,
           "v_head_dim": 128, "vocab_size": 157184,
           "gated_attention_proj_granularity_type": "head_wise"}
    differs = sorted(k for k, v in row.items() if c[k] != v)
    assert differs == ["first_k_dense_replace", "num_hidden_layers",
                       "num_nextn_predict_layers", "vocab_size"]
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "num_experts_held", "vocab_size",
                            "num_nextn_predict_layers"]
    assert c["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184,
        "num_nextn_predict_layers": 1}
    assert (c["num_hidden_layers"], c["num_experts_held"]) == (7, 8)
    assert c["vocab_size"] * 8 == 157184
    # the clamp is never run: both limits are 0 in the layers kept
    kept = range(c["first_layer_held"],
                 c["first_layer_held"] + c["num_hidden_layers"])
    assert all(c["expert_swiglu_limit_list"][i] == 0
               and c["share_expert_swiglu_limit_list"][i] == 0 for i in kept)
    for key in ("decay_gate", "decay_rank", "head_gate", "qk_norm",
                "linear_kv_heads", "swiglu_limit", "switches_off", "weights",
                "seq_len", "optimizer"):
        assert c["assumed"][key], key
    assert "softplus" in c["assumed"]["decay_gate"]     # the other form
    for word in ("multi-token-prediction", "held fixed", "float32",
                 "no dropout"):
        assert any(word in d for d in c["departs"]), word


def test_operations_are_the_issues_count():
    """MAC = 2 at the published widths: the recurrence a call, the bytes
    any form must move, a step; and the parameters the leaves give."""
    c = PUBLISHED
    assert flops.kda_fwd_flops(c) == 8192 * 32 * 7 * 128 * 128
    assert flops.kda_bwd_flops(c) == 2 * flops.kda_fwd_flops(c)
    assert flops.kda_fwd_bytes(c) == 8192 * 4096 * 12 + 2 * 8192 * 32
    assert flops.kda_bwd_bytes(c) == 8192 * 4096 * 22 + 4 * 8192 * 32
    # the bytes bound both: no form can read over 100%
    for f, b in ((flops.kda_fwd_flops, flops.kda_fwd_bytes),
                 (flops.kda_bwd_flops, flops.kda_bwd_bytes)):
        assert f(c) / 197e12 < b(c) / 819e9
    assert flops.flash_fwd_flops(c) == 2 * (8192 * 8193 // 2) * 32 * 320
    # 1.22 G a token forward (the issue, by hand: "about 1.1 G")
    assert abs(flops.train_flops_per_sample(c) - 2.99e13) < 0.01e13
    n = sum(int(np.prod(s[1])) for s in ref.param_specs(c))
    assert abs(n - 884.5e6) < 0.1e6


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


# ------------------------------------------------- the chunked operator
def _recurrence(q, k, v, g, beta, heads):
    b, t, _ = q.shape
    split = lambda x: x.reshape(b, t, heads, -1)            # noqa: E731
    return ref.delta_rule(split(q), split(k), split(v), split(g),
                          beta).reshape(b, t, -1)


def _operands(seed, b, t, h, dk, decay):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key):
        x = jax.random.normal(key, (b, t, h, dk))
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            b, t, h * dk)

    g = {"bound": jnp.full((b, t, h * dk), -5.0),
         "none": -1e-4 * jax.random.uniform(ks[3], (b, t, h * dk)),
         "mixed": -5 * jax.nn.sigmoid(
             2 * jax.random.normal(ks[3], (b, t, h * dk)))}[decay]
    return (unit(ks[0]), unit(ks[1]),
            jax.random.normal(ks[2], (b, t, h * dk)), g,
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))),
            jax.random.normal(ks[5], (b, t, h * dk)))


def _against_the_recurrence(shape, chunk, decay):
    """Largest error of the output and of each cotangent against the
    token-by-token recurrence, each over the largest entry of its own."""
    *xs, do = _operands(3, *shape, decay)
    heads = shape[2]

    @jax.jit                    # one program each: a tenth of eager's time
    def both(do, *xs):
        want, vjp = jax.vjp(lambda *a: _recurrence(*a, heads), *xs)
        got, vjp2 = jax.vjp(lambda *a: kda.kda_attention(*a, chunk), *xs)
        return [(got, want)] + list(zip(vjp2(do), vjp(do)))

    return [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in both(do, *xs)]


# float32 leaves the chunked form a relative error of |exponent| x 6e-8 on
# a decayed term (kda.py); the log-decay's own cotangent, at the bound
# where it all but vanishes, reads 3e-5 of its largest entry
@pytest.mark.parametrize("shape,chunk,decay", [
    ((1, 64, 2, 16), 8, "bound"), ((1, 64, 2, 16), 8, "none"),
    ((1, 64, 2, 16), 8, "mixed"), ((1, 160, 1, 16), 64, "bound"),
    ((1, 160, 1, 16), 64, "mixed")])
def test_the_chunks_equal_the_recurrence_on_the_plain_path(shape, chunk,
                                                           decay):
    """Chunks of 8 over 64 positions; chunks of 64 (four sub-chunks of 16,
    ``g = -5`` in every channel for whole chunks among the cases) over a
    sequence that is no multiple of the chunk."""
    errors = _against_the_recurrence(shape, chunk, decay)
    assert max(errors) <= 1e-4, errors
    assert max(errors[:3]) <= 2e-5, errors


@pytest.mark.parametrize("decay", ["bound", "none"])
def test_both_kernels_equal_the_recurrence(monkeypatch, decay):
    """``kda_fwd`` and ``kda_bwd`` under the Pallas interpreter, one head
    and two chunks of 64 at the decay's two ends: ``g = -5`` in every
    channel for both chunks, and ``g`` near 0."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    for name in COUNTERS[:2]:
        telemetry.reset_metric(name)
    errors = _against_the_recurrence((1, 128, 1, 16), 64, decay)
    assert max(errors) <= 1e-4, errors
    assert max(errors[:3]) <= 2e-5, errors
    assert telemetry.value("kda_attention.fallbacks") == 0
    assert telemetry.value("kda_attention.calls") >= 1


def test_a_chunk_that_is_no_power_of_two_is_refused():
    *xs, _ = _operands(1, 1, 96, 1, 16, "mixed")
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_attention(*xs, 48)


def test_bf16_operands_stay_near_the_recurrence():
    """bf16 inputs take fewer MXU passes (``_PASSES``): the output stays
    within bf16's own rounding of the float32 recurrence."""
    *xs, _ = _operands(4, 1, 128, 2, 16, "mixed")
    want = _recurrence(*xs, 2)
    low = [x.astype(jnp.bfloat16) for x in xs[:3]] + [xs[3]] \
        + [xs[4].astype(jnp.bfloat16)]
    got = jax.jit(lambda *a: kda.kda_attention(*a, 64))(*low)
    assert got.dtype == jnp.bfloat16
    assert _gap(got.astype(jnp.float32), want) <= 2e-2


def test_the_filter_is_causal_and_shares_lfm2s_taps():
    """``_contrib_kda_conv`` against its definition, and the gated filter
    of LFM2 through the same tap loop; the backward keeps no product."""
    ops_nn = importlib.import_module("mxtpu.ops.nn")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    z = jax.random.normal(ks[0], (2, 24, 32))
    w = jax.random.normal(ks[1], (32, 4))
    want = sum(w[:, j] * jnp.pad(z, ((0, 0), (3 - j, 0), (0, 0)))[:, :24]
               for j in range(4))
    got = get_op("_contrib_kda_conv").fn(z, w)
    assert _gap(got, jax.nn.silu(want)) <= 1e-6
    normed = get_op("_contrib_kda_conv").fn(z, w, head_dim=16)
    heads = jax.nn.silu(want).reshape(2, 24, 2, 16)
    heads = heads / jnp.sqrt(jnp.sum(heads ** 2, -1, keepdims=True) + 1e-6)
    assert _gap(normed, heads.reshape(2, 24, 32)) <= 1e-6
    # a later position moves no earlier output
    moved = get_op("_contrib_kda_conv").fn(z.at[:, 12:].add(1.0), w)
    assert np.array_equal(np.asarray(moved[:, :12]), np.asarray(got[:, :12]))
    assert _gap(ops_nn._causal_taps(z, w), want) <= 1e-6
    grad = jax.grad(lambda z, w: jnp.sum(jnp.sin(
        get_op("_contrib_kda_conv").fn(z, w, head_dim=16))), (0, 1))(z, w)
    plain = jax.grad(lambda z, w: jnp.sum(jnp.sin(
        ops_nn._kda_conv_plain(16, z, w))), (0, 1))(z, w)
    assert all(_gap(a, b) <= 1e-6 for a, b in zip(grad, plain))


# ------------------------------------------- the filter's Pallas pair
def _filter_case(head_dim, taps, dtype, shape=(2, 80, 256), seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], shape).astype(dtype),
            0.5 * jax.random.normal(ks[1], (shape[-1], taps)),
            jax.random.normal(ks[2], shape).astype(dtype))


def _filter_kernels(head_dim, tiles=(32, 128)):
    """Both kernels under the interpreter at tiles of 32 rows, so that 80
    positions are two whole tiles and a half."""
    how = dict(head_dim=head_dim, tiles=tiles, interpret=True)
    return (functools.partial(short_filter._forward, **how),
            functools.partial(short_filter._backward, **how))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("head_dim", [0, 128])
def test_the_filter_kernels_equal_the_plain_function(head_dim, taps, dtype):
    """``kda_conv_fwd`` / ``kda_conv_bwd`` under the Pallas interpreter
    against ``_kda_conv_plain`` and ``jax.vjp`` of it: the value, ``d
    data`` and ``d weight``; two batch rows (the second row's start sees
    zeros, not the first row's tail) over a sequence that is no whole
    number of tiles."""
    ops_nn = importlib.import_module("mxtpu.ops.nn")
    x, w, g = _filter_case(head_dim, taps, dtype)
    want, vjp = jax.vjp(functools.partial(ops_nn._kda_conv_plain, head_dim),
                        x, w)
    want_dx, want_dw = vjp(g)
    fwd, bwd = _filter_kernels(head_dim)
    got, (dx, dw) = fwd(x, w), bwd(x, w, g)
    assert got.dtype == dx.dtype == x.dtype and dw.dtype == w.dtype
    assert got.shape == dx.shape == x.shape and dw.shape == w.shape
    # float32: the sums' order is the only difference; bf16: an entry in
    # some thousands rounds the other way
    limit = 1e-6 if dtype == "float32" else 2e-3
    assert _gap(got, want) <= limit
    assert _gap(dx, want_dx) <= limit
    assert _gap(dw, want_dw) <= 1e-6
    alone, (dx_alone, _) = fwd(x[1:], w), bwd(x[1:], w, g[1:])
    assert np.array_equal(np.asarray(alone[0], np.float32),
                          np.asarray(got[1], np.float32))
    assert np.array_equal(np.asarray(dx_alone[0], np.float32),
                          np.asarray(dx[1], np.float32))


@pytest.mark.parametrize("at", [30, 63])
@pytest.mark.parametrize("head_dim", [0, 128])
def test_the_filter_kernels_reach_across_a_tile_boundary(head_dim, at):
    """Tiles of 32 rows: a change of the data at position ``at`` moves the
    outputs ``at .. at + 3`` only, over the boundary after it; a change of
    the cotangent at ``at + 3`` moves ``d data`` at ``at .. at + 3``
    only, back over it."""
    x, w, g = _filter_case(head_dim, 4, "float32", shape=(1, 96, 256))
    fwd, bwd = _filter_kernels(head_dim)

    def moved(a, b):
        return sorted(set(np.nonzero(np.asarray(a != b))[1].tolist()))

    reach = list(range(at, at + 4))
    assert moved(fwd(x.at[:, at].add(1.0), w), fwd(x, w)) == reach
    assert moved(bwd(x, w, g.at[:, at + 3].add(1.0))[0],
                 bwd(x, w, g)[0]) == reach


FILTER_COUNTERS = ("kda_conv.calls", "kda_conv.pallas", "kda_conv.xla")


@pytest.mark.parametrize("case,reason", [
    ("interpreted", None), ("off the chip", "platform"),
    ("whole numbers", "dtype"), ("a width of 192", "lanes"),
    ("heads of 64", "lanes"), ("17 taps", "taps")])
def test_the_filter_counts_its_path_and_reason(monkeypatch, case, reason):
    """``kda_conv.calls`` / ``.pallas`` / ``.xla{reason}`` at trace time, a
    pass a count (the value, then the two gradients), through the
    operator; a refused call is the plain function's."""
    ops_nn = importlib.import_module("mxtpu.ops.nn")
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    x, w, _ = _filter_case(0, 4, "float32", shape=(1, 32, 256))
    head_dim = 0
    if case in ("interpreted", "whole numbers", "17 taps"):
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    if case == "whole numbers":
        x = (4 * x).astype(jnp.int32)
    if case == "17 taps":
        w = jnp.ones((256, 17)) / 17
    if reason == "lanes":       # the chip's own rule; the plain path runs
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
        monkeypatch.setattr(fa, "_platform", lambda: "tpu")
        if case == "heads of 64":
            head_dim = 64
        else:
            x, w = x[..., :192], w[:192]
    for name in FILTER_COUNTERS:
        telemetry.reset_metric(name)
    op = functools.partial(get_op("_contrib_kda_conv").fn, head_dim=head_dim)
    got = op(x, w)
    assert _gap(got, ops_nn._kda_conv_plain(head_dim, x, w)) <= 1e-6
    passes = 1
    if case != "whole numbers":
        jax.grad(lambda x, w: jnp.sum(jnp.sin(op(x, w))), (0, 1))(x, w)
        passes = 3
    counted = [telemetry.value(name) for name in FILTER_COUNTERS]
    if reason is None:
        assert counted == [passes, passes, 0]
        assert telemetry.tagged("kda_conv.xla") == {}
    else:
        assert counted == [passes, 0, passes]
        assert telemetry.tagged("kda_conv.xla") == {reason: passes}


@pytest.mark.parametrize("case,want", [
    ("no window", None), ("no filter traced", None),
    ("both passes refused", 2), ("both passes on the kernels", 0)])
def test_the_benchmark_reads_the_filters_fallbacks(monkeypatch, case, want):
    """``kda_conv_fallbacks.train``: the passes counted under
    ``kda_conv.xla`` once a window was measured, nothing from a program
    that traced no filter (every other cell's, and the parent's)."""
    from benchmark import run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "kda_conv_fallbacks.train"]
    assert entry == [{
        "name": "kda_conv_fallbacks.train", "unit": "calls",
        "better": "lower", "source": "program_counter",
        "layer": "ops, kernels", "moves": "train_samples_per_s",
        "workloads": ["ling3_flash.train_b1_s8192",
                      "qwen3_next_80b_a3b.train_b1_s16384"]}]
    for name in FILTER_COUNTERS:
        telemetry.reset_metric(name)
    if case == "both passes on the kernels":
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    if want is not None:
        x, w, _ = _filter_case(0, 4, "float32", shape=(1, 32, 128))
        op = get_op("_contrib_kda_conv").fn
        jax.grad(lambda x, w: jnp.sum(jnp.sin(op(x, w))), (0, 1))(x, w)
    window = {"window": {"attempted": 0 if case == "no window" else 1}}
    assert run.reader("kda_conv_fallbacks.train")(window) == want


def test_the_filter_traces_a_body_once_a_shape(monkeypatch):
    """Three calls of one shape and ``head_dim`` under one trace, and a
    fourth without the norm: two forward bodies and two backward ones
    traced, not four and four (each kernel is a jit of its own)."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    x, w, _ = _filter_case(128, 4, "bfloat16", shape=(1, 64, 256), seed=3)
    op = get_op("_contrib_kda_conv").fn
    bodies = {"fwd": 0, "bwd": 0}
    for name in bodies:
        kernel = getattr(short_filter, "_%s_kernel" % name)

        def counting(*a, _kernel=kernel, _name=name, **kw):
            bodies[_name] += 1
            return _kernel(*a, **kw)

        monkeypatch.setattr(short_filter, "_%s_kernel" % name, counting)
    for name in FILTER_COUNTERS:
        telemetry.reset_metric(name)

    def loss(x, w):
        q, k, k2 = (op(x * s, w, head_dim=128) for s in (1.0, 2.0, 3.0))
        return jnp.sum(jnp.sin((q + k + k2 + op(x, w)).astype(jnp.float32)))

    short_filter._forward.clear_cache()
    short_filter._backward.clear_cache()
    jax.jit(jax.grad(loss, (0, 1))).lower(x, w)
    assert bodies == {"fwd": 2, "bwd": 2}
    assert [telemetry.value(n) for n in FILTER_COUNTERS] == [8, 8, 0]


def test_the_gate_is_the_bounded_form():
    f = jnp.linspace(-30.0, 30.0, 64).reshape(1, 2, 32)
    a_log, dt = jnp.log(jnp.array([1.0, 16.0])), jnp.full((32,), -3.0)
    gate = get_op("_contrib_kda_gate").fn
    g = gate(f, jnp.eye(32), a_log, dt, lower_bound=-5.0)
    # bf16 operands, a float32 result that is never rounded
    low = gate(f.astype(jnp.bfloat16), jnp.eye(32, dtype=jnp.bfloat16),
               a_log, dt)
    assert g.dtype == low.dtype == jnp.float32
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
    rate = jnp.repeat(jnp.array([1.0, 16.0]), 16)
    assert _gap(g, -5.0 * jax.nn.sigmoid(rate * (f - 3.0))) <= 1e-6


# ----------------------------------------------------- the group limit
def test_the_group_limit_changes_the_choice():
    """16 experts in 4 groups of 4, 2 groups kept, 4 chosen. Token 0: the
    four largest scores lie in four groups, one each; the groups' scores
    (their two largest) keep groups 0 and 1 only, so the limited choice is
    the two best of each while the unlimited one takes an expert of every
    group. Token 1's four largest already share two groups."""
    s = np.full((2, 16), 0.05, np.float32)
    s[0, [0, 4, 8, 12]] = [0.9, 0.8, 0.7, 0.6]       # one high a group
    s[0, [1, 5]] = [0.5, 0.55]                       # a second in 0 and 1
    s[1, [0, 1, 4, 5]] = [0.9, 0.8, 0.7, 0.6]
    logits = jnp.log(jnp.asarray(s) / (1 - jnp.asarray(s)))
    eye, bias = jnp.eye(16), jnp.zeros((16,))
    free, _ = moe.route_top_k(logits, eye, bias, 4)
    limited, w = moe.route_top_k(logits, eye, bias, 4, scale=2.5,
                                 n_group=4, topk_group=2)
    assert sorted(np.asarray(free[0])) == [0, 4, 8, 12]
    assert sorted(np.asarray(limited[0])) == [0, 1, 4, 5]
    assert sorted(np.asarray(limited[1])) == sorted(np.asarray(free[1])) \
        == [0, 1, 4, 5]
    # the weights: s at the chosen over their sum, scaled
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 2.5, rtol=1e-6)
    idx, want = ref.route(dict(CFG, n_group=4, topk_group=2,
                               num_experts_per_tok=4,
                               routed_scaling_factor=2.5), logits, eye, bias)
    assert np.array_equal(np.asarray(idx), np.asarray(limited))
    assert _gap(w, want) <= 1e-6
    with pytest.raises(mx.base.MXNetError, match="groups"):
        moe.route_top_k(logits, eye, bias, 4, n_group=3, topk_group=2)


def test_the_bias_steers_the_groups_and_takes_no_gradient():
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    bias = jnp.zeros((16,)).at[12:].set(5.0)          # lifts group 3
    idx, _ = moe.route_top_k(x, jnp.eye(16), bias, 4, n_group=4,
                             topk_group=1)
    assert np.all(np.asarray(idx) >= 12)
    grad = jax.grad(lambda b: jnp.sum(moe.route_top_k(
        x, jnp.eye(16), b, 4, n_group=4, topk_group=2)[1]))(bias)
    assert not np.any(np.asarray(grad))


# --------------------------------------------------------- the head gate
def test_the_head_gate_scales_each_heads_output():
    kwargs = {"num_heads": 2, "kv_rank": 8, "nope_dim": 8, "rope_dim": 4,
              "v_dim": 8}
    gated = latent_moe.MultiHeadLatentAttention(32, head_gate=True, **kwargs)
    plain = latent_moe.MultiHeadLatentAttention(32, **kwargs)
    gated.initialize()
    plain.initialize()
    x = mx.nd.NDArray(jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32)))
    gated(x), plain(x)                 # the deferred shapes
    assert "gate_weight" in "".join(gated.collect_params().keys())
    assert "gate_weight" not in "".join(plain.collect_params().keys())
    for name, p in plain.collect_params().items():
        p.set_data(gated.collect_params()[
            gated.prefix + name[len(plain.prefix):]].data())
    # a gate of zero weight halves every head
    gated.gate.weight.set_data(mx.nd.zeros((2, 32)))
    assert _gap(gated(x).asnumpy(), 0.5 * plain(x).asnumpy()) <= 1e-6
    # a gate open on head 0 alone, shut on head 1: head 1's columns of the
    # output projection no longer matter
    w = np.zeros((2, 32), np.float32)
    gated.gate.weight.set_data(mx.nd.array(w))
    before = gated(x).asnumpy()
    proj = gated.proj.weight.data().asnumpy().copy()
    proj[:, 8:] = 0.0
    gated.proj.weight.set_data(mx.nd.array(proj))
    assert _gap(gated(x).asnumpy(), before) > 1e-3


# --------------------------------------------------------- recomputation
def test_recomputation_changes_no_bit(case, program_grads):
    """The same leaves with ``recompute`` on and off: the same loss and the
    same gradients to float32's last digits (the two are one arithmetic;
    XLA fuses the two programs differently, so not every bit: 5.5452847
    against 5.545285 here); on, every block is under a checkpoint, and the
    step counted what it traced."""
    loss_on, g_on, text_on, got = program_grads
    off = model.build(dict(CFG, recompute=False), SPECS, case["leaves"])
    model._FIRST.clear()
    assert case["net"]._recompute and not off._recompute
    n, n_kda = len(ref.kinds(CFG)), ref.kinds(CFG).count("kda")
    # off the chip a KDA call takes the plain path and says so; a call's
    # chunks a head are counted from the shapes
    assert got["train_step.blocks_recomputed"] == n
    assert got["kda_attention.calls"] == got["kda_attention.fallbacks"] \
        >= n_kda
    assert got["kda_attention.chunks"] == got["kda_attention.calls"] \
        * (CFG["seq_len"] // CFG["kda_chunk"])
    assert got["moe.group_limited"] == got["moe.layers"] > 0
    assert [e for e in telemetry.events() if e[0] == "kda_attention.trace"]
    for scope in ("kda_attention", "kda_conv", "kda_gate", "mla_attention",
                  "moe.route"):
        assert scope in text_on, scope
    telemetry.reset_metric("train_step.blocks_recomputed")
    f_off, datas_off = _grads(off, case["x"], case["y"], CFG["vocab_size"])
    text_off = f_off.lower(datas_off).as_text()
    assert telemetry.value("train_step.blocks_recomputed") == 0
    # a recomputed block's forward stands behind a barrier in the backward
    assert "optimization_barrier" in text_on
    assert "optimization_barrier" not in text_off
    loss_off, g_off = f_off(datas_off)
    assert abs(loss_on - float(loss_off)) <= 2e-7 * float(loss_off)
    for a, spec in zip(g_off, SPECS):
        if spec[3]:
            assert _gap(g_on[spec[0]], a) <= 2e-6, spec[0]


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_a_recomputed_block_runs_its_kernels_forward_once(monkeypatch, path):
    """The model as the cell builds it, differentiated and not run: every
    kernel's forward stands in the jaxpr once for each backward, the
    recomputed blocks' second forward holds none
    (``hybrid_lm.kept_policy``; under a bare checkpoint each stood twice).
    ``kernels``: under the interpreter, at 128 positions (the flash pair
    wants its keys in 128s);
    ``plain``: as tier-1 runs the model off the chip, where a KDA call is
    a scan over chunks, which its backward rule differentiates: one
    forward scan in the value, one in the rule, one reversed. Either way
    a routed layer's router stands once (``moe.KEPT_NAMES``): its product,
    its choice, the group limit's two ``top_k``s and the plan's sort."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", str(int(path == "kernels")))
    cfg = dict(CFG, seq_len=128, items_per_sample=128) \
        if path == "kernels" else CFG
    net = model.build(cfg, SPECS, ref_common.init_params(SPECS, 5))
    model._FIRST.clear()
    telemetry.reset_metric("train_step.blocks_recomputed")
    x, y = ref.sample_inputs(cfg, jax.random.PRNGKey(9), 2)
    closed = differentiated(net, _loss_fn(), x, y)
    got = calls(closed)
    kinds = ref.kinds(cfg)
    n_kda = kinds.count("kda")
    assert telemetry.value("train_step.blocks_recomputed") == len(kinds)
    if path == "kernels":
        assert got["kda_fwd"] == got["kda_bwd"] == n_kda > 0
        assert got["flash_attention_fwd"] == got["flash_attention_bwd"] \
            == len(kinds) - n_kda > 0
    else:
        assert (got["scan"], got["scan.reverse"]) == (2 * n_kda, n_kda)
    # and it routes once: one choice over all the experts, one sort, one
    # router product and one gather of the picked scores for each routed
    # layer, none in the second forward
    routed = router_ops(closed, cfg["num_experts"])
    n_routed = sum(s[0].endswith("_moe_router_weight") for s in SPECS)
    assert n_routed > 0 and routed == {
        "top_k.full": n_routed, "sort": n_routed, "score": n_routed,
        "picked": n_routed, "top_k": 3 * n_routed}, routed


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_the_names_lower_to_nothing_without_a_checkpoint(monkeypatch, path):
    """lfm2's model, which calls the flash kernel and the routed layer and
    builds no checkpoint: its lowered step is the text it is with the
    names (the kernel's and the router's) patched to identities, on the plain path and through the kernels (the
    interpreter, 128 positions)."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", str(int(path == "kernels")))
    older = importlib.import_module("benchmark.models.lfm2_8b_a1b")
    older_ref = importlib.import_module("benchmark.reference.lfm2_8b_a1b")
    cfg = _config("lfm2_8b_a1b")
    cfg.update(cfg["rehearsal"], dtype="float32")
    if path == "kernels":
        cfg.update(seq_len=128, items_per_sample=128)
    specs = older_ref.param_specs(cfg)
    net = older.build(cfg, specs, ref_common.init_params(specs, 5))
    older._FIRST.clear()
    x, y = older_ref.sample_inputs(cfg, jax.random.PRNGKey(9), 2)
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    fa.reset_dispatch_stats()

    def text():
        # MLIR numbers the private functions of one name (``@_where_174``)
        # by what the process lowered before: not part of the program
        f, datas = _grads(net, x, y, cfg["vocab_size"])
        return re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1",
                      f.lower(datas).as_text())

    named = text()
    assert fa.DISPATCH_STATS["pallas" if path == "kernels" else "xla"] > 0
    seen = []
    moe = importlib.import_module("mxtpu.parallel.moe")
    for module in (fa, kda, moe):
        monkeypatch.setattr(module, "checkpoint_name",
                            lambda x, name: seen.append(name) or x)
    assert text() == named
    assert set(seen) == set(fa.KEPT_NAMES + moe.KEPT_NAMES)


@pytest.mark.parametrize("name", ["lfm2_8b_a1b"])
def test_older_hybrid_models_lower_what_they_lowered(name):
    """lfm2's model (the filter's tap loop is shared with it), built as its
    cell builds it: the reference's logits, no recomputation, no group limit, no KDA
    call; and the lowered step says ``recompute=False`` is no argument at
    all: the text of a model built with it equals the text of the model
    its cell builds."""
    older = importlib.import_module("benchmark.models." + name)
    older_ref = importlib.import_module("benchmark.reference." + name)
    cfg = _config(name)
    cfg.update(cfg["rehearsal"], dtype="float32")
    specs = older_ref.param_specs(cfg)
    leaves = ref_common.init_params(specs, 5)
    for counter in COUNTERS:
        telemetry.reset_metric(counter)
    net = older.build(cfg, specs, leaves)
    older._FIRST.clear()
    assert net._recompute is False
    x, y = older_ref.sample_inputs(cfg, jax.random.PRNGKey(9), 2)
    f, datas = _grads(net, x, y, cfg["vocab_size"])
    text = f.lower(datas).as_text()
    assert "optimization_barrier" not in text and "kda" not in text
    assert [telemetry.value(c) for c in COUNTERS[:5]] == [0] * 5
    made = []
    init = hybrid_lm.HybridLM.__init__

    def explicit(self, *args, **kwargs):
        made.append(kwargs.setdefault("recompute", False))
        init(self, *args, **kwargs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hybrid_lm.HybridLM, "__init__", explicit)
        again = older.build(cfg, specs, leaves)
        older._FIRST.clear()
    assert made == [False]
    f2, datas2 = _grads(again, x, y, cfg["vocab_size"])
    strip = lambda t: re.sub(r"hybridlm\d+_", "hybridlm_", t)  # noqa: E731
    assert strip(f2.lower(datas2).as_text()) == strip(text)


# --------------------------------------------------------- the share's tie
E, K, D, F_ = 16, 4, 32, 12          # experts, choices a token, widths


def _layer(seed, t=48):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = jax.random.normal
    return n(ks[0], (t, D), jnp.float32), [
        0.3 * n(ks[1], (E, D)), 0.01 * n(ks[5], (E,)),
        0.2 * n(ks[2], (E, D, F_)), 0.2 * n(ks[3], (E, D, F_)),
        0.2 * n(ks[4], (E, F_, D))], [
        0.2 * n(ks[6], (F_, D)), 0.2 * n(ks[7], (F_, D)),
        0.2 * n(ks[0], (D, F_))]


def _layer_cfg(held=E, first=0):
    return dict(CFG, num_experts=E, num_experts_per_tok=K, n_group=4,
                topk_group=2, num_experts_held=held, first_expert_held=first,
                routed_scaling_factor=2.5)


@pytest.mark.parametrize("holders", [1, 2, 8])
def test_the_shares_add_up_to_the_uncut_layer(holders):
    """Expert parallelism ``holders`` ways: the parts the holders' experts
    give (the program's layer, each told which experts it holds, under the
    group limit) add up to what the uncut reference gives for the routed
    part of the whole layer; what every chip computes alike (the shared
    expert, the operators, the router, the norms) is counted once."""
    m, leaves, shared = _layer(11)
    router, bias, eg, eu, ed = leaves
    zero = [jnp.zeros_like(w) for w in shared]
    want = ref.expert_layer(_layer_cfg(), m[None], leaves + zero)[0]
    held = E // holders
    total = 0.0
    for first in range(0, E, held):
        part = slice(first, first + held)
        mine = moe.routed_ffn(m, router, bias, eg[part], eu[part], ed[part],
                              top_k=K, first_expert=first, scale=2.5,
                              n_group=4, topk_group=2)
        theirs = ref.expert_layer(
            _layer_cfg(held, first), m[None],
            [router, bias, eg[part], eu[part], ed[part]] + zero)[0]
        assert _gap(mine, theirs) <= 1e-5
        total = total + mine
    assert _gap(total, want) <= 1e-5
    # and the shared expert, once
    with_shared = ref.expert_layer(_layer_cfg(), m[None], leaves + shared)[0]
    sg, su, sd = shared
    alone = (jax.nn.silu(m @ sg.T) * (m @ su.T)) @ sd.T
    assert _gap(total + alone, with_shared) <= 1e-5


def test_operators_of_the_kda_kind():
    make, prefix = hybrid_lm.OPERATORS["kda"]
    assert make is hybrid_lm.KimiDeltaAttention and prefix == "kda_"
    blk = make(32, num_heads=2, head_dim=16, prefix=prefix)
    names = [k[len(blk.prefix):] for k in blk.collect_params().keys()]
    assert names == ["q_conv_weight", "k_conv_weight", "v_conv_weight",
                     "a_log", "dt_bias", "f_weight", "q_weight", "k_weight",
                     "v_weight", "b_weight", "g_weight", "onorm_gamma",
                     "proj_weight"]
    assert math.prod(blk.q_conv.shape) == 32 * 4
