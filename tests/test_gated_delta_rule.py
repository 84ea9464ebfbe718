"""The second delta rule and what came with it, at the operator (the
model's own cases are ``tests/test_qwen3_next.py``; two files because the
tier-1 run hands out work by file): ``gated_delta_rule`` against the
token-by-token recurrence of ``benchmark/reference/qwen3_next_80b_a3b.py``
with gates down to -30 a token, two value heads a key head and a length
off the chunk, on the plain path and through both Pallas kernels under the
interpreter; what the two rules share; the gate's equation; the ``1 + w``
norm; the elementwise gate; the shared expert's gate; the flash backward's
second layout and the plan at head width 256."""
import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import telemetry
from mxtpu.gluon import nn
from mxtpu.gluon.contrib.nn import RoutedMoE
from mxtpu.gluon.model_zoo import hybrid_lm
from mxtpu.ops.registry import get_op

from benchmark.reference import qwen3_next_80b_a3b as ref

from _jaxpr_count import calls

kda = importlib.import_module("mxtpu.ops.pallas.kda")
fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
COUNTERS = ("gated_delta.calls", "gated_delta.fallbacks")


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


# ------------------------------------------------- the chunked operator
def _recurrence(q, k, v, g, beta, key_heads):
    b, t, h = beta.shape
    q, k = (jnp.repeat(x.reshape(b, t, key_heads, -1), h // key_heads, 2)
            for x in (q, k))
    return ref.delta_rule(q, k, v.reshape(b, t, h, -1), g, beta).reshape(
        b, t, -1)


def _operands(seed, b, t, hk, h, dk, deepest):
    """Gates log-uniform between -1e-3 and ``deepest`` a token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key):
        x = jax.random.normal(key, (b, t, hk, dk))
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            b, t, hk * dk)

    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h), minval=math.log(1e-3),
                                    maxval=math.log(-deepest)))
    return (unit(ks[0]), unit(ks[1]),
            jax.random.normal(ks[2], (b, t, h * dk)), g,
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))),
            jax.random.normal(ks[5], (b, t, h * dk)))


def _against_the_recurrence(shape, chunk, deepest):
    """Largest error of the output and of each cotangent against the
    token-by-token recurrence, each over the largest entry of its own."""
    *xs, do = _operands(3, *shape, deepest)
    hk = shape[2]

    @jax.jit
    def both(do, *xs):
        want, vjp = jax.vjp(lambda *a: _recurrence(*a, hk), *xs)
        got, vjp2 = jax.vjp(lambda *a: kda.gated_delta_rule(*a, hk, chunk),
                            *xs)
        return [(got, want)] + list(zip(vjp2(do), vjp(do)))

    out = both(do, *xs)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a, _ in out)
    return [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in out]


@pytest.mark.parametrize("shape,chunk,deepest", [
    ((2, 70, 2, 4, 16), 16, -30.0), ((1, 64, 2, 2, 16), 8, -1.0),
    ((1, 160, 1, 2, 16), 64, -30.0)])
def test_the_chunks_equal_the_recurrence_on_the_plain_path(shape, chunk,
                                                           deepest):
    """Gates down to -30 a token (-1,900 over a chunk of 64: ``exp(-G)``
    alone is no float32), two value heads a key head (and one), lengths
    that are no multiple of the chunk."""
    errors = _against_the_recurrence(shape, chunk, deepest)
    assert max(errors) <= 2e-5, errors


@pytest.mark.parametrize("shape,chunk", [((1, 100, 1, 2, 16), 32),
                                         ((2, 70, 2, 4, 16), 16)])
def test_both_kernels_equal_the_recurrence(monkeypatch, shape, chunk):
    """``gdn_fwd`` and ``gdn_bwd`` under the Pallas interpreter: q and k
    fetched at head ``j // 2`` by the index maps, a key head's two
    cotangents summed, a length off the chunk (and off the block of
    chunks), gates down to -30."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    for name in COUNTERS[:2]:
        telemetry.reset_metric(name)
    errors = _against_the_recurrence(shape, chunk, -30.0)
    assert max(errors) <= 2e-5, errors
    assert telemetry.value("gated_delta.fallbacks") == 0
    assert telemetry.value("gated_delta.calls") >= 1


def test_the_kernels_are_named_and_never_repeat_a_key_head(monkeypatch):
    """A differentiated call holds one ``gdn_fwd`` and one ``gdn_bwd`` and
    no other kernel; q and k enter them as the caller holds them."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    *xs, do = _operands(1, 1, 64, 2, 4, 16, -5.0)
    closed = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        kda.gated_delta_rule(*a, 2, 16) * do), (0, 1, 2, 3, 4)))(*xs)
    assert calls(closed) == {"gdn_fwd": 1, "gdn_bwd": 1}
    kernels = [e for e in closed.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    for eqn in kernels:             # q, k: [B, T', key heads * K]
        assert [v.aval.shape for v in eqn.invars[:2]] == [(1, 64, 32)] * 2


def test_value_heads_that_do_not_divide_are_refused():
    *xs, _ = _operands(1, 1, 32, 2, 4, 16, -1.0)
    with pytest.raises(ValueError, match="do not divide"):
        kda.gated_delta_rule(*xs, 3, 16)
    with pytest.raises(ValueError, match="power of two"):
        kda.gated_delta_rule(*xs, 2, 48)


def test_bf16_operands_stay_near_the_recurrence():
    """bf16 inputs take fewer MXU passes (``_PASSES``, the table KDA's
    kernels read): the output stays within bf16's own rounding of the
    float32 recurrence."""
    *xs, _ = _operands(4, 1, 128, 1, 2, 16, -20.0)
    want = _recurrence(*xs, 1)
    low = [x.astype(jnp.bfloat16) for x in xs[:3]] + [xs[3]] \
        + [xs[4].astype(jnp.bfloat16)]
    got = jax.jit(lambda *a: kda.gated_delta_rule(*a, 1, 64))(*low)
    assert got.dtype == jnp.bfloat16
    assert _gap(got.astype(jnp.float32), want) <= 2e-2


def test_the_two_rules_share_one_plan():
    """What the two rules share is one object each, not a copy: the chunk
    functions, the inverse, the product table, the kernels' bodies."""
    assert kda.KEPT_NAMES == ("kda_o", "kda_states")
    assert kda.GDN_KEPT_NAMES == ("gdn_o", "gdn_states")
    assert kda._KDA.head_decay is False and kda._GDN.head_decay is True
    assert (kda._GDN.kernels, kda._GDN.counters) == ("gdn", "gated_delta")
    source = open(kda.__file__).read()
    for shared in ("def _prep(", "def _apply(", "def _inverse_t(",
                   "def _fwd_kernel(", "def _bwd_kernel(", "def _plan(",
                   "_PASSES = {"):
        assert source.count(shared) == 1, shared
    # ``e^{-G}`` alone is never formed on the scalar path
    body = source[source.index("def _head_decay("):
                  source.index("def _prep(")]
    assert "jnp.exp(-" not in body and "g_col - g_at" in body


def test_the_gate_is_the_unbounded_form():
    """``g = -exp(A_log) softplus(x Wa + dt_bias)``, float32 from bf16
    operands, and past -5 a token at the source's initialiser."""
    gate = get_op("_contrib_gdn_gate").fn
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(ks[0], (2, 5, 8))
    w = 0.3 * jax.random.normal(ks[1], (4, 8))
    a_log = jnp.log(jnp.asarray([0.5, 2.0, 8.0, 16.0]))
    dt = jnp.ones((4,))
    want = -jnp.exp(a_log) * jax.nn.softplus(
        jnp.einsum("btd,hd->bth", x, w, precision="highest") + dt)
    got = gate(x, w, a_log, dt)
    assert got.dtype == jnp.float32 and _gap(got, want) <= 1e-6
    assert float(got.min()) < -5.0 and float(got.max()) < 0.0
    low = gate(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), a_log, dt)
    assert low.dtype == jnp.float32


def test_the_norm_takes_the_scale_from_one():
    """``zero_centered``: ``x / rms(x) * (1 + w)``, the leaf starting at
    zero; without it the plain scale, starting at one."""
    op = get_op("RMSNorm").fn
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    unit = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    assert _gap(op(x, w, zero_centered=True), unit * (1 + w)) <= 1e-6
    assert _gap(op(x, w), unit * w) <= 1e-6
    for centred, start in ((True, 0.0), (False, 1.0)):
        blk = nn.RMSNorm(zero_centered=centred, in_channels=16)
        blk.initialize()
        assert float(blk.gamma.data().asnumpy().mean()) == start
        assert _gap(blk(mx.nd.NDArray(x))._data, unit) <= 1e-6


def _attention(**kwargs):
    blk = hybrid_lm.GroupedQueryAttention(
        32, num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=8,
        prefix="attn_", **kwargs)
    blk.initialize()
    return blk


def test_the_elementwise_gate_is_the_query_projections_second_half():
    """``[q | gate]`` a head from one projection twice as wide: with the
    gate's rows zero every entry is halved (sigmoid(0)), and the query's
    rows are the ungated block's."""
    x = mx.nd.NDArray(jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32)))
    plain, gated = _attention(), _attention(element_gate=True)
    plain(x), gated(x)                      # settle the deferred shapes
    assert gated.q.weight.shape == (2 * 4 * 16, 32)
    assert gated.gate is None
    w = np.zeros((4, 2, 16, 32), np.float32)
    w[:, 0] = plain.q.weight.data().asnumpy().reshape(4, 16, 32)
    gated.q.weight.set_data(mx.nd.array(w.reshape(128, 32)))
    for name in ("k", "v", "proj", "q_norm", "k_norm"):
        for p, q in zip(getattr(plain, name).collect_params().values(),
                        getattr(gated, name).collect_params().values()):
            q.set_data(p.data())
    telemetry.reset_metric("attention.element_gated")
    assert _gap(gated(x)._data, 0.5 * plain(x)._data) <= 1e-5
    assert telemetry.value("attention.element_gated") == 1


def test_the_shared_experts_gate_is_one_number_a_token():
    kw = dict(hidden=12, num_experts=8, top_k=2, shared_hidden=12,
              score="softmax", prefix="moe_")
    with pytest.raises(ValueError, match="not there"):
        RoutedMoE(32, hidden=12, num_experts=8, top_k=2, shared_gate=True)
    plain, gated = RoutedMoE(32, **kw), RoutedMoE(32, shared_gate=True, **kw)
    for blk in (plain, gated):
        blk.initialize()
    x = mx.nd.NDArray(jax.random.normal(jax.random.PRNGKey(0), (2, 6, 32)))
    plain(x), gated(x)
    names = [k[len(gated.prefix):] for k in gated.collect_params().keys()]
    assert names == ["router_weight", "score_bias", "w_gate", "w_up",
                     "w_down", "sgate_weight", "shared_gate_weight",
                     "shared_up_weight", "shared_down_weight"]
    assert gated.shared_gate.weight.shape == (1, 32)
    for name, p in plain.collect_params().items():
        gated.collect_params()[name].set_data(p.data())
    routed = plain(x)._data - plain.shared(x)._data
    gate = jax.nn.sigmoid(x._data @ gated.shared_gate.weight.data()._data.T)
    assert gate.shape == (2, 6, 1)
    assert _gap(gated(x)._data, routed + gate * plain.shared(x)._data) <= 1e-5


# ------------------------------------- the flash backward's second layout
def test_dk_and_dv_a_query_head_are_the_whole_heads(monkeypatch):
    """Where dk and dv of a whole key/value head do not fit beside dq (16,384
    keys of 256), the backward kernel writes a k block of them a QUERY
    head and XLA sums the group: the same numbers as the resident layout,
    counted, and no fallback."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    b, h, hk, t, d = 2, 4, 2, 256, 32
    q, w = (jax.random.normal(k, (b, h, t, d)) for k in ks[:2])
    k, v = (jax.random.normal(k, (b, hk, t, d)) for k in ks[2:])
    grad = jax.grad(lambda *a: jnp.sum(fa.flash_attention(*a, True) * w),
                    (0, 1, 2))
    held = grad(q, k, v)
    monkeypatch.setattr(fa, "_kv_held", lambda *a: False)
    fa.reset_dispatch_stats()
    telemetry.reset_metric("pallas_flash.bwd_kv_by_query_head")
    by_query_head = grad(q, k, v)
    assert telemetry.value("pallas_flash.bwd_kv_by_query_head") == 1
    assert fa.DISPATCH_STATS["bwd_pallas"] == 1
    assert fa.DISPATCH_STATS["bwd_xla"] == 0
    for a, b_ in zip(by_query_head, held):
        assert _gap(a, b_) <= 1e-6


def test_width_256_plans_both_kernels(monkeypatch):
    """The cell's attention call (16 query heads over 2 key/value heads of
    256, 16,384 positions, bf16) is planned, not refused: 1,024 x 1,024
    blocks each way, the backward with dk and dv a query head; at 128 wide
    (a window / global stack's global layer) the whole heads still fit, at
    the blocks they had."""
    monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    mask = fa.Mask(True)
    for width, blocks, held in ((256, (1024, 1024), False),
                                (128, (512, 1024), True)):
        q = jax.ShapeDtypeStruct((1, 16, 16384, width), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, 2, 16384, width), jnp.bfloat16)
        assert fa._plan(q, k, k, mask, 1024, 1024, "forward") == (
            (1024, 1024), None)
        assert fa._plan(q, k, k, mask, 1024, 1024, "backward") == (
            blocks, None)
        assert fa._kv_held(q, k, k, mask, *blocks) is held
