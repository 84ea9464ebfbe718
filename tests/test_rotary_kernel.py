"""Rotary's Pallas pair (``mxtpu/ops/pallas/rotary.py``: ``rotary_turn`` /
``rotary_unturn``, the turn of a heads-first array in place) against the
plain ``ops/nn.py:rotary`` and a transposition, through the one entry ``ops/nn.py:rotary_heads_first``,
under the Pallas interpreter; who takes the kernels and who is refused, and
under which tag; and a block under the model's checkpoint.

Values are compared under ``jax.jit`` on both sides, as a step runs them.
XLA:CPU contracts ``x * cos + partner * sin`` into a fused multiply-add,
one product unrounded, in the plain path and in the interpreted kernel
alike; where the two programs' contractions fall differently an entry's
float32 differs in its last bit, and in bf16 about one entry in 100,000
then rounds the other way. So "bit for bit" here is: no entry further than
one unit in the last place, and fewer than one in 10,000 off at all (the
float32 cases read none); a wrong partner, sign or table moves every
entry. On the chip ``tools/perf_rotary.py`` holds the pair to equality
(``equal``), beside its times."""
import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import telemetry
from mxtpu.gluon.model_zoo import hybrid_lm
from mxtpu.ops.registry import get_op

from _jaxpr_count import calls

ops_nn = importlib.import_module("mxtpu.ops.nn")
fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
kernels = importlib.import_module("mxtpu.ops.pallas.rotary")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("rotary.calls", "rotary.pallas", "rotary.xla")
# 300 positions under row blocks of 256: a whole block and a partial one
# of 44
T = 300
YARN = {"factor": 8.0, "original_max_position_embeddings": 64,
        "beta_fast": 32.0, "beta_slow": 1.0, "attention_factor": 1.3}
FORMS = {
    "whole head": {},
    "64 of 128": {"width": 64},
    "yarn": {"width": 64, "scaling": YARN, "theta": 500000.0},
    "interleaved": {"interleave": True},
}


def _plain(x, **turn):
    return ops_nn.rotary(x, **turn).transpose(0, 2, 1, 3)


def _reset():
    for name in COUNTERS:
        telemetry.reset_metric(name)


def _counted():
    return [telemetry.value(name) for name in COUNTERS]


def _value_and_grad(fn):
    """-> jitted (x, g) -> (fn(x), the cotangent ``g`` pulled back)."""
    def both(x, g):
        out, back = jax.vjp(fn, x)
        return out, back(g)[0]
    return jax.jit(both)


def _ulps(got, want, scale):
    """(the largest distance in units of the last place of ``want``'s dtype
    at the magnitude ``scale``, the share of entries that differ)."""
    bits = {jnp.dtype(jnp.bfloat16): 8, jnp.dtype(jnp.float32): 24}[
        want.dtype]
    got, want, scale = (np.asarray(a, np.float64)
                        for a in (got, want, scale))
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(scale, 1e-30))) - (bits - 1))
    return float(np.max(np.abs(got - want) / ulp)), float(
        np.mean(got != want))


def _largest(x, gain):
    """Of each head's row of ``x`` [..., D] its largest magnitude times the
    table's ``gain``, at every entry: the magnitude of what a turned entry
    is the sum of (an entry's own may be far smaller, where the pair's two
    products cancel)."""
    return jnp.broadcast_to(gain * jnp.max(
        jnp.abs(x.astype(jnp.float32)), -1, keepdims=True), x.shape)


def _parity(monkeypatch, form, heads, dtype):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    monkeypatch.setattr(kernels, "_ROWS", 256)  # read when a pass is traced
    ops_nn._rotary_kernel_pass.clear_cache()
    turn = FORMS[form]
    ks = jax.random.split(jax.random.PRNGKey(heads), 2)
    x = jax.random.normal(ks[0], (2, T, heads, 128), jnp.float32)
    g = jax.random.normal(ks[1], (2, heads, T, 128), jnp.float32)
    x, g = x.astype(dtype), g.astype(dtype)
    _reset()
    got, d_got = _value_and_grad(
        lambda x: ops_nn.rotary_heads_first(x, **turn))(x, g)
    assert _counted() == [1, 1, 0]
    want, d_want = _value_and_grad(lambda x: _plain(x, **turn))(x, g)
    assert got.dtype == want.dtype == x.dtype and got.shape == g.shape
    assert d_got.dtype == x.dtype and d_got.shape == x.shape
    gain = turn.get("scaling", {}).get("attention_factor", 1.0)
    x_scale = _largest(x, gain)
    g_scale = _largest(g, gain).transpose(0, 2, 1, 3)
    far, share = _ulps(got, want, x_scale.transpose(0, 2, 1, 3))
    assert far <= 1 and share < 1e-4, (far, share)
    if "width" in turn:     # past the turned width nothing moves
        assert bool(jnp.all(got[..., turn["width"]:]
                            == x.transpose(0, 2, 1, 3)[..., turn["width"]:]))
    if dtype == "float32":
        far, share = _ulps(d_got, d_want, g_scale)
        assert far <= 1 and share < 1e-4, (far, share)
        return
    # the plain transpose rounds g cos and g sin to bf16 and then their
    # sum; the kernel rounds once: those four roundings are at most three
    # units. The float32 transpose rounded once is the kernel's own number
    far, _ = _ulps(d_got, d_want, g_scale)
    assert far <= 3, far
    _, once = _value_and_grad(lambda x: _plain(
        x.astype(jnp.float32), **turn).astype(dtype))(x, g)
    far, share = _ulps(d_got, once, g_scale)
    assert far <= 1 and share < 1e-4, (far, share)


def _refused(monkeypatch, reason, shape, dtype, turn, on_chip):
    """A refused call is the plain function's, bit for bit (the same
    operations), and counted under its tag; ``on_chip``: the platform says
    ``tpu`` (nothing here runs a kernel)."""
    if on_chip:
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
        monkeypatch.setattr(fa, "_platform", lambda: "tpu")
    else:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET",
                           "0" if reason == "platform" else "1")
    x = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    x = (4 * x).astype(dtype)
    _reset()
    assert kernels.refusal(x, turn.get("width", 0)) == reason
    if reason == "width":   # no rotary at all: the plain function says so
        with pytest.raises((TypeError, ValueError)):
            ops_nn.rotary_heads_first(x, **turn)
        assert telemetry.tagged("rotary.xla") == {reason: 1}
        return
    got = jax.jit(lambda x: ops_nn.rotary_heads_first(x, **turn))(x)
    assert _counted() == [1, 0, 1]
    assert telemetry.tagged("rotary.xla") == {reason: 1}
    want = jax.jit(lambda x: _plain(x, **turn))(x)
    assert got.dtype == want.dtype and bool(jnp.all(got == want))
    text = str(jax.make_jaxpr(
        lambda x: ops_nn.rotary_heads_first(x, **turn))(x))
    assert "pallas_call" not in text and "dot_general" in text


def _grouped(monkeypatch, window):
    """``grouped_attention`` turns q and k through the pair (two turns, and
    two transposes under differentiation), by the same numbers as with the
    plain rotary, and v is not turned."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2 * 128), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(jnp.sin(get_op("_contrib_grouped_attention").fn(
            q, k, v, window=window, rotary_dim=64, rope_scaling=YARN)))

    grad = jax.grad(loss, (0, 1, 2))
    _reset()
    counted = calls(jax.make_jaxpr(grad)(q, k, v))
    assert (counted["rotary_turn"], counted["rotary_unturn"]) == (2, 2)
    assert _counted() == [2, 2, 0]
    got = jax.jit(grad)(q, k, v)
    monkeypatch.setattr(kernels, "refusal", lambda x, width=0: "lanes")
    want = jax.jit(lambda *a: grad(*a))(q, k, v)    # traced again
    assert telemetry.tagged("rotary.xla") == {"lanes": 2}
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * float(
            jnp.max(jnp.abs(b)))


def _recomputed(monkeypatch):
    """A block around a turn under ``jax.checkpoint`` with the model's
    policy: the loss and the gradients of the block that is not recomputed
    (``test_ling3_flash.py``'s bound), the turn's kernel once more in the
    backward's second forward (nothing of it is kept: the tables are
    positions alone) and its transpose once."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(ks[0], (1, 128, 4, 128), jnp.float32)
    w = 0.1 * jax.random.normal(ks[1], (128, 128), jnp.float32)

    def block(x, w):
        return jnp.tanh(ops_nn.rotary_heads_first(x @ w, width=64) @ w)

    def loss(wrap):
        return lambda x, w: jnp.sum(wrap(block)(x, w) ** 2)

    kept = loss(lambda f: jax.checkpoint(f, policy=hybrid_lm.kept_policy()))
    plain = loss(lambda f: f)
    counted = calls(jax.make_jaxpr(jax.grad(kept, (0, 1)))(x, w))
    assert (counted["rotary_turn"], counted["rotary_unturn"]) == (2, 1)
    counted = calls(jax.make_jaxpr(jax.grad(plain, (0, 1)))(x, w))
    assert (counted["rotary_turn"], counted["rotary_unturn"]) == (1, 1)
    (l_on, g_on), (l_off, g_off) = (
        jax.jit(jax.value_and_grad(f, (0, 1)))(x, w) for f in (kept, plain))
    assert abs(float(l_on) - float(l_off)) <= 2e-7 * float(l_off)
    for a, b in zip(g_on, g_off):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap <= 2e-6, gap


def _traced_once(monkeypatch):
    """Three turns of one shape and form under one trace, and a fourth of
    another form: two kernel bodies traced forward and two back, not four
    and four (``_rotary_kernel_pass`` is one jit a shape, inlined)."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 2, 128),
                          jnp.bfloat16)
    bodies = {"turn": 0, "unturn": 0}
    kernel = kernels._kernel

    def counting(*a, **kw):
        bodies["unturn" if kw["back"] else "turn"] += 1
        return kernel(*a, **kw)

    monkeypatch.setattr(kernels, "_kernel", counting)
    ops_nn._rotary_kernel_pass.clear_cache()

    def loss(x):
        turned = [ops_nn.rotary_heads_first(x * s, theta=1e4 + 1)
                  for s in (1.0, 2.0, 3.0)]
        turned.append(ops_nn.rotary_heads_first(x, theta=1e4 + 1, width=64))
        return sum(jnp.sum(t.astype(jnp.float32) ** 2) for t in turned)

    counted = calls(jax.make_jaxpr(jax.grad(loss))(x))
    assert (counted["rotary_turn"], counted["rotary_unturn"]) == (4, 4)
    assert bodies == {"turn": 2, "unturn": 2}


def _reader(monkeypatch, case, want):
    """``rotary_fallbacks.train``: the calls counted under ``rotary.xla``
    once a window was measured, nothing from a program that counted no
    ``rotary.calls`` (the parent's, and a model that turns nothing)."""
    from benchmark import run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert [m for m in per_layer if m["name"] == "rotary_fallbacks.train"] \
        == [{
            "name": "rotary_fallbacks.train", "unit": "calls",
            "better": "lower", "source": "program_counter",
            "layer": "ops, kernels", "moves": "train_samples_per_s",
            "workloads": ["laguna_s_2_1.train_b1_s16384",
                          "smallthinker_21b_a3b.train_b1_s16384",
                          "keye_vl2_30b_a3b.train_b1_s16384",
                          "qwen3_next_80b_a3b.train_b1_s16384"]}]
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET",
                       "1" if case == "both on the kernels" else "0")
    _reset()
    if want is not None:
        x = jnp.ones((1, 32, 2, 128), jnp.float32)
        for _ in range(2):      # a function of its own: traced, not cached
            jax.make_jaxpr(lambda x: ops_nn.rotary_heads_first(x))(x)
    window = {"window": {"attempted": 0 if case == "no window" else 1}}
    assert run.reader("rotary_fallbacks.train")(window) == want


CASES = {}
for _form in FORMS:
    for _heads in (8, 72):
        for _dtype in ("float32", "bfloat16"):
            CASES["%s, %d heads, %s" % (_form, _heads, _dtype)] = (
                _parity, _form, _heads, _dtype)
CASES.update({
    "refused: off the chip": (
        _refused, "platform", (1, 40, 2, 128), "float32", {}, False),
    "refused: float16": (
        _refused, "dtype", (1, 40, 2, 128), "float16", {}, False),
    "refused: heads of 64": (
        _refused, "lanes", (1, 40, 2, 64), "float32", {}, False),
    "refused: heads of 64 on the chip": (
        _refused, "lanes", (1, 40, 2, 64), "bfloat16", {}, True),
    "refused: heads of 192 on the chip": (
        _refused, "lanes", (1, 40, 2, 192), "bfloat16", {"width": 64}, True),
    "refused: an odd width": (
        _refused, "width", (1, 40, 2, 128), "float32", {"width": 5}, False),
    "grouped attention, causal": (_grouped, 0),
    "grouped attention, a window of 40": (_grouped, 40),
    "a recomputed block": (_recomputed,),
    "a body traced once a shape": (_traced_once,),
    "the reader: no window": (_reader, "no window", None),
    "the reader: nothing turned": (_reader, "nothing turned", None),
    "the reader: both refused": (_reader, "both refused", 2),
    "the reader: both on the kernels": (_reader, "both on the kernels", 0),
})


@pytest.mark.parametrize("case", sorted(CASES))
def test_rotary_kernels(monkeypatch, case):
    check, *args = CASES[case]
    check(monkeypatch, *args)
