"""The gated short convolution (op ``_contrib_short_conv``, block
``gluon.nn.ShortConv``): ``y = C * conv_L(B * x)`` against three shifted
products written out in float64, its gradients, the first positions (which
see zeros before the sequence's start) and ``hybridize``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import autograd, telemetry
from mxtpu.gluon import nn
from mxtpu.ops import get_op, nn as opsnn

# the op as a function of arrays (the registered name wraps NDArrays)
short_conv = get_op("_contrib_short_conv").fn


def _shifted_products(data, w):
    """The definition, in float64: c[t] = sum_j w[:, j] z[t - (L-1) + j]."""
    data, w = np.asarray(data, np.float64), np.asarray(w, np.float64)
    d, taps = w.shape
    gate_in, gate_out, x = (data[..., i * d:(i + 1) * d] for i in range(3))
    z = gate_in * x
    c = np.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j
        shifted = np.zeros_like(z)
        shifted[..., back:, :] = z[..., :z.shape[-2] - back, :]
        c += w[:, j] * shifted
    return gate_out * c


def _inputs(shape, taps, dtype="float32", seed=3):
    rng = np.random.RandomState(seed)
    d = shape[-1] // 3
    return (jnp.asarray(rng.randn(*shape), dtype),
            jnp.asarray(rng.randn(d, taps), dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("taps", [1, 2, 3, 4])
@pytest.mark.parametrize("shape", [(2, 16, 24), (1, 5, 12), (3, 2, 7, 9)],
                         ids=["b2t16d8", "b1t5d4", "rank4"])
def test_op_matches_three_shifted_products(shape, taps, dtype):
    data, w = _inputs(shape, taps, dtype)
    out = mx.nd.short_conv(mx.nd.NDArray(data), mx.nd.NDArray(w)).asnumpy()
    want = _shifted_products(data.astype(jnp.float32),
                             w.astype(jnp.float32))
    assert out.shape == shape[:-1] + (shape[-1] // 3,)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=tol, atol=tol * np.abs(want).max())


def test_first_positions_see_zeros_before_the_start():
    """Position 0 reads the last tap alone, position 1 the last two; a
    change at position t moves no output before t (causal)."""
    data, w = _inputs((1, 8, 12), 3)
    d = 4
    out = np.asarray(short_conv(data, w))
    z = np.asarray(data[..., :d] * data[..., 2 * d:])
    c_gate = np.asarray(data[..., d:2 * d])
    w = np.asarray(w)
    np.testing.assert_allclose(out[0, 0], c_gate[0, 0] * w[:, 2] * z[0, 0],
                               rtol=1e-5)
    np.testing.assert_allclose(
        out[0, 1], c_gate[0, 1] * (w[:, 2] * z[0, 1] + w[:, 1] * z[0, 0]),
        rtol=1e-5)
    moved = np.asarray(short_conv(
        data.at[0, 5].add(1.0), jnp.asarray(w)))
    assert np.array_equal(moved[0, :5], out[0, :5])
    assert not np.allclose(moved[0, 5:8], out[0, 5:8])


@pytest.mark.parametrize("taps", [2, 3])
def test_gradients_match_the_definition(taps):
    """The op's backward computes the gated products again from its input
    (custom_vjp): data's and the taps' gradients against ``jax.grad`` of
    the plain form and against finite differences of the definition."""
    data, w = _inputs((2, 12, 18), taps)
    g = jnp.asarray(np.random.RandomState(9).randn(2, 12, 6), jnp.float32)
    loss = lambda f: lambda a, b: jnp.sum(f(a, b) * g)
    got = jax.grad(loss(short_conv), argnums=(0, 1))(data, w)
    want = jax.grad(loss(opsnn._short_conv_plain), argnums=(0, 1))(data, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    base = float(np.sum(_shifted_products(data, w) * np.asarray(g)))
    for arr, grad, at in ((data, got[0], (1, 3, 7)), (w, got[1], (2, 0))):
        bumped = np.asarray(arr, np.float64).copy()
        bumped[at] += 1e-3
        args = (bumped, w) if arr is data else (data, bumped)
        slope = (float(np.sum(_shifted_products(*args) * np.asarray(g)))
                 - base) / 1e-3
        assert abs(slope - float(grad[at])) <= 2e-3 * max(1.0, abs(slope))


def test_backward_keeps_only_the_projected_input():
    """What the forward saves for the backward: the op's two inputs, not
    the float32 gates and shifted products."""
    data, w = _inputs((2, 16, 24), 3, "bfloat16")
    _, vjp = jax.vjp(opsnn._short_conv, data, w)
    kept = [x for x in jax.tree_util.tree_leaves(vjp)
            if hasattr(x, "shape") and x.ndim >= 2]
    assert sorted(x.shape for x in kept) == [(2, 16, 24), (8, 3)]


def test_block_against_the_definition_and_hybridize():
    """``ShortConv``: in_proj -> op -> out_proj, own taps first in
    ``collect_params``; eager and hybridized agree; every leaf gets a
    gradient; the op counts its layers and names its scope."""
    rng = np.random.RandomState(1)
    net = nn.ShortConv(8, kernel_size=3, prefix="conv_")
    net.initialize(mx.init.Normal(0.5))
    x = mx.nd.array(rng.randn(2, 10, 8).astype(np.float32))
    telemetry.reset_metric("short_conv.layers")
    with autograd.record():
        out = net(x)
        loss = (out * out).sum()
    loss.backward()
    assert telemetry.value("short_conv.layers") >= 1
    names = list(net.collect_params().keys())
    assert names == ["conv_weight", "conv_in_weight", "conv_out_weight"]
    p = {n: v.data().asnumpy().astype(np.float64)
         for n, v in net.collect_params().items()}
    assert p["conv_weight"].shape == (8, 3)
    bcx = x.asnumpy().astype(np.float64) @ p["conv_in_weight"].T
    want = _shifted_products(bcx, p["conv_weight"]) @ p["conv_out_weight"].T
    np.testing.assert_allclose(out.asnumpy(), want, rtol=2e-4, atol=2e-5)
    for v in net.collect_params().values():
        assert float(np.abs(v.grad().asnumpy()).sum()) > 0
    net.hybridize()
    np.testing.assert_allclose(net(x).asnumpy(), out.asnumpy(), rtol=1e-5,
                               atol=1e-6)
    text = jax.jit(lambda a, b: short_conv(a, b)).lower(
        jnp.zeros((1, 4, 6)), jnp.zeros((2, 3))).as_text(debug_info=True)
    assert "short_conv" in text


def test_symbol_route():
    data, w = _inputs((2, 6, 9), 3)
    sym = mx.sym.short_conv(mx.sym.Variable("data"), mx.sym.Variable("w"))
    ex = sym.bind(mx.cpu(), {"data": mx.nd.NDArray(data),
                             "w": mx.nd.NDArray(w)})
    np.testing.assert_allclose(ex.forward()[0].asnumpy(),
                               _shifted_products(data, w), rtol=1e-5,
                               atol=1e-5)
