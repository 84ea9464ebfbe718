"""The one route every convolution takes (``ops.nn.conv_fast``: XLA's
convolution under the package precision policy, gradients by autodiff),
held to a float32 reference written here.

The reference shares nothing with the route: it pads (and, for a transposed
convolution, scatters) by hand and sums one ``einsum`` per kernel tap at
``Precision.HIGHEST``; its gradients are autodiff through slices and
einsums, not through a convolution's transpose rules.

(a) every distinct convolution of ``resnet50_v1`` under ``mx.layout("NHWC")``
    (the benchmark's ``resnet50_v1.train_b128`` runs exactly these, in
    bf16), forward and both gradients, bf16 and float32;
(b) stride, padding, dilation, grouping and the transposed form as checks of
    the ``Convolution`` / ``Deconvolution`` operators themselves, bias
    included, in both layouts;
(c) what the route does with dtypes and a bias, and under ``jit``/``vmap``.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxtpu as mx
from mxtpu.ops.nn import conv_fast
from mxtpu.ops.registry import REGISTRY

DN = ("NHWC", "HWIO", "NHWC")
HI = lax.Precision.HIGHEST
F32 = jnp.float32


def ref_conv(x, w, stride, pad, dilate=(1, 1), groups=1):
    """NHWC x HWIO convolution in float32, one einsum per kernel tap."""
    x = jnp.pad(x.astype(F32), ((0, 0), pad[0], pad[1], (0, 0)))
    w = w.astype(F32)
    (kh, kw, cig, co), n = w.shape, x.shape[0]
    oh = (x.shape[1] - (kh - 1) * dilate[0] - 1) // stride[0] + 1
    ow = (x.shape[2] - (kw - 1) * dilate[1] - 1) // stride[1] + 1
    out = jnp.zeros((n, oh, ow, groups, co // groups), F32)
    for i in range(kh):
        for j in range(kw):
            r0, c0 = i * dilate[0], j * dilate[1]
            xs = x[:, r0:r0 + (oh - 1) * stride[0] + 1:stride[0],
                   c0:c0 + (ow - 1) * stride[1] + 1:stride[1], :]
            out = out + jnp.einsum(
                "nhwgc,cgo->nhwgo", xs.reshape(n, oh, ow, groups, cig),
                w[i, j].reshape(cig, groups, co // groups), precision=HI)
    return out.reshape(n, oh, ow, co)


def ref_deconv(x, w, stride, pad, adj):
    """The transposed convolution as a scatter: input pixel (h, w) adds
    ``x[h, w] . w[i, j]`` at output (h*s + i - pad, w*s + j - pad). ``w`` is
    (kh, kw, C_out, C_in), as ``Deconvolution`` takes it channels-last."""
    x, w = x.astype(F32), w.astype(F32)
    (n, h, wd, _), (kh, kw, co, _) = x.shape, w.shape
    full = jnp.zeros((n, (h - 1) * stride[0] + kh + adj[0],
                      (wd - 1) * stride[1] + kw + adj[1], co), F32)
    for i in range(kh):
        for j in range(kw):
            full = full.at[:, i:i + (h - 1) * stride[0] + 1:stride[0],
                           j:j + (wd - 1) * stride[1] + 1:stride[1], :].add(
                jnp.einsum("nhwc,oc->nhwo", x, w[i, j], precision=HI))
    return full[:, pad[0]:full.shape[1] - pad[0],
                pad[1]:full.shape[2] - pad[1], :]


def _close(got, want, dtype):
    """Within the dtype's rounding of the reference's own scale: bf16 keeps
    8 bits of a float32 accumulator's result, float32 runs at HIGHEST."""
    tol = 1.5e-2 if dtype == jnp.bfloat16 else 2e-5
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-6)
    np.testing.assert_allclose(got / scale, want / scale, rtol=0, atol=tol)


# ------------------------------------------- (a) ResNet-50's convolutions
# (kernel, stride, pad, C_in, C_out): what `gluon.model_zoo.vision.
# resnet50_v1` holds; `test_the_table_is_the_models_own` walks the model
# and fails when this table and the model part ways
RESNET50 = [
    (7, 2, 3, 3, 64),                                   # the stem
    (1, 1, 0, 64, 64), (3, 1, 1, 64, 64), (1, 1, 0, 64, 256),
    (1, 1, 0, 256, 64),
    (1, 1, 0, 256, 128), (3, 2, 1, 128, 128), (1, 1, 0, 128, 512),
    (1, 2, 0, 256, 512), (1, 1, 0, 512, 128), (3, 1, 1, 128, 128),
    (1, 1, 0, 512, 256), (3, 2, 1, 256, 256), (1, 1, 0, 256, 1024),
    (1, 2, 0, 512, 1024), (1, 1, 0, 1024, 256), (3, 1, 1, 256, 256),
    (1, 1, 0, 1024, 512), (3, 2, 1, 512, 512), (1, 1, 0, 512, 2048),
    (1, 2, 0, 1024, 2048), (1, 1, 0, 2048, 512), (3, 1, 1, 512, 512),
]


def test_the_table_is_the_models_own():
    from mxtpu.gluon import nn
    from mxtpu.gluon.model_zoo import vision
    with mx.layout("NHWC"):
        net = vision.resnet50_v1()
    net.initialize()
    net(mx.nd.array(np.zeros((1, 32, 32, 3), np.float32)))  # settles C_in
    found = set()

    def walk(block):
        for child in block._children.values():
            if isinstance(child, nn.Conv2D):
                kw = child._kwargs
                assert kw["layout"] == "NHWC" and kw["num_group"] == 1
                assert kw["dilate"] == (1, 1) and kw["no_bias"]
                assert len(set(kw["kernel"])) == len(set(kw["stride"])) == 1
                found.add((kw["kernel"][0], kw["stride"][0], kw["pad"][0])
                          + tuple(child.weight.shape[2:]))
            walk(child)

    walk(net)
    assert found == set(RESNET50)


def _class_case(k, s, p, cin, cout, dtype):
    # an odd size: the strided classes end on a partial window
    hw = 15 if k == 7 else 9
    rng = np.random.RandomState(k * 1000 + s * 100 + cin % 97 + cout % 89)
    x = jnp.asarray(rng.randn(2, hw, hw, cin), dtype)
    w = jnp.asarray(rng.randn(k, k, cin, cout) / np.sqrt(k * k * cin), dtype)
    args = ((s, s), [(p, p), (p, p)])
    route = lambda x, w: conv_fast(x, w, *args, (1, 1), (1, 1), DN, 1)
    return x, w, route, lambda x, w: ref_conv(x, w, *args)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("k,s,p,cin,cout", RESNET50,
                         ids=["%dx%ds%d_%d_%d" % (c[0], c[0], c[1], c[3], c[4])
                              for c in RESNET50])
class TestResNet50Classes:
    def test_forward(self, k, s, p, cin, cout, dtype):
        x, w, route, ref = _class_case(k, s, p, cin, cout, dtype)
        y = jax.jit(route)(x, w)
        assert y.dtype == dtype
        _close(y, ref(x, w), dtype)

    def test_both_gradients(self, k, s, p, cin, cout, dtype):
        x, w, route, ref = _class_case(k, s, p, cin, cout, dtype)
        # a fixed cotangent: the loss is linear in the output, so the
        # gradients are held to the reference's whatever the forward rounds
        ct = jnp.asarray(np.random.RandomState(7).randn(
            *jax.eval_shape(route, x, w).shape), dtype)
        loss = lambda f: lambda x, w: jnp.sum(
            f(x, w).astype(F32) * ct.astype(F32))
        dx, dw = jax.jit(jax.grad(loss(route), argnums=(0, 1)))(x, w)
        rx, rw = jax.jit(jax.grad(loss(ref), argnums=(0, 1)))(
            x.astype(F32), w.astype(F32))
        assert dx.dtype == dw.dtype == dtype
        _close(dx, rx, dtype)
        _close(dw, rw, dtype)


# ------------------------- (b) the operators: stride, pad, dilation, groups
def _to_layout(layout, x, w):
    """Channels-last test arrays as the operator wants them in ``layout``:
    NCHW takes Convolution's HWIO as OIHW and Deconvolution's (kh, kw, out,
    in) as (in, out, kh, kw), the same permutation."""
    if layout == "NHWC":
        return x, w
    return (jnp.transpose(x, (0, 3, 1, 2)),
            jnp.transpose(w, (3, 2, 0, 1)))


def _from_layout(layout, y):
    return y if layout == "NHWC" else jnp.transpose(y, (0, 2, 3, 1))


@pytest.mark.parametrize("layout", ["NHWC", "NCHW"])
@pytest.mark.parametrize("stride,pad,dilate,groups,cin,cout,k", [
    (1, 1, 1, 1, 8, 16, 3),
    (2, 1, 1, 1, 8, 16, 3),
    (2, 3, 1, 1, 3, 16, 7),   # resnet stem shape
    (1, 0, 1, 1, 8, 16, 1),   # 1x1 bottleneck
    (1, 2, 2, 1, 8, 16, 3),   # dilated
    (1, 1, 1, 4, 8, 16, 3),   # grouped
    (1, 1, 1, 8, 8, 8, 3),    # depthwise
])
def test_convolution_gradients(stride, pad, dilate, groups, cin, cout, k,
                               layout):
    op = REGISTRY["Convolution"].fn
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 12, 12, cin), F32)
    w = jnp.asarray(rng.randn(k, k, cin // groups, cout) * 0.1, F32)
    b = jnp.asarray(rng.randn(cout), F32)

    def route(x, w, b):
        xl, wl = _to_layout(layout, x, w)
        return _from_layout(layout, op(
            xl, wl, b, kernel=(k, k), stride=(stride,) * 2, pad=(pad,) * 2,
            dilate=(dilate,) * 2, num_filter=cout, num_group=groups,
            layout=layout))

    def ref(x, w, b):
        return ref_conv(x, w, (stride,) * 2, [(pad, pad)] * 2,
                        (dilate,) * 2, groups) + b

    y, want = route(x, w, b), ref(x, w, b)
    _close(y, want, F32)
    ct = jnp.asarray(rng.randn(*want.shape), F32)
    got = jax.grad(lambda *a: jnp.sum(route(*a) * ct), argnums=(0, 1, 2))(
        x, w, b)
    exp = jax.grad(lambda *a: jnp.sum(ref(*a) * ct), argnums=(0, 1, 2))(
        x, w, b)
    for g, e in zip(got, exp):
        _close(g, e, F32)


@pytest.mark.parametrize("layout", ["NHWC", "NCHW"])
@pytest.mark.parametrize("stride,pad,adj,k", [(2, 1, 0, 3), (2, 1, 1, 3),
                                              (3, 0, 0, 4), (1, 1, 0, 3)])
def test_deconvolution_gradients(stride, pad, adj, k, layout):
    """The lhs-dilated form: stride, padding and ``adj`` of a transposed
    convolution against the scatter it stands for."""
    op = REGISTRY["Deconvolution"].fn
    rng = np.random.RandomState(2)
    cin, cout = 8, 6
    x = jnp.asarray(rng.randn(2, 6, 6, cin), F32)
    w = jnp.asarray(rng.randn(k, k, cout, cin) * 0.1, F32)   # kh kw O I

    def route(x, w):
        xl, wl = _to_layout(layout, x, w)
        return _from_layout(layout, op(
            xl, wl, kernel=(k, k), stride=(stride,) * 2, pad=(pad,) * 2,
            adj=(adj,) * 2, num_filter=cout, layout=layout))

    ref = lambda x, w: ref_deconv(x, w, (stride,) * 2, (pad,) * 2,
                                  (adj,) * 2)
    want = ref(x, w)
    assert want.shape[1] == (6 - 1) * stride - 2 * pad + k + adj
    _close(route(x, w), want, F32)
    ct = jnp.asarray(rng.randn(*want.shape), F32)
    got = jax.grad(lambda *a: jnp.sum(route(*a) * ct), argnums=(0, 1))(x, w)
    exp = jax.grad(lambda *a: jnp.sum(ref(*a) * ct), argnums=(0, 1))(x, w)
    for g, e in zip(got, exp):
        _close(g, e, F32)


# --------------------------------------------- (c) dtypes, bias, jit, vmap
_ARGS = ((1, 1), [(1, 1), (1, 1)], (1, 1), (1, 1), DN, 1)


def test_mixed_operand_dtypes_are_refused_not_rounded():
    """bf16 activations against float32 weights: XLA's convolution takes one
    dtype, and the route does not pick one for the caller (a silent cast of
    float32 master weights to bf16 would be a different program). Promoted
    by the caller, the result is float32."""
    x = jnp.ones((1, 8, 8, 16), jnp.bfloat16)
    w = jnp.ones((3, 3, 16, 8), F32)
    with pytest.raises(TypeError):
        conv_fast(x, w, *_ARGS)
    y = conv_fast(x.astype(F32), w, *_ARGS)
    assert y.dtype == F32
    _close(y, ref_conv(x, w, (1, 1), [(1, 1), (1, 1)]), F32)


@pytest.mark.parametrize("bias_dtype,out_dtype", [
    (jnp.float32, jnp.float32), (jnp.bfloat16, jnp.bfloat16)],
    ids=["f32_bias_promotes", "bf16_bias_keeps"])
def test_bias_is_an_external_add(bias_dtype, out_dtype):
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(1, 7, 7, 4), jnp.bfloat16)
    w = jnp.asarray(rng.randn(3, 3, 4, 8) * 0.1, jnp.bfloat16)
    b = jnp.asarray(rng.randn(8), bias_dtype)
    got = conv_fast(x, w, *_ARGS, bias=b)
    assert got.dtype == out_dtype == (conv_fast(x, w, *_ARGS) + b).dtype
    _close(got, ref_conv(x, w, (1, 1), [(1, 1), (1, 1)]) + b.astype(F32),
           jnp.bfloat16)


def test_bias_broadcasts_over_channels_first_outputs():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 4, 9, 9), F32)
    w = jnp.asarray(rng.randn(8, 4, 3, 3) * 0.1, F32)
    b = jnp.asarray(rng.randn(8), F32)
    got = conv_fast(x, w, (1, 1), [(1, 1), (1, 1)], (1, 1), (1, 1),
                    ("NCHW", "OIHW", "NCHW"), 1, bias=b)
    want = ref_conv(jnp.transpose(x, (0, 2, 3, 1)),
                    jnp.transpose(w, (2, 3, 1, 0)), (1, 1),
                    [(1, 1), (1, 1)]) + b
    _close(jnp.transpose(got, (0, 2, 3, 1)), want, F32)


def test_route_under_jit_and_vmap():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 2, 8, 8, 4), jnp.bfloat16)
    w = jnp.asarray(rng.randn(3, 3, 4, 4) * 0.1, jnp.bfloat16)

    @jax.jit
    def g(x, w):
        per = jax.vmap(lambda xi: conv_fast(xi, w, *_ARGS))(x)
        return jnp.sum(per.astype(F32) ** 2)

    def want(x, w):
        per = jnp.stack([ref_conv(xi, w, (1, 1), [(1, 1), (1, 1)])
                         for xi in x])
        return jnp.sum(per ** 2)

    val, (dx, dw) = jax.value_and_grad(g, argnums=(0, 1))(x, w)
    rval, (rx, rw) = jax.value_and_grad(want, argnums=(0, 1))(
        x.astype(F32), w.astype(F32))
    assert dx.shape == x.shape and dw.shape == w.shape
    np.testing.assert_allclose(float(val), float(rval), rtol=2e-2)
    _close(dx, rx, jnp.bfloat16)
    _close(dw, rw, jnp.bfloat16)


def test_the_route_is_one_xla_convolution():
    """No branch on the environment, no hand kernel: a convolution lowers
    to one ``stablehlo.convolution`` and nothing that calls out."""
    x = jnp.ones((1, 8, 8, 16), jnp.bfloat16)
    w = jnp.ones((3, 3, 16, 8), jnp.bfloat16)
    text = jax.jit(lambda x, w: conv_fast(x, w, *_ARGS)).lower(x, w).as_text()
    assert text.count("stablehlo.convolution") == 1
    assert "custom_call" not in text and "dot_general" not in text
