"""Neural-network ops: the FLOP-carrying layer of the framework.

Reference: src/operator/nn/* — each op is an (-inl.h, .cc, .cu) kernel triple with
cuDNN/MKL-DNN backends and an autotuned algo registry (cudnn_algoreg-inl.h).

TPU-native re-design: every op lowers to the XLA HLO that maps onto the MXU/VPU —
``lax.conv_general_dilated`` (MXU), ``lax.reduce_window`` (VPU), ``jax.nn.*`` — and
XLA's own autotuner/fusion replaces the cuDNN algo registry and MKL-DNN format
machinery. Layouts: the reference is NCHW-only; here every spatial op takes a
``layout`` attr and NHWC is preferred on TPU (channels-last vectorizes on the 8x128
VPU and feeds the MXU without transposes) while NCHW remains the API default for
reference parity — XLA inserts the transposes when needed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import autograd
from ..random import next_key
from .precision_util import dot_acc, mxu_precision
from .registry import register


def _pair(v, n=2):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    if len(v) == 1:
        return v * n
    return v


# ------------------------------------------------------------- dense / conv
@register("FullyConnected", aliases=("fully_connected",))
def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False,
                   flatten=True):
    """y = x W^T + b (ref: src/operator/nn/fully_connected.cc:239-328).

    Weight layout (num_hidden, in_units) matches the reference exactly so
    checkpoints are interchangeable. bf16 inputs run one-pass on the MXU
    with an f32 accumulator output cast back to bf16 in the epilogue —
    the measurably fastest v5e schedule (tools/perf_peak.py: 140 vs 102
    TFLOP/s for the bf16-out form) and exact accumulation for free; f32
    inputs get true-f32 contractions via the global
    jax_default_matmul_precision setting (mxtpu/__init__.py). See
    precision_util.dot_acc.
    """
    x = data
    if flatten and x.ndim > 2:
        x = jnp.reshape(x, (x.shape[0], -1))
    y = dot_acc(x, weight, (((x.ndim - 1,), (1,)), ((), ())))
    if bias is not None and not no_bias:
        y = y + bias
    return y


_LAYOUTS = {
    1: {"NCW": ("NCH", "OIH", "NCH"), "NWC": ("NHC", "HIO", "NHC")},
}


def _conv_dims(ndim, layout):
    """Dimension-number strings for lax.conv_general_dilated."""
    if ndim == 1:
        if layout in (None, "NCW"):
            return ("NCH", "OIH", "NCH")
        return ("NHC", "HIO", "NHC")
    if ndim == 2:
        if layout in (None, "NCHW"):
            return ("NCHW", "OIHW", "NCHW")
        return ("NHWC", "HWIO", "NHWC")
    if ndim == 3:
        if layout in (None, "NCDHW"):
            return ("NCDHW", "OIDHW", "NCDHW")
        return ("NDHWC", "DHWIO", "NDHWC")
    raise ValueError("unsupported conv ndim %d" % ndim)


def conv_fast(x, w, strides, padding, lhs_dilation, rhs_dilation, dims,
              groups, bias=None):
    """The one route every convolution takes: ``lax.conv_general_dilated``
    under the package precision policy, then the per-channel ``bias``
    (a [C_out] vector) as a broadcast add."""
    out = lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
        dimension_numbers=dims, feature_group_count=groups,
        precision=mxu_precision(x, w))
    if bias is None:
        return out
    if dims[2][-1] == "C":          # channels-last: trailing broadcast
        return out + bias
    return out + jnp.reshape(bias, (1, -1) + (1,) * (out.ndim - 2))


@register("Convolution", aliases=("convolution",))
def Convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False, layout=None,
                workspace=None, cudnn_tune=None, cudnn_off=None):
    """N-D convolution (ref: src/operator/nn/convolution.cc; CUDA path
    src/operator/nn/convolution.cu + cudnn wrappers). One HLO ConvGeneralDilated;
    grouped/depthwise via feature_group_count (the reference needed a dedicated
    TF-derived depthwise kernel, depthwise_convolution_tf.cuh — here it's the same
    HLO and XLA picks the kernel). Operands that are all bf16/f16 ask for
    one MXU pass (``precision_util.mxu_precision``), float32 ones keep the
    package's full-precision default; the output takes the operands'
    promoted dtype and the gradients are autodiff's."""
    ndim = data.ndim - 2
    kernel = _pair(kernel, ndim)
    stride = _pair(stride, ndim)
    dilate = _pair(dilate, ndim)
    pad = _pair(pad, ndim) if pad is not None else (0,) * ndim
    dims = _conv_dims(ndim, layout)
    return conv_fast(
        data, weight,
        strides=stride,
        padding=[(p, p) for p in pad],
        lhs_dilation=(1,) * ndim,
        rhs_dilation=dilate,
        dims=dims,
        groups=num_group,
        bias=bias if (bias is not None and not no_bias) else None,
    )


@register("Deconvolution", aliases=("deconvolution",))
def Deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, target_shape=None, num_filter=None, num_group=1,
                  no_bias=True, layout=None, workspace=None, cudnn_tune=None,
                  cudnn_off=None):
    """Transposed convolution (ref: src/operator/nn/deconvolution.cc). Implemented as
    the gradient of Convolution wrt data — lhs-dilated ConvGeneralDilated."""
    ndim = data.ndim - 2
    kernel = _pair(kernel, ndim)
    stride = _pair(stride, ndim)
    dilate = _pair(dilate, ndim)
    pad = _pair(pad, ndim) if pad is not None else (0,) * ndim
    adj = _pair(adj, ndim) if adj is not None else (0,) * ndim
    dims = _conv_dims(ndim, layout)
    channels_last = dims[0][-1] == "C"
    # weight layout (in, out/g, *k) per reference; flip spatial + swap io for transpose
    spatial_axes = tuple(range(2, 2 + ndim)) if not channels_last else tuple(range(0, ndim))
    if channels_last:
        w = jnp.flip(weight, axis=spatial_axes)
        w = jnp.swapaxes(w, -1, -2)
    else:
        w = jnp.flip(weight, axis=spatial_axes)
        w = jnp.swapaxes(w, 0, 1)
    padding = []
    for i in range(ndim):
        k = (kernel[i] - 1) * dilate[i]
        padding.append((k - pad[i], k - pad[i] + adj[i]))
    return conv_fast(
        data, w,
        strides=(1,) * ndim,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dims=dims,
        groups=num_group,
        bias=bias if (bias is not None and not no_bias) else None,
    )


# ------------------------------------------------------------------ pooling
@register("Pooling", aliases=("pooling",))
def Pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", count_include_pad=True,
            layout=None, cudnn_off=None, p_value=None):
    """Spatial pooling (ref: src/operator/nn/pooling.cc + pool.cuh hand kernels).
    One HLO ReduceWindow; 'full' (ceil) convention handled via asymmetric padding."""
    ndim = data.ndim - 2
    channels_last = layout is not None and layout.endswith("C")
    sp = tuple(range(1, 1 + ndim)) if channels_last else tuple(range(2, 2 + ndim))
    if global_pool:
        if pool_type == "max":
            return jnp.max(data, axis=sp, keepdims=True)
        if pool_type in ("avg", "sum"):
            r = jnp.mean if pool_type == "avg" else jnp.sum
            return r(data, axis=sp, keepdims=True)
        if pool_type == "lp":
            p = p_value or 2
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p), axis=sp, keepdims=True), 1.0 / p)
    kernel = _pair(kernel, ndim)
    stride = _pair(stride, ndim) if stride is not None else (1,) * ndim
    pad = _pair(pad, ndim) if pad is not None else (0,) * ndim

    window = [1] * data.ndim
    strides = [1] * data.ndim
    padding = [(0, 0)] * data.ndim
    for i, a in enumerate(sp):
        window[a] = kernel[i]
        strides[a] = stride[i]
        lo = hi = pad[i]
        if pooling_convention == "full":
            size = data.shape[a]
            out_sz = -(-(size + 2 * pad[i] - kernel[i]) // stride[i]) + 1  # ceil
            needed = (out_sz - 1) * stride[i] + kernel[i] - size - pad[i]
            hi = max(hi, needed)
        padding[a] = (lo, hi)

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max,
                                 window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0, lax.add,
                              window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for i in range(ndim):
                denom *= kernel[i]
            return s / denom
        ones = jnp.ones(data.shape, data.dtype)
        cnt = lax.reduce_window(ones, 0, lax.add,
                                window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        p = p_value or 2
        s = lax.reduce_window(jnp.power(jnp.abs(data), p), 0,
                              lax.add, window, strides, padding)
        return jnp.power(s, 1.0 / p)
    raise ValueError("unknown pool_type " + pool_type)


@register("UpSampling")
def UpSampling(*data, scale=1, sample_type="nearest", num_args=1, num_filter=0,
               multi_input_mode="concat", workspace=None):
    """Ref: src/operator/nn/upsampling.cc (nearest; bilinear via Deconvolution)."""
    x = data[0]
    n, c, h, w = x.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        return out
    # bilinear
    out = jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")
    return out


# ----------------------------------------------------------------- softmax
@register("softmax", aliases=("Softmax",), as_method=True)
def softmax(x, axis=-1, temperature=None, length=None, **_ig):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        mask = jnp.arange(x.shape[axis]) < jnp.expand_dims(length.astype(jnp.int32), -1)
        x = jnp.where(mask, x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax", as_method=True)
def log_softmax(x, axis=-1, temperature=None, **_ig):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


def log_softmax_at(data, label, axis=-1):
    """``log_softmax(data)[label]`` along ``axis`` (kept, at size 1) as
    ``data[label] - logsumexp(data)``: both terms reduced in float32 from one
    read of ``data``, the labelled element by a one-hot select. Spelled as
    ``pick(log_softmax(data), label)`` XLA writes the whole log-softmax array
    (and, for logits reshaped from [B, T, V], a relayout copy of them) to
    gather one element a row; this form's gradient, ``onehot - softmax``,
    fuses into whatever reads it."""
    from .. import telemetry
    telemetry.inc("loss.softmax_ce.one_pass")
    with jax.named_scope("softmax_ce"):
        idx = jnp.clip(label.astype(jnp.int32), 0, data.shape[axis] - 1)
        hit = (lax.broadcasted_iota(jnp.int32, data.shape, axis % data.ndim)
               == jnp.expand_dims(idx, axis))
        x = data.astype(jnp.float32)
        top = lax.stop_gradient(jnp.max(x, axis=axis, keepdims=True))
        lse = top + jnp.log(jnp.sum(jnp.exp(x - top), axis=axis, keepdims=True))
        at = jnp.sum(jnp.where(hit, x, 0.0), axis=axis, keepdims=True)
        return (at - lse).astype(data.dtype)


@register("_contrib_log_softmax_pick", aliases=("log_softmax_pick",))
def log_softmax_pick(data, label, axis=-1, keepdims=True):
    """``pick(log_softmax(data, axis), label, axis, keepdims)`` without the
    log-softmax array (``log_softmax_at``). ``label`` holds class ids (any
    numeric dtype, clipped to the axis as ``pick``'s ``mode="clip"``) in
    ``data``'s shape less ``axis``. float32 arithmetic whatever ``data``'s
    dtype, one rounding to it."""
    out = log_softmax_at(data, label, axis)
    return out if keepdims else jnp.squeeze(out, axis=axis)


@register("softmin")
def softmin(x, axis=-1, **_ig):
    return jax.nn.softmax(-x, axis=axis)


@register("SoftmaxActivation")
def SoftmaxActivation(x, mode="instance"):
    """Deprecated alias family (ref: src/operator/nn/softmax_activation.cc)."""
    if mode == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x, axis=-1)


@register("SoftmaxOutput", aliases=("softmax_output",))
def SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1.0,
                  multi_output=False, use_ignore=False, preserve_shape=False,
                  normalization="null", out_grad=False, smooth_alpha=0.0):
    """Softmax with implicit cross-entropy gradient (ref: src/operator/softmax_output.cc).

    Forward returns softmax(data); the custom vjp makes d(data) = (p - onehot(label))
    * grad_scale exactly as the reference's fused backward kernel, including
    ignore_label masking and batch/valid normalization.
    """
    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def _so(d, lab):
        return jax.nn.softmax(d, axis=axis)

    def _fwd(d, lab):
        p = jax.nn.softmax(d, axis=axis)
        return p, (p, lab)

    def _bwd(res, g):
        p, lab = res
        li = lab.astype(jnp.int32)
        nclass = p.shape[axis]
        oh = jax.nn.one_hot(li, nclass, axis=axis, dtype=p.dtype)
        if smooth_alpha:
            oh = oh * (1.0 - smooth_alpha) + smooth_alpha / (nclass - 1) * (1.0 - oh)
        grad = p - oh
        if use_ignore:
            valid = (lab != ignore_label).astype(p.dtype)
            grad = grad * jnp.expand_dims(valid, axis=axis)
        scale = grad_scale
        if normalization == "batch":
            scale = scale / lab.shape[0]
        elif normalization == "valid" and use_ignore:
            nvalid = jnp.maximum(jnp.sum(lab != ignore_label), 1)
            grad = grad / nvalid.astype(p.dtype)
        return (grad * scale, jnp.zeros_like(lab))

    _so.defvjp(_fwd, _bwd)
    return _so(data, label)


# ------------------------------------------------------------- activations
@register("Activation", aliases=("activation",))
def Activation(x, act_type="relu"):
    """Ref: src/operator/nn/activation.cc."""
    if act_type == "relu":
        return jnp.maximum(x, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return x / (1 + jnp.abs(x))
    if act_type == "silu":
        return jax.nn.silu(x)
    raise ValueError("unknown act_type " + act_type)


@register("LeakyReLU", wrap=False)
def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
              upper_bound=0.334):
    """Leaky/PReLU/ELU/SELU/RReLU family (ref: src/operator/leaky_relu.cc)."""
    from ..ndarray.ndarray import _apply
    if act_type == "rrelu":
        return _rrelu_apply(data, lower_bound, upper_bound)
    if act_type == "prelu":
        return _apply(lambda x, g: _leaky_impl(x, g, "prelu", slope), (data, gamma),
                      name="LeakyReLU")
    return _apply(lambda x: _leaky_impl(x, None, act_type, slope), (data,),
                  name="LeakyReLU")


def _leaky_impl(x, gamma, act_type, slope):
    if act_type == "leaky":
        return jnp.where(x > 0, x, slope * x)
    if act_type == "prelu":
        g = gamma
        if g.ndim < x.ndim and g.ndim == 1:
            g = jnp.reshape(g, (1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(x > 0, x, g * x)
    if act_type == "elu":
        return jnp.where(x > 0, x, slope * (jnp.exp(x) - 1))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x > 0, x, alpha * (jnp.exp(x) - 1))
    if act_type == "gelu":
        return jax.nn.gelu(x)
    raise ValueError("unknown act_type " + act_type)


def bn_batch_stats(xf, red):
    """(mean, var) over axes ``red``, as BatchNorm compiles them: E[x] and
    E[x^2] in one fused read, var clamped >= 0 (catastrophic-cancellation
    floor; BN's eps covers the residue)."""
    mean = jnp.mean(xf, axis=red)
    var = jnp.maximum(
        jnp.mean(jnp.square(xf), axis=red) - jnp.square(mean), 0.0)
    return mean, var


@register("BatchNorm", aliases=("batch_norm",), wrap=False)
def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
              fix_gamma=True, use_global_stats=False, output_mean_var=False,
              axis=1, cudnn_off=False):
    """Batch normalization (ref: src/operator/nn/batch_norm.cc).

    Pure-functional: in training mode normalizes by batch stats; the *layer*
    (gluon.nn.BatchNorm) owns the moving-stat update, mirroring how the reference
    mutates aux states inside the kernel while keeping XLA purity. Train/predict
    mode is resolved here at call time (see statefulness note above).
    """
    from ..ndarray.ndarray import _apply
    training = autograd.is_training() and not use_global_stats

    def fn(x, g_, b_, mm, mv):
        shape = [1] * x.ndim
        ax = axis % x.ndim
        shape[ax] = x.shape[ax]
        g = jnp.ones_like(g_) if fix_gamma else g_
        if training:
            red = tuple(i for i in range(x.ndim) if i != ax)
            mean, var = bn_batch_stats(x.astype(jnp.float32), red)
        else:
            mean, var = mm, mv
        inv = lax.rsqrt(var + eps)
        out = (x.astype(jnp.float32) - jnp.reshape(mean, shape)) \
            * jnp.reshape(inv * g.astype(jnp.float32), shape) \
            + jnp.reshape(b_.astype(jnp.float32), shape)
        out = out.astype(x.dtype)
        if output_mean_var:
            return out, mean, var
        return out

    return _apply(fn, (data, gamma, beta, moving_mean, moving_var), name="BatchNorm")


@register("Dropout", aliases=("dropout",), wrap=False)
def Dropout(data, p=0.5, mode="training", axes=(), cudnn_off=None):
    """Inverted dropout (ref: src/operator/nn/dropout.cc). Active only in autograd
    training mode (or mode='always'); RNG key drawn at call time (note above)."""
    from ..ndarray.ndarray import _apply
    if p <= 0 or (mode != "always" and not autograd.is_training()):
        return _apply(lambda x: x, (data,), name="identity")
    key = next_key()
    keep = 1.0 - p

    def fn(x):
        shape = list(x.shape)
        for a in axes or ():
            shape[a] = 1
        mask = jax.random.bernoulli(key, keep, tuple(shape))
        return jnp.where(mask, x / keep, jnp.zeros_like(x))

    return _apply(fn, (data,), name="Dropout")


# NOTE on statefulness: ops whose semantics depend on RNG or train/predict mode
# (Dropout, RReLU, BatchNorm batch-stats) resolve that state *at call time* in an
# unwrapped wrapper, then tape a pure closure. The tape re-executes the closure under
# jax.vjp during backward (recompute-based autograd), so anything resolved inside the
# closure would be re-resolved at backward time — a different dropout mask or the
# wrong BatchNorm branch. This mirrors the reference recording the resolved op state
# (FCreateOpState) on the tape, not the env that produced it.
@register("_rrelu_train", wrap=False)
def _rrelu_apply(data, lower_bound, upper_bound):
    from ..ndarray.ndarray import _apply
    if autograd.is_training():
        key = next_key()

        def fn(x):
            s = jax.random.uniform(key, x.shape, jnp.float32,
                                   lower_bound, upper_bound).astype(x.dtype)
            return jnp.where(x > 0, x, s * x)
    else:
        mid = (lower_bound + upper_bound) / 2.0

        def fn(x):
            return jnp.where(x > 0, x, mid * x)
    return _apply(fn, (data,), name="rrelu")


# ---------------------------------------------------------------- normalize
@register("LayerNorm", aliases=("layer_norm",))
def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """Ref: src/operator/nn/layer_norm.cc. f32 statistics even for bf16 inputs."""
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.var(x32, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    out = ((x32 - mean) * inv).astype(data.dtype)
    shape = [1] * data.ndim
    ax = axis % data.ndim
    shape[ax] = data.shape[ax]
    out = out * jnp.reshape(gamma, shape) + jnp.reshape(beta, shape)
    if output_mean_var:
        return [out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)]
    return out


@register("RMSNorm", aliases=("rms_norm",))
def RMSNorm(data, gamma, axis=-1, eps=1e-6, zero_centered=False):
    """x / sqrt(mean(x^2) + eps) * gamma (Zhang & Sennrich,
    arXiv:1910.07467). No reference counterpart. f32 statistics even for
    bf16 inputs, as :func:`LayerNorm`. ``zero_centered``: the scale is ``1
    + gamma`` (the leaf is the scale's distance from one, which weight
    decay then pulls to a scale of 1 and not of 0), applied in float32
    before the one rounding to ``data``'s dtype."""
    x32 = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(x32), axis=axis, keepdims=True) + eps)
    shape = [1] * data.ndim
    ax = axis % data.ndim
    shape[ax] = data.shape[ax]
    if zero_centered:
        return (x32 * inv * (1.0 + jnp.reshape(gamma, shape).astype(
            jnp.float32))).astype(data.dtype)
    return (x32 * inv).astype(data.dtype) * jnp.reshape(gamma, shape)


def yarn_inv_freq(theta, width, factor, original_max_position_embeddings,
                  beta_fast=32.0, beta_slow=1.0):
    """YaRN's frequency table (Peng et al., arXiv:2309.00071, as
    transformers computes it) for ``width`` turned entries: of pair ``i``,
    ``e_i = theta^(-2i/width)`` where it turns more than ``beta_fast``
    times over the original context (kept), ``e_i / factor`` where it
    turns fewer than ``beta_slow`` times (interpolated), a linear ramp
    between the two pairs ``low = floor(c(beta_fast))`` and ``high =
    ceil(c(beta_slow))``, ``c(b) = width ln(L / (2 pi b)) / (2 ln theta)``.
    float64 on the host -> float32 [width / 2]."""
    import numpy as np
    half = width // 2
    e = float(theta) ** (-np.arange(half, dtype=np.float64) * 2.0 / width)

    def pair_turning(times):
        return width * np.log(original_max_position_embeddings
                              / (times * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(pair_turning(beta_fast)), 0)
    high = min(np.ceil(pair_turning(beta_slow)), width - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    return (e / factor * ramp + e * (1 - ramp)).astype(np.float32)


def _rotary_angles(t, turned, theta, scaling):
    """``pos * freq`` [T, turned / 2] float32 and the gain on cos and sin
    (None for 1): the plain table, or YaRN's from ``scaling``'s keys."""
    half = turned // 2
    pos = jnp.arange(t, dtype=jnp.float32)
    if scaling is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / turned)
        gain = None
    else:
        freq = jnp.asarray(yarn_inv_freq(
            theta, turned, scaling["factor"],
            scaling["original_max_position_embeddings"],
            scaling.get("beta_fast", 32.0), scaling.get("beta_slow", 1.0)))
        gain = scaling.get("attention_factor") or (
            0.1 * math.log(scaling["factor"]) + 1.0)
    return pos[:, None] * freq[None, :], gain


def _rotary_paired(ang, interleave):
    """[T, turned / 2] -> [T, turned]: each pair's angle at both of its
    entries."""
    if interleave:
        return jnp.repeat(ang, 2, axis=-1)
    return jnp.concatenate([ang, ang], axis=-1)


def _rotary_table(fn, ang, gain, rest, still):
    """cos or sin (``fn``) of ``ang`` [T, turned] -> [T, turned + rest]:
    the ``rest`` entries past the turned width stand ``still`` (cos 1, sin
    0)."""
    t = fn(ang) if gain is None else fn(ang) * gain
    if rest > 0:
        t = jnp.pad(t, [(0, 0), (0, rest)], constant_values=still)
    return t


def rotary(x, theta=10000.0, interleave=False, seq_axis=1, width=0,
           scaling=None):
    """Rotary position embedding (Su et al., arXiv:2104.09864) over the
    last axis of ``x``, positions 0..T-1 along ``seq_axis``. Pair i
    turns by ``pos * theta^(-2i/D)``; ``interleave`` pairs the entries
    (2i, 2i+1), otherwise (i, i + D/2). The partner of each entry comes
    from a product with a constant signed permutation (exact in any
    dtype: one non-zero a column), so no array with a minor axis of 2 is
    ever laid out on the TPU's 128 lanes. Angles and the result's
    arithmetic are float32; the result has ``x``'s dtype.

    ``width = R > 0``: only the first ``R`` entries turn (``D`` is then
    ``R`` in the above: a partial rotary, ``partial_rotary_factor = R /
    D``); the rest pass as they are (cos 1, sin 0, no partner: bit for
    bit). ``scaling``: the keys of a ``rope_type: yarn`` group (``factor``,
    ``original_max_position_embeddings``, and optionally ``beta_fast``,
    ``beta_slow``, ``attention_factor``): the table is
    :func:`yarn_inv_freq`'s and cos and sin of the turned entries are
    multiplied by ``attention_factor`` (``0.1 ln(factor) + 1`` unless
    given). Scopes ``rotary`` / ``rotary_yarn``.

    This is the definition and XLA's path. The grouped operators (:func:`grouped_attention`,
    :func:`sparse_grouped_attention`) ask for the result heads first
    through :func:`rotary_heads_first`, which on the TPU takes the Pallas
    pair of :mod:`mxtpu.ops.pallas.rotary` where it can (the turn in place,
    one pass each way) and says which in ``rotary.calls`` / ``.pallas`` /
    ``.xla``; a
    call of this function itself (latent attention's 64-wide turn, the
    registered operator) is the plain path always and counts nowhere."""
    d = x.shape[-1]
    turned = width or d
    half = turned // 2
    if scaling is not None:
        from .. import telemetry
        telemetry.inc("rotary.scaled")
    ang, gain = _rotary_angles(x.shape[seq_axis], turned, theta, scaling)
    with jax.named_scope("rotary" if scaling is None else "rotary_yarn"):
        i = jnp.arange(half)
        ang = _rotary_paired(ang, interleave)
        lo, hi = (2 * i, 2 * i + 1) if interleave else (i, i + half)
        # partner[lo] = -x[hi], partner[hi] = x[lo]
        swap = jnp.zeros((d, d), x.dtype).at[hi, lo].set(-1).at[lo, hi].set(1)
        partner = jnp.matmul(x, swap, precision=mxu_precision(x, swap))
        shape = [1] * x.ndim
        shape[seq_axis % x.ndim], shape[-1] = x.shape[seq_axis], d
        cos = _rotary_table(jnp.cos, ang, gain, d - turned, 1.0).reshape(shape)
        sin = _rotary_table(jnp.sin, ang, gain, d - turned, 0.0).reshape(shape)
        return (x.astype(jnp.float32) * cos
                + partner.astype(jnp.float32) * sin).astype(x.dtype)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("form", "back", "interpret"))
def _rotary_kernel_pass(x, *, form, back, interpret):
    """One pass of the Pallas pair with its tables: ``x`` [B, T, H, D] ->
    [B, H, T, D], transposed by XLA and turned in place by ``rotary_turn``,
    or (``back``) a cotangent [B, H, T, D] through ``rotary_unturn`` and
    back to [B, T, H, D]. Jitted (and inlined where it is called) so that a
    step traces the tables and the kernel once a shape and form, not once a
    layer (``parallel/moe.py:_gathered_sum``)."""
    from .pallas import rotary as kernels
    theta, interleave, width, scaling = form
    t, d = x.shape[2 if back else 1], x.shape[-1]
    turned = width or d
    ang, gain = _rotary_angles(t, turned, theta,
                               dict(scaling) if scaling else None)
    ang = _rotary_paired(ang, interleave)
    cos = _rotary_table(jnp.cos, ang, gain, d - turned, 1.0)
    sin = _rotary_table(jnp.sin, ang, gain, d - turned, 0.0)
    swap = kernels.swap_matrix(d, width, interleave, x.dtype)
    return (kernels.unturn if back else kernels.turn)(
        x, cos, sin, swap, interpret=interpret)


# nothing is kept for the transpose: the tables are positions alone
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _rotary_kernels(x, form, interpret):
    return _rotary_kernel_pass(x, form=form, back=False, interpret=interpret)


_rotary_kernels.defvjp(
    lambda x, form, interpret: (_rotary_kernels(x, form, interpret), None),
    lambda form, interpret, _, g: (_rotary_kernel_pass(
        g, form=form, back=True, interpret=interpret),))


def _rotary_by_kernels(x, theta=10000.0, interleave=False, width=0,
                       scaling=None):
    """``rotary(x, ...)`` of ``x`` [B, T, H, D] laid heads first, [B, H,
    T, D], through the Pallas pair ``rotary_turn`` / ``rotary_unturn``, or
    None where the call cannot take it
    (:func:`mxtpu.ops.pallas.rotary.refusal`). Counted at trace time:
    ``rotary.calls``, then ``rotary.pallas`` or ``rotary.xla`` by reason
    (``platform`` / ``dtype`` / ``lanes`` / ``width``)."""
    from .. import telemetry
    from .pallas import rotary as kernels
    telemetry.inc("rotary.calls")
    reason = kernels.refusal(x, width)
    if reason is not None:
        telemetry.inc("rotary.xla", tag=reason)
        return None
    telemetry.inc("rotary.pallas")
    if scaling is not None:
        telemetry.inc("rotary.scaled")
    form = (float(theta), bool(interleave), int(width),
            tuple(sorted(scaling.items())) if scaling else None)
    with jax.named_scope("rotary" if scaling is None else "rotary_yarn"):
        return _rotary_kernels(x, form, kernels._fa._interpret())


def rotary_heads_first(x, theta=10000.0, interleave=False, width=0,
                       scaling=None):
    """``rotary(x, ...)`` of ``x`` [B, T, H, D] (positions along axis 1)
    laid heads first, [B, H, T, D], as the flash kernels take it. Where
    the call can take them (a TPU, bf16 or float32, heads of whole lane
    widths, an even turned width inside the head) XLA moves ``x`` heads
    first (inside whatever fusion produced it, a per-head norm's scaling in
    the models here) and the Pallas pair ``rotary_turn`` / ``rotary_unturn``
    turns that array in place, one read and one write each way, with the
    same numbers bit for bit; any other call is :func:`rotary` and a
    transposition, as XLA fuses them, and is counted by its reason in
    ``rotary.xla``: 0 is the number to expect there where heads are 128
    wide."""
    turned = _rotary_by_kernels(x, theta, interleave, width, scaling)
    if turned is None:
        turned = rotary(x, theta, interleave, 1, width,
                        scaling).transpose(0, 2, 1, 3)
    return turned


register("_contrib_rotary_embedding", aliases=("rotary_embedding",))(rotary)


@register("_contrib_latent_attention", aliases=("latent_attention",))
def latent_attention(q, kv, k_rope, num_heads=1, nope_dim=128, rope_dim=64,
                     v_dim=128, rope_theta=10000.0, rope_interleave=False,
                     causal=True):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
    after its projections: ``q`` [B, T, H*(nope+rope)], ``kv`` [B, T,
    H*(nope+v)] (the up-projected latent: each head's position-free key
    and its value) and ``k_rope`` [B, T, rope], the ONE rotary key all
    heads share. Rotary turns q's last ``rope`` entries and ``k_rope``;
    each head's key is its position-free part with the shared rotary part
    appended, so keys and queries are ``nope + rope`` wide and values
    ``v_dim``: both flash kernels take the two widths. Scores are scaled
    by ``1/sqrt(nope + rope)``. Returns [B, T, H*v_dim]."""
    from .pallas import flash_attention
    b, t = q.shape[:2]
    h = num_heads
    with jax.named_scope("mla_attention"):
        q = q.reshape(b, t, h, nope_dim + rope_dim)
        q = jnp.concatenate(
            [q[..., :nope_dim],
             rotary(q[..., nope_dim:], rope_theta, rope_interleave)], -1)
        kv = kv.reshape(b, t, h, nope_dim + v_dim)
        k_r = rotary(k_rope, rope_theta, rope_interleave)[:, :, None, :]
        k = jnp.concatenate(
            [kv[..., :nope_dim],
             jnp.broadcast_to(k_r, (b, t, h, rope_dim))], -1)
        out = flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              kv[..., nope_dim:].transpose(0, 2, 1, 3),
                              causal)                        # [B, H, T, v]
        return out.transpose(0, 2, 1, 3).reshape(b, t, h * v_dim)


@register("_contrib_grouped_attention", aliases=("grouped_attention",))
def grouped_attention(q, k, v, rope_theta=10000.0, window=0, rope=True,
                      rotary_dim=0, rope_scaling=None):
    """Causal grouped-query attention (Ainslie et al., arXiv:2305.13245)
    after its projections and per-head norms: ``q`` [B, T, H, D], ``k`` [B,
    T, H_kv, D] and ``v`` [B, T, H_kv * D] (or [B, T, H_kv, D]), ``H`` a
    multiple of ``H_kv``; query head ``j`` reads key/value head ``j // (H /
    H_kv)``. The mask: key ``j`` is visible to query ``i`` iff ``j <= i``,
    and with ``window = W > 0`` iff ``i - W < j <= i`` (a sliding window of
    ``W`` keys, the query's own among them: transformers' convention).
    Rotary (halves rotated) turns q and k: over the whole head, or over the
    first ``rotary_dim`` entries alone, and by ``rope_scaling``'s table
    where one is given (:func:`rotary`'s ``width`` and ``scaling``); with
    ``rope=False`` nothing turns and nothing is added: the layer carries no
    position encoding (the global layers of a window / global stack, whose
    only order is the mask's). Both flash kernels take K and V at their
    ``H_kv`` heads and fetch them by group, so no copy of them at the query
    heads exists. Scores are scaled by ``1/sqrt(D)``. Returns [B, T, H *
    D]. The call sits under the scope ``window_attention`` with a window,
    ``gqa_attention`` without."""
    from .pallas import flash_attention
    turn = {"theta": rope_theta, "width": rotary_dim,
            "scaling": rope_scaling} if rope else None
    with jax.named_scope("window_attention" if window else "gqa_attention"):
        return _grouped_heads(
            q, k, v, turn,
            lambda *qkv: flash_attention(*qkv, True, window=window))


def _grouped_heads(q, k, v, turn, attend):
    """What the grouped operators share around their kernels: rotary
    (``turn``: :func:`rotary`'s keyword arguments, or None for none),
    heads first, ``attend`` on q [B, H, T, D] and k, v [B, H_kv, T, D],
    and back to [B, T, H * D]."""
    b, t, h, _ = q.shape
    # :func:`rotary_heads_first` of q and of k (one choice: they differ in
    # heads alone), the plain path's operations in the order they have
    # always had in a step's text
    turned = None
    if turn is not None:
        turned = [_rotary_by_kernels(x, **turn) for x in (q, k)]
        if turned[0] is None:
            turned = None
            q, k = rotary(q, **turn), rotary(k, **turn)
    v = v.reshape(b, t, k.shape[2], -1)
    q, k = turned or (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3))
    out = attend(q, k, v.transpose(0, 2, 1, 3))                 # [B, H, T, D]
    return out.transpose(0, 2, 1, 3).reshape(b, t, h * v.shape[-1])


def _kth_largest(u, k, axis):
    """The ``k``-th largest of the uint32 keys ``u`` along ``axis`` (kept),
    exactly, from the top bits down: the largest ``v`` that at least ``k``
    entries reach. Two bits a pass (the three candidates ``v | m << s``
    are counted in one reading of ``u``): 16 counting passes, no sort."""
    shape = list(u.shape)
    shape[axis] = 1
    marks = jnp.arange(1, 4, dtype=jnp.uint32).reshape([3] + [1] * u.ndim)

    def two_bits(i, v):
        cand = v[None] | (marks << (30 - 2 * i).astype(jnp.uint32))
        reach = jnp.sum((u[None] >= cand).astype(jnp.int32), axis=axis + 1,
                        keepdims=True)
        # the counts fall as the candidate grows: the largest that k reach
        best = jnp.sum((reach >= k).astype(jnp.uint32), axis=0)
        return v | (best << (30 - 2 * i).astype(jnp.uint32))

    return lax.fori_loop(0, 16, two_bits, jnp.zeros(shape, jnp.uint32))


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    flipped = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(flipped, jnp.uint32) ^ jnp.uint32(1 << 31)


def _index_score(q_idx, w_idx, k_idx):
    """``I^T[s, t] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, KEYS first:
    float32 [B, Tk, rows] from ``q_idx`` [B, rows, Hi, Di], ``w_idx`` [B,
    rows, Hi] and ``k_idx`` [B, Tk, Di], one index head at a time."""
    def head(score, a):
        qh, wh = a
        s = jnp.einsum("bsd,bqd->bsq", k_idx, qh,
                       precision=lax.Precision.HIGHEST)
        return score + wh[:, None, :] * jax.nn.relu(s), None

    zeros = jnp.zeros(k_idx.shape[:2] + q_idx.shape[1:2], jnp.float32)
    return lax.scan(head, zeros, (jnp.moveaxis(q_idx, 2, 0),
                                  jnp.moveaxis(w_idx, 2, 0)))[0]


def _select_block(q_idx, w_idx, k_idx, at, topk):
    """The sets of ``rows`` queries from position ``at`` on, against the
    keys ``0 .. at + rows``: int8 [B, at + rows, rows], keys first."""
    b, rows = q_idx.shape[:2]
    tk = k_idx.shape[1]
    causal = (jnp.arange(tk)[:, None] <= at + jnp.arange(rows)[None, :])[None]
    if tk <= topk:                 # every query's set is all it can see
        return jnp.broadcast_to(causal, (b, tk, rows)).astype(jnp.int8)
    score = _index_score(q_idx, w_idx, k_idx)
    # equal scores are one score (-0.0 is 0.0), a key ahead has none
    score = jnp.where(score == 0, 0.0, score)
    u = _ordered_bits(jnp.where(causal, score, -jnp.inf))
    edge = _kth_largest(u, topk, 1)          # the set's smallest score
    reach = jnp.sum((u >= edge).astype(jnp.int32), axis=1, keepdims=True)

    def level_fits():              # every key level with an edge is in
        return causal & (u >= edge)

    def lowest_first():
        # of the keys level with the edge, the lowest positions fill the
        # set (and a query that sees fewer than topk keys keeps them all)
        above, level = u > edge, u == edge
        room = topk - jnp.sum(above.astype(jnp.int32), axis=1, keepdims=True)
        first = jnp.cumsum(level.astype(jnp.int32), axis=1) <= room
        return causal & (above | (level & first))

    # a running count down 16,384 keys is a quarter of the block's time and
    # decides something only where scores tie across a set's edge
    return lax.cond(jnp.all(reach == topk), level_fits,
                    lowest_first).astype(jnp.int8)


# queries a block of the selection: 2,048 against 16,384 keys are 134 MB
_SELECT_BLOCK = 2048


@register("_contrib_index_select")
def index_select(data, q_weight, k_weight, w_weight, num_heads=16, topk=2048):
    """The selection of DeepSeek-Sparse-Attention's indexer
    (DeepSeek-V3.2-Exp report, eq. 1-2), without position encoding or norm:
    from ``data`` [B, T, d], ``qI = data WqI^T`` as [B, T, Hi, Di], ``kI =
    data WkI^T`` [B, T, Di] and ``w = data WwI^T`` [B, T, Hi], all three
    products in float32 from the operands as they are stored; the score of
    key ``s`` for query ``t`` is ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
    kI[s])``, float32; query ``t``'s set is the ``min(t + 1, topk)`` keys
    ``s <= t`` of largest score, of equal scores the lower ``s``
    (``jax.lax.top_k``'s order). Exact, and causal by construction: a key
    ahead of the query is never chosen, whatever its score.

    Returns the sets as int8 [B, T, T] with KEYS FIRST ([b, s, t] = 1 iff
    key ``s`` is in query ``t``'s set): what
    :func:`~mxtpu.ops.pallas.flash_attention.sparse_attention` reads,
    forward and backward. Computed ``_SELECT_BLOCK`` queries at a time
    against the keys up to the block's end, so no [T, T] float32 array is
    alive whole; the edge of a set is found by counting, two bits a pass,
    not by a sort. Takes no gradient: the sets are integers, and the three
    weights are leaves no loss of this program moves."""
    b, t, _ = data.shape
    with jax.named_scope("index_select"):
        x = lax.stop_gradient(data).astype(jnp.float32)

        def proj(w):
            return jnp.einsum("btd,od->bto", x, w.astype(jnp.float32),
                              precision=lax.Precision.HIGHEST)

        q_idx = proj(q_weight).reshape(b, t, num_heads, -1)
        k_idx, w_idx = proj(k_weight), proj(w_weight)
        rows = _SELECT_BLOCK if t % _SELECT_BLOCK == 0 else t
        parts = []
        for at in range(0, t, rows):
            part = _select_block(q_idx[:, at:at + rows],
                                 w_idx[:, at:at + rows],
                                 k_idx[:, :at + rows], at, topk)
            parts.append(jnp.pad(part, [(0, 0), (0, t - at - rows), (0, 0)]))
        return jnp.concatenate(parts, axis=2)


@register("_contrib_sparse_attention")
def sparse_grouped_attention(q, k, v, selected, rope_theta=10000.0, topk=0):
    """:func:`grouped_attention` over the keys each query selected
    (``selected``: :func:`index_select`'s int8 [B, T, T], keys first; one
    set a query, shared by all heads): ``q`` [B, T, H, D], ``k`` [B, T,
    H_kv, D], ``v`` [B, T, H_kv * D]; rotary over the whole head turns q
    and k; the softmax runs over the set alone. ``topk`` is what built the
    sets: with ``topk >= T`` every set is all the query can see and the
    call IS the causal call of ``grouped_attention`` (``selected`` unread,
    counted under ``pallas_flash.*``). Returns [B, T, H * D]; scope
    ``sparse_attention``."""
    from .. import telemetry
    from .pallas.flash_attention import flash_attention, sparse_attention
    def attend(*qkv):
        if topk >= q.shape[1]:
            return flash_attention(*qkv, True)
        return sparse_attention(*qkv, selected, topk=topk)

    with telemetry.span("sparse_attention.trace"), \
            jax.named_scope("sparse_attention"):
        return _grouped_heads(q, k, v, {"theta": rope_theta}, attend)


def _causal_taps(z, w):
    """``c[t] = sum_j w[:, j] z[t - (taps - 1) + j]`` along axis -2, ``z``
    zero before the start (float32 ``z`` [..., T, D], ``w`` [D, taps]):
    shifted multiply-adds, which XLA fuses with what surrounds them into
    passes over [B, T, D] (no grouped convolution: PERF.md §6)."""
    taps = w.shape[1]
    t = z.shape[-2]
    c = w[:, taps - 1] * z
    for j in range(taps - 1):
        back = taps - 1 - j
        pad = [(0, 0)] * (z.ndim - 2) + [(back, 0), (0, 0)]
        c = c + w[:, j] * jnp.pad(z, pad)[..., :t, :]
    return c


def _short_conv_plain(data, weight):
    d = weight.shape[0]
    f32 = jnp.float32
    gate_in, gate_out, x = (data[..., i * d:(i + 1) * d].astype(f32)
                            for i in range(3))
    w = weight.astype(f32)
    return (gate_out * _causal_taps(gate_in * x, w)).astype(data.dtype)


@jax.custom_vjp
def _short_conv(data, weight):
    return _short_conv_plain(data, weight)


# the backward computes the gated products again from the projected input:
# kept, they would be five float32 [B, T, D] arrays a layer
_short_conv.defvjp(
    lambda data, weight: (_short_conv_plain(data, weight), (data, weight)),
    lambda kept, g: jax.vjp(_short_conv_plain, *kept)[1](g))


@register("_contrib_short_conv", aliases=("short_conv",))
def short_conv(data, weight):
    """The gated short convolution of LFM2 (Liquid AI, ``model_type:
    lfm2``) after its input projection: ``data`` [..., T, 3D] is ``(B | C |
    x)``, ``weight`` [D, L] a depthwise causal filter of ``L`` taps;
    ``y = C * conv_L(B * x)`` with ``conv_L(z)[t] = sum_j weight[:, j]
    z[t - (L - 1) + j]`` and ``z`` zero before the sequence's start
    (``torch.nn.Conv1d(D, D, L, groups=D, padding=L - 1)`` cut to T). No
    activation, no bias. float32 arithmetic, one rounding to ``data``'s
    dtype. Returns [..., T, D]."""
    from .. import telemetry
    telemetry.inc("short_conv.layers")
    with jax.named_scope("short_conv"):
        return _short_conv(data, weight)


def _kda_conv_plain(head_dim, data, weight):
    f32 = jnp.float32
    y = jax.nn.silu(_causal_taps(data.astype(f32), weight.astype(f32)))
    if head_dim:
        heads = y.reshape(y.shape[:-1] + (-1, head_dim))
        y = (heads * lax.rsqrt(jnp.sum(jnp.square(heads), -1, keepdims=True)
                               + 1e-6)).reshape(y.shape)
    return y.astype(data.dtype)


def _kda_conv_kernels(head_dim, data, weight):
    """The Pallas pair of ``ops/pallas/short_filter.py`` if this call can
    take it, else None; counted at trace time, a pass (the value, or the
    two gradients) a count: ``kda_conv.calls``, then ``kda_conv.pallas`` or
    ``kda_conv.xla`` by reason (``platform`` / ``dtype`` / ``lanes`` /
    ``taps``). 0 is the number to expect of the latter in a timed
    program."""
    from .. import telemetry
    from .pallas import short_filter
    telemetry.inc("kda_conv.calls")
    reason = short_filter.refusal(data, weight, head_dim)
    if reason is None:
        telemetry.inc("kda_conv.pallas")
        return short_filter
    telemetry.inc("kda_conv.xla", tag=reason)
    return None


def _kda_conv_value(head_dim, data, weight):
    kernels = _kda_conv_kernels(head_dim, data, weight)
    if kernels is None:
        return _kda_conv_plain(head_dim, data, weight)
    return kernels.forward(data, weight, head_dim)


_kda_conv = jax.custom_vjp(_kda_conv_value, nondiff_argnums=(0,))


def _kda_conv_bwd(head_dim, kept, g):
    kernels = _kda_conv_kernels(head_dim, *kept)
    if kernels is None:
        return jax.vjp(functools.partial(_kda_conv_plain, head_dim),
                       *kept)[1](g)
    return kernels.backward(*kept, g, head_dim)


# as the gated filter's: the backward filters the projected input again
# (inside ``kda_conv_bwd``, in VMEM, where the kernels run)
_kda_conv.defvjp(
    lambda head_dim, data, weight: (
        _kda_conv_value(head_dim, data, weight), (data, weight)),
    _kda_conv_bwd)


@register("_contrib_kda_conv", aliases=("kda_conv",))
def kda_conv(data, weight, head_dim=0):
    """The short filter of a Kimi-Delta-Attention layer (Kimi Linear,
    arXiv:2510.26692 §4) on one of its projections: ``silu(conv_L(data))``
    with ``conv_L`` the depthwise causal filter of :func:`short_conv`
    (``weight`` [D, L]; the same tap loop), ``data`` [..., T, D]; with
    ``head_dim`` > 0 each head's ``head_dim`` entries are then divided by
    ``sqrt(sum of their squares + 1e-6)`` (the layer's L2 norm of q and
    k), in the same pass. float32 arithmetic, one rounding to ``data``'s
    dtype; the backward computes it again from ``data``.
    :func:`_kda_conv_plain` is the definition; on the TPU the Pallas pair
    ``kda_conv_fwd`` / ``kda_conv_bwd`` (:mod:`mxtpu.ops.pallas.short_filter`)
    computes it, one kernel call a pass, counted at trace time in
    ``kda_conv.calls`` / ``.pallas`` / ``.xla`` (by reason: a call left to
    the plain function). Scope ``kda_conv``."""
    with jax.named_scope("kda_conv"):
        return _kda_conv(head_dim, data, weight)


@register("_contrib_kda_gate", aliases=("kda_gate",))
def kda_gate(data, weight, a_log, dt_bias, lower_bound=-5.0):
    """The log-decay a channel of a Kimi-Delta-Attention layer in its
    bounded form (flash-linear-attention's ``fla/ops/kda`` with
    ``lower_bound``: the "safe gate"): ``g = lower_bound * sigmoid(exp(
    a_log[h]) * (data weight^T + dt_bias))``, in ``(lower_bound, 0)``.
    ``data`` [..., D] (the layer's normed input), ``weight`` [H * K, D]
    (the decay's projection, no bias), ``a_log`` [H], ``dt_bias`` [H * K].
    The product takes its operands as they are stored and leaves a float32
    result that is never rounded: ``exp(a_log)`` reaches 16 and the
    sigmoid's slope carries a bf16 step of the product into ``g``, whose
    sums the chunked kernels exponentiate. Everything after it is float32.
    The kernels' exponents rest on the bound. Returns float32 [..., H * K];
    scope ``kda_gate``."""
    f32 = jnp.float32
    with jax.named_scope("kda_gate"):
        f = jnp.einsum("...d,cd->...c", data, weight,
                       precision=mxu_precision(data, weight),
                       preferred_element_type=f32)
        rate = jnp.repeat(jnp.exp(a_log.astype(f32)),
                          weight.shape[0] // a_log.shape[0])
        return lower_bound * jax.nn.sigmoid(rate * (f + dt_bias.astype(f32)))


@register("_contrib_kda_attention", aliases=("kda_attention",))
def kda_attention(q, k, v, g, beta, chunk=64):
    """Kimi Delta Attention after its projections, filters and gates: by
    head, ``S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t
    v_t^T`` from ``S_0 = 0`` (float32, [K, V]) and ``o_t = S_t^T q_t /
    sqrt(K)``. ``q``, ``k`` [B, T, H * K] (L2-normed by head), ``v`` [B, T,
    H * V], ``g`` [B, T, H * K] float32 in [-5, 0) (:func:`kda_gate`),
    ``beta`` [B, T, H]. By chunks of ``chunk`` tokens through the Pallas
    pair ``kda_fwd`` / ``kda_bwd`` (:mod:`mxtpu.ops.pallas.kda`: the chunk's
    WY factors, the state carried in VMEM); off the TPU the same chunks on
    a plain path, counted in ``kda_attention.fallbacks``. Returns [B, T, H
    * V]; scope ``kda_attention``, span ``kda_attention.trace``."""
    from .. import telemetry
    from .pallas.kda import kda_attention as attend
    with telemetry.span("kda_attention.trace"), \
            jax.named_scope("kda_attention"):
        return attend(q, k, v, g.astype(jnp.float32), beta, chunk)


@register("_contrib_gdn_gate", aliases=("gdn_gate",))
def gdn_gate(data, weight, a_log, dt_bias):
    """The log-decay a head of a Gated-DeltaNet layer (Yang et al.,
    arXiv:2412.06464, Mamba-2's parametrisation): ``g = -exp(a_log) *
    softplus(data weight^T + dt_bias)``, <= 0 and unbounded below (``exp(
    a_log)`` reaches 16 under the usual initialiser, and ``g`` -20 a
    token). ``data`` [..., D] (the layer's normed input), ``weight`` [H, D]
    (no bias), ``a_log`` and ``dt_bias`` [H]. As :func:`kda_gate`, the
    product takes its operands as they are stored and leaves a float32
    result that is never rounded, and everything after it is float32.
    Returns float32 [..., H]; scope ``gdn_gate``."""
    f32 = jnp.float32
    with jax.named_scope("gdn_gate"):
        f = jnp.einsum("...d,hd->...h", data, weight,
                       precision=mxu_precision(data, weight),
                       preferred_element_type=f32)
        return -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            f + dt_bias.astype(f32))


@register("_contrib_gated_delta_rule", aliases=("gated_delta_rule",))
def gated_delta_rule(q, k, v, g, beta, key_heads=1, chunk=64):
    """Gated DeltaNet after its projections, filters and gates: by value
    head, ``S' = exp(g_t) S_{t-1}; S_t = S' + k_t (b_t (v_t - S'^T
    k_t))^T`` from ``S_0 = 0`` (float32, [K, V]) and ``o_t = S_t^T q_t /
    sqrt(K)``. ``q``, ``k`` [B, T, key_heads * K] (L2-normed by head),
    ``v`` [B, T, H * V], ``g`` [B, T, H] float32 (:func:`gdn_gate`: one
    decay a head, no bound), ``beta`` [B, T, H]; value head ``j`` reads key
    head ``j // (H / key_heads)``. By chunks of ``chunk`` tokens through
    the Pallas pair ``gdn_fwd`` / ``gdn_bwd`` (:mod:`mxtpu.ops.pallas.kda`,
    the chunk plan of :func:`kda_attention`'s pair with the decay as a [C,
    C] matrix of differences); off the TPU the same chunks on a plain
    path, counted in ``gated_delta.fallbacks``. Returns [B, T, H * V];
    scope ``gated_delta_rule``, span ``gated_delta.trace``."""
    from .. import telemetry
    from .pallas.kda import gated_delta_rule as rule
    with telemetry.span("gated_delta.trace"), \
            jax.named_scope("gated_delta_rule"):
        return rule(q, k, v, g.astype(jnp.float32), beta, key_heads, chunk)


@register("InstanceNorm")
def InstanceNorm(data, gamma, beta, eps=1e-3):
    """Ref: src/operator/instance_norm.cc (NCHW; normalize over spatial dims)."""
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * jnp.reshape(gamma, shape) + jnp.reshape(beta, shape)


@register("LRN")
def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response normalization over channels (ref: src/operator/nn/lrn.cc)."""
    sq = jnp.square(data)
    half = nsize // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2)
    sq = jnp.pad(sq, pad)
    window = [1, nsize] + [1] * (data.ndim - 2)
    s = lax.reduce_window(sq, 0, lax.add,
                          window, [1] * data.ndim, [(0, 0)] * data.ndim)
    return data / jnp.power(knorm + alpha / nsize * s, beta)


# ------------------------------------------------------------ regression/heads
@register("LinearRegressionOutput", aliases=("linear_regression_output",))
def LinearRegressionOutput(data, label, grad_scale=1.0):
    """Identity forward, (pred-label)*scale backward (ref: src/operator/regression_output.cc)."""
    return _regression(data, label, grad_scale, lambda d: d)


@register("LogisticRegressionOutput", aliases=("logistic_regression_output",))
def LogisticRegressionOutput(data, label, grad_scale=1.0):
    return _regression(data, label, grad_scale, jax.nn.sigmoid)


@register("MAERegressionOutput", aliases=("mae_regression_output",))
def MAERegressionOutput(data, label, grad_scale=1.0):
    return _regression(data, label, grad_scale, lambda d: d, grad=jnp.sign)


def _regression(data, label, grad_scale, link, grad=None):
    @jax.custom_vjp
    def _f(d, lab):
        return link(d)

    def _fwd(d, lab):
        return link(d), (link(d), lab)

    def _bwd(res, g):
        p, lab = res
        # the reference reshapes the label to the prediction's shape
        # (regression_output-inl.h) — without this a (N,) label against a
        # (N,1) pred silently broadcasts the grad to (N,N)
        lab_r = jnp.reshape(lab, p.shape)
        diff = grad(p - lab_r) if grad is not None else (p - lab_r)
        num_output = p.size // p.shape[0] if p.ndim > 0 and p.shape[0] \
            else 1
        return (diff * (grad_scale / num_output), jnp.zeros_like(lab))

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


# ------------------------------------------------------------- sequence ops
def _seq_mask(data, sequence_length, use_sequence_length, value, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    L = sequence_length.astype(jnp.int32)
    if axis == 0:
        mask = steps[:, None] < L[None, :]
        mask = jnp.reshape(mask, mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = steps[None, :] < L[:, None]
        mask = jnp.reshape(mask, mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


@register("SequenceMask")
def SequenceMask(data, sequence_length=None, use_sequence_length=False, value=0.0,
                 axis=0):
    """Ref: src/operator/sequence_mask.cc (TNC or NTC via axis)."""
    return _seq_mask(data, sequence_length, use_sequence_length, value, axis)


@register("SequenceLast")
def SequenceLast(data, sequence_length=None, use_sequence_length=False, axis=0):
    """Ref: src/operator/sequence_last.cc."""
    if not use_sequence_length or sequence_length is None:
        idx = [slice(None)] * data.ndim
        idx[axis] = -1
        return data[tuple(idx)]
    L = jnp.maximum(sequence_length.astype(jnp.int32) - 1, 0)
    moved = jnp.moveaxis(data, axis, 0)  # (T, N, ...)
    return jnp.take_along_axis(moved, jnp.reshape(L, (1, -1) + (1,) * (moved.ndim - 2)),
                               axis=0)[0]


@register("SequenceReverse")
def SequenceReverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    """Ref: src/operator/sequence_reverse.cc (time axis 0)."""
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    steps = jnp.arange(T)
    L = sequence_length.astype(jnp.int32)  # (N,)
    rev_idx = jnp.where(steps[:, None] < L[None, :], L[None, :] - 1 - steps[:, None],
                        steps[:, None])  # (T, N)
    rev_idx = jnp.reshape(rev_idx, rev_idx.shape + (1,) * (data.ndim - 2))
    return jnp.take_along_axis(data, jnp.broadcast_to(rev_idx, data.shape), axis=0)


# ---------------------------------------------------- parameter shape rules
# FInferShape backward fill (ref: each op's FInferShape in src/operator/nn/*
# deriving weight shapes from the data shape). Consumed by
# Symbol.infer_shape via the registry (mxtpu/ops/registry.py).
from .registry import register_param_shapes  # noqa: E402


@register_param_shapes("FullyConnected")
def _fc_param_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return {}
    num_hidden = int(attrs.get("num_hidden"))
    flatten = attrs.get("flatten", True)
    in_units = 1
    if flatten:
        for s in data[1:]:
            in_units *= s
    else:
        in_units = data[-1]
    out = {1: (num_hidden, in_units)}
    if len(shapes) > 2 and not attrs.get("no_bias", False):
        out[2] = (num_hidden,)
    return out


@register_param_shapes("Convolution")
def _conv_param_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return {}
    ndim = len(data) - 2
    kernel = _pair(attrs.get("kernel"), ndim)
    num_filter = int(attrs.get("num_filter"))
    num_group = int(attrs.get("num_group", 1))
    layout = attrs.get("layout") or "NC" + "DHW"[3 - ndim:]
    channels_last = layout[-1] == "C"
    c_axis = layout.index("C")
    in_ch = data[c_axis]
    if channels_last:
        # weight is HWIO for channels-last (mirrors _conv_dims)
        w = kernel + (in_ch // num_group, num_filter)
    else:
        w = (num_filter, in_ch // num_group) + kernel
    out = {1: w}
    if len(shapes) > 2 and not attrs.get("no_bias", False):
        out[2] = (num_filter,)
    return out


@register_param_shapes("Deconvolution")
def _deconv_param_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return {}
    ndim = len(data) - 2
    kernel = _pair(attrs.get("kernel"), ndim)
    num_filter = int(attrs.get("num_filter"))
    num_group = int(attrs.get("num_group", 1))
    layout = attrs.get("layout") or "NC" + "DHW"[3 - ndim:]
    channels_last = layout[-1] == "C"
    in_ch = data[len(data) - 1 if channels_last else 1]
    if channels_last:
        w = kernel + (num_filter // num_group, in_ch)
    else:
        w = (in_ch, num_filter // num_group) + kernel
    out = {1: w}
    if len(shapes) > 2 and not attrs.get("no_bias", True):
        out[2] = (num_filter,)
    return out


def _channel_param_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return {}
    axis = int(attrs.get("axis", 1)) % len(data)
    c = (data[axis],)
    return {i: c for i in range(1, len(shapes))}


register_param_shapes("BatchNorm")(_channel_param_shapes)
register_param_shapes("InstanceNorm")(_channel_param_shapes)


@register_param_shapes("LayerNorm")
def _ln_param_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return {}
    axis = int(attrs.get("axis", -1)) % len(data)
    c = (data[axis],)
    return {i: c for i in range(1, len(shapes))}


@register_param_shapes("RMSNorm")
def _rms_param_shapes(shapes, attrs):
    data = shapes[0]
    if data is None:
        return {}
    return {1: (data[int(attrs.get("axis", -1)) % len(data)],)}


@register_param_shapes("LeakyReLU")
def _leaky_param_shapes(shapes, attrs):
    # only PReLU has a learnable gamma, shaped per-channel (ref:
    # src/operator/leaky_relu-inl.h FInferShape)
    if attrs.get("act_type") != "prelu" or shapes[0] is None \
            or len(shapes) < 2:
        return {}
    return {1: (shapes[0][1],)}
