"""bf16 convolution with an f32 MXU accumulator, fwd AND bwd.

Why: on v5e, XLA picks a measurably faster MXU schedule when a bf16
contraction is asked to produce an f32 accumulator output (the cast back
to bf16 fuses into the epilogue and keeps the gain) — tools/perf_peak.py
measures 102 -> 140 TFLOP/s on a square matmul and tools/perf_conv_acc.py
+10%% on a resnet-like 3x3 conv stack. Numerics only improve: the
per-tile accumulator was f32 either way.

Why a custom_vjp: jax 0.9 supports ``preferred_element_type`` under
autodiff for ``dot_general`` but NOT for ``conv_general_dilated`` — its
transpose rule calls the grad convs with the (now f32) cotangent against
the bf16 saved operand and rejects the dtype mix. Here the primal output
is cast back to bf16, so the cotangent arrives in bf16 and the two grad
convolutions run with matched bf16 operands + their own f32 accumulator:
every conv in fwd and bwd is on the fast path.

The grad convs reuse jax's own transpose-rule implementations
(jax._src.lax.convolution._conv_general_dilated_transpose_{lhs,rhs}) so
the stride/dilation/grouping padding arithmetic cannot drift from what
``jax.grad`` of a plain conv would compute. That import is private and
version-brittle: it is probed once at import; when unavailable,
``HAVE_ACC_VJP`` is False and callers (ops/nn.py Convolution) fall back
to the plain autodiff path — a perf regression, never a correctness one.
tests/test_precision.py asserts grads match the plain path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

try:  # private jax internals — probed once, fallback below
    from jax._src.lax.convolution import (
        _conv_general_dilated_transpose_lhs as _t_lhs,
        _conv_general_dilated_transpose_rhs as _t_rhs,
    )
    HAVE_ACC_VJP = True
except ImportError:  # pragma: no cover - exercised only on a jax upgrade
    _t_lhs = _t_rhs = None
    HAVE_ACC_VJP = False

_LOW = (jnp.bfloat16, jnp.float16)


def _conv_raw(x, w, strides, padding, lhs_dilation, rhs_dilation, dims,
              groups, pet):
    out = lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=padding,
        lhs_dilation=lhs_dilation,
        rhs_dilation=rhs_dilation,
        dimension_numbers=dims,
        feature_group_count=groups,
        precision=lax.Precision.DEFAULT,
        preferred_element_type=pet,
    )
    return out.astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def conv_acc(x, w, strides, padding, lhs_dilation, rhs_dilation, dims,
             groups):
    """bf16/f16 conv, f32-accumulated fwd and bwd, output in x.dtype.

    ``dims`` is the (lhs, rhs, out) string triple; ``padding`` a tuple of
    per-dim (lo, hi) pairs. Callers guarantee all-low-precision operands
    (ops/nn.py routes here only when acc_dtype(...) fires).
    """
    return _conv_raw(x, w, strides, padding, lhs_dilation, rhs_dilation,
                     dims, groups, jnp.float32)


def _fwd(x, w, strides, padding, lhs_dilation, rhs_dilation, dims, groups):
    out = conv_acc(x, w, strides, padding, lhs_dilation, rhs_dilation, dims,
                   groups)
    return out, (x, w)


def _bwd(strides, padding, lhs_dilation, rhs_dilation, dims, groups, res, g):
    x, w = res
    dn = lax.conv_dimension_numbers(x.shape, w.shape, dims)
    kw = dict(window_strides=strides, padding=padding,
              lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
              dimension_numbers=dn, feature_group_count=groups,
              batch_group_count=1, precision=lax.Precision.DEFAULT,
              preferred_element_type=jnp.float32)
    gx = _t_lhs(g, x, w, out_sharding=None, **kw)
    gw = _t_rhs(g, x, w, out_sharding=None, **kw)
    return gx.astype(x.dtype), gw.astype(w.dtype)


conv_acc.defvjp(_fwd, _bwd)


def _enabled():
    """DEFAULT OFF as of round 5: the same-session on-chip A/B measured
    the custom conv path at −2.8% end-to-end ResNet-50 (2331.7 control
    vs 2267.2, round-5 builder chip session) and the best-known config excludes
    it (resnet_best 2580.3 img/s, perf_followup.log) — the +10%
    conv-stack microbench win does not survive the real mixed graph.
    MXTPU_CONV_ACC=1 re-enables for A/Bs. The f32-accumulate MATMUL
    policy (precision_util.contract_acc: dense/RNN/attention) is
    unaffected by this flag and stays on."""
    import os
    return os.environ.get("MXTPU_CONV_ACC", "0") == "1"


def _im2col_enabled():
    """MXTPU_CONV_IM2COL=1 lowers qualifying convs (NHWC, stride 1, no
    dilation, groups 1, C_in <= 128) through explicit patch extraction +
    ONE matmul instead of XLA's conv path. Why (round-5 measurement,
    PERF.md): the early resnet stages' small-channel convs run at ~7
    TFLOP/s on the conv path while the same chip's MATMUL path measures
    102-135 TFLOP/s — im2col trades ~k^2 x input HBM traffic (~1 ms at
    these shapes) for matmul-path compute. STAGED off by default pending
    the on-chip A/B (the auto-battery's resnet_im2col phase); in the jit
    policy cache key (registry.policy_key)."""
    import os
    return os.environ.get("MXTPU_CONV_IM2COL", "0") == "1"


def _im2col_applicable(x, w, strides, padding, lhs_dilation, rhs_dilation,
                       dims, groups):
    if dims != ("NHWC", "HWIO", "NHWC") or groups != 1:
        return False
    if tuple(strides) != (1, 1) or tuple(lhs_dilation) != (1, 1) \
            or tuple(rhs_dilation) != (1, 1):
        return False
    kh, kw, cin, _ = w.shape
    if kh == 1 and kw == 1:
        return False        # 1x1 IS already a matmul to XLA
    return cin <= 128       # where the conv path measured slow


def conv_im2col(x, w, padding):
    """NHWC stride-1 conv as patch-extraction + one matmul (exact).
    lax.conv_general_dilated_patches emits channel-major (c, kh, kw)
    patch features; weights are transposed to match."""
    kh, kw, cin, cout = w.shape
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), (1, 1), list(map(tuple, padding)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))   # [..., cin*kh*kw]
    wmat = jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout)
    from .precision_util import contract_acc
    n, oh, ow, k = patches.shape
    out = contract_acc(jnp.dot, patches.reshape(n * oh * ow, k), wmat)
    # match the conv path's output dtype (operand promotion, NOT x.dtype:
    # bf16 activations x f32 master weights must stay f32 either way or
    # the im2col A/B would compare different-precision programs)
    return out.reshape(n, oh, ow, cout).astype(
        jnp.promote_types(x.dtype, w.dtype))


def conv_fast(x, w, strides, padding, lhs_dilation, rhs_dilation, dims,
              groups, bias=None):
    """Dispatch, highest-priority first: the Pallas implicit-GEMM kernel
    for MXU-underfilled NHWC shapes (MXTPU_PALLAS_CONV — stem/1x1/small-C
    convs, pallas/conv.py; the per-channel ``bias`` rides its fused
    epilogue), then the staged im2col lowering, then the f32-accumulate
    custom-vjp path for all-low-precision operands (when the private
    transpose helpers imported), else plain conv_general_dilated under
    the package precision policy. ``bias`` (a [C_out] vector) is applied
    on every path so callers get one set of semantics."""
    if _pallas_enabled():
        from .pallas.conv import fused_conv, pallas_applicable
        ok, _reason = pallas_applicable(x, w, strides, padding,
                                        lhs_dilation, rhs_dilation, dims,
                                        groups)
        if ok:
            # a bias whose dtype would promote the conv output (f32 bias
            # on bf16 operands) must stay an external add — the fused
            # epilogue keeps the conv dtype, and flipping the lever must
            # never change a program's output dtype
            out_dt = jnp.promote_types(x.dtype, w.dtype)
            fuse_bias = (bias is not None
                         and jnp.promote_types(out_dt, bias.dtype) == out_dt)
            out = fused_conv(x, w, strides=tuple(strides),
                             padding=tuple(map(tuple, padding)),
                             bias=bias if fuse_bias else None)
            return out if fuse_bias else _with_bias(out, bias, dims)
    if _im2col_enabled() and _im2col_applicable(
            x, w, strides, padding, lhs_dilation, rhs_dilation, dims,
            groups):
        return _with_bias(conv_im2col(x, w, padding), bias, dims)
    if (HAVE_ACC_VJP and _enabled() and x.dtype in _LOW and w.dtype in _LOW):
        return _with_bias(
            conv_acc(x, w, tuple(strides), tuple(map(tuple, padding)),
                     tuple(lhs_dilation), tuple(rhs_dilation), dims,
                     int(groups)), bias, dims)
    from .precision_util import mxu_precision
    return _with_bias(lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
        dimension_numbers=dims, feature_group_count=groups,
        precision=mxu_precision(x, w)), bias, dims)


def _with_bias(out, bias, dims):
    if bias is None:
        return out
    if dims[2][-1] == "C":          # channels-last: trailing broadcast
        return out + bias
    return out + jnp.reshape(bias, (1, -1) + (1,) * (out.ndim - 2))


def _pallas_enabled():
    """MXTPU_PALLAS_CONV=1 routes MXU-underfilled shapes through the hand
    kernel (read site: pallas/conv.py). STAGED off pending the on-chip
    resnet_pallas battery phase; in registry.policy_key."""
    import os
    return os.environ.get("MXTPU_PALLAS_CONV", "0") == "1"
