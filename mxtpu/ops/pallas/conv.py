"""Pallas fused implicit-GEMM convolution for the small-K early conv stages.

Why this kernel exists (PERF.md round-5 attribution): stem + stage2 of the
bench ResNet-50 consume ~78% of the train step (39.7 of 50.6 ms fwd+bwd)
while holding ~15% of the FLOPs — the 7x7s2 stem measures ~3 TFLOP/s and
the 1x1 bottleneck pointwise convs ~3.1-3.3 TFLOP/s against the 93-135
TFLOP/s the same chip sustains on well-shaped contractions. These convs
underfill the MXU on at least one side (im2col K = kh*kw*C_in, or C_out,
below the 128-lane granule), and XLA's generic conv lowering leaves the
gap on the table. The hand kernel turns the conv into the implicit GEMM
XLA won't form and keeps the epilogue (BN one-pass affine, ReLU, residual
add) in VMEM instead of round-tripping HBM between ops.

Design (mirrors flash_attention.py):

* forward: ONE Pallas kernel. The input is phase-decomposed by the stride
  (a space-to-depth on the padded image: plane (p, q) holds rows ≡ p,
  cols ≡ q mod stride) so the kernel only ever takes *static stride-1
  slices*; output rows are tiled into halo-materialized row blocks so
  each grid step's VMEM block is small and offsets stay block-aligned.
  Per grid step the kernel accumulates kh*kw MXU contractions
  [bo*OW, C_in] x [C_in, C_out] into an f32 accumulator, then applies the
  fused epilogue (scale, bias, residual, ReLU) and writes the output tile
  once — conv + BN(affine) + ReLU + add in a single HBM pass.
* backward: ``jax.custom_vjp``, blockwise over the batch in plain jax
  (the flash_attention pattern — the MXU work is matmuls XLA already
  schedules well): dW = Σ_blocks im2col(x_b)^T @ dz_b and
  dX = col2im(dz_b @ W^T), with im2col/col2im expressed through the same
  phase decomposition (static slices + adds, no strided scatters). The
  SAME backward serves the Pallas and fallback forwards — the math is
  exact either way, so fwd AND bwd stay on the hand path.
* dispatch: ``conv_acc.conv_fast`` routes a conv here only when
  ``MXTPU_PALLAS_CONV`` is on AND the shape underfills the MXU
  (``pallas_applicable``); inside, ``_resolve`` may still fall back to
  the XLA conv (non-TPU platform, VMEM budget) with the reason recorded
  in ``DISPATCH_STATS`` — everything else never leaves the XLA path that
  already runs near ceiling. The lever is in ``registry.policy_key`` so
  0/1 A/B flips genuinely recompile.
* parity off-chip: ``MXTPU_PALLAS_CONV_INTERPRET=1`` runs the kernel
  through the Pallas interpreter, so tier-1 pins fwd + both grads against
  ``lax.conv_general_dilated`` on CPU without a chip.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import autotune
# one platform resolver and one interpret rule per package
from .flash_attention import _interpret_flag, _platform

__all__ = ["fused_conv", "pallas_applicable", "shape_class_of",
           "DISPATCH_STATS", "reset_dispatch_stats"]

_MXU_LANES = 128
# VMEM spend the forward kernel may plan for (input block double-buffered +
# f32 accumulator + output tile); v5e has ~16 MB/core and the pipeline
# needs headroom for double buffering, so plan well under it.
_VMEM_BUDGET = 10 * 1024 * 1024
# target GEMM M rows per grid step (a few MXU passes; keeps the f32
# accumulator tile small)
_TARGET_M = 2048
# im2col patches materialized per backward scan block (~32 MB)
_BWD_COLS_BUDGET = 32 << 20
_LOW = (jnp.bfloat16, jnp.float32)

# observability for tests and tools: how often the hand kernel actually
# ran vs why it fell back. The SOURCE OF TRUTH is the telemetry registry
# (``pallas_conv.pallas`` / ``pallas_conv.xla`` counters, reason-tagged
# ``pallas_conv.fallback``) so bench/report/JSONL all see one copy;
# this dict-shaped view keeps the original module-level surface alive
# for existing tests and tools.
class _DispatchStatsView:
    """Read-only dict-shaped view over the telemetry counters."""

    _KEYS = ("pallas", "xla", "fallback_reasons")

    def __getitem__(self, key):
        from ... import telemetry
        if key == "fallback_reasons":
            return telemetry.tagged("pallas_conv.fallback")
        if key not in self._KEYS:
            raise KeyError(key)
        return int(telemetry.value("pallas_conv." + key))

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def keys(self):
        return list(self._KEYS)

    def items(self):
        return [(k, self[k]) for k in self._KEYS]

    def __repr__(self):
        return repr(dict(self.items()))


DISPATCH_STATS = _DispatchStatsView()


def reset_dispatch_stats():
    from ... import telemetry
    telemetry.reset_metric("pallas_conv.pallas")
    telemetry.reset_metric("pallas_conv.xla")
    telemetry.reset_metric("pallas_conv.fallback")


def _interpret():
    """MXTPU_PALLAS_CONV_INTERPRET=1 runs the kernel via the Pallas
    interpreter off the chip — the tier-1 parity path; an error on a TPU
    (``flash_attention._interpret_flag``). Trace-time, so it rides
    policy_key like every other lever."""
    return _interpret_flag("MXTPU_PALLAS_CONV_INTERPRET")


class _Cfg(NamedTuple):
    """Static conv config baked into the custom_vjp (hashable)."""
    strides: Tuple[int, int]
    padding: Tuple[Tuple[int, int], Tuple[int, int]]
    relu: bool
    has_scale: bool
    has_bias: bool
    has_residual: bool
    res_dtype: str = ""   # residual dtype name — saves the dtype, not the
    #                       tensor, in the vjp residuals (d_residual = g)


def _out_hw(size, lo, hi, k, s):
    return (size + lo + hi - k) // s + 1


def pallas_applicable(x, w, strides, padding, lhs_dilation, rhs_dilation,
                      dims, groups):
    """(True, None) when the conv is in the hand kernel's domain AND the
    shape underfills the MXU, else (False, reason). The shape gate is the
    PERF.md finding made executable: route only convs whose im2col K
    (= kh*kw*C_in) or C_out sits below the 128-lane granule — the 7x7s2
    stem (C_out=64), the 1x1 bottleneck pointwise convs (K or C_out = 64),
    the stage-2 small-C spatials — and leave large-K convs (both sides
    >= 128) on the XLA path that already runs near the conv-stack
    ceiling."""
    if dims != ("NHWC", "HWIO", "NHWC"):
        return False, "layout not NHWC/HWIO"
    if x.ndim != 4:
        return False, "not a 2D conv"
    if int(groups) != 1:
        return False, "grouped conv"
    if tuple(lhs_dilation) != (1, 1):
        return False, "lhs dilation (transposed conv)"
    if tuple(rhs_dilation) != (1, 1):
        return False, "rhs dilation"
    if x.dtype not in _LOW or w.dtype not in _LOW:
        return False, "dtype not f32/bf16"
    if x.dtype != w.dtype:
        # lax.conv_general_dilated rejects mixed operands; the kernel's
        # dot_general would silently promote — the lever must not change
        # which programs are valid
        return False, "mixed operand dtypes"
    if any(p < 0 for pair in padding for p in pair):
        return False, "negative padding"
    kh, kw, cin, cout = w.shape
    k_im2col = kh * kw * cin
    if k_im2col >= _MXU_LANES and cout >= _MXU_LANES:
        return False, ("MXU-filled shape (K=%d, C_out=%d): XLA path is "
                       "already near ceiling" % (k_im2col, cout))
    sh, sw = tuple(strides)
    (plo, phi), (qlo, qhi) = (tuple(p) for p in padding)
    oh = _out_hw(x.shape[1], plo, phi, kh, sh)
    ow = _out_hw(x.shape[2], qlo, qhi, kw, sw)
    if oh < 1 or ow < 1:
        return False, "degenerate output"
    return True, None


def _count_fallback(reason):
    from ... import telemetry
    telemetry.inc("pallas_conv.xla")
    telemetry.inc("pallas_conv.fallback", tag=reason)


def _divisor_block(n, want):
    """Largest divisor of n that is <= max(want, 1)."""
    b = max(min(want, n), 1)
    while n % b:
        b -= 1
    return b


def _lane_pad(c):
    return -(-c // _MXU_LANES) * _MXU_LANES


def _plan_vmem(bo, oh, ow, cin, cout, kh, kw, sh, sw, itm, has_scale,
               has_residual):
    """VMEM bytes the forward kernel plans for at row-block ``bo``: the
    pipelined working set — double-buffered input block + the resident
    whole-weight block (the gate allows C_out<128 at ANY C_in, so a
    fat-C_in kernel must fall back here, not die in Mosaic) + output
    tile (+ residual tile, + f32 conv_raw tile when the affine epilogue
    saves it) + the f32 accumulator across the contractions. Shared by
    trace-time _resolve and the autotuner's pre-compile feasibility
    gate, so a tuned plan can never admit geometry _resolve would
    reject."""
    bo_in = bo + (kh - 1) // sh
    ws = ow + (kw - 1) // sw
    return (2 * sh * sw * bo_in * ws * _lane_pad(cin) * itm
            + kh * kw * max(cin, 8) * _lane_pad(cout) * itm
            + 2 * bo * ow * _lane_pad(cout) * itm
            + (2 * bo * ow * _lane_pad(cout) * itm if has_residual
               else 0)
            + (2 * bo * ow * _lane_pad(cout) * 4 if has_scale else 0)
            + bo * ow * _lane_pad(cout) * 4)


def shape_class_of(x, w, cfg):
    """The autotuner's shape class for this conv: full launch geometry +
    dtype + the epilogue flags that change the VMEM plan. Works on
    tracers (shape/dtype only)."""
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    return {"n": int(n), "h": int(h), "w": int(wd), "cin": int(cin),
            "kh": int(kh), "kw": int(kw), "cout": int(cout),
            "sh": cfg.strides[0], "sw": cfg.strides[1],
            "p0": cfg.padding[0][0], "p1": cfg.padding[0][1],
            "q0": cfg.padding[1][0], "q1": cfg.padding[1][1],
            "dtype": jnp.dtype(x.dtype).name,
            "scale": int(cfg.has_scale), "res": int(cfg.has_residual)}


def _resolve(x, w, cfg):
    """Kernel launch geometry (bo = output rows per grid step) or
    (None, reason) -> XLA fallback. Separated from the launch so tests
    can assert routing decisions without running the kernel. A tuned
    plan (autotune.lookup) may override the hand-picked row block, but
    only after revalidating against the SAME divisor + VMEM gates — a
    stale or foreign artifact degrades to the default with a counted
    drop, never a Mosaic error."""
    if _platform() != "tpu" and not _interpret():
        return None, "platform is not tpu"
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    sh, sw = cfg.strides
    (plo, phi), (qlo, qhi) = cfg.padding
    oh = _out_hw(h, plo, phi, kh, sh)
    ow = _out_hw(wd, qlo, qhi, kw, sw)
    itm = jnp.dtype(x.dtype).itemsize
    bo = _divisor_block(oh, max(1, _TARGET_M // ow))
    tuned = autotune.lookup("pallas_conv", shape_class_of(x, w, cfg))
    if tuned is not None:
        tbo = int(tuned.get("bo", 0))
        if (1 <= tbo <= oh and oh % tbo == 0
                and _plan_vmem(tbo, oh, ow, cin, cout, kh, kw, sh, sw,
                               itm, cfg.has_scale, cfg.has_residual)
                <= _VMEM_BUDGET):
            bo = tbo
        else:
            autotune.plan_infeasible("pallas_conv")
    vmem = _plan_vmem(bo, oh, ow, cin, cout, kh, kw, sh, sw, itm,
                      cfg.has_scale, cfg.has_residual)
    if vmem > _VMEM_BUDGET:
        return None, ("VMEM budget: block needs ~%.1f MB > %.1f MB"
                      % (vmem / 2**20, _VMEM_BUDGET / 2**20))
    return {"bo": bo, "oh": oh, "ow": ow}, None


# ------------------------------------------------------ phase decomposition
def _phase_pack(x, kh, kw, sh, sw, plo, qlo, oh, ow):
    """Padded input -> [N, sh*sw, Hs, Ws, C] stride-phase planes.

    Plane p*sw+q holds padded rows ≡ p (mod sh), cols ≡ q (mod sw); input
    row sh*y + dy of output row y lives at row y + dy//sh of plane
    p = dy % sh — every kernel/grad access becomes a STATIC stride-1
    slice (no strided loads for Mosaic, no strided scatters in the
    backward)."""
    n, h, wd, c = x.shape
    hs = oh + (kh - 1) // sh
    ws = ow + (kw - 1) // sw
    hp, wp = sh * hs, sw * ws
    x = jnp.pad(x, ((0, 0), (plo, max(0, hp - h - plo)),
                    (qlo, max(0, wp - wd - qlo)), (0, 0)))
    x = x[:, :hp, :wp]  # rows the conv never reads need no phase slot
    x = x.reshape(n, hs, sh, ws, sw, c).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(n, sh * sw, hs, ws, c)


def _phase_unpack_add(dplanes, h, wd, plo, qlo, sh, sw):
    """[N, sh*sw, Hs, Ws, C] gradient planes -> [N, H, W, C] (inverse of
    _phase_pack; padding rows are dropped, cropped rows restored as 0)."""
    n, _, hs, ws, c = dplanes.shape
    hp, wp = sh * hs, sw * ws
    d = dplanes.reshape(n, sh, sw, hs, ws, c).transpose(0, 3, 1, 4, 2, 5)
    d = d.reshape(n, hp, wp, c)
    d = jnp.pad(d, ((0, 0), (0, max(0, plo + h - hp)),
                    (0, max(0, qlo + wd - wp)), (0, 0)))
    return d[:, plo:plo + h, qlo:qlo + wd]


# ------------------------------------------------------------ pallas forward
def _conv_kernel(*refs, kh, kw, sh, sw, bo, ow, cin, cout, cfg):
    it = iter(refs)
    x_ref = next(it)                       # [1, sh*sw, bo_in, ws, cin]
    w_ref = next(it)                       # [kh, kw, cin, cout]
    scale_ref = next(it) if cfg.has_scale else None      # [1, cout]
    bias_ref = next(it) if cfg.has_bias else None        # [1, cout]
    res_ref = next(it) if cfg.has_residual else None     # [1, bo, ow, cout]
    out_ref = next(it)                     # [1, bo, ow, cout]
    craw_ref = next(it) if cfg.has_scale else None       # f32 conv output

    x = x_ref[0]
    # f32 operands keep reference-parity numerics; bf16 runs the
    # single-pass MXU form with the f32 accumulator requested below
    # (the flash_attention precision policy)
    prec = (lax.Precision.HIGHEST if x.dtype == jnp.float32
            else lax.Precision.DEFAULT)
    acc = jnp.zeros((bo * ow, cout), jnp.float32)
    for dy in range(kh):
        p, a = dy % sh, dy // sh
        for dx in range(kw):
            q, b = dx % sw, dx // sw
            patch = x[p * sw + q, a:a + bo, b:b + ow, :]
            acc = acc + lax.dot_general(
                patch.reshape(bo * ow, cin), w_ref[dy, dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
    pre = acc
    if cfg.has_scale:
        craw_ref[0] = acc.reshape(bo, ow, cout)
        pre = pre * scale_ref[0].astype(jnp.float32)
    if cfg.has_bias:
        pre = pre + bias_ref[0].astype(jnp.float32)
    if cfg.has_residual:
        pre = pre + res_ref[0].reshape(bo * ow, cout).astype(jnp.float32)
    if cfg.relu:
        pre = jnp.maximum(pre, 0.0)
    out_ref[0] = pre.reshape(bo, ow, cout).astype(out_ref.dtype)


def _forward_pallas(x, w, scale, bias, residual, cfg, geom):
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    sh, sw = cfg.strides
    (plo, _), (qlo, _) = cfg.padding
    oh, ow, bo = geom["oh"], geom["ow"], geom["bo"]
    nb = oh // bo
    bo_in = bo + (kh - 1) // sh
    ws = ow + (kw - 1) // sw
    out_dtype = jnp.promote_types(x.dtype, w.dtype)

    xp = _phase_pack(x, kh, kw, sh, sw, plo, qlo, oh, ow)
    # halo-materialize the row blocks so grid-step offsets are multiples
    # of the block shape (BlockSpec index maps address whole blocks);
    # adjacent blocks duplicate only the (kh-1)//sh halo rows
    ridx = jnp.arange(nb)[:, None] * bo + jnp.arange(bo_in)[None, :]
    xb = xp[:, :, ridx]                       # [n, P, nb, bo_in, ws, cin]
    xb = xb.transpose(0, 2, 1, 3, 4, 5).reshape(
        n * nb, sh * sw, bo_in, ws, cin)

    operands = [xb, w]
    in_specs = [
        pl.BlockSpec((1, sh * sw, bo_in, ws, cin),
                     lambda i: (i, 0, 0, 0, 0)),
        pl.BlockSpec((kh, kw, cin, cout), lambda i: (0, 0, 0, 0)),
    ]
    if cfg.has_scale:
        operands.append(scale.reshape(1, cout))
        in_specs.append(pl.BlockSpec((1, cout), lambda i: (0, 0)))
    if cfg.has_bias:
        operands.append(bias.reshape(1, cout))
        in_specs.append(pl.BlockSpec((1, cout), lambda i: (0, 0)))
    if cfg.has_residual:
        operands.append(residual.reshape(n * nb, bo, ow, cout))
        in_specs.append(pl.BlockSpec((1, bo, ow, cout),
                                     lambda i: (i, 0, 0, 0)))
    out_specs = [pl.BlockSpec((1, bo, ow, cout), lambda i: (i, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((n * nb, bo, ow, cout), out_dtype)]
    if cfg.has_scale:  # raw conv output saved for d(scale) — flash's lse
        out_specs.append(pl.BlockSpec((1, bo, ow, cout),
                                      lambda i: (i, 0, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((n * nb, bo, ow, cout), jnp.float32))

    kernel = functools.partial(_conv_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                               bo=bo, ow=ow, cin=cin, cout=cout, cfg=cfg)
    res = pl.pallas_call(
        kernel,
        grid=(n * nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
        name="conv_fwd",              # the kernel's name in a device trace
    )(*operands)
    out = res[0].reshape(n, oh, ow, cout)
    craw = res[1].reshape(n, oh, ow, cout) if cfg.has_scale else None
    return out, craw


# -------------------------------------------------------------- xla fallback
def _xla_conv(x, w, cfg, pet=None):
    """The conv conv_fast's terminal branch would run (same precision
    policy), used off-TPU / over-budget. Without a scale epilogue pet is
    None, so the lever A/B compares IDENTICAL conv numerics; the affine
    form requests the f32 accumulator the kernel also keeps (conv_raw
    feeds d(scale))."""
    from ..precision_util import mxu_precision
    return lax.conv_general_dilated(
        x, w, window_strides=cfg.strides, padding=cfg.padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=mxu_precision(x, w),
        preferred_element_type=pet)


def _forward_xla(x, w, scale, bias, residual, cfg):
    out_dt = jnp.promote_types(x.dtype, w.dtype)
    if cfg.has_scale:
        craw = _xla_conv(x, w, cfg, jnp.float32)
        pre = craw * scale.astype(jnp.float32)
        if cfg.has_bias:
            pre = pre + bias.astype(jnp.float32)
        if cfg.has_residual:
            pre = pre + residual.astype(jnp.float32)
        if cfg.relu:
            pre = jnp.maximum(pre, 0.0)
        return pre.astype(out_dt), craw
    # no affine: mirror conv_fast's terminal branch op for op, so
    # flipping MXTPU_PALLAS_CONV off-TPU never changes a program's math
    out = _xla_conv(x, w, cfg)
    if cfg.has_bias:
        out = out + bias
    if cfg.has_residual:
        out = out + residual
    if cfg.relu:
        out = jnp.maximum(out, 0)
    return out.astype(out_dt), None


# ------------------------------------------------------------------ backward
def _conv_grads_blockwise(x, w, dz, cfg):
    """dL/dx and dL/dw from the conv cotangent dz [N, OH, OW, C_out],
    blockwise over the batch via lax.scan (flash-attention-style bounded
    memory): per block, im2col patches give dW += patches^T @ dz_b and
    dpatches = dz_b @ W^T, scattered back through the phase planes with
    static adds (col2im). Exact — parity vs jax's own conv transpose is
    pinned in tests."""
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    sh, sw = cfg.strides
    (plo, _), (qlo, _) = cfg.padding
    oh = _out_hw(h, plo, cfg.padding[0][1], kh, sh)
    ow = _out_hw(wd, qlo, cfg.padding[1][1], kw, sw)
    k_col = kh * kw * cin
    prec = (lax.Precision.HIGHEST if x.dtype == jnp.float32
            else lax.Precision.DEFAULT)

    xp = _phase_pack(x, kh, kw, sh, sw, plo, qlo, oh, ow)
    wmat = w.reshape(k_col, cout)
    # bound the materialized patches per scan block
    want = max(1, _BWD_COLS_BUDGET // max(1, oh * ow * k_col
                                          * jnp.dtype(x.dtype).itemsize))
    bn = _divisor_block(n, want)

    taps = [(dy, dx) for dy in range(kh) for dx in range(kw)]

    def body(dw_acc, i):
        xb = lax.dynamic_slice_in_dim(xp, i * bn, bn, axis=0)
        dzb = lax.dynamic_slice_in_dim(dz, i * bn, bn, axis=0)
        cols = []
        for dy, dx in taps:
            p, a = dy % sh, dy // sh
            q, b = dx % sw, dx // sw
            cols.append(xb[:, p * sw + q, a:a + oh, b:b + ow, :])
        patches = jnp.concatenate(cols, axis=-1)      # [bn, oh, ow, K]
        m = bn * oh * ow
        pm = patches.reshape(m, k_col)
        zm = dzb.reshape(m, cout)
        dw_acc = dw_acc + lax.dot_general(
            pm, zm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dpatches = lax.dot_general(
            zm, wmat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dpatches = dpatches.reshape(bn, oh, ow, k_col)
        dplanes = jnp.zeros(xb.shape, jnp.float32)
        for t, (dy, dx) in enumerate(taps):
            p, a = dy % sh, dy // sh
            q, b = dx % sw, dx // sw
            dplanes = dplanes.at[:, p * sw + q, a:a + oh, b:b + ow, :].add(
                dpatches[..., t * cin:(t + 1) * cin])
        dxb = _phase_unpack_add(dplanes, h, wd, plo, qlo, sh, sw)
        return dw_acc, dxb.astype(x.dtype)

    dw, dx_blocks = lax.scan(body, jnp.zeros((k_col, cout), jnp.float32),
                             jnp.arange(n // bn))
    # scan stacks [n_blocks, bn, h, w, c]; block i IS batch [i*bn, (i+1)*bn)
    # so the flatten is a plain reshape — no axis swap
    dx = dx_blocks.reshape(x.shape)
    return dx, dw.reshape(w.shape).astype(w.dtype)


# ------------------------------------------------------------- custom vjp op
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused_conv_core(x, w, scale, bias, residual, cfg):
    out, _ = _core_fwd_impl(x, w, scale, bias, residual, cfg)
    return out


def _core_fwd_impl(x, w, scale, bias, residual, cfg):
    geom, reason = _resolve(x, w, cfg)
    if geom is None:
        _count_fallback(reason)
        out, craw = _forward_xla(x, w, scale, bias, residual, cfg)
    else:
        from ... import telemetry
        telemetry.inc("pallas_conv.pallas")
        out, craw = _forward_pallas(x, w, scale, bias, residual, cfg, geom)
    # residuals carry only what the backward reads: `out` feeds the ReLU
    # mask alone, and d_residual is just the (cast) cotangent — saving
    # either tensor unconditionally would hold an extra output-sized
    # buffer per gated conv from forward to backward
    return out, (x, w, scale, bias, out if cfg.relu else None, craw)


def _core_fwd(x, w, scale, bias, residual, cfg):
    return _core_fwd_impl(x, w, scale, bias, residual, cfg)


def _core_bwd(cfg, res, g):
    x, w, scale, bias, out, craw = res
    g32 = g.astype(jnp.float32)
    if cfg.relu:
        g32 = jnp.where(out > 0, g32, 0.0)
    d_residual = (g32.astype(cfg.res_dtype) if cfg.has_residual else None)
    d_bias = (jnp.sum(g32, axis=(0, 1, 2)).astype(bias.dtype)
              if cfg.has_bias else None)
    if cfg.has_scale:
        d_scale = jnp.sum(g32 * craw, axis=(0, 1, 2)).astype(scale.dtype)
        dz32 = g32 * scale.astype(jnp.float32)
    else:
        d_scale = None
        dz32 = g32
    # matched-operand MXU form for the two grad contractions (conv_acc's
    # reasoning: the cotangent meets the saved operands in their dtype,
    # accumulation stays f32 via preferred_element_type)
    dz = dz32.astype(jnp.promote_types(x.dtype, w.dtype))
    dx, dw = _conv_grads_blockwise(x, w, dz, cfg)
    return dx, dw, d_scale, d_bias, d_residual


_fused_conv_core.defvjp(_core_fwd, _core_bwd)


def fused_conv(x, w, strides=(1, 1), padding=((0, 0), (0, 0)), scale=None,
               bias=None, residual=None, relu=False):
    """relu(conv(x, w) * scale + bias + residual) in one fused pass.

    NHWC x [N, H, W, C_in], HWIO w [kh, kw, C_in, C_out]; ``scale``/
    ``bias`` are per-C_out vectors (a BN one-pass affine folds to exactly
    this form), ``residual`` an output-shaped tensor (the bottleneck-block
    shortcut), all optional. Differentiable in x, w, scale, bias,
    residual. Falls back to the XLA conv (+ unfused epilogue) off-TPU or
    when the shape exceeds the VMEM plan — same signature, same math."""
    cfg = _Cfg(strides=tuple(int(s) for s in strides),
               padding=tuple((int(a), int(b)) for a, b in padding),
               relu=bool(relu),
               has_scale=scale is not None,
               has_bias=bias is not None,
               has_residual=residual is not None,
               res_dtype=("" if residual is None
                          else jnp.dtype(residual.dtype).name))
    return _fused_conv_core(x, w, scale, bias, residual, cfg)


# ------------------------------------------------------- autotune descriptor
def _class_geom(sc):
    """(oh, ow, itemsize) from an autotune shape-class dict."""
    oh = _out_hw(sc["h"], sc["p0"], sc["p1"], sc["kh"], sc["sh"])
    ow = _out_hw(sc["w"], sc["q0"], sc["q1"], sc["kw"], sc["sw"])
    return oh, ow, jnp.dtype(sc["dtype"]).itemsize


# candidate GEMM-M targets the space sweeps; each realizes to the largest
# divisor row block bo <= target/ow, so the space covers "fewer, fatter
# grid steps" through "many thin ones" around the hand-picked _TARGET_M
_TUNE_TARGET_M = (256, 512, 1024, 2048, 4096, 8192, 16384)


def _tune_space(sc):
    oh, ow, _ = _class_geom(sc)
    return [{"bo": _divisor_block(oh, max(1, tm // ow))}
            for tm in _TUNE_TARGET_M]


def _tune_default(sc):
    oh, ow, _ = _class_geom(sc)
    return {"bo": _divisor_block(oh, max(1, _TARGET_M // ow))}


def _tune_feasible(plan, sc):
    oh, ow, itm = _class_geom(sc)
    bo = int(plan.get("bo", 0))
    if not (1 <= bo <= oh and oh % bo == 0):
        return False, "bo=%d is not a divisor of oh=%d" % (bo, oh)
    vmem = _plan_vmem(bo, oh, ow, sc["cin"], sc["cout"], sc["kh"],
                      sc["kw"], sc["sh"], sc["sw"], itm,
                      bool(sc["scale"]), bool(sc["res"]))
    if vmem > _VMEM_BUDGET:
        return False, ("VMEM budget: bo=%d needs ~%.1f MB > %.1f MB"
                       % (bo, vmem / 2**20, _VMEM_BUDGET / 2**20))
    return True, None


def _tune_runner(sc):
    """Real buffers + a dispatch through fused_conv's public entry (the
    timed program IS the serving program for this shape class)."""
    import numpy as np
    rng = np.random.default_rng(0)
    dt = jnp.dtype(sc["dtype"])
    x = jnp.asarray(rng.standard_normal(
        (sc["n"], sc["h"], sc["w"], sc["cin"])), dt)
    w = jnp.asarray(0.1 * rng.standard_normal(
        (sc["kh"], sc["kw"], sc["cin"], sc["cout"])), dt)
    strides = (sc["sh"], sc["sw"])
    padding = ((sc["p0"], sc["p1"]), (sc["q0"], sc["q1"]))
    oh, ow, _ = _class_geom(sc)
    args = [x, w]
    has_scale, has_res = bool(sc["scale"]), bool(sc["res"])
    if has_scale:
        args.append(jnp.asarray(
            1.0 + 0.1 * rng.standard_normal(sc["cout"]), jnp.float32))
    if has_res:
        args.append(jnp.asarray(rng.standard_normal(
            (sc["n"], oh, ow, sc["cout"])), dt))

    def fn(*a):
        it = iter(a)
        xx, ww = next(it), next(it)
        sc_v = next(it) if has_scale else None
        rs_v = next(it) if has_res else None
        return fused_conv(xx, ww, strides=strides, padding=padding,
                          scale=sc_v, residual=rs_v, relu=True)

    return fn, tuple(args)


def _tune_classes(host_tier):
    """Representative shape classes a tuning session sweeps (the bench
    conv_class families). The host tier shrinks batch/H so interpret-mode
    candidates stay inside the perf-battery budget; on a chip the bench
    shapes run as-is."""
    if host_tier:
        geoms = [(2, 64, 3, 64, 7, 2, 3),     # stem 7x7s2
                 (2, 28, 256, 64, 1, 1, 0),   # bottleneck pointwise
                 (2, 28, 64, 64, 3, 1, 1)]    # stage-2 spatial
    else:
        geoms = [(8, 224, 3, 64, 7, 2, 3),
                 (8, 56, 256, 64, 1, 1, 0),
                 (8, 56, 64, 64, 3, 1, 1)]
    return [{"n": n, "h": h, "w": h, "cin": cin, "kh": k, "kw": k,
             "cout": cout, "sh": s, "sw": s, "p0": p, "p1": p,
             "q0": p, "q1": p, "dtype": "float32", "scale": 1, "res": 0}
            for (n, h, cin, cout, k, s, p) in geoms]


autotune.register_kernel(autotune.TunableKernel(
    kernel_id="pallas_conv",
    space=_tune_space,
    default=_tune_default,
    feasible=_tune_feasible,
    runner=_tune_runner,
    classes=_tune_classes,
    interpret_env="MXTPU_PALLAS_CONV_INTERPRET",
))
