"""Space-to-depth stem transform for conv nets (the MLPerf ResNet trick).

The first conv of ImageNet nets (7x7, stride 2, 3 input channels) wastes
the MXU: 3 channels against the 8x128 tiling leaves most lanes idle. The
standard fix reshapes the input into 2x2 blocks (224x224x3 -> 112x112x12)
and runs an EXACTLY equivalent 4x4 stride-1 convolution whose weights are
a zero-padded re-indexing of the original 7x7 kernel — same function, same
gradients, 4x the input channels on the MXU.

This implementation derives the 4x4 weights from the ORIGINAL 7x7
parameter inside the traced forward (a scatter of 9,408 elements — free),
so the wrapped model keeps its parameter structure: checkpoints
round-trip, gradients flow to the original weight, and the transform can
be toggled per run (bench: BENCH_S2D_STEM=1).

Derivation (NHWC, block b=2, original stride 2 pad 3): output row y reads
input rows R = 2y + k' for k' = ky-3 in [-3, 3]. With R = 2r + py,
py = k' mod 2 and r = y + floor(k'/2) in [y-2, y+1] — a 4-tap kernel over
s2d rows at stride 1 with padding (2, 1); columns identically. The s2d
channel of (py, px, c) is (py*2 + px)*3 + c.
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from ..base import MXNetError

__all__ = ["space_to_depth_nhwc", "embed_stem_weight", "apply_to_resnet",
           "stem_mode"]

_B = 2  # block size of the transform (fixed by the stride-2 stem)


def stem_mode():
    """The first-class stem lever, promoted from bench-env-only (round 7):
    ``MXTPU_S2D_STEM`` = 0 (plain 7x7s2 stem), 1 (single s2d), 2 (double
    s2d, the staged MXU-shaped variant). Read at TRACE time by a
    policy-mode ``_StemFn`` (mode=None), and part of
    ``registry.policy_key`` — so a per-run flip recompiles every jit
    cache (CachedOp, executors) instead of silently reusing the other
    stem's executable. bench.py maps its BENCH_S2D_STEM knob onto this
    env."""
    v = os.environ.get("MXTPU_S2D_STEM", "0")
    if v not in ("0", "1", "2"):
        raise MXNetError("MXTPU_S2D_STEM=%r: valid values are 0 (plain "
                         "stem), 1 (s2d), 2 (double-s2d)" % (v,))
    return int(v)


def space_to_depth_nhwc(x):
    """(N, H, W, C) -> (N, H/2, W/2, 4C), channel-major in (py, px)."""
    n, h, w, c = x.shape
    y = x.reshape(n, h // _B, _B, w // _B, _B, c)
    y = y.transpose(0, 1, 3, 2, 4, 5)  # n, r, s, py, px, c
    return y.reshape(n, h // _B, w // _B, _B * _B * c)


def embed_stem_weight(w):
    """Zero-embed a (7, 7, C, F) HWIO stem kernel into the equivalent
    (4, 4, 4C, F) kernel for the s2d input (see module derivation)."""
    kh, kw, c, f = w.shape
    if (kh, kw) != (7, 7):
        raise MXNetError("s2d stem embedding expects a 7x7 kernel, got %s"
                         % ((kh, kw),))
    out = jnp.zeros((4, 4, _B * _B * c, f), w.dtype)
    for ky in range(7):
        kyp = ky - 3
        py = kyp % _B
        a = (kyp - py) // _B + 2
        for kx in range(7):
            kxp = kx - 3
            px = kxp % _B
            b = (kxp - px) // _B + 2
            ch = (py * _B + px) * c
            out = out.at[a, b, ch:ch + c, :].set(w[ky, kx])
    return out


def space_to_depth4_nhwc(x):
    """(N, H, W, C) -> (N, H/4, W/4, 16C), channel-major in (rho, sigma)."""
    n, h, w, c = x.shape
    y = x.reshape(n, h // 4, 4, w // 4, 4, c)
    y = y.transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(n, h // 4, w // 4, 16 * c)


def depth_to_space2_nhwc(y, f):
    """(N, H, W, 4F) with channel layout (py, px, f) -> (N, 2H, 2W, F)."""
    n, h, w, _ = y.shape
    y = y.reshape(n, h, w, 2, 2, f)
    y = y.transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(n, 2 * h, 2 * w, f)


def embed_stem_weight4(w):
    """Zero-embed a (7, 7, C, F) stem kernel into the (3, 3, 16C, 4F)
    kernel of the DOUBLE-s2d stem (mode 2).

    Derivation: output row Y = 2y + py (py in {0,1}) reads input rows
    R = 2Y + ky - 3 = 4y + t with t = 2py + ky - 3 in [-3, 5]. Writing
    R = 4(y + a - 1) + rho gives a = t//4 + 1 in {0,1,2} and rho = t % 4
    — a 3-tap kernel over 4-row input blocks at stride 1 with SYMMETRIC
    padding 1 (t = -4, i.e. block row -1 tap 0, never occurs, so no
    asymmetric padding is needed, unlike mode 1). Columns identically.
    The output packs the 2x2 output-pixel block into channels
    (py*2 + px)*F + f, un-packed by depth_to_space2_nhwc.

    Why: mode 1's conv is K=192 (im2col), N=64 — both underfill the MXU
    (half the lanes, 1.5 contraction passes) and it measured no faster
    than the plain 7x7 in isolation (perf_followup.log stem phase). This
    shape is K=432, N=256: full lanes both sides, ~3.4 contraction
    passes, at 56x56 spatial. ~2.9x padded FLOPs, but at large-matmul
    efficiency the net is the win the stem needs (PERF.md stem table)."""
    kh, kw, c, f = w.shape
    if (kh, kw) != (7, 7):
        raise MXNetError("s2d stem embedding expects a 7x7 kernel, got %s"
                         % ((kh, kw),))
    out = jnp.zeros((3, 3, 16 * c, 4 * f), w.dtype)
    for py in range(2):
        for ky in range(7):
            t = 2 * py + ky - 3
            a, rho = t // 4 + 1, t % 4
            for px in range(2):
                for kx in range(7):
                    u = 2 * px + kx - 3
                    b, sig = u // 4 + 1, u % 4
                    ch = (rho * 4 + sig) * c
                    fo = (py * 2 + px) * f
                    out = out.at[a, b, ch:ch + c, fo:fo + f].set(w[ky, kx])
    return out


class _StemFn:
    """Callable forward for the wrapped stem (kept tiny and pickle-free).
    mode 1: single 2x2 s2d + 4x4 conv; mode 2: 4x4 s2d + 3x3 conv +
    2x2 depth-to-space (see embed_stem_weight4); mode 0: the plain 7x7s2
    conv (byte-identical semantics to the unwrapped stem, so a wrapped
    net is a no-op at mode 0); mode None: POLICY mode — the mode is read
    from MXTPU_S2D_STEM at trace time (stem_mode), making the stem a
    per-run lever that recompiles through registry.policy_key."""

    def __init__(self, weight_param, bias_param, mode=1):
        # strings/typos must not silently run mode 1; None = policy mode
        if mode not in (None, 0, 1, 2):
            raise MXNetError("s2d stem mode must be None, 0, 1 or 2, "
                             "got %r" % (mode,))
        self._w = weight_param
        self._b = bias_param
        self._mode = mode

    def __call__(self, x):
        from ..ops.nn import conv_fast
        mode = self._mode if self._mode is not None else stem_mode()
        if mode == 0:
            # the untransformed stem (the conv the wrap replaced)
            return conv_fast(x, self._w, strides=(2, 2),
                             padding=[(3, 3), (3, 3)],
                             lhs_dilation=(1, 1), rhs_dilation=(1, 1),
                             dims=("NHWC", "HWIO", "NHWC"), groups=1,
                             bias=self._b)
        if mode == 2:
            s = space_to_depth4_nhwc(x)
            w2 = embed_stem_weight4(self._w)
            out = conv_fast(s, w2, strides=(1, 1),
                            padding=[(1, 1), (1, 1)],
                            lhs_dilation=(1, 1), rhs_dilation=(1, 1),
                            dims=("NHWC", "HWIO", "NHWC"), groups=1)
            out = depth_to_space2_nhwc(out, self._w.shape[-1])
        else:
            s = space_to_depth_nhwc(x)
            w4 = embed_stem_weight(self._w)
            out = conv_fast(s, w4, strides=(1, 1), padding=[(2, 1), (2, 1)],
                            lhs_dilation=(1, 1), rhs_dilation=(1, 1),
                            dims=("NHWC", "HWIO", "NHWC"), groups=1)
        if self._b is not None:
            out = out + self._b
        return out


def apply_to_resnet(net, mode=None):
    """Swap the stem Conv2D of an NHWC zoo resnet for the s2d-equivalent
    path, in place. The conv's Parameters are untouched — only its forward
    is re-routed — so checkpoints and trainers keep working. Returns net.
    mode None (default) = POLICY mode: the variant is picked per trace
    from MXTPU_S2D_STEM (0 = plain stem, so wrapping is free), letting
    one wrapped net A/B all three stems through policy_key recompiles;
    mode 1 = single s2d (112^2 x 12 conv4x4); mode 2 = double s2d
    (56^2 x 48 conv3x3 -> 256ch -> depth-to-space; MXU-shaped, see
    embed_stem_weight4)."""
    if mode not in (None, 0, 1, 2):
        raise MXNetError("s2d stem mode must be None, 0, 1 or 2, got %r"
                         % (mode,))
    feats = list(net.features._children.values())
    conv = feats[0]
    if type(conv).__name__ != "Conv2D":
        raise MXNetError("expected the first feature block to be the stem "
                         "Conv2D; got %s" % type(conv).__name__)
    if getattr(conv, "_layout", None) not in ("NHWC",):
        raise MXNetError("s2d stem transform supports NHWC nets (build the "
                         "zoo model under mx.layout('NHWC'))")
    # the derivation hardcodes the ImageNet stem: 7x7, stride 2, pad 3,
    # no dilation/groups/activation — anything else would be silently
    # transformed into a DIFFERENT function
    bad = []
    if tuple(getattr(conv, "_kwargs", {}).get("kernel", ())) != (7, 7):
        bad.append("kernel != 7x7")
    if tuple(conv._kwargs.get("stride", ())) != (2, 2):
        bad.append("stride != 2")
    if tuple(conv._kwargs.get("pad", ())) != (3, 3):
        bad.append("pad != 3")
    if tuple(conv._kwargs.get("dilate", (1, 1))) != (1, 1):
        bad.append("dilate != 1")
    if conv._kwargs.get("num_group", 1) != 1:
        bad.append("grouped")
    if getattr(conv, "act", None) is not None:
        bad.append("fused activation")
    if bad:
        raise MXNetError("stem conv not s2d-transformable: %s"
                         % ", ".join(bad))

    from ..ndarray.ndarray import _apply

    def hybrid_forward(self, F, x, weight=None, bias=None):
        return _apply(
            lambda xd, wd, *rest: _StemFn(wd, rest[0] if rest else None,
                                          mode=mode)(xd),
            (x, weight) + (() if bias is None else (bias,)),
            name="s2d_stem")

    conv.hybrid_forward = hybrid_forward.__get__(conv, type(conv))
    return net
