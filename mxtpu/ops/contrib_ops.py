"""Contrib op family (ref: src/operator/contrib/*): detection/bbox ops, resize/pool
variants, transformer helper, quadratic, fft. Implemented as XLA lowerings; the
reference's hand CUDA kernels (nms, roi_align, deformable conv) become vectorized
gather/scatter HLO."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register


@register("_contrib_div_sqrt_dim", aliases=("div_sqrt_dim",))
def div_sqrt_dim(data):
    """Scale by 1/sqrt(last dim) — the attention-score helper
    (ref: src/operator/contrib/transformer.cc)."""
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], dtype=data.dtype))


@register("quadratic", aliases=("_contrib_quadratic",))
def quadratic(data, a=0.0, b=0.0, c=0.0):
    """Ref: src/operator/contrib/quadratic_op.cc (the tutorial op)."""
    return a * data * data + b * data + c


@register("_contrib_arange_like")
def contrib_arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    from .init_ops import arange_like
    return arange_like(data, start=start, step=step, repeat=repeat, axis=axis)


# ----------------------------------------------------------- resize / pooling
@register("_contrib_BilinearResize2D", aliases=("BilinearResize2D",))
def BilinearResize2D(data, height=1, width=1, scale_height=None, scale_width=None,
                     **_ig):
    """Ref: src/operator/contrib/bilinear_resize.cc."""
    n, c, h, w = data.shape
    if scale_height is not None:
        height, width = int(h * scale_height), int(w * scale_width)
    return jax.image.resize(data, (n, c, height, width), method="bilinear")


@register("_contrib_AdaptiveAvgPooling2D", aliases=("AdaptiveAvgPooling2D",))
def AdaptiveAvgPooling2D(data, output_size=None, **_ig):
    """Ref: src/operator/contrib/adaptive_avg_pooling.cc."""
    n, c, h, w = data.shape
    if output_size is None:
        oh = ow = 1
    elif isinstance(output_size, int):
        oh = ow = output_size
    else:
        oh, ow = output_size
    # decompose into resize-style mean pooling (exact when divisible)
    if h % oh == 0 and w % ow == 0:
        x = data.reshape(n, c, oh, h // oh, ow, w // ow)
        return x.mean(axis=(3, 5))
    return jax.image.resize(data, (n, c, oh, ow), method="linear")


# ------------------------------------------------------------------ boxes
@register("_contrib_box_iou", aliases=("box_iou",))
def box_iou(lhs, rhs, format="corner"):  # noqa: A002
    """Pairwise IoU (ref: src/operator/contrib/bounding_box.cc box_iou)."""
    def to_corner(b):
        if format == "center":
            x, y, w, h = jnp.split(b, 4, axis=-1)
            return jnp.concatenate([x - w / 2, y - h / 2, x + w / 2, y + h / 2], -1)
        return b

    a = to_corner(lhs)[..., :, None, :]
    b = to_corner(rhs)[..., None, :, :]
    tl = jnp.maximum(a[..., :2], b[..., :2])
    br = jnp.minimum(a[..., 2:], b[..., 2:])
    wh = jnp.maximum(br - tl, 0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = (a[..., 2] - a[..., 0]) * (a[..., 3] - a[..., 1])
    area_b = (b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1])
    return inter / jnp.maximum(area_a + area_b - inter, 1e-12)


@register("_contrib_box_nms", aliases=("box_nms",))
def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1, coord_start=2,
            score_index=1, id_index=-1, background_id=-1, force_suppress=False,
            in_format="corner", out_format="corner"):
    """Greedy NMS via a fixed-iteration lax loop (ref: bounding_box.cc BoxNMS).
    Suppressed boxes get score -1, matching the reference's output convention."""
    def nms_one(boxes):
        scores = boxes[:, score_index]
        coords = boxes[:, coord_start:coord_start + 4]
        n = boxes.shape[0]
        order = jnp.argsort(-scores)
        coords_s = coords[order]
        valid = scores[order] > valid_thresh
        if topk > 0:
            valid = valid & (jnp.arange(n) < topk)

        tl = jnp.maximum(coords_s[:, None, :2], coords_s[None, :, :2])
        br = jnp.minimum(coords_s[:, None, 2:], coords_s[None, :, 2:])
        wh = jnp.maximum(br - tl, 0)
        inter = wh[..., 0] * wh[..., 1]
        area = (coords_s[:, 2] - coords_s[:, 0]) * (coords_s[:, 3] - coords_s[:, 1])
        iou = inter / jnp.maximum(area[:, None] + area[None, :] - inter, 1e-12)

        def body(i, keep):
            sup = (iou[i] > overlap_thresh) & (jnp.arange(n) > i) & keep[i]
            return keep & ~sup

        keep = jax.lax.fori_loop(0, n, body, valid)
        new_scores = jnp.where(keep, scores[order], -1.0)
        out = boxes[order].at[:, score_index].set(new_scores)
        return out

    if data.ndim == 2:
        return nms_one(data)
    return jax.vmap(nms_one)(data)


@register("_contrib_ROIAlign", aliases=("ROIAlign",))
def ROIAlign(data, rois, pooled_size=(7, 7), spatial_scale=1.0, sample_ratio=-1,
             position_sensitive=False):
    """ROI Align (ref: src/operator/contrib/roi_align.cc) via bilinear gather."""
    ph, pw = pooled_size if not isinstance(pooled_size, int) else (pooled_size, pooled_size)
    n, c, h, w = data.shape
    sr = 2 if sample_ratio <= 0 else sample_ratio

    def one_roi(roi):
        batch_id = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = roi[1] * spatial_scale, roi[2] * spatial_scale, \
            roi[3] * spatial_scale, roi[4] * spatial_scale
        rw = jnp.maximum(x2 - x1, 1.0)
        rh = jnp.maximum(y2 - y1, 1.0)
        bin_w, bin_h = rw / pw, rh / ph
        # sample grid (ph*sr, pw*sr)
        ys = y1 + (jnp.arange(ph * sr) + 0.5) * bin_h / sr
        xs = x1 + (jnp.arange(pw * sr) + 0.5) * bin_w / sr
        yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
        img = data[batch_id]  # (c, h, w)

        y0 = jnp.clip(jnp.floor(yy), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xx), 0, w - 1)
        y1i = jnp.clip(y0 + 1, 0, h - 1)
        x1i = jnp.clip(x0 + 1, 0, w - 1)
        wy = jnp.clip(yy, 0, h - 1) - y0
        wx = jnp.clip(xx, 0, w - 1) - x0
        y0, x0, y1i, x1i = y0.astype(jnp.int32), x0.astype(jnp.int32), \
            y1i.astype(jnp.int32), x1i.astype(jnp.int32)
        v = (img[:, y0, x0] * (1 - wy) * (1 - wx) + img[:, y1i, x0] * wy * (1 - wx)
             + img[:, y0, x1i] * (1 - wy) * wx + img[:, y1i, x1i] * wy * wx)
        v = v.reshape(c, ph, sr, pw, sr).mean(axis=(2, 4))
        return v

    return jax.vmap(one_roi)(rois)


@register("ROIPooling")
def ROIPooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0):
    """Max ROI pooling (ref: src/operator/roi_pooling.cc), via ROIAlign-style
    sampling with max reduction."""
    ph, pw = pooled_size if not isinstance(pooled_size, int) else (pooled_size, pooled_size)
    n, c, h, w = data.shape

    def one_roi(roi):
        batch_id = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        rw = jnp.maximum(x2 - x1 + 1, 1)
        rh = jnp.maximum(y2 - y1 + 1, 1)
        img = data[batch_id]
        ys = jnp.clip(y1 + (jnp.arange(ph * 2) * rh) // (ph * 2), 0, h - 1)
        xs = jnp.clip(x1 + (jnp.arange(pw * 2) * rw) // (pw * 2), 0, w - 1)
        v = img[:, ys[:, None], xs[None, :]]
        return v.reshape(c, ph, 2, pw, 2).max(axis=(2, 4))

    return jax.vmap(one_roi)(rois)


@register("_contrib_fft", aliases=("fft",))
def fft(data, compute_size=128):
    """Ref: src/operator/contrib/fft.cc (cuFFT). Real→interleaved-complex layout."""
    f = jnp.fft.fft(data, axis=-1)
    out = jnp.stack([f.real, f.imag], axis=-1)
    return out.reshape(data.shape[:-1] + (-1,)).astype(jnp.float32)


@register("_contrib_ifft", aliases=("ifft",))
def ifft(data, compute_size=128):
    c = data.reshape(data.shape[:-1] + (-1, 2))
    z = c[..., 0] + 1j * c[..., 1]
    return jnp.real(jnp.fft.ifft(z, axis=-1)).astype(jnp.float32) * z.shape[-1]


@register("_contrib_count_sketch", aliases=("count_sketch",))
def count_sketch(data, h, s, out_dim=16, processing_batch_size=32):
    """Count sketch projection (ref: src/operator/contrib/count_sketch.cc)."""
    hh = h.astype(jnp.int32).reshape(-1)
    ss = s.reshape(-1)
    proj = jnp.zeros(data.shape[:-1] + (out_dim,), data.dtype)
    vals = data * ss
    return proj.at[..., hh % out_dim].add(vals)


@register("GridGenerator")
def GridGenerator(data, transform_type="affine", target_shape=(0, 0)):
    """Ref: src/operator/grid_generator.cc."""
    h, w = target_shape
    if transform_type == "affine":
        n = data.shape[0]
        theta = data.reshape(n, 2, 3)
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
        yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
        grid = jnp.stack([xx, yy, jnp.ones_like(xx)], axis=0).reshape(3, -1)
        out = jnp.matmul(theta, grid)  # (n, 2, h*w)
        return out.reshape(n, 2, h, w)
    return data  # warp type passes flow through


@register("BilinearSampler")
def BilinearSampler(data, grid, cudnn_off=None):
    """Bilinear sampling by normalized grid (ref: src/operator/bilinear_sampler.cc)."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1) * (w - 1) / 2
    gy = (grid[:, 1] + 1) * (h - 1) / 2

    def sample_one(img, x, y):
        x0 = jnp.clip(jnp.floor(x), 0, w - 1)
        y0 = jnp.clip(jnp.floor(y), 0, h - 1)
        x1 = jnp.clip(x0 + 1, 0, w - 1)
        y1 = jnp.clip(y0 + 1, 0, h - 1)
        wx = jnp.clip(x, 0, w - 1) - x0
        wy = jnp.clip(y, 0, h - 1) - y0
        x0i, y0i, x1i, y1i = x0.astype(jnp.int32), y0.astype(jnp.int32), \
            x1.astype(jnp.int32), y1.astype(jnp.int32)
        v = (img[:, y0i, x0i] * (1 - wy) * (1 - wx) + img[:, y1i, x0i] * wy * (1 - wx)
             + img[:, y0i, x1i] * (1 - wy) * wx + img[:, y1i, x1i] * wy * wx)
        in_bound = (x >= 0) & (x <= w - 1) & (y >= 0) & (y <= h - 1)
        return v * in_bound.astype(v.dtype)

    return jax.vmap(sample_one)(data, gx, gy)


@register("SpatialTransformer")
def SpatialTransformer(data, loc, target_shape=(0, 0), transform_type="affine",
                       sampler_type="bilinear", cudnn_off=None):
    """Ref: src/operator/spatial_transformer.cc = GridGenerator + BilinearSampler."""
    from .registry import get_op
    g = get_op("GridGenerator").fn(loc, transform_type="affine", target_shape=target_shape)
    return get_op("BilinearSampler").fn(data, g)


# ---------------------------------------------------------------- matching
@register("_contrib_bipartite_matching", aliases=("bipartite_matching",))
def bipartite_matching(data, is_ascend=False, threshold=None, topk=-1):
    """Greedy bipartite matching on a (B, N, M) or (N, M) score matrix
    (ref: src/operator/contrib/bounding_box.cc:147). Returns (x, y):
    x[b, n] = matched column of row n (-1 unmatched), y[b, m] = matched row
    of column m. Implemented as a lax.fori_loop of argmax-pick-and-mask
    steps — min(N, M) iterations of O(NM) masked argmax, XLA-friendly."""
    squeeze = data.ndim == 2
    scores = data[None] if squeeze else data
    b, n, m = scores.shape
    neg = jnp.asarray(-jnp.inf, scores.dtype)
    sc = -scores if is_ascend else scores
    thr = None if threshold is None else (
        -threshold if is_ascend else threshold)

    limit = min(n, m) if topk is None or topk <= 0 else min(topk, n, m)

    def one(s):
        def body(_, carry):
            s_, x, y = carry
            flat = jnp.argmax(s_)
            i, j = flat // m, flat % m
            best = s_[i, j]
            ok = best > (thr if thr is not None else neg)
            x = jnp.where(ok, x.at[i].set(j.astype(jnp.int32)), x)
            y = jnp.where(ok, y.at[j].set(i.astype(jnp.int32)), y)
            s_ = jnp.where(ok, s_.at[i, :].set(neg).at[:, j].set(neg), s_)
            return s_, x, y

        x0 = jnp.full((n,), -1, jnp.int32)
        y0 = jnp.full((m,), -1, jnp.int32)
        _, x, y = jax.lax.fori_loop(0, limit, body, (s, x0, y0))
        return x.astype(data.dtype), y.astype(data.dtype)

    x, y = jax.vmap(one)(sc)
    if squeeze:
        return x[0], y[0]
    return x, y


# ------------------------------------------------- position-sensitive ROI
def _roi_bilinear_grid(img, yy, xx):
    """Bilinear-sample img (c, h, w) at float grids yy/xx -> (c, *grid)."""
    c, h, w = img.shape
    y0 = jnp.clip(jnp.floor(yy), 0, h - 1)
    x0 = jnp.clip(jnp.floor(xx), 0, w - 1)
    y1 = jnp.clip(y0 + 1, 0, h - 1)
    x1 = jnp.clip(x0 + 1, 0, w - 1)
    wy = jnp.clip(yy, 0, h - 1) - y0
    wx = jnp.clip(xx, 0, w - 1) - x0
    y0i, x0i, y1i, x1i = (a.astype(jnp.int32) for a in (y0, x0, y1, x1))
    return (img[:, y0i, x0i] * (1 - wy) * (1 - wx)
            + img[:, y1i, x0i] * wy * (1 - wx)
            + img[:, y0i, x1i] * (1 - wy) * wx
            + img[:, y1i, x1i] * wy * wx)


@register("_contrib_PSROIPooling", aliases=("PSROIPooling",))
def PSROIPooling(data, rois, spatial_scale=1.0, output_dim=1, pooled_size=7,
                 group_size=0):
    """Position-sensitive ROI pooling (ref: src/operator/contrib/
    psroi_pooling.cc): bin (i, j) of output channel c averages input channel
    c*g*g + i*g + j over that bin. TPU re-design: the reference's exact
    integer-extent average is replaced by a fixed 2x2 bilinear sample grid
    per bin (the ROIAlign discretization) so shapes stay static."""
    g = int(group_size) or int(pooled_size)
    p = int(pooled_size)
    n, c, h, w = data.shape
    sr = 2

    def one_roi(roi):
        batch_id = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = (roi[1] * spatial_scale, roi[2] * spatial_scale,
                          roi[3] * spatial_scale, roi[4] * spatial_scale)
        rw = jnp.maximum(x2 - x1, 0.1)
        rh = jnp.maximum(y2 - y1, 0.1)
        ys = y1 + (jnp.arange(p * sr) + 0.5) * rh / (p * sr)
        xs = x1 + (jnp.arange(p * sr) + 0.5) * rw / (p * sr)
        yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
        v = _roi_bilinear_grid(data[batch_id], yy, xx)  # (c, p*sr, p*sr)
        v = v.reshape(c, p, sr, p, sr).mean(axis=(2, 4))  # (c, p, p)
        # position-sensitive channel select: out[d, i, j] = v[d*g*g + gi*g + gj, i, j]
        v = v.reshape(output_dim, g, g, p, p)
        gi = (jnp.arange(p) * g) // p
        gj = (jnp.arange(p) * g) // p
        return v[:, gi[:, None], gj[None, :], jnp.arange(p)[:, None],
                 jnp.arange(p)[None, :]]

    return jax.vmap(one_roi)(rois)


@register("_contrib_DeformablePSROIPooling",
          aliases=("DeformablePSROIPooling",))
def DeformablePSROIPooling(data, rois, trans=None, spatial_scale=1.0,
                           output_dim=1, group_size=1, pooled_size=7,
                           part_size=0, sample_per_part=2, trans_std=0.0,
                           no_trans=False):
    """Deformable position-sensitive ROI pooling (ref: src/operator/contrib/
    deformable_psroi_pooling.cc): PSROIPooling whose bins are shifted by the
    learned normalized offsets in ``trans`` (N, 2*cls, part, part)."""
    g = int(group_size)
    p = int(pooled_size)
    pt = int(part_size) or p
    n, c, h, w = data.shape
    sr = int(sample_per_part)

    def one_roi(roi, tr):
        batch_id = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = (roi[1] * spatial_scale - 0.5,
                          roi[2] * spatial_scale - 0.5,
                          roi[3] * spatial_scale + 0.5,
                          roi[4] * spatial_scale + 0.5)
        rw = jnp.maximum(x2 - x1, 0.1)
        rh = jnp.maximum(y2 - y1, 0.1)
        bin_h, bin_w = rh / p, rw / p
        # per-bin offsets from trans: (2*cls, pt, pt) -> class 0 layout like
        # the reference's class-agnostic use (cls = output channels share)
        if no_trans or tr is None:
            dy = jnp.zeros((p, p))
            dx = jnp.zeros((p, p))
        else:
            pi = (jnp.arange(p) * pt) // p
            dy = tr[0][pi[:, None], pi[None, :]] * trans_std * rh
            dx = tr[1][pi[:, None], pi[None, :]] * trans_std * rw
        sub = (jnp.arange(sr) + 0.5) / sr
        # grids (p, sr, p, sr): bin (i, j), sub-sample (a, b), both axes
        # shifted by that bin's learned offset (dy, dx)[i, j]
        i_ = jnp.arange(p)[:, None, None, None]
        a_ = sub[None, :, None, None]
        j_ = jnp.arange(p)[None, None, :, None]
        b_ = sub[None, None, None, :]
        full = (p, sr, p, sr)
        yy = jnp.broadcast_to(y1 + (i_ + a_) * bin_h + dy[:, None, :, None],
                              full)
        xx = jnp.broadcast_to(x1 + (j_ + b_) * bin_w + dx[:, None, :, None],
                              full)
        v = _roi_bilinear_grid(data[batch_id],
                               yy.reshape(p * sr, p * sr),
                               xx.reshape(p * sr, p * sr))
        v = v.reshape(c, p, sr, p, sr).mean(axis=(2, 4))
        v = v.reshape(output_dim, g, g, p, p)
        gi = (jnp.arange(p) * g) // p
        return v[:, gi[:, None], gi[None, :], jnp.arange(p)[:, None],
                 jnp.arange(p)[None, :]]

    if trans is None or no_trans:
        tr_in = jnp.zeros((rois.shape[0], 2, pt, pt), data.dtype)
    else:
        # rois carry batch ids; trans is per-image — gather per roi
        ids = rois[:, 0].astype(jnp.int32)
        tr_in = trans[ids, :2]
    return jax.vmap(one_roi)(rois, tr_in)


@register("_contrib_DeformableConvolution", aliases=("DeformableConvolution",))
def DeformableConvolution(data, offset, weight, bias=None, kernel=(3, 3),
                          stride=(1, 1), dilate=(1, 1), pad=(0, 0),
                          num_filter=None, num_group=1,
                          num_deformable_group=1, no_bias=False,
                          workspace=None, layout=None):
    """Deformable convolution v1 (ref: src/operator/contrib/
    deformable_convolution.cc, deformable_im2col.h). NCHW only, like the
    reference.

    TPU re-design: instead of the reference's deformable_im2col CUDA
    kernel, each kernel tap (ky, kx) bilinear-samples the input at
    base_grid + dilation_offset + learned_offset, producing a
    (N, Hout, Wout, C*kh*kw) tensor that contracts with the flattened
    weight on the MXU — the gather feeds one big matmul, which is the
    XLA-friendly shape of im2col.

    ``offset`` is (N, 2*kh*kw*ndg, Hout, Wout), reference channel layout
    offset[:, 2*(dg*kh*kw + k) + {0: y, 1: x}]."""
    kh, kw = kernel
    sh, sw = stride
    dh, dw = dilate
    ph, pw = pad
    n, c, h, w = data.shape
    cout = weight.shape[0]
    ndg = int(num_deformable_group)
    hout = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    wout = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1

    base_y = jnp.arange(hout) * sh - ph   # top-left of each window
    base_x = jnp.arange(wout) * sw - pw
    off = offset.reshape(n, ndg, kh * kw, 2, hout, wout)

    def _zero_pad_bilinear(img, yy, xx):
        """Bilinear sample with ZERO padding outside the image — each of the
        four corners contributes only if it lies in-bounds, so fractional
        taps near the border fade to zero exactly like the reference's
        deformable_im2col (deformable_im2col.h im2col_bilinear), unlike the
        clip-to-edge sampling the ROI ops use."""
        y0f = jnp.floor(yy)
        x0f = jnp.floor(xx)
        wy = yy - y0f
        wx = xx - x0f
        out = 0.0
        for (cy, wyc) in ((y0f, 1 - wy), (y0f + 1, wy)):
            for (cx, wxc) in ((x0f, 1 - wx), (x0f + 1, wx)):
                ok = (cy >= 0) & (cy <= h - 1) & (cx >= 0) & (cx <= w - 1)
                ci = jnp.clip(cy, 0, h - 1).astype(jnp.int32)
                cj = jnp.clip(cx, 0, w - 1).astype(jnp.int32)
                out = out + img[:, ci, cj] * (wyc * wxc * ok)[None]
        return out

    def one_image(img, off_i):
        # img (c, h, w); off_i (ndg, kh*kw, 2, hout, wout)
        cols = []
        cpg = c // ndg  # channels per deformable group
        for k in range(kh * kw):
            ky, kx = k // kw, k % kw
            taps = []
            for dg in range(ndg):
                yy = (base_y[:, None] + ky * dh + off_i[dg, k, 0])
                xx = (base_x[None, :] + kx * dw + off_i[dg, k, 1])
                taps.append(_zero_pad_bilinear(
                    img[dg * cpg:(dg + 1) * cpg], yy, xx))
            cols.append(jnp.concatenate(taps, axis=0))  # (c, hout, wout)
        return jnp.stack(cols, axis=1)  # (c, kh*kw, hout, wout)

    cols = jax.vmap(one_image)(data, off)  # (n, c, kh*kw, hout, wout)
    cols = cols.reshape(n, c * kh * kw, hout * wout)
    wmat = weight.reshape(cout, -1)  # (cout, c/g*kh*kw) with num_group=1
    if num_group == 1:
        out = jnp.einsum("ok,nkp->nop", wmat, cols)
    else:
        cg = c // num_group
        og = cout // num_group
        cols_g = cols.reshape(n, num_group, cg * kh * kw, hout * wout)
        wg = wmat.reshape(num_group, og, cg * kh * kw)
        out = jnp.einsum("gok,ngkp->ngop", wg, cols_g) \
            .reshape(n, cout, hout * wout)
    out = out.reshape(n, cout, hout, wout)
    if bias is not None and not no_bias:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


# ------------------------------------------------------------------- RPN
def _gen_anchors(base_size, ratios, scales):
    """Faster-RCNN anchor generation (ref: src/operator/contrib/
    proposal.cc GenerateAnchors): base box -> ratio enum -> scale enum."""
    import numpy as _np
    base = _np.array([0, 0, base_size - 1, base_size - 1], _np.float32)
    w = base[2] - base[0] + 1
    h = base[3] - base[1] + 1
    cx = base[0] + 0.5 * (w - 1)
    cy = base[1] + 0.5 * (h - 1)
    out = []
    for r in ratios:
        size = w * h
        ws = _np.round(_np.sqrt(size / r))
        hs = _np.round(ws * r)
        for s in scales:
            ws_s, hs_s = ws * s, hs * s
            out.append([cx - 0.5 * (ws_s - 1), cy - 0.5 * (hs_s - 1),
                        cx + 0.5 * (ws_s - 1), cy + 0.5 * (hs_s - 1)])
    return _np.asarray(out, _np.float32)  # (A, 4)


def _proposal_one(scores, deltas, im_info, anchors, feature_stride,
                  pre_n, post_n, thresh, min_size, iou_loss):
    """RPN proposals for ONE image. scores (A, H, W) fg; deltas (A*4, H, W)."""
    a, h, w = scores.shape
    sx = jnp.arange(w, dtype=jnp.float32) * feature_stride
    sy = jnp.arange(h, dtype=jnp.float32) * feature_stride
    # boxes indexed (a, y, x)
    anc = anchors[:, None, None, :]  # (A,1,1,4)
    shift = jnp.stack([sx[None, None, :].repeat(h, 1).repeat(a, 0),
                       sy[None, :, None].repeat(w, 2).repeat(a, 0)], -1)
    boxes = jnp.concatenate([anc[..., :2] + shift, anc[..., 2:] + shift], -1)
    d = deltas.reshape(a, 4, h, w).transpose(0, 2, 3, 1)  # (A,H,W,4)
    wa = boxes[..., 2] - boxes[..., 0] + 1
    ha = boxes[..., 3] - boxes[..., 1] + 1
    cxa = boxes[..., 0] + 0.5 * (wa - 1)
    cya = boxes[..., 1] + 0.5 * (ha - 1)
    if iou_loss:
        x1 = boxes[..., 0] + d[..., 0]
        y1 = boxes[..., 1] + d[..., 1]
        x2 = boxes[..., 2] + d[..., 2]
        y2 = boxes[..., 3] + d[..., 3]
    else:
        cx = d[..., 0] * wa + cxa
        cy = d[..., 1] * ha + cya
        pw = jnp.exp(jnp.clip(d[..., 2], -10, 10)) * wa
        ph = jnp.exp(jnp.clip(d[..., 3], -10, 10)) * ha
        x1, y1 = cx - 0.5 * (pw - 1), cy - 0.5 * (ph - 1)
        x2, y2 = cx + 0.5 * (pw - 1), cy + 0.5 * (ph - 1)
    imh, imw, imscale = im_info[0], im_info[1], im_info[2]
    x1 = jnp.clip(x1, 0, imw - 1)
    y1 = jnp.clip(y1, 0, imh - 1)
    x2 = jnp.clip(x2, 0, imw - 1)
    y2 = jnp.clip(y2, 0, imh - 1)
    ms = min_size * imscale
    keep_sz = ((x2 - x1 + 1) >= ms) & ((y2 - y1 + 1) >= ms)
    sc = jnp.where(keep_sz, scores, -jnp.inf).reshape(-1)
    flat = jnp.stack([x1, y1, x2, y2], -1).reshape(-1, 4)

    k = min(pre_n, sc.shape[0])
    top_sc, top_i = jax.lax.top_k(sc, k)
    top_box = flat[top_i]
    # greedy NMS over the score-ordered top-k. The IoU row for pivot i is
    # computed inside the loop: O(k) live memory instead of a k*k matrix
    # (6000^2 f32 = 144 MB/image at reference defaults, x batch under vmap)
    area = (top_box[:, 2] - top_box[:, 0] + 1) * \
        (top_box[:, 3] - top_box[:, 1] + 1)

    def body(i, keep):
        tl = jnp.maximum(top_box[i, :2], top_box[:, :2])
        br = jnp.minimum(top_box[i, 2:], top_box[:, 2:])
        whi = jnp.maximum(br - tl + 1, 0)
        inter = whi[:, 0] * whi[:, 1]
        iou_row = inter / jnp.maximum(area[i] + area - inter, 1e-12)
        sup = (iou_row > thresh) & (jnp.arange(k) > i) & keep[i]
        return keep & ~sup

    keep = jax.lax.fori_loop(0, k, body, top_sc > -jnp.inf)
    # stable-select first post_n kept boxes (score order preserved)
    rank = jnp.cumsum(keep) - 1
    sel = jnp.where(keep & (rank < post_n), rank, post_n)
    out = jnp.zeros((post_n + 1, 4), top_box.dtype) \
        .at[sel].set(top_box)[:post_n]
    out_sc = jnp.zeros((post_n + 1,), top_sc.dtype).at[sel].set(top_sc)[:post_n]
    nkept = jnp.maximum(jnp.minimum(jnp.sum(keep), post_n), 1)
    # reference pads short lists by repeating; repeat the LAST kept box so
    # the score column stays descending
    idx = jnp.minimum(jnp.arange(post_n), nkept - 1)
    return out[idx], out_sc[idx]


@register("_contrib_MultiProposal", aliases=("MultiProposal",))
def MultiProposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
                  rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
                  scales=(4, 8, 16, 32), ratios=(0.5, 1, 2),
                  feature_stride=16, output_score=False, iou_loss=False):
    """Batched RPN proposal generation (ref: src/operator/contrib/
    multi_proposal.cc). Returns rois (N*post, 5) [batch_idx, x1..y2]
    (+ scores (N*post, 1) when output_score)."""
    n, a2, h, w = cls_prob.shape
    a = a2 // 2
    anchors = jnp.asarray(_gen_anchors(feature_stride, ratios, scales))

    def one(scores_i, deltas_i, info_i):
        return _proposal_one(scores_i, deltas_i, info_i, anchors,
                             feature_stride, int(rpn_pre_nms_top_n),
                             int(rpn_post_nms_top_n), threshold,
                             float(rpn_min_size), iou_loss)

    boxes, scores = jax.vmap(one)(cls_prob[:, a:], bbox_pred, im_info)
    ids = jnp.repeat(jnp.arange(n, dtype=boxes.dtype),
                     int(rpn_post_nms_top_n))
    rois = jnp.concatenate([ids[:, None], boxes.reshape(-1, 4)], axis=1)
    if output_score:
        return rois, scores.reshape(-1, 1)
    return rois


@register("_contrib_Proposal", aliases=("Proposal",))
def Proposal(cls_prob, bbox_pred, im_info, **kwargs):
    """Single-image RPN proposals (ref: src/operator/contrib/proposal.cc)
    — MultiProposal restricted to batch 1, like the reference."""
    from .registry import get_op
    return get_op("_contrib_MultiProposal").fn(cls_prob, bbox_pred, im_info,
                                               **kwargs)


@register("_contrib_switch_moe", aliases=("switch_moe",), num_outputs=2)
def switch_moe(data, router, w1, b1, w2, b2, capacity_factor=1.25):
    """Top-1 switch MoE as a registered op (backs gluon.contrib.nn.SwitchMoE;
    no reference counterpart — SURVEY §2.3 lists MoE as absent upstream).
    data (..., D) is flattened to tokens; returns (out, aux_loss)."""
    from ..parallel.moe import switch_ffn
    dim = data.shape[-1]
    toks = data.reshape(-1, dim)
    out, aux = switch_ffn(toks, router, w1, b1, w2, b2,
                          capacity_factor=capacity_factor)
    return out.reshape(data.shape), aux


@register("_contrib_routed_moe", aliases=("routed_moe",))
def routed_moe(data, router, score_bias, w_gate, w_up, w_down, top_k=1,
               first_expert=0, scale=1.0, grouped=True, router_data=None,
               score="sigmoid", activation="silu", n_group=1, topk_group=1):
    """Top-k routed gated experts without a capacity, over the experts held
    here (backs gluon.contrib.nn.RoutedMoE; mxtpu.parallel.moe.routed_ffn).
    data (..., D) is flattened to tokens; ``router_data`` (the same shape),
    where given, is what the router scores in ``data``'s place."""
    from ..parallel.moe import routed_ffn
    toks = data.reshape(-1, data.shape[-1])
    if router_data is not None:
        router_data = router_data.reshape(toks.shape)
    out = routed_ffn(toks, router, score_bias, w_gate, w_up, w_down,
                     top_k=top_k, first_expert=first_expert, scale=scale,
                     grouped=grouped, router_x=router_data, score=score,
                     activation=activation, n_group=n_group,
                     topk_group=topk_group)
    return out.reshape(data.shape)
