"""Reduction + broadcast-axis op family.

Reference: src/operator/tensor/broadcast_reduce_op_value.cc (+ broadcast_reduce-inl.cuh
hand-tiled CUDA reduction kernels). On TPU a reduction is a single HLO Reduce that XLA
tiles for the VPU, so the whole family is declarative here.

MXNet reduce semantics: ``axis`` may be int/tuple/None, ``keepdims`` bool, and
``exclude=True`` means "reduce over all axes NOT listed" (python/mxnet docs for sum).
"""
from __future__ import annotations

import jax.numpy as jnp

from .registry import register


def _norm_axis(axis, ndim, exclude=False):
    if axis is None:
        return None
    if isinstance(axis, int):
        axis = (axis,)
    axis = tuple(a % ndim for a in axis)
    if exclude:
        axis = tuple(a for a in range(ndim) if a not in axis)
    return axis


def _reduce(name, jfn, aliases=(), as_method=True):
    @register(name, aliases=aliases, as_method=as_method)
    def fn(x, axis=None, keepdims=False, exclude=False, **_ig):
        ax = _norm_axis(axis, x.ndim, exclude)
        return jfn(x, axis=ax, keepdims=keepdims)
    fn.__name__ = name
    return fn


sum_ = _reduce("sum", jnp.sum, aliases=("sum_axis",))
mean = _reduce("mean", jnp.mean)
prod = _reduce("prod", jnp.prod)
nansum = _reduce("nansum", jnp.nansum)
nanprod = _reduce("nanprod", jnp.nanprod)
max_ = _reduce("max", jnp.max, aliases=("max_axis",))
min_ = _reduce("min", jnp.min, aliases=("min_axis",))


@register("_square_sum", wrap=False)
def _square_sum(data, axis=None, keepdims=False, exclude=False, out=None, **_ig):
    """Sum of squares over an axis (ref: src/operator/tensor/square_sum.cc:50,
    square_sum-inl.h). Storage rule mirrors the reference's
    SquareSumForwardInferStorageType: a row_sparse input with axis=1 &
    keepdims=True yields a row_sparse output sharing the input's row ids
    (zero rows contribute zero); every other case is dense — for sparse
    input the stored values alone are reduced, so the dense logical shape
    never materializes."""
    from ..ndarray.ndarray import NDArray, _apply as _ap
    from ..ndarray.sparse import BaseSparseNDArray, RowSparseNDArray
    if isinstance(data, BaseSparseNDArray) and \
            not isinstance(data, RowSparseNDArray):
        # CSR: densify first (reference storage fallback) — the 1-D values
        # buffer is not axis-addressable
        data = data.todense()
    if isinstance(data, RowSparseNDArray):
        ax = _norm_axis(axis, len(data.shape), exclude)
        idx, shape = data._aux["indices"], data.shape
        if ax == (1,) and keepdims:
            vals = _ap(lambda v: jnp.sum(jnp.square(v), axis=1, keepdims=True),
                       (data,), name="_square_sum")
            res = RowSparseNDArray(vals._data, idx, (shape[0], 1))
            res._ag_entry = vals._ag_entry
        elif ax == (1,):
            res = _ap(lambda v: jnp.zeros((shape[0],), v.dtype)
                      .at[idx].add(jnp.sum(jnp.square(v), axis=1)),
                      (data,), name="_square_sum")
        elif ax == (0,):
            res = _ap(lambda v: jnp.sum(jnp.square(v), axis=0,
                                        keepdims=keepdims),
                      (data,), name="_square_sum")
        else:  # full reduction (axis=None or both axes)
            res = _ap(lambda v: jnp.sum(jnp.square(v), keepdims=keepdims),
                      (data,), name="_square_sum")
    else:
        ax = _norm_axis(axis, data.ndim if isinstance(data, NDArray)
                        else jnp.ndim(data), exclude)
        res = _ap(lambda v: jnp.sum(jnp.square(v), axis=ax, keepdims=keepdims),
                  (data,), name="_square_sum")
    if out is not None:
        return res.copyto(out)  # copyto moves sparse aux with the values
    return res


@register("norm", as_method=True)
def norm(x, ord=2, axis=None, keepdims=False, **_ig):  # noqa: A002
    """L1/L2 norm (ref: broadcast_reduce_op_value.cc norm)."""
    ax = _norm_axis(axis, x.ndim)
    if ord == 1:
        return jnp.sum(jnp.abs(x), axis=ax, keepdims=keepdims)
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=keepdims))


@register("argmax", as_method=True)
def argmax(x, axis=None, keepdims=False):
    r = jnp.argmax(x, axis=axis, keepdims=keepdims).astype(jnp.float32)
    return r


@register("argmin", as_method=True)
def argmin(x, axis=None, keepdims=False):
    return jnp.argmin(x, axis=axis, keepdims=keepdims).astype(jnp.float32)


@register("argmax_channel")
def argmax_channel(x):
    """argmax over axis 1 (ref: broadcast_reduce_op_index.cc argmax_channel)."""
    return jnp.argmax(x, axis=1).astype(jnp.float32)


@register("broadcast_axis", aliases=("broadcast_axes",), as_method=True)
def broadcast_axis(x, axis=(), size=()):
    if isinstance(axis, int):
        axis, size = (axis,), (size,)
    shape = list(x.shape)
    for a, s in zip(axis, size):
        shape[a] = s
    return jnp.broadcast_to(x, tuple(shape))


@register("broadcast_to", as_method=False)
def broadcast_to(x, shape=()):
    # MXNet: 0 in target shape means "keep source dim"
    tgt = tuple(x.shape[i] if s == 0 else s for i, s in enumerate(shape))
    return jnp.broadcast_to(x, tgt)


@register("broadcast_like", as_method=False)
def broadcast_like(x, like):
    return jnp.broadcast_to(x, like.shape)


@register("pick", as_method=True)
def pick(x, index, axis=-1, keepdims=False, mode="clip"):
    """Pick per-row elements by index (ref: broadcast_reduce_op_index.cc pick)."""
    idx = index.astype(jnp.int32)
    if mode == "clip":
        idx = jnp.clip(idx, 0, x.shape[axis] - 1)
    else:
        idx = jnp.mod(idx, x.shape[axis])
    picked = jnp.take_along_axis(x, jnp.expand_dims(idx, axis=axis), axis=axis)
    if not keepdims:
        picked = jnp.squeeze(picked, axis=axis)
    return picked


@register("L2Normalization")
def L2Normalization(x, eps=1e-10, mode="instance"):
    """Ref: src/operator/l2_normalization.cc."""
    if mode == "instance":
        ax = tuple(range(1, x.ndim))
    elif mode == "channel":
        ax = (1,)
    elif mode == "spatial":
        ax = tuple(range(2, x.ndim))
    else:
        raise ValueError("unknown mode " + mode)
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=True) + eps)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    """Fused CE (ref: src/operator/loss_binary_op.cc). Returns scalar sum."""
    from .nn import log_softmax_at
    return -jnp.sum(log_softmax_at(data, label))
