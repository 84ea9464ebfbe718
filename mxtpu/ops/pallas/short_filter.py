"""The short filter of a Kimi-Delta-Attention layer (``ops/nn.py``:
``_contrib_kda_conv``) as a pair of Pallas kernels: a depthwise causal
filter of ``L`` taps along the sequence, SiLU, and where ``head_dim`` > 0
each head's L2 norm,

    c[t] = sum_j w[j] x[t - (L - 1) + j]        (x zero before the start)
    y    = c * sigmoid(c)
    z    = y * rsqrt(sum_head(y^2) + 1e-6)      (head_dim > 0)

float32 arithmetic on the input as it is stored, ONE rounding to the
input's dtype: ``ops/nn.py:_kda_conv_plain`` is the definition, the path
off the TPU and what the tests compare with.

``kda_conv_fwd`` takes a tile of ``[rows, cols]`` of ``[B, T, D]``, ``cols``
whole heads (a head is whole lane widths), and the 16 rows before it as a
second view of the same array (zeros at a sequence's start): one read of
the data, one write of the result; the shifted rows are sublane rotations
of the tile in VMEM, never a padded copy in HBM. ``kda_conv_bwd`` reads the
data and the cotangent (each with the 16 rows before and after the tile),
computes ``c``, the SiLU and the norm again in VMEM, and writes ``d data[t]
= sum_j w[j] dc[t + (L - 1) - j]`` once; ``d weight[j] = sum_t dc[t] x[t -
(L - 1) + j]`` adds up in float32 in the output's block, which stays in
VMEM while a column's batch rows and row tiles pass, eight partial sums a
tap and channel (a vreg's sublanes; the caller adds the eight).

The tap loop and its halo are one function, :func:`_taps`, of (the tile
with its halo rows, the weights, where the tile lies in it, the direction);
the epilogue (:func:`_silu_norm` and its derivative) lies outside it: a
gated filter (LFM2's ``short_conv``: ROADMAP S4) is the same taps under
another prologue and epilogue.

Each kernel is a ``jax.jit`` of its own and not inlined: a step traces and
lowers it once a (shape, dtype, ``head_dim``) however many layers call it.
:func:`refusal` says why a call cannot take the kernels (platform, dtype,
lanes); the caller then keeps the plain function. ``MXTPU_FLASH_INTERPRET=1``
runs them through the Pallas interpreter (the tier-1 parity path).
"""
from __future__ import annotations

import functools
import importlib

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# the module (the package exports the function under the same name): its
# platform check and interpreter flag are this kernel's too
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["refusal", "forward", "backward"]

_F32 = jnp.float32
_LANES = 128
# rows of a halo view: a bf16 tile's sublanes (a float32 tile has 8), so a
# view of them is a block of either dtype; the filter may reach that far
_HALO = 16
_EPS = 1e-6
# the tile: rows a grid step takes of the sequence and the most columns
# (whole heads), on one v5e the fastest of the sweep in PERF.md section 6,
# PR 43; and the VMEM asked of Mosaic (of a v5e core's 128 MiB): the
# backward's float32 intermediates of a tile outgrow its default 16 MiB
_ROWS, _COLS = 512, 512
_VMEM_LIMIT_BYTES = 64 << 20


def refusal(data, weight, head_dim):
    """Why this call cannot take the kernels, or None: ``"platform"`` (not
    a TPU, and no interpreter asked for), ``"dtype"`` (data that is not
    bf16, float16 or float32), ``"lanes"`` (a ``D`` or a head that is not
    whole lane widths; the interpreter takes any), ``"taps"`` (a filter
    longer than the halo's rows)."""
    interpret = _fa._interpret()
    if _fa._platform() != "tpu" and not interpret:
        return "platform"
    if data.dtype not in (jnp.bfloat16, jnp.float16, jnp.float32):
        return "dtype"
    d = data.shape[-1]
    if head_dim and d % head_dim:
        return "lanes"
    if not interpret and (d % _LANES or head_dim % _LANES):
        return "lanes"
    if not 1 <= weight.shape[1] <= _HALO:
        return "taps"
    return None


def _tiles(t, d, head_dim, tiles):
    """(rows, cols, unit) of a grid step: ``_ROWS`` rows, fewer where the
    sequence is shorter (whole halo views, so a multiple of ``_HALO``);
    ``unit`` the columns the body works at a time, a head or a lane width;
    ``cols`` the most of them that divide ``D`` and are at most ``_COLS``."""
    unit = head_dim or (_LANES if d % _LANES == 0 else d)
    if tiles is not None:
        return tiles + (unit,)
    rows = min(_ROWS, -(-t // _HALO) * _HALO)
    cols = max(c for c in range(unit, d + 1, unit)
               if d % c == 0 and (c <= _COLS or c == unit))
    return rows, cols, unit


# The bodies spell their arithmetic with ``lax`` primitives, not ``jnp``
# functions or operators: under a trace each of those is a jitted call
# traced on its own (an event for every ``jax.monitoring`` listener: PERF.md
# section 6, PR 42).
def _f32(x):
    return lax.convert_element_type(x, _F32)


def _rows_of(x, lo, n):
    return lax.slice_in_dim(x, lo, lo + n, axis=0)


def _taps(xe, w, lo, n, back=False):
    """``out[i] = sum_j w[j] xe[lo + i - (L - 1) + j]`` for ``i`` in ``[0,
    n)``; ``back``: the transpose, ``sum_j w[j] xe[lo + i + (L - 1) - j]``.
    ``xe`` float32 [R, C] holds every row that is read (the tile with its
    halo: ``L - 1`` rows before ``lo``, or as many after ``lo + n``), ``w``
    float32 [L, C]. A shifted operand is a sublane rotation of ``xe``
    (what wraps around lies outside ``[lo, lo + n)``), in registers: an
    offset load of a float32 copy in VMEM is forwarded from its store and
    becomes the same rotation at more operations (PERF.md section 6, PR
    43). Returns the sum and the ``L`` shifted operands (the weight's
    gradient multiplies them again)."""
    from jax.experimental.pallas import tpu as pltpu
    taps, size = w.shape[0], xe.shape[0]
    out, shifted = None, []
    for j in range(taps):
        shift = (j - (taps - 1) if back else (taps - 1) - j) % size
        rolled = pltpu.roll(xe, shift, 0) if shift else xe
        shifted.append(_rows_of(rolled, lo, n))
        term = lax.mul(shifted[-1], lax.broadcast_in_dim(
            lax.slice_in_dim(w, j, j + 1, axis=0), (n, w.shape[1]), (0, 1)))
        out = term if out is None else lax.add(out, term)
    return out, shifted


def _head_sum(x):
    """A head's [n, head_dim] -> its rows' sums, broadcast back."""
    return lax.broadcast_in_dim(lax.reduce(
        x, np.float32(0), lax.add, (1,)), x.shape, (0,))


def _silu_norm(c, normed):
    """The epilogue after the taps, on one head's columns (``normed``) or
    on any: -> (z, s, r), ``s = sigmoid(c)``, ``r`` the norm's factor
    broadcast over the columns (None without a norm), ``z = c s r``."""
    s = lax.logistic(c)
    y = lax.mul(c, s)
    if not normed:
        return y, s, None
    r = lax.rsqrt(lax.add(_head_sum(lax.mul(y, y)), np.float32(_EPS)))
    return lax.mul(y, r), s, r


def _silu_norm_vjp(g, c, z, s, r):
    """``d c`` from the epilogue's cotangent ``g``."""
    if r is not None:
        # d y = r (g - z sum_head(g z))
        g = lax.mul(r, lax.sub(g, lax.mul(z, _head_sum(lax.mul(g, z)))))
    # d silu = s (1 + c (1 - s))
    one = np.float32(1)
    return lax.mul(g, lax.mul(s, lax.add(one, lax.mul(
        c, lax.sub(one, s)))))


def _view(ref, lanes, absent):
    """float32 of a halo view's ``lanes``, zeros where the sequence has no
    such rows (``absent``: the tile is the sequence's first, or its last)."""
    x = _f32(ref[:, lanes])
    return lax.select(lax.broadcast(absent, x.shape),
                      jnp.zeros(x.shape, _F32), x)


# A grid step works a strip of ``unit`` columns at a time (a head, or a
# lane width), each through the whole chain: Mosaic emits an operation
# over all of its operand's vregs before the next, and the scheduler keeps
# a strip's chain in registers where a whole tile's spills (PERF.md section
# 6, PR 43).
def _fwd_kernel(before, x, w, out, *, unit, normed):
    rows, cols = x.shape
    first = lax.eq(pl.program_id(2), np.int32(0))
    for at in range(0, cols, unit):
        lanes = slice(at, at + unit)
        xe = lax.concatenate([_view(before, lanes, first),
                              _f32(x[:, lanes])], 0)
        c, _ = _taps(xe, w[:, lanes], _HALO, rows)
        out[:, lanes] = lax.convert_element_type(
            _silu_norm(c, normed)[0], out.dtype)


def _bwd_kernel(x_before, x, x_after, g, g_after, w, dx, dw, *, unit,
                normed):
    """A grid step (column tile, batch row, row tile): ``dc`` over the tile
    AND the halo after it (the filter's transpose reads it), from ``c``
    over the same rows."""
    rows, cols = x.shape
    taps = w.shape[0]
    t, n_t = pl.program_id(2), pl.num_programs(2)
    first = lax.eq(t, np.int32(0))
    last = lax.eq(t, lax.sub(n_t, np.int32(1)))

    @pl.when(lax.bitwise_and(lax.eq(pl.program_id(1), np.int32(0)), first))
    def _zero():
        dw[...] = jnp.zeros(dw.shape, _F32)

    for at in range(0, cols, unit):
        lanes = slice(at, at + unit)
        xe = lax.concatenate([_view(x_before, lanes, first),
                              _f32(x[:, lanes]),
                              _view(x_after, lanes, last)], 0)
        # a zero cotangent past the sequence's end: dc is zero there
        ge = lax.concatenate([_f32(g[:, lanes]),
                              _view(g_after, lanes, last)], 0)
        wj = w[:, lanes]
        c, shifted = _taps(xe, wj, _HALO, rows + _HALO)
        z, s, r = _silu_norm(c, normed)
        dc = _silu_norm_vjp(ge, c, z, s, r)
        dx[:, lanes] = lax.convert_element_type(
            _taps(dc, wj, 0, rows, back=True)[0], dx.dtype)
        own = _rows_of(dc, 0, rows)
        for j in range(taps):   # eight partial sums a tap: a vreg's rows
            prod = lax.mul(own, _rows_of(shifted[j], 0, rows))
            dw[j, :, lanes] = lax.add(dw[j, :, lanes], lax.reduce(
                lax.reshape(prod, (rows // 8, 8, unit)), np.float32(0),
                lax.add, (0,)))


def _plan(data, weight, head_dim, tiles):
    """What both kernels' calls share: the data as [B, T', D] (T' whole
    tiles: rows of zeros after the end change nothing before it), the
    weights as float32 [L, D], the tile, the grid and the specs' makers."""
    t, d = data.shape[-2:]
    rows, cols, unit = _tiles(t, d, head_dim, tiles)
    x = data.reshape((-1, t, d))
    pad = -t % rows
    w = weight.astype(_F32).T
    per = rows // _HALO
    n_t = (t + pad) // rows
    n_halo = (t + pad) // _HALO

    def tile(j, b, i):
        return (b, i, j)

    def before(j, b, i):
        return (b, jnp.maximum(i * per - 1, 0), j)

    def after(j, b, i):
        return (b, jnp.minimum((i + 1) * per, n_halo - 1), j)

    specs = {
        "tile": pl.BlockSpec((None, rows, cols), tile),
        "before": pl.BlockSpec((None, _HALO, cols), before),
        "after": pl.BlockSpec((None, _HALO, cols), after),
        "w": pl.BlockSpec((w.shape[0], cols), lambda j, b, i: (0, j)),
    }
    grid = (d // cols, x.shape[0], n_t)
    kernel = dict(unit=unit, normed=head_dim > 0)
    return x, pad, w, specs, grid, kernel


def _padded(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _params(interpret, name):
    params = dict(interpret=interpret, name=name)
    if not interpret:       # Mosaic-only hints: the interpreter takes none
        from jax.experimental.pallas import tpu as pltpu
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    return params


def forward(data, weight, head_dim):
    """``_kda_conv_plain(head_dim, data, weight)`` through ``kda_conv_fwd``;
    ``refusal(data, weight, head_dim)`` is None."""
    return _forward(data, weight, head_dim=head_dim,
                    interpret=_fa._interpret())


def backward(data, weight, g, head_dim):
    """``(d data, d weight)`` of :func:`forward` for the cotangent ``g``,
    through ``kda_conv_bwd``: one call, the forward computed again in
    VMEM."""
    return _backward(data, weight, g, head_dim=head_dim,
                     interpret=_fa._interpret())


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret",
                                             "tiles"))
def _forward(data, weight, *, head_dim, interpret=False, tiles=None):
    """:func:`forward`, jitted and not inlined. ``tiles`` (rows, columns)
    is for measurements and tests only: nothing in the package passes it."""
    x, pad, w, specs, grid, kernel = _plan(data, weight, head_dim, tiles)
    t = data.shape[-2]
    xp = _padded(x, pad)
    n = x.size
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, **kernel), grid=grid,
        in_specs=[specs["before"], specs["tile"], specs["w"]],
        out_specs=specs["tile"],
        out_shape=jax.ShapeDtypeStruct(xp.shape, data.dtype),
        cost_estimate=pl.CostEstimate(
            flops=(2 * w.shape[0] + 8) * n, transcendentals=n,
            bytes_accessed=2 * n * data.dtype.itemsize),
        **_params(interpret, "kda_conv_fwd"))(xp, xp, w)
    return out[:, :t].reshape(data.shape)


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret",
                                             "tiles"))
def _backward(data, weight, g, *, head_dim, interpret=False, tiles=None):
    """:func:`backward`, jitted and not inlined (``tiles``: as
    :func:`_forward`'s)."""
    x, pad, w, specs, grid, kernel = _plan(data, weight, head_dim, tiles)
    t, d = data.shape[-2:]
    taps = w.shape[0]
    xp, gp = _padded(x, pad), _padded(g.reshape(x.shape), pad)
    n = x.size
    halo = [specs["before"], specs["tile"], specs["after"]]
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, **kernel), grid=grid,
        in_specs=halo + halo[1:] + [specs["w"]],
        out_specs=[specs["tile"],
                   pl.BlockSpec((taps, 8, specs["w"].block_shape[1]),
                                lambda j, b, i: (0, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, data.dtype),
                   jax.ShapeDtypeStruct((taps, 8, d), _F32)],
        cost_estimate=pl.CostEstimate(
            flops=(6 * taps + 24) * n, transcendentals=n,
            bytes_accessed=3 * n * data.dtype.itemsize),
        **_params(interpret, "kda_conv_bwd"))(xp, xp, xp, gp, gp, w)
    return (dx[:, :t].reshape(data.shape),
            jnp.sum(dw, axis=1).T.astype(weight.dtype))
