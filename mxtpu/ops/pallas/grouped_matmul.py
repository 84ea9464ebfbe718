"""Grouped matrix products over rows sorted by group, as one Pallas kernel
in the three forms a routed expert layer uses (``parallel/moe.py``):

* ``ROWS``: ``a[rows of e] @ b[e]``, ``a`` [rows, K], ``b`` [H, K, N];
* ``ROWS_T``: ``a[rows of e] @ b[e].T`` with ``b`` [H, N, K] taken as it
  lies and contracted on its last dimension (the MXU loads the stationary
  operand transposed at no cost: no ``swapaxes`` copy of the weights);
* ``WEIGHTS``: every group's ``a[rows of e].T @ b[rows of e]``, ``a``
  [rows, K], ``b`` [rows, N], [H, K, N]: the weights' gradients.

The rows lie sorted by group and ``sizes`` [H] says how many each group
has; what lies past the last group belongs to none. A grid step takes one
row tile for one group. Which, it reads from a scalar table built from
``sizes`` (:func:`tile_table`) and prefetched: a tile that straddles two
groups is visited once a group under a row mask, a tile past the last
group is not visited at all (the grid's length is the table's, a device
value), and an empty group is visited once so that ``WEIGHTS`` writes its
zeros. The table depends on ``sizes``, the row count and the row tile
alone, so one table serves every product of a branch of the layer.

The arithmetic is what the framework's MXU policy asks of any contraction
(``ops/precision_util.py:contract_acc``): operands in their own dtype in
one pass, a float32 accumulator (a VMEM scratch that stays over the whole
contraction), ONE rounding to the operands' dtype, in the last contraction
step, and one write. ``ROWS`` / ``ROWS_T`` never write a row past the last
group (it holds what the memory held); an output row tile that a group
shares is written whole, each visit keeping the other groups' rows.

:func:`grouped_matmul` is a ``jax.jit`` of its own and not inlined: a step
traces it once for each distinct (shapes, dtype, form) and lowers it to one
function that every branch and layer with that shape calls. Tile sizes are
a function of the static shapes alone (:func:`row_tile`, :func:`_tiles`).
:func:`refusal` says why a product cannot take the kernel (platform,
operands wider than 2 bytes, a width that the 128 lanes do not tile); the
caller then keeps XLA's ``ragged_dot``. ``MXTPU_FLASH_INTERPRET=1`` runs
the kernel through the Pallas interpreter (the tier-1 parity path).
"""
from __future__ import annotations

import functools
import importlib
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# the module (the package exports the function under the same name): its
# platform check and interpreter flag are this kernel's too
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["ROWS", "ROWS_T", "WEIGHTS", "refusal", "row_tile", "tile_table",
           "grouped_matmul"]

ROWS, ROWS_T, WEIGHTS = "rows", "rows_t", "weights"

_F32 = jnp.float32
_LANES = 128
# the rows a grid step takes, and the fewest: a 128-row tile still fills
# the MXU's 128 x 128 array, a smaller one would not
_ROW_TILE, _ROW_TILE_MIN = 256, 128
# VMEM bytes the block that stays while rows stream may take (a group's
# weights, both buffers; ``WEIGHTS``' output, both buffers and the float32
# accumulator), and the limit asked of Mosaic for the whole kernel (of a
# v5e core's 128 MiB; its default, 16 MiB, holds no expert's weights whole)
_RESIDENT_BYTES = 32 << 20
_VMEM_LIMIT_BYTES = 64 << 20


class TileTable(NamedTuple):
    """:func:`tile_table`'s answer, int32; all but the last are prefetched
    to SMEM, the last is the grid's length."""
    offsets: jax.Array      # (H + 1,) the row each group starts at
    group_of: jax.Array     # (visits,) the group a grid step works for
    tile_of: jax.Array      # (visits,) the row tile it takes
    n_visits: jax.Array     # () the grid steps that are visits at all


def refusal(a, b):
    """Why this product cannot take the kernel, or None: ``"platform"``
    (not a TPU, and no interpreter asked for), ``"dtype"`` (operands wider
    than 2 bytes: float32 takes ``HIGHEST`` passes under the framework's
    policy), ``"lanes"`` (a width of either operand that 128 lanes do not
    tile)."""
    if _fa._platform() != "tpu" and not _fa._interpret():
        return "platform"
    if a.dtype != b.dtype or a.dtype.itemsize > 2:
        return "dtype"
    if a.shape[-1] % _LANES or b.shape[-1] % _LANES or (
            b.ndim == 3 and b.shape[1] % _LANES):
        return "lanes"
    return None


def row_tile(rows, held):
    """Rows a grid step takes at ``rows`` sorted rows over ``held`` groups:
    256 (on one v5e 1-4% faster than 128 or 512 at every rung of the expert
    cells: PERF.md section 6, PR 42), halved while an even share of the
    rows is smaller (a straddled tile is visited once a group at full
    price: eight groups of 192 rows in six tiles of 256 would be thirteen
    visits for six tiles' rows), never under 128; all of them where there
    are fewer."""
    tile = _ROW_TILE
    while tile > _ROW_TILE_MIN and tile * held > rows:
        tile //= 2
    return min(tile, rows)


def _divisor(width, most):
    """The largest multiple of 128 that divides ``width`` and is at most
    ``most`` (``width`` is a multiple of 128)."""
    return max(t for t in range(_LANES, width + 1, _LANES)
               if width % t == 0 and (t <= most or t == _LANES))


def _tiles(k, n, itemsize, form):
    """(contraction tile, output-column tile) of a product of widths ``k``
    and ``n``: both whole where the block that stays resident fits
    ``_RESIDENT_BYTES``, the larger halved until it does. Whole, a group's
    weights are fetched once a group however many row tiles it has, and
    ``WEIGHTS`` reads each operand once."""
    each = 2 * itemsize + (4 if form == WEIGHTS else 0)
    tk, tn = k, n
    while tk * tn * each > _RESIDENT_BYTES and max(tk, tn) > _LANES:
        if tk >= tn:
            tk = _divisor(k, tk // 2)
        else:
            tn = _divisor(n, tn // 2)
    return tk, tn


@functools.partial(jax.jit, static_argnums=(1, 2))
def tile_table(sizes, rows, tile):
    """The grid's table for ``rows`` rows in tiles of ``tile`` over groups
    of ``sizes`` rows (their sum at most ``rows``): a group with rows is
    visited once for every tile it touches, in order, an empty one once
    (at the tile its start lies in), so tiles ascend and the visits of one
    tile, and of one group, are consecutive; there are at most ``tiles +
    H - 1``, and ``n_visits`` says how many. Jitted like the kernel: a
    step traces and lowers it once a (rows, tile, H), not once a branch
    and layer (its few dozen small array operations are each a trace of
    their own to every ``jax.monitoring`` listener). Everything is a
    comparison and a sum over [visits, H] or [H, H]: no scan, no gather."""
    held = sizes.shape[0]
    tiles = -(-rows // tile)
    length = tiles + held - 1
    sizes = sizes.astype(jnp.int32)
    upto_me = np.tri(held, dtype=bool)            # [g, j]: j <= g
    ends = jnp.sum(jnp.where(upto_me, sizes[None, :], 0), axis=1)
    starts = ends - sizes
    visits = jnp.where(sizes > 0, -(-ends // tile) - starts // tile, 1)
    last = jnp.sum(jnp.where(upto_me, visits[None, :], 0), axis=1)
    first = last - visits                         # a group's first visit
    visit = np.arange(length, dtype=np.int32)[:, None]
    mine = (first[None, :] <= visit) & (visit < last[None, :])
    group_of = jnp.minimum(jnp.sum(last[None, :] <= visit, axis=1,
                                   dtype=jnp.int32), held - 1)
    tile_of = jnp.sum(jnp.where(
        mine, jnp.minimum(starts // tile, tiles - 1)[None, :] + visit
        - first[None, :], 0), axis=1, dtype=jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return TileTable(offsets, group_of, jnp.minimum(tile_of, tiles - 1),
                     last[-1])


# The kernels' bodies spell their scalar and mask arithmetic with ``lax``
# primitives, not ``jnp`` functions or operators: under a trace each of
# those is a jitted call traced on its own (half a millisecond, and an
# event for every ``jax.monitoring`` listener), three quarters of a body's
# trace, and a step traces a body for every rung and form.
def _dot(a, b, dims, widen):
    """One MXU pass over the operands as they are, float32 out (DEFAULT
    said out loud: the package's global ``float32`` default would ask
    Mosaic for a multi-pass contraction); ``widen``, under the interpreter,
    the same numbers from the operands widened (XLA:CPU has no bf16 x bf16
    = f32 dot)."""
    precision = lax.Precision.DEFAULT
    if widen:
        a, b, precision = lax.convert_element_type(a, _F32), \
            lax.convert_element_type(b, _F32), lax.Precision.HIGHEST
    return lax.dot_general(a, b, ((dims), ((), ())), precision=precision,
                           preferred_element_type=_F32)


def _group(offsets, group_of, visit):
    """The group grid step ``visit`` works for, and its rows [start, end)."""
    group = group_of[visit]
    return group, offsets[group], offsets[lax.add(group, np.int32(1))]


def _mine(row0, shape, start, end):
    """``shape`` bools: which rows of the tile that starts at ``row0`` lie
    in [start, end)."""
    row = lax.add(row0, lax.broadcasted_iota(jnp.int32, shape, 0))
    return lax.bitwise_and(lax.ge(row, start), lax.lt(row, end))


def _rows_kernel(offsets, group_of, tile_of, a, b, out, acc, *, dims,
                 k_tiles, widen):
    """A grid step of ``ROWS`` / ``ROWS_T``: (column tile, visit,
    contraction tile), the contraction innermost."""
    visit, k = pl.program_id(1), pl.program_id(2)
    _, start, end = _group(offsets, group_of, visit)

    @pl.when(lax.eq(k, np.int32(0)))
    def _zero():
        acc[...] = jnp.zeros(acc.shape, _F32)

    @pl.when(lax.gt(end, start))    # an empty group's visit is WEIGHTS' own
    def _step():
        acc[...] += _dot(a[...], b[...], dims, widen)

        @pl.when(lax.eq(k, np.int32(k_tiles - 1)))
        def _write():
            row0 = lax.mul(tile_of[visit], np.int32(acc.shape[0]))
            out[...] = lax.convert_element_type(lax.select(
                _mine(row0, acc.shape, start, end), acc[...],
                lax.convert_element_type(out[...], _F32)), out.dtype)


def _weights_kernel(offsets, group_of, tile_of, a, b, out, acc, *, widen):
    """A grid step of ``WEIGHTS``: (column tile, contraction-row tile,
    visit), the visits innermost: a group's row tiles add into the
    accumulator and its last visit writes."""
    visit, last = pl.program_id(2), lax.sub(pl.num_programs(2), np.int32(1))
    group, start, end = _group(offsets, group_of, visit)
    rows = np.int32(a.shape[0])
    row0 = lax.mul(tile_of[visit], rows)
    one, dims = np.int32(1), ((0,), (0,))

    @pl.when(lax.bitwise_or(lax.eq(visit, np.int32(0)), lax.ne(
        group_of[lax.max(lax.sub(visit, one), np.int32(0))], group)))
    def _zero():
        acc[...] = jnp.zeros(acc.shape, _F32)

    whole = lax.bitwise_and(lax.le(start, row0),
                            lax.le(lax.add(row0, rows), end))

    @pl.when(whole)
    def _inside():
        acc[...] += _dot(a[...], b[...], dims, widen)

    @pl.when(lax.bitwise_and(lax.bitwise_not(whole), lax.gt(end, start)))
    def _straddling():
        # a select, not a product: a row of another group, or of none, may
        # hold anything (NaN among it)
        ours = [lax.convert_element_type(lax.select(
            _mine(row0, x.shape, start, end),
            lax.convert_element_type(x[...], _F32),
            jnp.zeros(x.shape, _F32)), x.dtype) for x in (a, b)]
        acc[...] += _dot(*ours, dims, widen)

    @pl.when(lax.bitwise_or(lax.eq(visit, last), lax.ne(
        group_of[lax.min(lax.add(visit, one), last)], group)))
    def _write():
        out[...] = lax.convert_element_type(acc[...], out.dtype)


def grouped_matmul(a, b, table, form):
    """The product ``form`` names (the module's docstring) of ``a`` and
    ``b`` over the groups of ``table``, :func:`tile_table` at
    ``row_tile(rows, held)``; ``refusal(a, b)`` is None."""
    return _call(a, b, table, form=form, interpret=_fa._interpret())


@functools.partial(jax.jit, static_argnames=("form", "interpret", "tiles"))
def _call(a, b, table, *, form, interpret=False, tiles=None):
    """:func:`grouped_matmul`, jitted and not inlined: a caller inside a
    larger program traces and lowers it once a (shapes, dtype, form).
    ``tiles`` (row, contraction, column; the row tile is the table's) is
    for measurements only: nothing in the package passes it."""
    from jax.experimental.pallas import tpu as pltpu
    rows, held = a.shape[0], table.offsets.shape[0] - 1
    k, n = a.shape[1], b.shape[2 if form == ROWS else 1]
    tm = row_tile(rows, held)
    tk, tn = _tiles(k, n, a.dtype.itemsize, form)
    if tiles is not None:
        tm, tk, tn = tiles
    params = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name="grouped_matmul_" + form)
    n_visits = table.n_visits
    if form == WEIGHTS:
        return pl.pallas_call(
            functools.partial(_weights_kernel, widen=interpret),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(n // tn, k // tk, n_visits),
                in_specs=[
                    pl.BlockSpec((tm, tk), lambda j, i, v, o, g, t:
                                 (t[v], i)),
                    pl.BlockSpec((tm, tn), lambda j, i, v, o, g, t:
                                 (t[v], j))],
                out_specs=pl.BlockSpec(
                    (None, tk, tn), lambda j, i, v, o, g, t: (g[v], i, j)),
                scratch_shapes=[pltpu.VMEM((tk, tn), _F32)]),
            out_shape=jax.ShapeDtypeStruct((held, k, n), a.dtype),
            cost_estimate=pl.CostEstimate(
                flops=2 * rows * k * n, transcendentals=0,
                bytes_accessed=a.dtype.itemsize * (
                    rows * k * (n // tn) + rows * n * (k // tk)
                    + held * k * n)),
            **params)(*table[:3], a, b)
    if form == ROWS_T:
        dims = ((1,), (1,))
        weights = pl.BlockSpec((None, tn, tk), lambda j, v, i, o, g, t:
                               (g[v], j, i))
    else:
        dims = ((1,), (0,))
        weights = pl.BlockSpec((None, tk, tn), lambda j, v, i, o, g, t:
                               (g[v], i, j))
    return pl.pallas_call(
        functools.partial(_rows_kernel, dims=dims, k_tiles=k // tk,
                          widen=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_visits, k // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, v, i, o, g, t:
                             (t[v], i)),
                weights],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, i, o, g, t:
                                   (t[v], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), _F32)]),
        out_shape=jax.ShapeDtypeStruct((rows, n), a.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=a.dtype.itemsize * (
                rows * k * (n // tn) + held * k * n + rows * n)),
        **params)(*table[:3], a, b)
