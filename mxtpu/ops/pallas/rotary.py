"""Rotary position embedding (``ops/nn.py``: ``rotary``) of q or k laid
heads first, the turn as ONE Pallas kernel each way, in place:

    out[b, h, t, :] = x[b, h, t, :] * cos[t] + (x[b, h, t, :] @ swap) * sin[t]

float32 arithmetic on the input as it is stored, ONE rounding to the
input's dtype: ``ops/nn.py:rotary`` followed by ``.transpose(0, 2, 1, 3)``
is the definition, the path off the TPU and what the tests compare with.

``rotary_turn`` takes a block of ``[heads, rows, D]`` of ``x`` ``[B, H, T,
D]`` with the rows' two tables ``[rows, D]`` float32 and writes the block
where it stood (``input_output_aliases``: no second array of q's size is
alive): one read of the data, one write. The partner of an entry is what the
plain function takes, a product with the signed permutation ``swap`` ``[D,
D]``, on the MXU, which has nothing else to do here: exact (one non-zero a
column; one bf16 pass, or float32 in its bf16 pieces). A lane rotation gives
the same numbers and was measured first: 4.4 XLU operations a vreg, 1.90 ms
a call of 72 heads where its bytes take 0.82 (PERF.md section 6, PR 47).
The entries past a partial width read ``cos = 1, sin = 0`` from the tables
and no partner from ``swap``.

The move to heads first is NOT the kernel's: the caller transposes, and XLA
folds that into the fusion that scales a per-head norm, which it has to run
anyway and which reads the projection's float32 output once. A kernel that
read rows first and wrote heads first was built first and lost: XLA then
spelt the norm's scaling in the rows-first layout as a broadcast and a
relayout of float32 arrays of q's size (PERF.md section 6, PR 47).

``rotary_unturn`` is the transpose, the same body with the sine negated (a
pair's two entries share their angle, so ``(g * sin) @ swap^T = -(g @ swap)
* sin``). Nothing is kept between the two but the tables, which are
positions alone and are computed again.

:func:`refusal` says why a call cannot take the kernels (platform, dtype,
lanes, width); the caller then keeps the plain function. The caller
(``ops/nn.py:_rotary_kernel_pass``) holds tables and kernel under one
``jax.jit`` with ``inline=True``: a step traces them once a (shape, dtype,
form) and not once a layer, and each call keeps its caller's scope.
``MXTPU_FLASH_INTERPRET=1`` runs them through the Pallas interpreter (the
tier-1 parity path).
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# the module (the package exports the function under the same name): its
# platform check and interpreter flag are this kernel's too
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["refusal", "swap_matrix", "turn", "unturn"]

_F32 = jnp.float32
_LANES = 128
# rows of the sequence a grid step takes, at most (of up to eight heads: 2
# MiB of bf16 a block): the sweep is in PERF.md section 6, PR 47; and the
# VMEM asked of Mosaic (of a v5e core's 128 MiB): a block in and out, each
# buffered twice, the tables and a head's float32 intermediates
_ROWS = 1024
_VMEM_LIMIT_BYTES = 64 << 20


def refusal(x, width=0):
    """Why this call cannot take the kernels, or None: ``"platform"`` (not
    a TPU, and no interpreter asked for), ``"dtype"`` (data that is not
    bf16 or float32), ``"lanes"`` (``x`` is not [B, T, H, D], or a head is
    not whole lane widths: a block's last axis is a head), ``"width"`` (a
    turned width that is odd or wider than the head)."""
    if _fa._platform() != "tpu" and not _fa._interpret():
        return "platform"
    if x.dtype not in (jnp.bfloat16, jnp.float32):
        return "dtype"
    if x.ndim != 4 or x.shape[-1] % _LANES:
        return "lanes"
    if width % 2 or not 0 <= width <= x.shape[-1]:
        return "width"
    return None


def swap_matrix(d, width, interleave, dtype):
    """The plain function's signed permutation [D, D]: ``x @ swap`` is the
    partner of each entry, ``partner[lo] = -x[hi], partner[hi] = x[lo]``
    (one non-zero a column: the product is exact in any dtype)."""
    half = (width or d) // 2
    i = jnp.arange(half)
    lo, hi = (2 * i, 2 * i + 1) if interleave else (i, i + half)
    return jnp.zeros((d, d), dtype).at[hi, lo].set(-1).at[lo, hi].set(1)


# A grid step works one head at a time, each through the whole chain: the
# scheduler keeps a head's chain in registers where a whole block's spills
# (``short_filter.py``'s strips). ``back``: the transpose, the sine negated.
def _kernel(x, cos, sin, swap, out, *, back):
    c, s, w = cos[...], sin[...], swap[...]
    # exact either way: one pass of bf16, or float32 in its bf16 pieces
    precision = (lax.Precision.HIGHEST if x.dtype == _F32
                 else lax.Precision.DEFAULT)
    if back:
        s = lax.neg(s)
    for h in range(x.shape[0]):
        xh = x[h]
        partner = lax.dot_general(
            xh, w, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=_F32)
        out[h] = lax.convert_element_type(lax.add(
            lax.mul(lax.convert_element_type(xh, _F32), c),
            lax.mul(partner, s)), out.dtype)


def _heads(h):
    """Heads a grid step: the most that divide ``H``, up to eight."""
    return max(n for n in range(1, 9) if h % n == 0)


def _call(x, cos, sin, swap, *, back, interpret):
    """``rotary_turn`` (``back``: ``rotary_unturn``) on ``x`` [B, H, T, D],
    in place: ``_ROWS`` rows of ``_heads(H)`` heads a grid step, all of a
    shorter sequence; a last block that runs past the end reads what it
    need not and its writes there are dropped. The heads are the inner
    grid axis, so a row block's tables are fetched once."""
    b, h, t, d = x.shape
    rows, hb = min(_ROWS, t), _heads(h)
    data = pl.BlockSpec((None, hb, rows, d), lambda b, i, j: (b, j, i, 0))
    table = pl.BlockSpec((rows, d), lambda b, i, j: (i, 0))
    params = dict(interpret=interpret,
                  name="rotary_unturn" if back else "rotary_turn")
    if not interpret:       # Mosaic-only hints: the interpreter takes none
        from jax.experimental.pallas import tpu as pltpu
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES)
    n = x.size
    return pl.pallas_call(
        functools.partial(_kernel, back=back),
        grid=(b, pl.cdiv(t, rows), h // hb),
        in_specs=[data, table, table,
                  pl.BlockSpec((d, d), lambda b, i, j: (0, 0))],
        out_specs=data, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
        cost_estimate=pl.CostEstimate(
            flops=(4 + 2 * d) * n, transcendentals=0,
            bytes_accessed=2 * n * x.dtype.itemsize + 2 * cos.size * 4),
        **params)(x, cos, sin, swap)


def turn(x, cos, sin, swap, *, interpret=False):
    """``x`` [B, T, H, D] turned by the tables ``cos`` and ``sin`` [T, D]
    float32 and the signed permutation ``swap`` (:func:`swap_matrix`) ->
    [B, H, T, D], through ``rotary_turn``; ``refusal(x, width)`` is None."""
    return _call(x.transpose(0, 2, 1, 3), cos, sin, swap, back=False,
                 interpret=interpret)


def unturn(g, cos, sin, swap, *, interpret=False):
    """The transpose of :func:`turn` in ``x``: the cotangent ``g`` [B, H,
    T, D] -> [B, T, H, D], through ``rotary_unturn`` (the same tables)."""
    return _call(g, cos, sin, swap, back=True,
                 interpret=interpret).transpose(0, 2, 1, 3)
