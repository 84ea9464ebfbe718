"""Flash attention: fused online-softmax attention for TPU.

No reference counterpart (the reference predates flash attention; its only
attention helper is ``_contrib_div_sqrt_dim``, src/operator/contrib/
transformer.cc). This is the single-chip hot path under
:func:`mxtpu.parallel.ring_attention.ring_self_attention`'s per-shard compute
and the model zoo transformer.

Design (TPU-first):
* forward: one Pallas kernel (``flash_attention_fwd``), grid (batch*heads,
  Tq/bq, Tk/bk), the k-block axis innermost so the online-softmax state
  lives in VMEM scratch across k steps; the [T, T] score matrix never
  materializes in HBM. It works on the TRANSPOSED score tile ``s^T = k q^T``
  [bk on sublanes, bq on lanes], as the backward does: K comes as the
  caller holds it (no transposed copy in HBM; the MXU takes q transposed
  as it loads it), the running max, running sum and rescale are [1, bq]
  rows whose reductions run down the sublanes (no cross-lane step) and
  which broadcast for nothing, the accumulator is kept transposed
  (``acc^T [dv, bq] += v^T p^T``: only the small V block is turned a step)
  and turned once a q block, and lse leaves as a (bh, 1, t) row, which is
  what the backward kernel reads. A grid step walks its q block in slabs
  of 256 columns (exact: the statistics are per q row), every slab's
  scores first, so that one slab's exp runs beside another's matmul; with
  the whole row in one k block nothing is carried and there is no
  scratch. On the chip this took the kernel from 29% to 58% of the MXU's
  peak at latent attention's 192 / 128 widths (PERF.md §6, PR 29).
* the causal mask: ONE block-level predicate (:func:`_block_case`: a
  (q block, k block) pair is skipped, wholly visible or crossed by the
  diagonal) that both kernels ask. Skipped pairs compute nothing
  (``pl.when``) and fetch nothing (the index maps name a block already
  held), saving about half the work; every other pair runs one body with
  the mask (a second body without it for the visible pairs read no
  faster). ``pallas_flash.block_pairs{skipped,visible,crossed}`` counts
  a call's pairs a head at trace time.
* a sliding window (``window = W > 0`` beside ``causal``): key ``j`` is
  visible to query ``i`` iff ``i - W < j <= i``, the query's own key among
  the ``W``. The same predicate gets its second bound (a pair wholly left
  of the window is skipped, one the window's edge crosses is masked), and
  the sequential axis of each kernel's grid holds only the steps a block
  can need (:func:`_window_steps`: five of sixteen at blocks of 1,024, a
  window of 4,096 and 16,384 positions), counted from the first block the
  window reaches; the index maps clamp on both sides, so nothing is
  fetched for a step outside. Such a call names its kernels
  ``flash_window_fwd`` / ``flash_window_bwd``, so a trace tells the two
  kinds of call apart. ``W >= T`` masks nothing and IS the causal call.
* a set of keys chosen query by query (:func:`sparse_attention`, the
  third kind of mask: data, where the diagonal and the window are
  geometry): the same two kernel bodies, blocks and grids as a causal
  call, with an int8 tile of the sets, keys first, fetched beside each k
  block and applied where the diagonal's mask would be; kernels
  ``sparse_attention_fwd`` / ``sparse_attention_bwd``, counters
  ``sparse_attention.*`` (the section at the end of this file).
* backward: custom_vjp, flash-attention-2 equations from the saved
  log-sum-exp. Where the forward ran the kernel, ONE fused Pallas kernel
  (``flash_attention_bwd``), grid (batch*heads, Tk/bk, Tq/bq) with the
  q-block axis innermost: it recomputes P per (k block, q block) on the
  transposed tile (k on sublanes, q on lanes), so s, p, dp and ds live in
  VMEM only; dk/dv accumulate in scratch over the q blocks, dq of the whole
  head stays in VMEM while the k blocks pass; operands in the input dtype
  (bf16 = one MXU pass), statistics and accumulators float32 — the
  forward's precision policy. It replaced a blockwise XLA backward that a
  device trace of BERT-base showed at 4.1x the forward (30.8% of the step):
  float32 einsums under ``jax_default_matmul_precision=float32`` are
  multi-pass on the MXU, and at T = 512 its four [B, H, T, T] float32
  matrices went through HBM in every layer (PERF.md §6, PR 25).
  ``_fa_backward_blockwise`` stays as the path for a case the kernel
  refuses and as the float32 oracle the tests compare the kernel against.
* two head widths: keys and queries share one width, values (and the
  output, and its cotangent) may have another (latent attention: 192 and
  128). A width under 128 is zero-padded to 128, 192 runs as it is
  (:func:`_pad_head_dim`); every block and accumulator of both kernels
  takes the width of the operand it holds.
* blocks: one rule for both kernels (:func:`_tile_blocks`): q on the 128
  lanes (a length off that granule is one whole block), k in 128s, 1024 x
  1024 asked for where no caller names a pair, halved while a grid step
  does not fit the VMEM budget.
* fallback: non-TPU platforms or non-divisible shapes use the XLA softmax
  path with the same signature (its backward is XLA's own). Why each
  fallback happened is counted in the reason-tagged
  ``pallas_flash.{pallas,xla,fallback}`` telemetry family; the backward
  of a kernel forward counts in
  ``pallas_flash.{bwd_pallas,bwd_xla,bwd_fallback}``.
* parity off-chip: ``MXTPU_FLASH_INTERPRET=1`` runs the kernel through
  the Pallas interpreter, so tier-1 pins the real online-softmax kernel
  against the XLA path on CPU without a chip.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _platform():
    return jax.devices()[0].platform


def _interpret_flag(var):
    """``var``=1 runs a kernel through the Pallas interpreter — the
    tier-1 parity path (CPU, no chip). On a TPU it is an error, not a
    slow run: a forgotten flag would otherwise put interpreted-kernel
    times under a device metric."""
    on = os.environ.get(var, "0") == "1"
    if on and _platform() == "tpu":
        from ...base import MXNetError
        raise MXNetError(
            "%s=1 while the platform is tpu: the interpreter is the "
            "off-chip parity path; unset it to run the compiled kernel"
            % var)
    return on


def _interpret():
    """MXTPU_FLASH_INTERPRET=1 (see :func:`_interpret_flag`). Trace-time,
    so it rides policy_key like every other lever."""
    return _interpret_flag("MXTPU_FLASH_INTERPRET")


# observability: how often the hand kernel ran vs why it fell back — a
# dict-shaped view over the telemetry registry, so bench/report/JSONL read
# one copy of the truth.
class _DispatchStatsView:
    """Read-only dict-shaped view over the telemetry counters."""

    _KEYS = ("pallas", "xla", "fallback_reasons", "grouped", "kv_repeated",
             "bwd_pallas", "bwd_xla", "bwd_fallback_reasons", "block_pairs",
             "windowed", "window_unskipped")
    _TAGGED = {"fallback_reasons": "pallas_flash.fallback",
               "bwd_fallback_reasons": "pallas_flash.bwd_fallback",
               "block_pairs": "pallas_flash.block_pairs"}

    def __getitem__(self, key):
        from ... import telemetry
        if key in self._TAGGED:
            return telemetry.tagged(self._TAGGED[key])
        if key not in self._KEYS:
            raise KeyError(key)
        return int(telemetry.value("pallas_flash." + key))

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
    for name in ("pallas", "xla", "fallback", "grouped", "kv_repeated",
                 "bwd_pallas", "bwd_xla", "bwd_fallback", "block_pairs",
                 "windowed", "window_unskipped"):
        telemetry.reset_metric("pallas_flash." + name)


def _count_fallback(reason):
    from ... import telemetry
    telemetry.inc("pallas_flash.xla")
    telemetry.inc("pallas_flash.fallback", tag=reason)


def _xla_attention(q, k, v, causal, scale, window=0):
    out, _ = _xla_attention_lse(q, k, v, causal, scale, window)
    return out


def _seen(q_pos, k_pos, window):
    """The mask, position by position: key ``k_pos`` is visible to query
    ``q_pos`` at or before it and, under a window, fewer than ``window``
    positions back. The plain paths' own copy (the kernels ask
    :func:`_block_case` and :func:`_causal_mask`)."""
    seen = q_pos >= k_pos
    return seen & (q_pos - k_pos < window) if window else seen


def _xla_attention_lse(q, k, v, causal, scale, window=0, mask_t=None):
    """Fallback (out, lse): ONE copy of the XLA math; differentiable.
    ``mask_t`` ([B, Tk, T] int8, keys first): a sparse call's selection,
    in the causal mask's place."""
    k, v = _repeat_kv(q, k, v)     # grouped heads: K, V at the query heads
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if mask_t is not None:
        s = jnp.where(jnp.swapaxes(mask_t, 1, 2)[:, None] != 0, s, _NEG_INF)
    elif causal:
        tq, tk = s.shape[-2:]
        mask = _seen(jnp.arange(tq)[:, None], jnp.arange(tk)[None, :], window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), lse


def _block_case(qi, ki, block_q, block_k, window=0):
    """What the mask does to block pair (q block ``qi``, k block ``ki``):
    ``(visible, crossed)``. *Visible*: every query of the block sees every
    key, so no position is masked. *Crossed*: an edge of the mask passes
    through, so some are. Neither: *skipped*, no query sees a key. Plain
    arithmetic on the block indices, so it takes Python ints and arrays
    (the counts) as well as a kernel's program ids; the one place the
    mask's block geometry is written down: both kernels ask it
    (:func:`_live`).

    The causal mask has one edge, the diagonal. A window of ``window`` keys
    (key ``j`` visible to query ``i`` iff ``i - window < j <= i``) added
    the second: a pair whose last key lies left of the FIRST query's window
    is skipped, and a pair is visible only if its first key lies inside the
    LAST query's window, so a pair may be crossed by the diagonal, by the
    window's edge, or (a block wider than the window) by both. A further
    mask (segment ids) would bound the same two sets."""
    first_q, first_k = qi * block_q, ki * block_k
    last_q, last_k = first_q + block_q - 1, first_k + block_k - 1
    visible = last_k <= first_q
    crossed = (first_k <= last_q) & (last_k > first_q)
    if not window:
        return visible, crossed
    live = (visible | crossed) & (last_k > first_q - window)
    visible = visible & (first_k > last_q - window)
    return visible, live ^ visible      # visible implies live


def _live(causal, qi, ki, block_q, block_k, window=0):
    """Whether step (qi, ki) of a kernel computes: every pair without a
    mask, the visible and the crossed ones under one."""
    if not causal:
        return True
    visible, crossed = _block_case(qi, ki, block_q, block_k, window)
    return visible | crossed


def _causal_mask(st, qi, ki, block_q, block_k, first_col=0, window=0):
    """The mask on the transposed tile ``st`` [k rows, q columns from
    ``first_col`` of the q block on] of a live block pair: the diagonal
    and, under a window, its far edge. It runs
    on the visible pairs too, where it changes nothing: a second copy of
    a kernel's body without it read no faster on the chip, forward or
    backward (the mask's passes fill VALU slots the MXU-bound schedule
    leaves empty; PERF.md §6, PR 29)."""
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    q_pos = qi * block_q + first_col + jax.lax.broadcasted_iota(
        jnp.int32, st.shape, 1)
    seen = q_pos >= k_pos
    if window:
        seen = seen & (q_pos - k_pos < window)
    return jnp.where(seen, st, _NEG_INF)


def _selected(st, tile):
    """The selection's mask on the transposed tile ``st`` [k rows, q
    columns]: ``tile`` is the int8 block of the same shape, non-zero where
    the key is in the query's set."""
    return jnp.where(tile.astype(jnp.int32) != 0, st, _NEG_INF)


def _first_k_block(i, block_q, block_k, window, xp=jnp):
    """The first k block that q block ``i``'s window reaches: where its
    steps of the forward start, and where its rows of dq open in the
    backward. (``xp``: ``numpy`` for the static counts.)"""
    return xp.maximum(i * block_q - (window - 1), 0) // block_k


def _last_q_block(j, block_q, block_k, n_q, window, xp=jnp):
    """The last q block whose window still reaches k block ``j``."""
    return xp.minimum((j * block_k + block_k + window - 2) // block_q,
                      n_q - 1)


def _window_steps(n_q, n_k, block_q, block_k, window):
    """Steps of each kernel's sequential axis under a window, static:
    ``(k steps a q block needs at most, q steps a k block needs at
    most)``, each counted from the first block the window reaches
    (:func:`_first_k_block`; ``j * block_k // block_q`` for a k block)."""
    import numpy as np
    i, j = np.arange(n_q), np.arange(n_k)
    k_steps = _last_k_block(i, n_k - 1, block_q, block_k, np) \
        - _first_k_block(i, block_q, block_k, window, np)
    q_steps = _last_q_block(j, block_q, block_k, n_q, window, np) \
        - j * block_k // block_q
    return int(k_steps.max()) + 1, int(q_steps.max()) + 1


def _count_block_pairs(n_q, n_k, block_q, block_k, causal, window=0):
    """``pallas_flash.block_pairs{skipped,visible,crossed}``: one call's
    (q block, k block) pairs a head, as :func:`_block_case` sorts them."""
    import numpy as np
    from ... import telemetry
    visible, crossed = n_q * n_k, 0
    if causal:
        visible, crossed = (int(x.sum()) for x in _block_case(
            np.arange(n_q)[:, None], np.arange(n_k)[None, :], block_q,
            block_k, window))
    for tag, n in (("skipped", n_q * n_k - visible - crossed),
                   ("visible", visible), ("crossed", crossed)):
        telemetry.inc("pallas_flash.block_pairs", n, tag=tag)


def _dot(a, b, contract):
    """A product of both kernels: operands in their input dtype (bf16: one
    MXU pass, DEFAULT; the global jax_default_matmul_precision=float32
    would ask for a multi-pass bf16 contraction Mosaic cannot lower),
    float32 operands at HIGHEST so reference parity holds, float32 out."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)


# q columns of the tile that one slab holds. A grid step walks its q block
# slab by slab: row statistics are per q row, so the slabs share nothing and
# need no rescale, and the scheduler runs one slab's exp beside the next
# slab's matmul (whole, the tile's phases follow each other with the MXU
# idle through the softmax; PERF.md §6, PR 29)
_Q_SLAB = 256


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, scale, causal,
               block_q, block_k, n_k, window=0, mask_ref=None):
    """One (head, q block, k block) step of the forward, on the TRANSPOSED
    score tile ``s^T = k q^T`` [bk on sublanes, bq on lanes], the form of
    :func:`_fa_bwd_kernel`: the row statistics (running max m, running sum
    l, the rescale alpha) are [1, bq] rows, their reductions run down the
    sublanes (vreg-to-vreg max / add, no cross-lane step), they broadcast
    along sublanes for nothing, and the accumulator is kept transposed,
    ``acc^T [dv, bq] += v^T p^T``, turned once a q block. With the whole
    row in one k block (``n_k == 1``) nothing is carried: no scratch, no
    rescale. ``n_k`` is the grid's k steps: every k block, or under a
    window the steps a q block can need, counted from the first block its
    window reaches. ``mask_ref`` (a sparse call): the int8 tile [bk, bq] of
    the selection, keys on sublanes as the scores are; where it is zero the
    pair is masked, in the diagonal's place (a selection is causal by
    construction, so the block predicate stays the causal one)."""
    qi = pl.program_id(1)
    step = pl.program_id(2)
    ki = step + _first_k_block(qi, block_q, block_k, window) if window \
        else step
    carried = n_k > 1
    if carried:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(step == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    def _store(cols, m, l, acc_t):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, cols, :] = jnp.transpose(acc_t / l).astype(o_ref.dtype)
        lse_ref[0, :, cols] = m + jnp.log(l)      # a [1, bq] row of lse

    @pl.when(_live(causal, qi, ki, block_q, block_k, window))
    def _step():
        k = k_ref[0]                              # [bk, d]
        v = v_ref[0]                              # [bk, dv]
        slab = _Q_SLAB if block_q % _Q_SLAB == 0 else block_q
        slabs = [slice(c, c + slab) for c in range(0, block_q, slab)]
        # every slab's scores first: the products do not wait for a softmax
        tiles = [_dot(k, q_ref[0, cols, :], ((1,), (1,))) * scale
                 for cols in slabs]               # [bk, slab] float32 each
        for cols, st in zip(slabs, tiles):
            if mask_ref is not None:
                st = _selected(st, mask_ref[0, :, cols])
            elif causal:
                st = _causal_mask(st, qi, ki, block_q, block_k, cols.start,
                                  window)
            m_new = jnp.max(st, axis=0, keepdims=True)          # [1, slab]
            if carried:
                m_prev = m_scr[:, cols]
                m_new = jnp.maximum(m_prev, m_new)
            pt = jnp.exp(st - m_new)              # float32 exp, P^T
            l_new = jnp.sum(pt, axis=0, keepdims=True)
            # p cast to the value dtype for a single-pass product (standard
            # flash practice); the accumulator stays float32
            pv = _dot(v, pt.astype(v.dtype), ((0,), (0,)))      # [dv, slab]
            if not carried:
                _store(cols, m_new, l_new, pv)
                continue
            alpha = jnp.exp(m_prev - m_new)
            m_scr[:, cols] = m_new
            l_scr[:, cols] = alpha * l_scr[:, cols] + l_new
            acc_scr[:, cols] = alpha * acc_scr[:, cols] + pv

    if carried:
        @pl.when(step == n_k - 1)
        def _finalize():
            _store(slice(None), m_scr[...], l_scr[...], acc_scr[...])


def _with_mask(kernel, at, *refs, **kwargs):
    """``kernel`` of a sparse call: the selection's tile is the operand at
    position ``at``, after the dense call's inputs, and goes in by name."""
    return kernel(*refs[:at], *refs[at + 1:], mask_ref=refs[at], **kwargs)


def _mask_vmem(bq, bk):
    """Bytes of a sparse call's int8 selection tile, double-buffered."""
    return 2 * bq * bk


def _last_k_block(i, j, block_q, block_k, xp=jnp):
    """The k block that step (q block ``i``, k block ``j``) of the causal
    forward names: ``j`` where it computes, else the last block the q
    block needed, so that nothing is fetched for a skipped step."""
    return xp.minimum(j, (i * block_q + block_q - 1) // block_k)


def _lanes(d):
    return -(-d // 128) * 128


def _vmem_limit(reckoned):
    """What a kernel asks Mosaic for: v5e's scoped default of 16 MiB, or a
    quarter over what ``_fwd_vmem`` / ``_bwd_vmem`` reckons."""
    return max(16 * 2**20, int(1.25 * reckoned))


def _fwd_vmem(bq, bk, d, dv, itm):
    """Bytes one grid step of the forward kernel holds."""
    dp, dvp = _lanes(d), _lanes(dv)
    return (2 * (bq * (dp + dvp) + bk * (dp + dvp)) * itm   # q, out, k, v
            + 2 * 8 * bq * 4                     # the lse row (dbuf)
            + bq * bk * (4 + 4 + itm)            # s^T, P^T and its cast
            + (dvp + 2 * 8) * bq * 4)            # acc^T, m, l


def _fa_forward_pallas(q, k, v, causal, scale, block_q, block_k, window=0,
                       mask_t=None):
    """``mask_t`` ([B, Tk, T] int8, keys first): a sparse call, the
    selection's tile fetched beside each k block (:func:`sparse_attention`),
    under the causal block predicate."""
    b, h, t, d = q.shape
    tk, dv = k.shape[2], v.shape[3]   # values may be narrower than keys
    bh, kv_head = b * h, _kv_head_map(_group(q, k))
    n_q = t // block_q
    n_k = tk // block_k
    sparse = mask_t is not None
    if not sparse:
        _count_block_pairs(n_q, n_k, block_q, block_k, causal, window)
    if window:      # the grid's k axis: the steps a q block can need
        n_k = _window_steps(n_q, n_k, block_q, block_k, window)[0]
    from jax.experimental.pallas import tpu as pltpu
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k,
                               window=window)
    if sparse:
        kernel = functools.partial(_with_mask, kernel, 3)
    interpret = _interpret()
    extra = {}
    if not interpret:  # Mosaic-only hints: the interpreter takes none
        itm = jnp.dtype(q.dtype).itemsize
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                _fwd_vmem(block_q, block_k, d, dv, itm)
                + sparse * _mask_vmem(block_q, block_k)))

    def k_block(i, j):
        if window:      # step j of q block i, from its window's first block
            j = j + _first_k_block(i, block_q, block_k, window)
        return _last_k_block(i, j, block_q, block_k) if causal else j

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
            # K as the caller holds it: k q^T contracts both last axes on
            # the MXU, no transposed copy in HBM
            pl.BlockSpec((1, block_k, d),
                         lambda b_, i, j: (kv_head(b_), k_block(i, j), 0)),
            pl.BlockSpec((1, block_k, dv),
                         lambda b_, i, j: (kv_head(b_), k_block(i, j), 0)),
        ] + ([pl.BlockSpec((1, block_k, block_q),
                           lambda b_, i, j: (b_ // h, k_block(i, j), i))]
             if sparse else []),
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b_, i, j: (b_, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            # lse as (bh, 1, t) rows: what the backward kernel reads
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),    # running max m
            pltpu.VMEM((1, block_q), jnp.float32),    # running sum l
            pltpu.VMEM((dv, block_q), jnp.float32),   # acc^T
        ] if n_k > 1 else [],
        interpret=interpret,
        # the kernel's name in a device trace
        name="sparse_attention_fwd" if sparse else
        "flash_window_fwd" if window else "flash_attention_fwd",
        **extra,
    )(q.reshape(bh, t, d), k.reshape(-1, tk, d), v.reshape(-1, tk, dv),
      *([mask_t] if sparse else []))
    return out.reshape(b, h, t, dv), lse


def _group(q, k):
    """Query heads a key/value head: ``q`` is [B, H_q, T, D], ``k`` (and
    ``v``) [B, H_kv, T, D] with ``H_q`` a multiple of ``H_kv``; query head
    ``j`` reads key/value head ``j // group`` (grouped-query attention,
    Ainslie et al., arXiv:2305.13245)."""
    h, hk = q.shape[1], k.shape[1]
    if h % hk:
        from ...base import MXNetError
        raise MXNetError("flash_attention: %d query heads do not divide "
                         "over %d key/value heads" % (h, hk))
    return h // hk


def _repeat_kv(q, k, v):
    """K and V at the query heads, for the plain paths: a [B, H_q, T, *]
    copy of each, which the kernels never make. Counted in
    ``pallas_flash.kv_repeated``."""
    group = _group(q, k)
    if group == 1:
        return k, v
    from ... import telemetry
    telemetry.inc("pallas_flash.kv_repeated")
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def _sum_group(dx, like):
    """[B, H_q, T, D] gradients of repeated K or V -> [B, H_kv, T, D]."""
    b, hk, t, d = like.shape
    if dx.shape[1] == hk:
        return dx
    return jnp.sum(dx.reshape(b, hk, -1, t, d), axis=2)


def _kv_head_map(group):
    """Row of the flattened [B * H_kv, T, D] keys and values that row
    ``b_`` of the flattened [B * H_q, T, D] queries reads: ``(b * H_q +
    j) // group = b * H_kv + j // group``. K and V blocks are fetched by
    it in the index maps, so no copy of them at the query heads exists."""
    if group == 1:
        return lambda b_: b_
    return lambda b_: b_ // group


@jax.named_scope("flash_attention_bwd")   # plain XLA: found by this scope
def _fa_backward_blockwise(q, k, v, out, lse, g, causal, scale, block_k,
                           g_lse=None, window=0, mask_t=None):
    """Flash-attention-2 backward, blockwise over k in plain jax:
    P = exp(S - lse); dv = P^T g; ds = P * (g v^T - D); dq += ds k; dk += ds^T q.

    ``g_lse`` is the cotangent of the lse OUTPUT (flash_attention_with_lse;
    d lse_i / d s_ik = P_ik, so it adds ``P * g_lse`` to ds).
    """
    f32 = jnp.float32
    k_rep, v_rep = _repeat_kv(q, k, v)     # grouped heads: the plain way
    q32, k32, v32 = q.astype(f32), k_rep.astype(f32), v_rep.astype(f32)
    g32, out32 = g.astype(f32), out.astype(f32)
    t, tk = q.shape[2], k.shape[2]
    delta = jnp.sum(out32 * g32, axis=-1)            # [b, h, t]
    if g_lse is not None:
        # fold the lse cotangent into the per-row constant: ds = P * (dP
        # - delta + g_lse), same row-broadcast shape as delta
        delta = delta - g_lse.astype(f32)
    n_k = tk // block_k
    q_pos = jnp.arange(t)

    def body(dq_acc, j):
        ks = jax.lax.dynamic_slice_in_dim(k32, j * block_k, block_k, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(v32, j * block_k, block_k, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, ks,
                       preferred_element_type=f32) * scale
        if mask_t is not None:      # a sparse call: [B, Tk, T], keys first
            rows = jax.lax.dynamic_slice_in_dim(mask_t, j * block_k, block_k,
                                                axis=1)
            s = jnp.where(jnp.swapaxes(rows, 1, 2)[:, None] != 0, s, _NEG_INF)
        elif causal:
            k_pos = j * block_k + jnp.arange(block_k)
            mask = _seen(q_pos[:, None], k_pos[None, :], window)
            s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])              # [b,h,t,bk]
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32,
                        preferred_element_type=f32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g32, vs,
                        preferred_element_type=f32)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, ks,
                                     preferred_element_type=f32)
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q32,
                        preferred_element_type=f32)
        return dq_acc, (dk, dv)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, jnp.zeros_like(q32), jnp.arange(n_k))
    # scan stacks [n_k, b, h, bk, d] -> [b, h, tk, d]
    dk = _sum_group(jnp.moveaxis(dk_blocks, 0, 2).reshape(k32.shape), k)
    dv = _sum_group(jnp.moveaxis(dv_blocks, 0, 2).reshape(v32.shape), v)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                   *, scale, causal, block_q, block_k, n_q, n_k, group=1,
                   window=0, q_steps, mask_ref=None):
    """One (head, k block, q block) step of the flash backward. Works on
    the TRANSPOSED score tile ``s^T = k q^T`` [bk, bq]: dv and dk are then
    plain matmuls with the tile on the left, lse and delta broadcast along
    sublanes from [1, bq] rows, and only dq contracts the tile's first
    axis. dk/dv accumulate over the inner (q) axis; dq for the whole head
    stays in VMEM while the k blocks pass.

    ``group`` > 1 (grouped heads, the grid (key/value head, query head of
    its group, k block, q block)): dk and dv of the WHOLE key/value head
    stay in VMEM while its query heads pass, so they leave the kernel once,
    at the key/value heads, summed over the group in float32.

    ``q_steps`` is the grid's q axis under a window: the steps a k block
    can need, counted from its diagonal's q block, in place of all ``n_q``
    q blocks; a q block's rows of dq then open at the first k block its
    window reaches and leave at its diagonal's, not at the first and last
    k block."""
    if group == 1:
        ki, step, kv = pl.program_id(1), pl.program_id(2), slice(None)

        def in_head(gi, cond):        # one query head a key/value head
            return cond
    else:
        head, ki, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
        kv = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)

        def in_head(gi, cond):        # ``cond``, in the group's head ``gi``
            return cond & (head == gi)
    # the k blocks at which q block ``qi``'s rows of dq open and leave
    if window:
        qi = step + ki * block_k // block_q
        first_k = functools.partial(_first_k_block, qi, block_q, block_k,
                                    window)
        last_k = functools.partial(_last_k_block, qi, n_k - 1, block_q,
                                   block_k)

        def here(cond):
            # the last k blocks' steps run past the last q block: the mask
            # would let such queries see these keys, the array has none
            return cond & (qi < n_q)
    else:
        qi, first_k, last_k = step, lambda: 0, lambda: n_k - 1

        def here(cond):
            return cond
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(in_head(0, step == 0))
    def _init_kv():
        dk_acc[kv] = jnp.zeros((block_k, dk_acc.shape[1]), jnp.float32)
        dv_acc[kv] = jnp.zeros((block_k, dv_acc.shape[1]), jnp.float32)

    @pl.when(here(ki == first_k()))
    def _init_q():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    @pl.when(here(_live(causal, qi, ki, block_q, block_k, window)))
    def _step():
        q = q_ref[0]                              # [bq, d]
        k = k_ref[0]                              # [bk, d]
        v = v_ref[0]                              # [bk, dv]
        g = g_ref[0]                              # [bq, dv]
        # the forward kernel's precision policy (:func:`_dot`)
        st = _dot(k, q, ((1,), (1,))) * scale     # [bk, bq]
        if mask_ref is not None:                  # a sparse call's selection
            st = _selected(st, mask_ref[0])
        elif causal:
            st = _causal_mask(st, qi, ki, block_q, block_k, window=window)
        pt = jnp.exp(st - lse_ref[0])             # P^T, lse as a [1, bq] row
        dv_acc[kv] += _dot(pt.astype(g.dtype), g, ((1,), (0,)))
        dpt = _dot(v, g, ((1,), (1,)))            # dP^T [bk, bq]
        # ds^T without its factor ``scale``: that is applied once to the
        # [*, d] results instead of the [bk, bq] tile
        dst = (pt * (dpt - delta_ref[0])).astype(q.dtype)
        dk_acc[kv] += _dot(dst, q, ((1,), (0,)))
        dq_acc[rows, :] += _dot(dst, k, ((0,), (0,)))

    @pl.when(in_head(group - 1, step == q_steps - 1))
    def _store_kv():
        dk_ref[0, kv] = (dk_acc[kv] * scale).astype(dk_ref.dtype)
        dv_ref[0, kv] = dv_acc[kv].astype(dv_ref.dtype)

    @pl.when(here(ki == last_k()))
    def _store_q():
        dq_ref[0, rows, :] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)


def _first_q_block(j, i, block_q, block_k, n_q):
    """The q block that step (k block ``j``, q block ``i``) of the causal
    backward names: ``i`` where it computes, else the first q block whose
    rows reach k block ``j``, held inside the array (a block index past
    the end halts the chip; the interpreter clamps it and says nothing)."""
    return jnp.minimum(jnp.maximum(i, (j * block_k) // block_q), n_q - 1)


# VMEM a kernel may plan for: v5e's scoped default is 16 MiB of 128 MiB; a
# kernel asks for what _fwd_vmem / _bwd_vmem reckons, up to this
_VMEM_BUDGET = 64 * 1024 * 1024


def _bwd_vmem(bq, bk, t, d, dv, itm, tk=0):
    """Bytes one grid step of the backward kernel holds, with dq of the
    whole head resident (``t`` rows). ``d`` is the width of queries and
    keys, ``dv`` of values and the cotangent. ``tk``: the rows of dk and dv
    resident where they are whole heads (grouped heads), else a k block."""
    dp, dvp = _lanes(d), _lanes(dv)
    return (2 * (bq + bk) * (dp + dvp) * itm     # q, g, k, v blocks (dbuf)
            + 2 * 2 * 8 * bq * 4                 # lse, delta rows (dbuf)
            + bq * bk * (4 * 4 + 2 * itm)        # s^T, P^T, dP^T, ds^T + casts
            + (tk or bk) * (dp + dvp) * (4 + 2 * itm)  # dk, dv: scratch + out
            + t * dp * (4 + 2 * itm))            # dq of the head: scratch + out


def _tile_blocks(t, tk, block_q, block_k, vmem, too_big):
    """``((block_q, block_k), None)`` for a kernel, or ``(None, reason)``
    where it refuses: the one block rule of both kernels. They work on the
    transposed tile [bk, bq]: q lies on the 128 lanes (a length off that
    granule is one whole block, which is always tileable) and k on the
    sublanes, in blocks of 128s. The larger side halves until
    ``vmem(block_q, block_k)`` fits the budget; ``too_big`` is the reason
    where nothing smaller is left."""
    while True:
        bq = _pick_block(t, block_q, 128) or (t if t % 8 == 0 else None)
        bk = _pick_block(tk, block_k, 128)
        if bq is None or bk is None:
            return None, "sequence length has no TPU-tileable block"
        if vmem(bq, bk) <= _VMEM_BUDGET:
            return (bq, bk), None
        smaller_q = _pick_block(t, bq // 2, 128) if bq > 128 else None
        smaller_k = _pick_block(tk, bk // 2, 128) if bk > 128 else None
        if smaller_q and (bq >= bk or not smaller_k):
            block_q = smaller_q
        elif smaller_k:
            block_k = smaller_k
        else:
            return None, too_big


def _resolve_bwd_blocks(q, k, v, block_q, block_k):
    """:func:`_tile_blocks` for the backward kernel, which keeps dq of the
    whole head in VMEM (:func:`_bwd_vmem`)."""
    t, tk, d, dv = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    itm = jnp.dtype(q.dtype).itemsize
    whole = tk if _group(q, k) > 1 else 0
    return _tile_blocks(
        t, tk, block_q, block_k,
        lambda bq, bk: _bwd_vmem(bq, bk, t, d, dv, itm, whole),
        "dq of one head does not fit the VMEM budget")


@jax.named_scope("flash_attention_bwd")   # prologue, kernel and epilogue
def _fa_backward_pallas(q, k, v, out, lse, g, causal, scale, block_q,
                        block_k, g_lse=None, window=0, mask_t=None):
    """The flash backward as ONE fused Pallas kernel: P is recomputed per
    (k block, q block) from the saved ``lse``; s, p, dp and ds never leave
    VMEM. Same contract as :func:`_fa_backward_blockwise`."""
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    # the per-row constant of ds = P * (dP - delta); the lse cotangent
    # folds into it (d lse_i / d s_ik = P_ik)
    delta = jnp.sum(out.astype(f32) * g.astype(f32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(f32)
    # head dims off the 128-lane granule are zero-padded as the forward
    # pads them (zero columns change no score and give zero gradient
    # columns, sliced off below): on the chip, at BERT-base's d = 64, the
    # step is 4.4% shorter padded than with 64-wide blocks (PERF.md §6)
    d_out, dv_out = q.shape[3], v.shape[3]
    q, k, v, g = _pad_head_dim(q, k, v, g.astype(q.dtype))
    b, h, t, d = q.shape
    hk, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = _group(q, k)
    grouped = group > 1
    bh = b * h
    n_q = t // block_q
    n_k = tk // block_k
    # the grid's q axis: every q block, or the steps a k block can need
    q_steps = _window_steps(n_q, n_k, block_q, block_k, window)[1] \
        if window else n_q
    kernel = functools.partial(_fa_bwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_q=n_q,
                               n_k=n_k, group=group, window=window,
                               q_steps=q_steps)
    sparse = mask_t is not None
    if sparse:
        kernel = functools.partial(_with_mask, kernel, 6)
    interpret = _interpret()
    extra = {}
    if not interpret:  # Mosaic-only hints: the interpreter takes none
        itm = jnp.dtype(q.dtype).itemsize
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * (
                3 if grouped else 2),
            vmem_limit_bytes=_vmem_limit(
                _bwd_vmem(block_q, block_k, t, d, dv, itm,
                          tk if grouped else 0)
                + sparse * _mask_vmem(block_q, block_k)))

    def q_block(j, i):
        # causal: the q blocks above a k block's diagonal compute nothing,
        # and name the first block that does, which is then fetched once
        # (the last q block where no row sees this k block: tk > t)
        if not causal:
            return i
        if window:      # step i of k block j, from its diagonal's q block
            return jnp.minimum(
                i + j * block_k // block_q,
                _last_q_block(j, block_q, block_k, n_q, window))
        return _first_q_block(j, i, block_q, block_k, n_q)

    # the grid's axes -> (query head, key/value head, k block, q block)
    if grouped:
        # a key/value head's query heads on a sequential axis: dk, dv of
        # the whole head wait in VMEM for all of them, so they leave at the
        # key/value heads, summed in float32 (as float32 partials a query
        # head summed by XLA the call read 1.05 ms longer at the lfm2
        # cell's shape, 18.58 against 19.63: PERF.md §6, PR 30)
        def at(c, gi, j, i):
            return c * group + gi, c, j, i
        grid = (b * hk, group, n_k, q_steps)
        kv_rows = tk
    else:
        def at(b_, j, i):
            return b_, b_, j, i
        grid = (bh, n_k, q_steps)
        kv_rows = block_k

    def spec(block, index):
        return pl.BlockSpec(block, lambda *ids: index(*at(*ids)))

    q_spec = spec((1, block_q, d), lambda hq, hkv, j, i: (hq, q_block(j, i), 0))
    g_spec = spec((1, block_q, dv),
                  lambda hq, hkv, j, i: (hq, q_block(j, i), 0))
    k_spec = spec((1, block_k, d), lambda hq, hkv, j, i: (hkv, j, 0))
    v_spec = spec((1, block_k, dv), lambda hq, hkv, j, i: (hkv, j, 0))
    row_spec = spec((1, 1, block_q),
                    lambda hq, hkv, j, i: (hq, 0, q_block(j, i)))
    # dk, dv: the k block of the step, or the key/value head whole
    kv_block = (lambda hq, hkv, j, i: (hkv, 0, 0)) if grouped else (
        lambda hq, hkv, j, i: (hkv, j, 0))
    dq, dk, dv_ = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec, g_spec, row_spec, row_spec] + (
            [spec((1, block_k, block_q),
                  lambda hq, hkv, j, i: (hq // h, j, q_block(j, i)))]
            if sparse else []),
        out_specs=[
            spec((1, t, d), lambda hq, hkv, j, i: (hq, 0, 0)),
            spec((1, kv_rows, d), kv_block),
            spec((1, kv_rows, dv), kv_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * hk, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * hk, tk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, d), f32),          # dq of the head
            pltpu.VMEM((kv_rows, d), f32),    # dk of the k block (or head)
            pltpu.VMEM((kv_rows, dv), f32),   # dv of the k block (or head)
        ],
        interpret=interpret,
        # the kernel's name in a device trace
        name="sparse_attention_bwd" if sparse else
        "flash_window_bwd" if window else "flash_attention_bwd",
        **extra,
    )(q.reshape(bh, t, d), k.reshape(-1, tk, d), v.reshape(-1, tk, dv),
      g.reshape(bh, t, dv), lse.reshape(bh, 1, t), delta.reshape(bh, 1, t),
      *([mask_t] if sparse else []))
    return (dq.reshape(q.shape)[..., :d_out], dk.reshape(k.shape)[..., :d_out],
            dv_.reshape(v.shape)[..., :dv_out])


def _pick_block(n, want, mult):
    """Largest block ≤ want that is a multiple of ``mult`` and divides n —
    so sequence lengths like 768 or 1536 (not divisible by the default 512)
    still get a Pallas kernel instead of silently falling back. A ``want``
    below the hardware granule rounds UP to ``mult`` (a user asking for
    block_k=64 should get the 128-lane kernel, not the fallback)."""
    b = min(want, n)
    b -= b % mult
    if b == 0 and n >= mult:
        b = mult
    while b >= mult:
        if n % b == 0:
            return b
        b -= mult
    return None


_warned_fallbacks = set()


def _resolve_blocks(q, k, v, block_q, block_k):
    """(block_q, block_k) for the Pallas kernel, or None → XLA fallback.

    On TPU the fallback is a real memory cliff (the [T, T] score matrix
    materializes in HBM), so it warns ONCE per offending shape instead of
    silently absorbing it (VERDICT r4 weak #7). Every outcome is counted
    in ``pallas_flash.{pallas,xla}`` / reason-tagged
    ``pallas_flash.fallback``."""
    t, tk, d, dv = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    on_tpu = _platform() == "tpu"
    from ... import telemetry

    def _fallback(reason):
        _count_fallback(reason)
        if on_tpu:
            key = (reason, t, tk, d)
            if key not in _warned_fallbacks:
                _warned_fallbacks.add(key)
                import warnings
                warnings.warn(
                    "flash_attention falling back to the XLA softmax path "
                    "(%s; q[T=%d] k[T=%d] D=%d): the [T,T] score matrix "
                    "will materialize in HBM — pad the keys' T to a "
                    "multiple of 128 and the queries' to one of 128 (or, "
                    "for one block, of 8) for the fused kernel (head dims "
                    "are padded to the 128-lane granule automatically)"
                    % (reason, t, tk, d))
        return None

    if not on_tpu and not _interpret():
        # expected off-TPU; counted but not a cliff worth warning about
        return _fallback("platform is not tpu")
    # a head dim off the 128-lane granule (64 for BERT-base et al.) is no
    # reason to fall back: _pad_head_dim zero-pads it, and scores and lse
    # are invariant to zero columns
    itm = jnp.dtype(q.dtype).itemsize
    blocks, refused = _tile_blocks(
        t, tk, block_q, block_k,
        lambda bq, bk: _fwd_vmem(bq, bk, d, dv, itm),
        "one q block does not fit the VMEM budget")
    if blocks is None:
        return _fallback(refused)
    telemetry.inc("pallas_flash.pallas")
    return blocks


def _pad_head_dim(*xs):
    """Zero-pad [B, H, T, D] operands narrower than the 128 lanes to 128,
    each by its own D (queries and keys share one width, values and the
    output's cotangent another: latent attention has 192 and 128). Zero
    key/query columns contribute nothing to scores and zero value columns
    are sliced off the output, so attention is exact under this padding.
    A width above 128 that fills whole half-tiles (a multiple of 64: 192)
    stays as it is: Mosaic takes such blocks, the MXU passes are the same
    128-wide ones, and on the chip both kernels at 192 / 128 were 3-5%
    faster unpadded, the whole step 2.3% (PERF.md §6, PR 26); at 64,
    padded was the faster (PR 25)."""
    def pad(x):
        d = x.shape[-1]
        d_pad = d if d > 128 and d % 64 == 0 else -(-d // 128) * 128
        if d_pad == d:
            return x
        return jnp.pad(x, [(0, 0)] * 3 + [(0, d_pad - d)])
    return tuple(pad(x) for x in xs)


# the blocks both kernels ask for where the caller names none (a shorter
# sequence is one block): read fastest on the chip, in the model, at 8,192
# causal positions against {256, 512, 2048} each way (PERF.md §6, PR 29)
_BLOCK_Q, _BLOCK_K = 1024, 1024


def _window(q, k, causal, window):
    """``window`` as both kernels take it: 0 for none, and for one that
    masks nothing (``window >= T``: the call then IS the causal call, bit
    for bit). Refuses a window the mask is not defined for."""
    if not window:
        return 0
    if window < 0 or not causal or q.shape[2] != k.shape[2]:
        from ...base import MXNetError
        raise MXNetError(
            "flash_attention: window=%r needs causal=True, a positive "
            "width and as many keys as queries (key j is visible to query "
            "i iff i - window < j <= i); got causal=%r, %d queries, %d keys"
            % (window, causal, q.shape[2], k.shape[2]))
    return 0 if window >= q.shape[2] else int(window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=_BLOCK_Q,
                    block_k=_BLOCK_K, window=0):
    """Fused attention [B, H, T, D] -> [B, H, T, D]; falls back to XLA softmax
    off-TPU or for non-divisible shapes. ``causal``: key ``j`` is visible
    to query ``i`` iff ``j <= i``; with ``window = W > 0`` (causal only)
    iff ``i - W < j <= i``, the query's own key among the ``W``
    (transformers' sliding-window mask)."""
    out, _ = _fa_fwd(q, k, v, causal, scale, block_q, block_k, window)
    return out


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, window=0):
    out, _, res = _fa_lse_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                                   window)
    return out, res


def _fa_backward(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                 g_lse=None, window=0):
    """The backward of a forward that ran the Pallas kernel (``lse`` was
    saved): the fused kernel, or the blockwise XLA path for a case the
    kernel refuses, counted with its reason like the forward's fallbacks."""
    from ... import telemetry
    blocks, refused = _resolve_bwd_blocks(q, k, v, block_q, block_k)
    if blocks is None:
        telemetry.inc("pallas_flash.bwd_xla")
        telemetry.inc("pallas_flash.bwd_fallback", tag=refused)
        if window:      # the plain path visits the pairs left of the window
            telemetry.inc("pallas_flash.window_unskipped")
        # plain jax (no lane constraint), but its k-block must DIVIDE tk —
        # the scan would silently drop a ragged tail otherwise
        block_k = _pick_block(k.shape[2], block_k, 1) or k.shape[2]
        return _fa_backward_blockwise(q, k, v, out, lse.reshape(q.shape[:3]),
                                      g, causal, scale, block_k, g_lse=g_lse,
                                      window=window)
    telemetry.inc("pallas_flash.bwd_pallas")
    return _fa_backward_pallas(q, k, v, out, lse, g, causal, scale, *blocks,
                               g_lse=g_lse, window=window)


def _fa_bwd(causal, scale, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    window = _window(q, k, causal, window)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if lse is None:
        # fallback path: differentiate the XLA implementation directly
        _, vjp = jax.vjp(lambda q_, k_, v_: _xla_attention(
            q_, k_, v_, causal, scale, window), q, k, v)
        return vjp(g)
    return _fa_backward(q, k, v, out, lse, g, causal, scale, block_q,
                        block_k, window=window)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=_BLOCK_Q, block_k=_BLOCK_K):
    """Like :func:`flash_attention` but ALSO returns the per-row
    log-sum-exp [B, H, T] — the quantity that lets partial attention
    results over disjoint key sets be merged exactly (ring attention's
    per-step blocks combine as out = Σ_j softmax(lse_j) out_j)."""
    out, lse, _res = _fa_lse_fwd_impl(q, k, v, causal, scale, block_q,
                                      block_k)
    return out, lse


def _fa_lse_fwd_impl(q, k, v, causal, scale, block_q, block_k, window=0):
    from ... import telemetry
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    window = _window(q, k, causal, window)
    if _group(q, k) > 1:
        telemetry.inc("pallas_flash.grouped")
    if window:
        telemetry.inc("pallas_flash.windowed")
    blocks = _resolve_blocks(q, k, v, block_q, block_k)
    if blocks is None:
        if window:      # the plain path visits the pairs left of the window
            telemetry.inc("pallas_flash.window_unskipped")
        out, lse = _xla_attention_lse(q, k, v, causal, scale, window)
        return out, lse, (q, k, v, out, None)
    out, lse = _fa_forward_pallas(*_pad_head_dim(q, k, v), causal, scale,
                                  *blocks, window)
    if out.shape[-1] != v.shape[-1]:
        out = out[..., :v.shape[-1]]
    # the residual is the kernel's own (bh, 1, t) rows, which the backward
    # kernel reads as they are; the public lse is [B, H, T]
    return out, lse.reshape(q.shape[:3]), (q, k, v, out, lse)


def _fa_lse_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse, res = _fa_lse_fwd_impl(q, k, v, causal, scale, block_q,
                                     block_k)
    return (out, lse), res


def _fa_lse_bwd(causal, scale, block_q, block_k, res, cots):
    g, g_lse = cots
    q, k, v, out, lse = res
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if lse is None:
        _, vjp = jax.vjp(lambda q_, k_, v_:
                         _xla_attention_lse(q_, k_, v_, causal, scale),
                         q, k, v)
        return vjp((g, g_lse))
    return _fa_backward(q, k, v, out, lse, g, causal, scale, block_q,
                        block_k, g_lse=g_lse)


flash_attention_with_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)


# ------------------------------------------------------- sparse attention
# Attention over a set of keys chosen query by query (a learned indexer's
# top-k: DeepSeek-V3.2-Exp's sparse attention). The set comes as an int8
# array [B, Tk, T], KEYS FIRST, as the kernels hold the score tile: entry
# [b, s, t] is non-zero iff key s is in query t's set; one set a query,
# shared by every head. The built form is the MASKED one: both kernels are
# the causal kernels above (same bodies, same blocks, K and V at their own
# heads), every causal block pair is visited, the selection's tile is
# fetched beside the k block and the pairs outside the set are masked
# where the diagonal would be. Nothing is skipped by data, so a call costs
# what a causal call costs and does ``pairs_visited / pairs_selected`` times
# the algorithm's work (4.5 at 2,048 of 16,384); the form that gathers the
# selected rows would move 2 x topk x H_kv x D x itemsize bytes a query
# (PERF.md §6, PR 38). Counters, at trace time: ``sparse_attention.calls``,
# ``.pairs_selected`` / ``.pairs_visited`` (a call, a head),
# ``.fallbacks`` (by reason: a call on a plain path that holds [H, T, T]),
# ``.bwd_pallas``.
def _count_sparse(mask_t, topk, visited, reason=None):
    """One call's counters. ``pairs_selected`` is what the algorithm needs
    (``sum_t min(t + 1, topk)`` a sequence, where the caller says what
    ``topk`` built the set), ``visited`` what the path taken touches."""
    from ... import telemetry
    b, tk, t = mask_t.shape
    telemetry.inc("sparse_attention.calls")
    if topk:
        k = min(topk, t)
        telemetry.inc("sparse_attention.pairs_selected",
                      b * (k * t - k * (k - 1) // 2))
    telemetry.inc("sparse_attention.pairs_visited", b * visited)
    if reason is not None:
        telemetry.inc("sparse_attention.fallbacks", tag=reason)


def _sparse_blocks(q, k, v, block_q, block_k):
    """``((block_q, block_k), None)`` for the sparse forward kernel, or
    ``(None, reason)``: :func:`_tile_blocks` under the forward's budget
    with the selection's tile in it."""
    if _platform() != "tpu" and not _interpret():
        return None, "platform is not tpu"
    t, tk, d, dv = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    if t != tk:
        return None, "as many keys as queries are needed"
    itm = jnp.dtype(q.dtype).itemsize
    return _tile_blocks(
        t, tk, block_q, block_k,
        lambda bq, bk: _fwd_vmem(bq, bk, d, dv, itm) + _mask_vmem(bq, bk),
        "one q block does not fit the VMEM budget")


def _sparse_fwd_impl(q, k, v, mask_t, scale, block_q, block_k, topk):
    import numpy as np
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    t = q.shape[2]
    blocks, refused = _sparse_blocks(q, k, v, block_q, block_k)
    if blocks is None:
        _count_sparse(mask_t, topk, t * t, refused)
        out, _ = _xla_attention_lse(q, k, v, True, scale, mask_t=mask_t)
        return out, (q, k, v, out, None, mask_t)
    bq, bk = blocks
    live = _live(True, np.arange(t // bq)[:, None],
                 np.arange(t // bk)[None, :], bq, bk)
    _count_sparse(mask_t, topk, int(live.sum()) * bq * bk)
    out, lse = _fa_forward_pallas(*_pad_head_dim(q, k, v), True, scale, bq,
                                  bk, mask_t=mask_t)
    if out.shape[-1] != v.shape[-1]:
        out = out[..., :v.shape[-1]]
    return out, (q, k, v, out, lse, mask_t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def sparse_attention(q, k, v, mask_t, scale=None, block_q=_BLOCK_Q,
                     block_k=_BLOCK_K, topk=0):
    """Attention of ``q`` [B, H, T, D] over the keys each query selected:
    ``k``, ``v`` [B, H_kv, T, D] at their own heads (query head ``j`` reads
    head ``j // (H / H_kv)``), ``mask_t`` [B, T, T] int8 with KEYS FIRST,
    non-zero at [b, s, t] iff key ``s`` is in query ``t``'s set, which has
    to be causal (``s <= t``: the kernels skip the blocks above the
    diagonal unread) and non-empty. ``out[t] = sum_{s in S_t} softmax_{s in
    S_t}(q_t . k_s * scale) v_s``. The set is a constant of the step: it
    takes no gradient, and the backward reads the array the forward read.
    ``topk`` only tells the counters what the algorithm needed. Kernels
    ``sparse_attention_fwd`` / ``sparse_attention_bwd`` in a trace; off the
    TPU (or a shape no block tiles) the plain path, which holds [H, T, T]
    and counts in ``sparse_attention.fallbacks``."""
    return _sparse_fwd_impl(q, k, v, mask_t, scale, block_q, block_k,
                            topk)[0]


def _sparse_bwd(scale, block_q, block_k, topk, res, g):
    import numpy as np
    from ... import telemetry
    q, k, v, out, lse, mask_t = res
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    no_grad = np.zeros(mask_t.shape, jax.dtypes.float0)
    if lse is None:
        _, vjp = jax.vjp(lambda q_, k_, v_: _xla_attention_lse(
            q_, k_, v_, True, scale, mask_t=mask_t)[0], q, k, v)
        return vjp(g) + (no_grad,)
    itm = jnp.dtype(q.dtype).itemsize
    t, tk, d, dv = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    whole = tk if _group(q, k) > 1 else 0
    blocks, refused = _tile_blocks(
        t, tk, block_q, block_k,
        lambda bq, bk: _bwd_vmem(bq, bk, t, d, dv, itm, whole)
        + _mask_vmem(bq, bk), "dq of one head does not fit the VMEM budget")
    if blocks is None:
        telemetry.inc("sparse_attention.fallbacks", tag="backward: " + refused)
        grads = _fa_backward_blockwise(
            q, k, v, out, lse.reshape(q.shape[:3]), g, True, scale,
            _pick_block(tk, block_k, 1) or tk, mask_t=mask_t)
    else:
        telemetry.inc("sparse_attention.bwd_pallas")
        with jax.named_scope("sparse_attention_bwd"):
            grads = _fa_backward_pallas(q, k, v, out, lse, g, True, scale,
                                        *blocks, mask_t=mask_t)
    return tuple(grads) + (no_grad,)


sparse_attention.defvjp(_sparse_fwd_impl, _sparse_bwd)
