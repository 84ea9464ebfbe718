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
* the mask: ONE description (:class:`Mask`: causal, a window, a selection)
  that owns every form the mask takes, and every function below takes it
  whole. The causal mask: ONE block-level predicate
  (:meth:`Mask.block_case`: a (q block, k block) pair is skipped, wholly
  visible or crossed by the diagonal) that both kernels ask. Skipped pairs
  compute nothing (``pl.when``) and fetch nothing (the index maps name a
  block already held), saving about half the work; every other pair runs
  one body with the mask (a second body without it for the visible pairs
  read no faster). ``pallas_flash.block_pairs{skipped,visible,crossed}`` counts
  a call's pairs a head at trace time.
* a sliding window (``window = W > 0`` beside ``causal``): key ``j`` is
  visible to query ``i`` iff ``i - W < j <= i``, the query's own key among
  the ``W``. The same predicate gets its second bound (a pair wholly left
  of the window is skipped, one the window's edge crosses is masked), and
  the sequential axis of each kernel's grid holds only the steps a block
  can need (:meth:`Mask.steps`: five of sixteen at blocks of 1,024, a
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
  ``sparse_attention.*`` (:func:`_count_sparse`).
* one core under the three public functions: one forward
  (:func:`_forward`), one backward (:func:`_backward`), one block rule
  (:func:`_plan`), registered twice (with and without the lse output).
* what a recomputed caller keeps: the forward names its output and its
  log-sum-exp rows (:data:`KEPT_NAMES`, ``checkpoint_name``; a windowed
  call's go unnamed) inside the ``custom_vjp``'s forward rule, on the
  kernel path and the plain one, so that a block under
  ``jax.checkpoint(..., policy=save_only_these_names(*KEPT_NAMES))`` holds
  them and its backward's second forward runs no such kernel
  (``HybridLM(recompute=True)``; PERF.md §6, PR 46). Anywhere else a name
  is the identity and lowers to nothing.
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
  1024 asked for where no caller names a pair (under a window narrower
  than that, the window's width: :meth:`Mask.blocks`), halved while a grid
  step does not fit the VMEM budget.
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

import collections.abc
import contextlib
import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
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
class _DispatchStatsView(collections.abc.Mapping):
    """Read-only dict-shaped view over the telemetry counters."""

    _KEYS = ("pallas", "xla", "fallback_reasons", "grouped", "kv_repeated",
             "bwd_pallas", "bwd_xla", "bwd_fallback_reasons", "block_pairs",
             "windowed", "window_unskipped", "window_pairs_seen",
             "window_pairs_visited")
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

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def __repr__(self):
        return repr(dict(self.items()))


DISPATCH_STATS = _DispatchStatsView()


def reset_dispatch_stats():
    from ... import telemetry
    for key in _DispatchStatsView._KEYS:
        telemetry.reset_metric(_DispatchStatsView._TAGGED.get(
            key, "pallas_flash." + key))


class Mask(NamedTuple):
    """What a query may see, static and hashable: the ONE description of
    the mask. Every form it takes is derived here and nowhere else:
    positions (:meth:`seen`, :meth:`on_scores`), blocks
    (:meth:`block_case`, :meth:`live`), a kernel's tile (:meth:`on_tile`),
    the cut of each grid (:meth:`steps`, :meth:`k_at` / :meth:`q_at`, the
    index maps' :meth:`k_block` / :meth:`q_block`), bytes of VMEM
    (:meth:`vmem`), counts and the kernels' name. A tile form and a block
    form that disagree are a silent wrong answer on the chip that no test
    of the plain path sees (``tests/test_flash_mask.py`` holds them to each
    other), so a further bound (segment ids, a sink) is one field here and
    the methods that read it.

    ``causal``: key ``j`` is visible to query ``i`` iff ``j <= i``;
    ``window = W > 0`` (causal only) iff ``i - W < j <= i``; ``selected``
    (causal only) iff ``j`` is in ``i``'s set, an int8 array [B, Tk, T],
    KEYS FIRST, passed beside the mask (data, where the diagonal and the
    window are geometry): causal by construction, so its block forms are
    the causal ones."""
    causal: bool = False
    window: int = 0
    selected: bool = False

    @classmethod
    def of(cls, q, k, causal, window=0):
        """:func:`flash_attention`'s arguments as a mask: ``window`` 0 for
        none, and for one that masks nothing (``window >= T``: the call
        then IS the causal call, bit for bit). Refuses a window the mask is
        not defined for."""
        if not window:
            return cls(bool(causal))
        if window < 0 or not causal or q.shape[2] != k.shape[2]:
            from ...base import MXNetError
            raise MXNetError(
                "flash_attention: window=%r needs causal=True, a positive "
                "width and as many keys as queries (key j is visible to query "
                "i iff i - window < j <= i); got causal=%r, %d queries, %d keys"
                % (window, causal, q.shape[2], k.shape[2]))
        return cls(True, 0 if window >= q.shape[2] else int(window))

    def seen(self, q_pos, k_pos):
        """The definition, position by position (geometry only)."""
        if not self.causal:
            return True
        seen = q_pos >= k_pos
        return seen & (q_pos - k_pos < self.window) if self.window else seen

    def on_scores(self, s, selection=None, q_pos=None, first_k=None):
        """The mask on plain scores ``s`` [B, H, q, k] (the two plain
        paths): queries at ``q_pos`` [q] (all, from 0), keys from
        ``first_k`` on (0); ``selection`` [B, k, q]: these keys' rows."""
        if self.selected:
            return jnp.where(jnp.swapaxes(selection, 1, 2)[:, None] != 0, s,
                             _NEG_INF)
        if not self.causal:
            return s
        tq, tk = s.shape[-2:]
        q_pos = (jnp.arange(tq) if q_pos is None else q_pos)[:, None]
        k_pos = jnp.arange(tk)
        if first_k is not None:
            k_pos = first_k + k_pos
        return jnp.where(self.seen(q_pos, k_pos[None, :])[None, None], s,
                         _NEG_INF)

    def block_case(self, qi, ki, block_q, block_k):
        """What the mask does to block pair (q block ``qi``, k block
        ``ki``): ``(visible, crossed)``. *Visible*: every query of the
        block sees every key, so no position is masked. *Crossed*: an edge
        of the mask passes through, so some are. Neither: *skipped*, no
        query sees a key. Plain arithmetic on the block indices, so it
        takes Python ints and arrays (the counts) as well as a kernel's
        program ids; the one place the mask's block geometry is written
        down: both kernels ask it (:meth:`live`).

        The causal mask has one edge, the diagonal. A window added the
        second: a pair whose last key lies left of the FIRST query's window
        is skipped, and a pair is visible only if its first key lies inside
        the LAST query's window, so a pair may be crossed by the diagonal,
        by the window's edge, or (a block wider than the window) by both. A
        further mask (segment ids) would bound the same two sets."""
        if not self.causal:
            return True, False
        first_q, first_k = qi * block_q, ki * block_k
        last_q, last_k = first_q + block_q - 1, first_k + block_k - 1
        visible = last_k <= first_q
        crossed = (first_k <= last_q) & (last_k > first_q)
        if not self.window:
            return visible, crossed
        live = (visible | crossed) & (last_k > first_q - self.window)
        visible = visible & (first_k > last_q - self.window)
        return visible, live ^ visible      # visible implies live

    def live(self, qi, ki, block_q, block_k):
        """Whether step (qi, ki) of a kernel computes: every pair without
        a mask, the visible and the crossed ones under one."""
        visible, crossed = self.block_case(qi, ki, block_q, block_k)
        return visible | crossed

    def block_pairs(self, n_q, n_k, block_q, block_k):
        """``(visible, crossed)`` of a head's ``n_q x n_k`` block pairs."""
        import numpy as np
        return tuple(int(np.broadcast_to(x, (n_q, n_k)).sum())
                     for x in self.block_case(
                         np.arange(n_q)[:, None], np.arange(n_k)[None, :],
                         block_q, block_k))

    def pairs_seen(self, t):
        """(query, key) pairs the mask lets through over ``t`` positions
        (geometry only: a selection's count is its caller's)."""
        if not self.causal:
            return t * t
        w = self.window or t
        return w * t - w * (w - 1) // 2

    def count_block_pairs(self, n_q, n_k, block_q, block_k):
        """``pallas_flash.block_pairs{skipped,visible,crossed}``: one
        call's pairs a head, as :meth:`block_case` sorts them."""
        from ... import telemetry
        visible, crossed = self.block_pairs(n_q, n_k, block_q, block_k)
        for tag, n in (("skipped", n_q * n_k - visible - crossed),
                       ("visible", visible), ("crossed", crossed)):
            telemetry.inc("pallas_flash.block_pairs", n, tag=tag)

    def on_tile(self, st, qi, ki, block_q, block_k, first_col=0, tile=None):
        """The mask on the transposed tile ``st`` [k rows, q columns from
        ``first_col`` of the q block on] of a live block pair: the diagonal
        and, under a window, its far edge; under a selection ``tile``, the
        int8 block of the same shape, non-zero where the key is in the
        query's set. It runs on the visible pairs too, where it changes
        nothing: a second copy of a kernel's body without it read no faster
        on the chip, forward or backward (the mask's passes fill VALU slots
        the MXU-bound schedule leaves empty; PERF.md §6, PR 29)."""
        if self.selected:
            return jnp.where(tile.astype(jnp.int32) != 0, st, _NEG_INF)
        if not self.causal:
            return st
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        q_pos = qi * block_q + first_col + jax.lax.broadcasted_iota(
            jnp.int32, st.shape, 1)
        return jnp.where(self.seen(q_pos, k_pos), st, _NEG_INF)

    def split(self, refs):
        """``(the selection's tile ref or None, the other refs)``: the tile
        is the LAST input of both kernels whenever the mask selects."""
        return (refs[0], refs[1:]) if self.selected else (None, refs)

    def vmem(self, block_q, block_k):
        """Bytes a grid step holds for the mask: a selection's int8 tile,
        double-buffered."""
        return 2 * block_q * block_k if self.selected else 0

    def blocks(self, block_q, block_k):
        """The pair of blocks a call asks :func:`_plan` for: what its caller
        named, else 1,024 x 1,024 (``_BLOCK_Q``, ``_BLOCK_K``), under a
        window narrower than that the window's width (in 128s): a q block of
        1,024 under a window of 512 visits two k blocks of 1,024 for 512
        keys a query, 3.9 times the window's pairs; at 512 x 512 it visits
        2.0 times, and on the chip both kernels read a quarter faster
        (PERF.md §6, PR 45). A window of 1,024 or more changes nothing."""
        widest = _lanes(self.window) if self.window else _BLOCK_Q
        return (block_q or min(_BLOCK_Q, widest),
                block_k or min(_BLOCK_K, widest))

    def name(self, direction):
        """The kernels' name in a device trace (``direction``: ``fwd`` |
        ``bwd``), so that a trace tells the kinds of call apart."""
        kind = ("sparse_attention" if self.selected else
                "flash_window" if self.window else "flash_attention")
        return "%s_%s" % (kind, direction)

    # the grid's cut: without a window every block is a step (the causal
    # call skips by predicate, and its index maps fetch nothing for a
    # skipped step); under one the sequential axis holds only the steps a
    # block can need, counted from the first block the window reaches
    def first_k_block(self, i, block_q, block_k, xp=jnp):
        """The first k block that q block ``i``'s window reaches: where its
        steps of the forward start, and where its rows of dq open in the
        backward. (``xp``: ``numpy`` for the static counts.)"""
        if not self.window:
            return 0
        return xp.maximum(i * block_q - (self.window - 1), 0) // block_k

    def last_k_block(self, i, n_k, block_q, block_k, xp=jnp):
        """The k block at which q block ``i``'s rows of dq leave the
        backward: under a window its diagonal's, else the last."""
        if not self.window:
            return n_k - 1
        return xp.minimum(n_k - 1, (i * block_q + block_q - 1) // block_k)

    def last_q_block(self, j, n_q, block_q, block_k, xp=jnp):
        """The last q block whose window still reaches k block ``j``."""
        if not self.window:
            return n_q - 1
        return xp.minimum((j * block_k + block_k + self.window - 2)
                          // block_q, n_q - 1)

    def steps(self, n_q, n_k, block_q, block_k):
        """Steps of each kernel's sequential axis, static: ``(k steps a q
        block needs at most, q steps a k block needs at most)``: five of
        sixteen at blocks of 1,024, a window of 4,096 and 16,384
        positions."""
        if not self.window:
            return n_k, n_q
        import numpy as np
        i, j = np.arange(n_q), np.arange(n_k)
        k_steps = self.last_k_block(i, n_k, block_q, block_k, np) \
            - self.first_k_block(i, block_q, block_k, np)
        q_steps = self.last_q_block(j, n_q, block_q, block_k, np) \
            - j * block_k // block_q
        return int(k_steps.max()) + 1, int(q_steps.max()) + 1

    def k_at(self, i, step, block_q, block_k):
        """The k block that step ``step`` of q block ``i`` stands for in
        the forward's grid."""
        if self.window:
            return step + self.first_k_block(i, block_q, block_k)
        return step

    def q_at(self, j, step, block_q, block_k):
        """The q block that step ``step`` of k block ``j`` stands for in
        the backward's grid: under a window counted from the k block's
        diagonal, and then possibly past the last q block."""
        if self.window:
            return step + j * block_k // block_q
        return step

    def k_block(self, i, step, block_q, block_k):
        """The forward's index map: the k block that step ``step`` of q
        block ``i`` names: its own where it computes, else the last block
        the q block needed, so that nothing is fetched for a skipped
        step."""
        j = self.k_at(i, step, block_q, block_k)
        if not self.causal:
            return j
        return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)

    def q_block(self, j, step, block_q, block_k, n_q):
        """The backward's index map. Causal: the q blocks above a k block's
        diagonal compute nothing, and name the first block that does, which
        is then fetched once (the last q block where no row sees this k
        block: tk > t), held inside the array (a block index past the end
        halts the chip; the interpreter clamps it and says nothing). Under
        a window the steps stop at the last q block the window lets
        reach."""
        i = self.q_at(j, step, block_q, block_k)
        if not self.causal:
            return i
        if self.window:
            return jnp.minimum(
                i, self.last_q_block(j, n_q, block_q, block_k))
        return jnp.minimum(jnp.maximum(i, (j * block_k) // block_q), n_q - 1)


def _xla_attention(q, k, v, mask, scale):
    """The plain path's output. ``mask``: a :class:`Mask`, or ``causal``
    alone, as ``parallel/ring_attention.py``'s dense path passes it."""
    if not isinstance(mask, Mask):
        mask = Mask(bool(mask))
    out, _ = _xla_attention_lse(q, k, v, mask, scale)
    return out


def _xla_attention_lse(q, k, v, mask, scale, selection=None):
    """Fallback (out, lse): ONE copy of the XLA math; differentiable.
    ``selection`` ([B, Tk, T] int8, keys first): a selecting mask's sets."""
    k, v = _repeat_kv(q, k, v)     # grouped heads: K, V at the query heads
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    s = mask.on_scores(s, selection)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), lse


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


def _fa_kernel(q_ref, k_ref, v_ref, *refs, scale, mask, block_q, block_k,
               n_k):
    """One (head, q block, k block) step of the forward, on the TRANSPOSED
    score tile ``s^T = k q^T`` [bk on sublanes, bq on lanes], the form of
    :func:`_fa_bwd_kernel`: the row statistics (running max m, running sum
    l, the rescale alpha) are [1, bq] rows, their reductions run down the
    sublanes (vreg-to-vreg max / add, no cross-lane step), they broadcast
    along sublanes for nothing, and the accumulator is kept transposed,
    ``acc^T [dv, bq] += v^T p^T``, turned once a q block. With the whole
    row in one k block (``n_k == 1``) nothing is carried: no scratch, no
    rescale. ``n_k`` is the grid's k steps (:meth:`Mask.steps`). ``refs``:
    under a selecting mask the int8 tile [bk, bq] of the selection, keys on
    sublanes as the scores are (:meth:`Mask.split`); then the outputs
    ``o_ref``, ``lse_ref`` and, where the state is carried, its scratch."""
    sel_ref, (o_ref, lse_ref, *scratch) = mask.split(refs)
    qi = pl.program_id(1)
    step = pl.program_id(2)
    ki = mask.k_at(qi, step, block_q, block_k)
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

    @pl.when(mask.live(qi, ki, block_q, block_k))
    def _step():
        k = k_ref[0]                              # [bk, d]
        v = v_ref[0]                              # [bk, dv]
        slab = _Q_SLAB if block_q % _Q_SLAB == 0 else block_q
        slabs = [slice(c, c + slab) for c in range(0, block_q, slab)]
        # every slab's scores first: the products do not wait for a softmax
        tiles = [_dot(k, q_ref[0, cols, :], ((1,), (1,))) * scale
                 for cols in slabs]               # [bk, slab] float32 each
        for cols, st in zip(slabs, tiles):
            st = mask.on_tile(
                st, qi, ki, block_q, block_k, cols.start,
                None if sel_ref is None else sel_ref[0, :, cols])
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


def _fa_forward_pallas(q, k, v, mask, scale, block_q, block_k,
                       selection=None):
    """``selection`` ([B, Tk, T] int8, keys first) under a selecting mask:
    its tile is fetched beside each k block, the kernel's last input."""
    b, h, t, d = q.shape
    tk, dv = k.shape[2], v.shape[3]   # values may be narrower than keys
    bh, kv_head = b * h, _kv_head_map(_group(q, k))
    n_q = t // block_q
    # the grid's k axis: the steps a q block can need
    n_k = mask.steps(n_q, tk // block_k, block_q, block_k)[0]
    from jax.experimental.pallas import tpu as pltpu
    kernel = functools.partial(_fa_kernel, scale=scale, mask=mask,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    interpret = _interpret()
    extra = {}
    if not interpret:  # Mosaic-only hints: the interpreter takes none
        itm = jnp.dtype(q.dtype).itemsize
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                _fwd_vmem(block_q, block_k, d, dv, itm)
                + mask.vmem(block_q, block_k)))

    def k_block(i, j):
        return mask.k_block(i, j, block_q, block_k)

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
             if mask.selected else []),
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
        name=mask.name("fwd"),
        **extra,
    )(q.reshape(bh, t, d), k.reshape(-1, tk, d), v.reshape(-1, tk, dv),
      *([selection] if mask.selected else []))
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
def _fa_backward_blockwise(q, k, v, out, lse, g, mask, scale, block_k,
                           g_lse=None, selection=None):
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
        first = j * block_k

        def block(x, axis=2):
            return jax.lax.dynamic_slice_in_dim(x, first, block_k, axis=axis)
        ks, vs = block(k32), block(v32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, ks,
                       preferred_element_type=f32) * scale
        # a selection is [B, Tk, T], keys first: the rows of these keys
        s = mask.on_scores(s, None if selection is None
                           else block(selection, 1), q_pos, first)
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


def _fa_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, *refs,
                   scale, mask, block_q, block_k, n_q, n_k, group=1, q_steps):
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

    ``q_steps`` is the grid's q axis (:meth:`Mask.steps`): under a window
    the steps a k block can need, counted from its diagonal's q block, in
    place of all ``n_q`` q blocks; a q block's rows of dq then open at the
    first k block its window reaches and leave at its diagonal's, not at
    the first and last k block. ``refs``: under a selecting mask its int8
    tile [bk, bq] (:meth:`Mask.split`), then the outputs ``dq_ref``,
    ``dk_ref``, ``dv_ref`` and their float32 accumulators."""
    sel_ref, (dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = mask.split(
        refs)
    if group == 1:
        ki, step, kv = pl.program_id(1), pl.program_id(2), slice(None)

        def in_head(gi, cond):        # one query head a key/value head
            return cond
    else:
        head, ki, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
        kv = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)

        def in_head(gi, cond):        # ``cond``, in the group's head ``gi``
            return cond & (head == gi)
    qi = mask.q_at(ki, step, block_q, block_k)

    def here(cond):
        # under a window the last k blocks' steps run past the last q block:
        # the mask would let such queries see these keys, the array has none
        return cond & (qi < n_q) if mask.window else cond
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(in_head(0, step == 0))
    def _init_kv():
        dk_acc[kv] = jnp.zeros((block_k, dk_acc.shape[1]), jnp.float32)
        dv_acc[kv] = jnp.zeros((block_k, dv_acc.shape[1]), jnp.float32)

    # q block ``qi``'s rows of dq open at the first k block it reaches
    @pl.when(here(ki == mask.first_k_block(qi, block_q, block_k)))
    def _init_q():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    @pl.when(here(mask.live(qi, ki, block_q, block_k)))
    def _step():
        q = q_ref[0]                              # [bq, d]
        k = k_ref[0]                              # [bk, d]
        v = v_ref[0]                              # [bk, dv]
        g = g_ref[0]                              # [bq, dv]
        # the forward kernel's precision policy (:func:`_dot`)
        st = _dot(k, q, ((1,), (1,))) * scale     # [bk, bq]
        st = mask.on_tile(st, qi, ki, block_q, block_k,
                          tile=None if sel_ref is None else sel_ref[0])
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

    @pl.when(here(ki == mask.last_k_block(qi, n_k, block_q, block_k)))
    def _store_q():       # and leave at the last
        dq_ref[0, rows, :] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)


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


def _plan(q, k, v, mask, block_q, block_k, direction):
    """``((block_q, block_k), None)`` for the kernel of ``direction``
    (``forward`` | ``backward``), or ``(None, reason)`` where it refuses
    and the plain path runs: the platform, then :func:`_tile_blocks` under
    the direction's VMEM sum (the backward keeps dq of the whole head, and
    under grouped heads dk and dv of the whole key/value head:
    :func:`_bwd_vmem`) with the mask's bytes in it. It counts nothing: the
    caller owns the counter family."""
    if _platform() != "tpu" and not _interpret():
        return None, "platform is not tpu"
    t, tk, d, dv = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    if mask.selected and t != tk:
        return None, "as many keys as queries are needed"
    # a head dim off the 128-lane granule (64 for BERT-base et al.) is no
    # reason to fall back: _pad_head_dim zero-pads it, and scores and lse
    # are invariant to zero columns
    itm = jnp.dtype(q.dtype).itemsize
    if direction == "forward":
        return _tile_blocks(
            t, tk, block_q, block_k,
            lambda bq, bk: _fwd_vmem(bq, bk, d, dv, itm) + mask.vmem(bq, bk),
            "one q block does not fit the VMEM budget")
    too_big = "dq of one head does not fit the VMEM budget"
    # under grouped heads dk and dv of the whole key/value head where that
    # fits; where no block does (16,384 keys of 256: 67 MB beside dq's 34),
    # a k block of them a QUERY head, which XLA then sums (:func:`_kv_held`)
    for whole in ((tk, 0) if _group(q, k) > 1 else (0,)):
        blocks, reason = _tile_blocks(
            t, tk, block_q, block_k,
            lambda bq, bk: _bwd_vmem(bq, bk, t, d, dv, itm, whole)
            + mask.vmem(bq, bk), too_big)
        if reason != too_big:
            break
    return blocks, reason


def _kv_held(q, k, v, mask, block_q, block_k):
    """Whether the grouped backward holds dk and dv of the whole key/value
    head in VMEM (the layout :func:`_plan` tries first) at these blocks."""
    itm = jnp.dtype(q.dtype).itemsize
    return _group(q, k) > 1 and _bwd_vmem(
        block_q, block_k, q.shape[2], q.shape[3], v.shape[3], itm,
        k.shape[2]) + mask.vmem(block_q, block_k) <= _VMEM_BUDGET


@jax.named_scope("flash_attention_bwd")   # prologue, kernel and epilogue
def _fa_backward_pallas(q, k, v, out, lse, g, mask, scale, block_q, block_k,
                        g_lse=None, selection=None):
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
    # dk and dv of the whole key/value head in VMEM, summed over its query
    # heads inside the kernel, or (not grouped, or too large for that) a k
    # block of them a query head
    grouped = _kv_held(q, k, v, mask, block_q, block_k)
    if group > 1 and not grouped:
        from ... import telemetry
        telemetry.inc("pallas_flash.bwd_kv_by_query_head")
    bh = b * h
    n_q = t // block_q
    n_k = tk // block_k
    # the grid's q axis: every q block, or the steps a k block can need
    q_steps = mask.steps(n_q, n_k, block_q, block_k)[1]
    kernel = functools.partial(_fa_bwd_kernel, scale=scale, mask=mask,
                               block_q=block_q, block_k=block_k, n_q=n_q,
                               n_k=n_k, group=group if grouped else 1,
                               q_steps=q_steps)
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
                + mask.vmem(block_q, block_k)))

    def q_block(j, i):
        return mask.q_block(j, i, block_q, block_k, n_q)

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
        def at(b_, j, i):       # K and V at their own head
            return b_, (b_ if group == 1
                        else b_ // h * hk + b_ % h // group), j, i
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
        lambda hq, hkv, j, i: (hq, j, 0))
    # where XLA sums the group, it sums float32
    kv_heads, kv_dtypes = (b * hk, (k.dtype, v.dtype)) \
        if grouped or group == 1 else (bh, (f32, f32))
    dq, dk, dv_ = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec, g_spec, row_spec, row_spec] + (
            [spec((1, block_k, block_q),
                  lambda hq, hkv, j, i: (hq // h, j, q_block(j, i)))]
            if mask.selected else []),
        out_specs=[
            spec((1, t, d), lambda hq, hkv, j, i: (hq, 0, 0)),
            spec((1, kv_rows, d), kv_block),
            spec((1, kv_rows, dv), kv_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((kv_heads, tk, d), kv_dtypes[0]),
            jax.ShapeDtypeStruct((kv_heads, tk, dv), kv_dtypes[1]),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, d), f32),          # dq of the head
            pltpu.VMEM((kv_rows, d), f32),    # dk of the k block (or head)
            pltpu.VMEM((kv_rows, dv), f32),   # dv of the k block (or head)
        ],
        interpret=interpret,
        name=mask.name("bwd"),
        **extra,
    )(q.reshape(bh, t, d), k.reshape(-1, tk, d), v.reshape(-1, tk, dv),
      g.reshape(bh, t, dv), lse.reshape(bh, 1, t), delta.reshape(bh, 1, t),
      *([selection] if mask.selected else []))
    if kv_heads != b * hk:
        dk, dv_ = (x.reshape(b, hk, group, tk, -1).sum(2).astype(like.dtype)
                   for x, like in ((dk, k), (dv_, v)))
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


def _count_forward(q, k, selection, mask, topk, blocks, refused):
    """One traced forward's counters, in the family its mask belongs to:
    ``sparse_attention.*`` under a selection (``topk``: what built the
    sets, for ``pairs_selected``), else ``pallas_flash.*``. Every outcome
    is counted in ``pallas_flash.{pallas,xla}`` / reason-tagged
    ``pallas_flash.fallback``.

    On TPU the fallback is a real memory cliff (the [T, T] score matrix
    materializes in HBM), so it warns ONCE per offending shape instead of
    silently absorbing it (VERDICT r4 weak #7); off the TPU it is expected,
    counted but not a cliff worth warning about."""
    from ... import telemetry
    t, tk, d = q.shape[2], k.shape[2], q.shape[3]
    if blocks is not None:
        bq, bk = blocks
        pairs = (t // bq, tk // bk, bq, bk)
    if mask.selected:       # visited: the square, or the kernels' live pairs
        _count_sparse(selection, topk, t * t if blocks is None else sum(
            mask.block_pairs(*pairs)) * bq * bk, refused)
        return
    if _group(q, k) > 1:
        telemetry.inc("pallas_flash.grouped")
    if mask.window:
        # what the window sees against what the path taken visits, a head:
        # the kernel's live blocks whole, or the plain path's square
        telemetry.inc("pallas_flash.windowed")
        telemetry.inc("pallas_flash.window_pairs_seen", mask.pairs_seen(t))
        telemetry.inc("pallas_flash.window_pairs_visited",
                      t * tk if blocks is None
                      else sum(mask.block_pairs(*pairs)) * bq * bk)
    if blocks is not None:
        telemetry.inc("pallas_flash.pallas")
        mask.count_block_pairs(*pairs)
        return
    telemetry.inc("pallas_flash.xla")
    telemetry.inc("pallas_flash.fallback", tag=refused)
    if mask.window:     # the plain path visits the pairs left of the window
        telemetry.inc("pallas_flash.window_unskipped")
    key = (refused, t, tk, d)
    if _platform() == "tpu" and key not in _warned_fallbacks:
        _warned_fallbacks.add(key)
        import warnings
        warnings.warn(
            "flash_attention falling back to the XLA softmax path "
            "(%s; q[T=%d] k[T=%d] D=%d): the [T,T] score matrix "
            "will materialize in HBM — pad the keys' T to a "
            "multiple of 128 and the queries' to one of 128 (or, "
            "for one block, of 8) for the fused kernel (head dims "
            "are padded to the 128-lane granule automatically)" % key)


def _count_backward(mask, refused):
    """The counters of one traced backward of a kernel forward: the fused
    kernel, or the blockwise path with the reason the kernel refused, like
    the forward's fallbacks."""
    from ... import telemetry
    if mask.selected:
        if refused is None:
            telemetry.inc("sparse_attention.bwd_pallas")
        else:
            telemetry.inc("sparse_attention.fallbacks",
                          tag="backward: " + refused)
    elif refused is None:
        telemetry.inc("pallas_flash.bwd_pallas")
    else:
        telemetry.inc("pallas_flash.bwd_xla")
        telemetry.inc("pallas_flash.bwd_fallback", tag=refused)
        if mask.window:  # the plain path visits the pairs left of the window
            telemetry.inc("pallas_flash.window_unskipped")


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
def _count_sparse(selection, topk, visited, reason=None):
    """One call's counters. ``pairs_selected`` is what the algorithm needs
    (``sum_t min(t + 1, topk)`` a sequence, where the caller says what
    ``topk`` built the set), ``visited`` what the path taken touches."""
    from ... import telemetry
    b, tk, t = selection.shape
    telemetry.inc("sparse_attention.calls")
    if topk:
        k = min(topk, t)
        telemetry.inc("sparse_attention.pairs_selected",
                      b * (k * t - k * (k - 1) // 2))
    telemetry.inc("sparse_attention.pairs_visited", b * visited)
    if reason is not None:
        telemetry.inc("sparse_attention.fallbacks", tag=reason)


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


# ------------------------------------------------------------ the one core
# What a forward gives that its backward reads, by the names a caller's
# ``jax.checkpoint(..., policy=save_only_these_names(*KEPT_NAMES))`` keeps:
# O(T) bytes that cost O(T^2) work to make again. Named INSIDE the forward
# rule, before the value parts into the output and the residual: a name put
# on the ``custom_vjp``'s result would mark the output's copy alone, and
# the residual, which is what the backward waits for, would still be made
# again by a second run of the kernel. Under no checkpoint, or one whose
# policy lists no name, a name is the identity and lowers to nothing.
KEPT_NAMES = ("flash_out", "flash_lse")


def _kept(out, lse, mask):
    """A call under a window names nothing: its output is the same bytes
    for O(T W) work (Laguna's three layers at a window of 512: 0.92 GB for
    18.6 ms, 20 ms a GB where a full layer's are 134), and the one caller
    that recomputes has no room for them (PERF.md §6, PR 46)."""
    if mask.window:
        return out, lse
    return tuple(checkpoint_name(x, name)
                 for x, name in zip((out, lse), KEPT_NAMES))


def _forward(q, k, v, selection, mask, scale, block_q, block_k, topk):
    """The one forward under the three public functions: ``(out, lse
    [B, H, T], residual)``, on the kernel or, where :func:`_plan` refuses,
    on the plain path, whose residual holds no lse (its backward is XLA's
    own). ``out`` (at ``v``'s width) and the lse rows carry their
    :data:`KEPT_NAMES` (under no window: :func:`_kept`) from here on, into
    the output and the residual alike."""
    blocks, refused = _plan(q, k, v, mask, block_q, block_k, "forward")
    _count_forward(q, k, selection, mask, topk, blocks, refused)
    if blocks is None:
        out, lse = _kept(*_xla_attention_lse(q, k, v, mask, scale, selection),
                        mask)
        return out, lse, (q, k, v, out, None, selection)
    out, lse = _fa_forward_pallas(*_pad_head_dim(q, k, v), mask, scale,
                                  *blocks, selection)
    if out.shape[-1] != v.shape[-1]:
        out = out[..., :v.shape[-1]]
    out, lse = _kept(out, lse, mask)
    # the residual is the kernel's own (bh, 1, t) rows, which the backward
    # kernel reads as they are; the public lse is [B, H, T]
    return out, lse.reshape(q.shape[:3]), (q, k, v, out, lse, selection)


def _backward(res, g, g_lse, mask, scale, block_q, block_k):
    """The one backward: ``(dq, dk, dv)``. Where the forward ran the
    kernel (``lse`` was saved) the fused kernel, or the blockwise XLA path
    for a case the kernel refuses, counted with its reason like the
    forward's fallbacks; else the plain forward differentiated. ``g_lse``:
    the cotangent of the lse output, None where nothing reads it."""
    q, k, v, out, lse, selection = res
    if lse is None:
        # fallback path: differentiate the XLA implementation directly
        def plain(q_, k_, v_):
            both = _xla_attention_lse(q_, k_, v_, mask, scale, selection)
            return both[0] if g_lse is None else both
        return jax.vjp(plain, q, k, v)[1](g if g_lse is None else (g, g_lse))
    blocks, refused = _plan(q, k, v, mask, block_q, block_k, "backward")
    _count_backward(mask, refused)
    if blocks is None:
        # plain jax (no lane constraint), but its k-block must DIVIDE tk —
        # the scan would silently drop a ragged tail otherwise
        block_k = _pick_block(k.shape[2], block_k, 1) or k.shape[2]
        return _fa_backward_blockwise(q, k, v, out, lse.reshape(q.shape[:3]),
                                      g, mask, scale, block_k, g_lse=g_lse,
                                      selection=selection)
    # a selecting call's backward is found in a trace by a scope of its own
    with jax.named_scope("sparse_attention_bwd") if mask.selected \
            else contextlib.nullcontext():
        return _fa_backward_pallas(q, k, v, out, lse, g, mask, scale, *blocks,
                                   g_lse=g_lse, selection=selection)


def _differentiable(with_lse):
    """A ``custom_vjp`` over the one forward and the one backward:
    ``(q, k, v, selection or None; mask, scale, block_q, block_k, topk)``
    -> ``(out, lse)`` or, not ``with_lse``, ``out``. Two registrations of
    the one pair, not one with symbolic zeros: the backward of a call whose
    lse nothing reads must not see an instantiated zero ``g_lse`` (``delta
    - 0`` in the prologue of every flash_attention call), and JAX has no
    ``symbolic_zeros`` under ``shard_map``, where ring attention calls."""
    def outputs(out, lse):
        return (out, lse) if with_lse else out

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
    def attention(*args):
        return outputs(*_forward(*args)[:2])

    def fwd(*args):
        out, lse, res = _forward(*args)
        return outputs(out, lse), res

    def bwd(mask, scale, block_q, block_k, topk, res, cots):
        import numpy as np
        g, g_lse = cots if with_lse else (cots, None)
        grads = _backward(res, g, g_lse, mask, scale, block_q, block_k)
        # the sets are a constant of the step: they take no gradient
        return tuple(grads) + (None if res[5] is None else np.zeros(
            res[5].shape, jax.dtypes.float0),)

    attention.defvjp(fwd, bwd)
    return attention


_attention, _attention_lse = _differentiable(False), _differentiable(True)


def _scale(q, scale):
    return 1.0 / (q.shape[-1] ** 0.5) if scale is None else scale


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, window=0):
    """Fused attention [B, H, T, D] -> [B, H, T, D]; falls back to XLA softmax
    off-TPU or for non-divisible shapes. ``causal``: key ``j`` is visible
    to query ``i`` iff ``j <= i``; with ``window = W > 0`` (causal only)
    iff ``i - W < j <= i``, the query's own key among the ``W``
    (transformers' sliding-window mask). ``block_q``, ``block_k``: the
    blocks to ask for, else :meth:`Mask.blocks`' (1,024 x 1,024, under a
    narrower window its width)."""
    mask = Mask.of(q, k, causal, window)
    return _attention(q, k, v, None, mask, _scale(q, scale),
                      *mask.blocks(block_q, block_k), 0)


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=_BLOCK_Q, block_k=_BLOCK_K):
    """Like :func:`flash_attention` but ALSO returns the per-row
    log-sum-exp [B, H, T] — the quantity that lets partial attention
    results over disjoint key sets be merged exactly (ring attention's
    per-step blocks combine as out = Σ_j softmax(lse_j) out_j)."""
    return _attention_lse(q, k, v, None, Mask(bool(causal)), _scale(q, scale),
                          block_q, block_k, 0)


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
    return _attention(q, k, v, mask_t, Mask(True, selected=True),
                      _scale(q, scale), block_q, block_k, topk)
