"""Flash attention: fused online-softmax attention for TPU.

No reference counterpart (the reference predates flash attention; its only
attention helper is ``_contrib_div_sqrt_dim``, src/operator/contrib/
transformer.cc). This is the single-chip hot path under
:func:`mxtpu.parallel.ring_attention.ring_self_attention`'s per-shard compute
and the model zoo transformer.

Design (TPU-first):
* forward: one Pallas kernel, grid (batch*heads, Tq/bq, Tk/bk) — the k-block
  axis is innermost so the online-softmax state (m, l, acc) lives in VMEM
  scratch across k steps; the [T, T] score matrix never materializes in HBM.
  Causal q/k block pairs above the diagonal are skipped (`pl.when`), saving
  ~half the FLOPs.
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

    _KEYS = ("pallas", "xla", "fallback_reasons",
             "bwd_pallas", "bwd_xla", "bwd_fallback_reasons")
    _TAGGED = {"fallback_reasons": "pallas_flash.fallback",
               "bwd_fallback_reasons": "pallas_flash.bwd_fallback"}

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
    for name in ("pallas", "xla", "fallback",
                 "bwd_pallas", "bwd_xla", "bwd_fallback"):
        telemetry.reset_metric("pallas_flash." + name)


def _count_fallback(reason):
    from ... import telemetry
    telemetry.inc("pallas_flash.xla")
    telemetry.inc("pallas_flash.fallback", tag=reason)


def _xla_attention(q, k, v, causal, scale):
    out, _ = _xla_attention_lse(q, k, v, causal, scale)
    return out


def _xla_attention_lse(q, k, v, causal, scale):
    """Fallback attention returning (out, lse) — ONE copy of the XLA math
    (softmax(s) == exp(s - lse) exactly); differentiable directly."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = s.shape[-2:]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), lse


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
               *, scale, causal, block_q, block_k, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: a k block strictly above the q block's diagonal is all-masked
    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        # operands stay in their input dtype (bf16 = single-pass MXU);
        # accumulation is f32 via preferred_element_type. K arrives
        # pre-transposed [d, bk] so both matmuls are plain (1,0)
        # contractions (Mosaic's native MXU form).
        q = q_ref[0]                              # [bq, d]
        kt = k_ref[0]                             # [d, bk]
        vb = v_ref[0]                             # [bk, d]
        # bf16 inputs: single-pass MXU (DEFAULT) — the global
        # jax_default_matmul_precision=float32 would request a multi-pass
        # bf16 contraction Mosaic cannot lower. f32 inputs keep HIGHEST so
        # reference-parity numerics hold.
        prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
        s = jax.lax.dot_general(q, kt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_scr[:, :1]                     # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                    # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)           # [bq, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p cast to the value dtype for a single-pass MXU matmul (standard
        # flash practice); accumulator stays f32
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # [bq, 128] lane-replicated (TPU tiling needs a 128 trailing dim);
        # lane 0 is sliced out on the host side
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30)), lse_ref.shape[1:])


def _fa_forward_pallas(q, k, v, causal, scale, block_q, block_k):
    b, h, t, d = q.shape
    tk, dv = k.shape[2], v.shape[3]   # values may be narrower than keys
    bh = b * h
    q3 = q.reshape(bh, t, d)
    k3 = jnp.swapaxes(k.reshape(bh, tk, d), 1, 2)  # [bh, d, tk] for the MXU
    v3 = v.reshape(bh, tk, dv)
    n_q = t // block_q
    n_k = tk // block_k
    from jax.experimental.pallas import tpu as pltpu
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    interpret = _interpret()
    extra = {}
    if not interpret:  # Mosaic-only hint: the interpreter takes none
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    def k_block(i, j):
        # causal: the steps past a q block's diagonal compute nothing, and
        # name the last block they needed, so that nothing is fetched
        # for them either
        if not causal:
            return j
        return jnp.minimum(j, (i * block_q + block_q - 1) // block_k)

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, d, block_k),
                         lambda b_, i, j: (b_, 0, k_block(i, j))),
            pl.BlockSpec((1, block_k, dv),
                         lambda b_, i, j: (b_, k_block(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b_, i, j: (b_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, dv), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention_fwd",   # the kernel's name in a device trace
        **extra,
    )(q3, k3, v3)
    return out.reshape(b, h, t, dv), lse[:, :, 0].reshape(b, h, t)


@jax.named_scope("flash_attention_bwd")   # plain XLA: found by this scope
def _fa_backward_blockwise(q, k, v, out, lse, g, causal, scale, block_k,
                           g_lse=None):
    """Flash-attention-2 backward, blockwise over k in plain jax:
    P = exp(S - lse); dv = P^T g; ds = P * (g v^T - D); dq += ds k; dk += ds^T q.

    ``g_lse`` is the cotangent of the lse OUTPUT (flash_attention_with_lse;
    d lse_i / d s_ik = P_ik, so it adds ``P * g_lse`` to ds).
    """
    f32 = jnp.float32
    q32, k32, v32 = q.astype(f32), k.astype(f32), v.astype(f32)
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
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)
            mask = q_pos[:, None] >= k_pos[None, :]
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
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(k.shape)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(v.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                   *, scale, causal, block_q, block_k, n_q, n_k):
    """One (head, k block, q block) step of the flash backward. Works on
    the TRANSPOSED score tile ``s^T = k q^T`` [bk, bq]: dv and dk are then
    plain matmuls with the tile on the left, lse and delta broadcast along
    sublanes from [1, bq] rows, and only dq contracts the tile's first
    axis. dk/dv accumulate over the inner (q) axis; dq for the whole head
    stays in VMEM while the k blocks pass."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(qi == 0)
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(ki == 0)
    def _init_q():
        dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]), jnp.float32)

    # causal: a k block strictly above the q block's diagonal is all-masked
    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                              # [bq, d]
        k = k_ref[0]                              # [bk, d]
        v = v_ref[0]                              # [bk, dv]
        g = g_ref[0]                              # [bq, dv]
        # the forward kernel's precision policy: operands in their input
        # dtype (bf16 = one MXU pass), float32 accumulation and statistics
        prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)

        def dot(a, b, contract):
            return jax.lax.dot_general(
                a, b, (contract, ((), ())),
                preferred_element_type=jnp.float32, precision=prec)

        st = dot(k, q, ((1,), (1,))) * scale      # [bk, bq]
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])             # P^T, lse as a [1, bq] row
        dv_acc[:] += dot(pt.astype(g.dtype), g, ((1,), (0,)))
        dpt = dot(v, g, ((1,), (1,)))             # dP^T [bk, bq]
        # ds^T without its factor ``scale``: that is applied once to the
        # [*, d] results instead of the [bk, bq] tile
        dst = (pt * (dpt - delta_ref[0])).astype(q.dtype)
        dk_acc[:] += dot(dst, q, ((1,), (0,)))
        dq_acc[rows, :] += dot(dst, k, ((0,), (0,)))

    @pl.when(qi == n_q - 1)
    def _store_kv():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(ki == n_k - 1)
    def _store_q():
        dq_ref[0, rows, :] = (dq_acc[rows, :] * scale).astype(dq_ref.dtype)


def _first_q_block(j, i, block_q, block_k, n_q):
    """The q block that step (k block ``j``, q block ``i``) of the causal
    backward names: ``i`` where it computes, else the first q block whose
    rows reach k block ``j``, held inside the array (a block index past
    the end halts the chip; the interpreter clamps it and says nothing)."""
    return jnp.minimum(jnp.maximum(i, (j * block_k) // block_q), n_q - 1)


# VMEM the backward kernel may plan for: v5e's scoped default is 16 MiB of
# 128 MiB; the kernel asks for what _bwd_vmem reckons, up to this
_BWD_VMEM_BUDGET = 64 * 1024 * 1024


def _bwd_vmem(bq, bk, t, d, dv, itm):
    """Bytes one grid step of the backward kernel holds, with dq of the
    whole head resident (``t`` rows). ``d`` is the width of queries and
    keys, ``dv`` of values and the cotangent."""
    dp, dvp = -(-d // 128) * 128, -(-dv // 128) * 128
    return (2 * (bq + bk) * (dp + dvp) * itm     # q, g, k, v blocks (dbuf)
            + 2 * 2 * 8 * bq * 4                 # lse, delta rows (dbuf)
            + bq * bk * (4 * 4 + 2 * itm)        # s^T, P^T, dP^T, ds^T + casts
            + bk * (dp + dvp) * (4 + 2 * itm)    # dk, dv: scratch + out (dbuf)
            + t * dp * (4 + 2 * itm))            # dq of the head: scratch + out


def _resolve_bwd_blocks(q, k, v, block_q, block_k):
    """``((block_q, block_k), None)`` for the backward kernel, or ``(None,
    reason)`` where it refuses. The tile is transposed against the
    forward's: q lies on the 128 lanes (a length off that granule is one
    whole block, which is always tileable) and k on the sublanes. The
    larger side halves until :func:`_bwd_vmem` fits the budget."""
    t, tk, d, dv = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    itm = jnp.dtype(q.dtype).itemsize
    while True:
        bq = _pick_block(t, block_q, 128) or (t if t % 8 == 0 else None)
        bk = _pick_block(tk, block_k, 128)
        if bq is None or bk is None:
            return None, "sequence length has no TPU-tileable block"
        if _bwd_vmem(bq, bk, t, d, dv, itm) <= _BWD_VMEM_BUDGET:
            return (bq, bk), None
        smaller_q = _pick_block(t, bq // 2, 128) if bq > 128 else None
        smaller_k = _pick_block(tk, bk // 2, 128) if bk > 128 else None
        if smaller_q and (bq >= bk or not smaller_k):
            block_q = smaller_q
        elif smaller_k:
            block_k = smaller_k
        else:
            return None, "dq of one head does not fit the VMEM budget"


@jax.named_scope("flash_attention_bwd")   # prologue, kernel and epilogue
def _fa_backward_pallas(q, k, v, out, lse, g, causal, scale, block_q,
                        block_k, g_lse=None):
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
    tk, dv = k.shape[2], v.shape[3]
    bh = b * h
    n_q = t // block_q
    n_k = tk // block_k
    kernel = functools.partial(_fa_bwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_q=n_q,
                               n_k=n_k)
    interpret = _interpret()
    extra = {}
    if not interpret:  # Mosaic-only hints: the interpreter takes none
        itm = jnp.dtype(q.dtype).itemsize
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=max(
                16 * 2**20,
                int(1.25 * _bwd_vmem(block_q, block_k, t, d, dv, itm))))

    def q_block(j, i):
        # causal: the q blocks above a k block's diagonal compute nothing,
        # and name the first block that does, which is then fetched once
        # (the last q block where no row sees this k block: tk > t)
        if not causal:
            return i
        return _first_q_block(j, i, block_q, block_k, n_q)

    q_spec = pl.BlockSpec((1, block_q, d),
                          lambda b_, j, i: (b_, q_block(j, i), 0))
    g_spec = pl.BlockSpec((1, block_q, dv),
                          lambda b_, j, i: (b_, q_block(j, i), 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda b_, j, i: (b_, j, 0))
    v_spec = pl.BlockSpec((1, block_k, dv), lambda b_, j, i: (b_, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda b_, j, i: (b_, 0, q_block(j, i)))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(bh, n_k, n_q),
        in_specs=[q_spec, k_spec, v_spec, g_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((1, t, d), lambda b_, j, i: (b_, 0, 0)),
            k_spec,
            v_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((t, d), f32),          # dq of the head
            pltpu.VMEM((block_k, d), f32),    # dk of the k block
            pltpu.VMEM((block_k, dv), f32),   # dv of the k block
        ],
        interpret=interpret,
        name="flash_attention_bwd",   # the kernel's name in a device trace
        **extra,
    )(q.reshape(bh, t, d), k.reshape(bh, tk, d), v.reshape(bh, tk, dv),
      g.reshape(bh, t, dv), lse.reshape(bh, 1, t), delta.reshape(bh, 1, t))
    return (dq.reshape(q.shape)[..., :d_out], dk.reshape(k.shape)[..., :d_out],
            dv.reshape(v.shape)[..., :dv_out])


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


def _resolve_blocks(q, k, block_q, block_k):
    """(block_q, block_k) for the Pallas kernel, or None → XLA fallback.

    On TPU the fallback is a real memory cliff (the [T, T] score matrix
    materializes in HBM), so it warns ONCE per offending shape instead of
    silently absorbing it (VERDICT r4 weak #7). Every outcome is counted
    in ``pallas_flash.{pallas,xla}`` / reason-tagged
    ``pallas_flash.fallback``."""
    t, tk, d = q.shape[2], k.shape[2], q.shape[3]
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
                    "will materialize in HBM — pad T to a multiple of 8 "
                    "(q) / 128 (k) for the fused kernel (head dims are "
                    "padded to the 128-lane granule automatically)"
                    % (reason, t, tk, d))
        return None

    if not on_tpu and not _interpret():
        # expected off-TPU; counted but not a cliff worth warning about
        return _fallback("platform is not tpu")
    # a head dim off the 128-lane granule (64 for BERT-base et al.) is no
    # reason to fall back: _pad_head_dim zero-pads it, and scores and lse
    # are invariant to zero columns
    bq = _pick_block(t, block_q, 8)       # sublane granularity
    bk = _pick_block(tk, block_k, 128)    # lane granularity
    if bq is None or bk is None:
        return _fallback("sequence length has no TPU-tileable block")
    telemetry.inc("pallas_flash.pallas")
    return bq, bk


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, scale=None, block_q=512,
                    block_k=512):
    """Fused attention [B, H, T, D] -> [B, H, T, D]; falls back to XLA softmax
    off-TPU or for non-divisible shapes."""
    out, _ = _fa_fwd(q, k, v, causal, scale, block_q, block_k)
    return out


def _fa_fwd(q, k, v, causal, scale, block_q, block_k):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    blocks = _resolve_blocks(q, k, block_q, block_k)
    if blocks is None:
        out = _xla_attention(q, k, v, causal, scale)
        return out, (q, k, v, out, None)
    out, lse = _fa_forward_pallas(*_pad_head_dim(q, k, v), causal, scale,
                                  *blocks)
    if out.shape[-1] != v.shape[-1]:
        out = out[..., :v.shape[-1]]
    return out, (q, k, v, out, lse)


def _fa_backward(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                 g_lse=None):
    """The backward of a forward that ran the Pallas kernel (``lse`` was
    saved): the fused kernel, or the blockwise XLA path for a case the
    kernel refuses, counted with its reason like the forward's fallbacks."""
    from ... import telemetry
    blocks, refused = _resolve_bwd_blocks(q, k, v, block_q, block_k)
    if blocks is None:
        telemetry.inc("pallas_flash.bwd_xla")
        telemetry.inc("pallas_flash.bwd_fallback", tag=refused)
        # plain jax (no lane constraint), but its k-block must DIVIDE tk —
        # the scan would silently drop a ragged tail otherwise
        block_k = _pick_block(k.shape[2], block_k, 1) or k.shape[2]
        return _fa_backward_blockwise(q, k, v, out, lse, g, causal, scale,
                                      block_k, g_lse=g_lse)
    telemetry.inc("pallas_flash.bwd_pallas")
    return _fa_backward_pallas(q, k, v, out, lse, g, causal, scale, *blocks,
                               g_lse=g_lse)


def _fa_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if lse is None:
        # fallback path: differentiate the XLA implementation directly
        _, vjp = jax.vjp(lambda q_, k_, v_:
                         _xla_attention(q_, k_, v_, causal, scale), q, k, v)
        return vjp(g)
    return _fa_backward(q, k, v, out, lse, g, causal, scale, block_q,
                        block_k)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal=False, scale=None, block_q=512,
                             block_k=512):
    """Like :func:`flash_attention` but ALSO returns the per-row
    log-sum-exp [B, H, T] — the quantity that lets partial attention
    results over disjoint key sets be merged exactly (ring attention's
    per-step blocks combine as out = Σ_j softmax(lse_j) out_j)."""
    out, lse, _res = _fa_lse_fwd_impl(q, k, v, causal, scale, block_q,
                                      block_k)
    return out, lse


def _fa_lse_fwd_impl(q, k, v, causal, scale, block_q, block_k):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    blocks = _resolve_blocks(q, k, block_q, block_k)
    if blocks is None:
        out, lse = _xla_attention_lse(q, k, v, causal, scale)
        return out, lse, (q, k, v, out, None)
    out, lse = _fa_forward_pallas(*_pad_head_dim(q, k, v), causal, scale,
                                  *blocks)
    if out.shape[-1] != v.shape[-1]:
        out = out[..., :v.shape[-1]]
    return out, lse, (q, k, v, out, lse)


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
