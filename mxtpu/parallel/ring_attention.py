"""Ring attention: sequence/context parallelism over the mesh.

No reference counterpart — the reference's long-sequence story is fused RNNs +
bucketing (SURVEY §5 "Long-context/sequence parallelism: Absent"). On TPU,
long-context attention shards the sequence axis across devices and rotates
key/value blocks around the ICI ring with ``ppermute`` while each device keeps
its query shard resident, accumulating the softmax *online* (flash-attention
style m/l running max/sum), so the full [T, T] score matrix never materializes
and per-device memory is O(T/n * T/n) per step.

Layout convention: ``[batch, heads, seq, head_dim]`` (the MXU-friendly layout:
the contraction q @ k^T is a [Tq, d] x [d, Tk] matmul per head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ring_flash_attention",
           "ring_self_attention"]

_NEG_INF = -1e30  # mask value; avoids -inf - -inf = nan in the online rescale


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Per-shard ring attention body — call INSIDE ``shard_map`` (or ``pmap``)
    with the sequence axis sharded over ``axis_name``.

    q, k, v: [B, H, T_local, D] local shards. Returns [B, H, T_local, D].
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, t_local, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    q32 = q.astype(jnp.float32)
    q_pos = idx * t_local + jnp.arange(t_local)

    acc0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    m0 = jnp.full((b, h, t_local), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def attend(k_c, v_c, acc, m, l, src):
        k_pos = src * t_local + jnp.arange(t_local)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_c.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_c.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    def step(carry, i):
        k_c, v_c, acc, m, l = carry
        # after i rotations of send-to-next, we hold the block that started
        # on shard (idx - i) mod n
        src = (idx - i) % n
        if causal:
            # blocks strictly in the future (src > idx) are fully masked:
            # skip both einsums (saves ~half the attention FLOPs on average)
            acc, m, l = jax.lax.cond(
                src <= idx,
                lambda args: attend(*args, src),
                lambda args: (args[2], args[3], args[4]),
                (k_c, v_c, acc, m, l))
        else:
            acc, m, l = attend(k_c, v_c, acc, m, l, src)
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        return (k_c, v_c, acc, m, l), None

    (_, _, acc, _, l), _ = jax.lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(n))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_flash_attention(q, k, v, axis_name, causal=False, scale=None,
                         block_q=None, block_k=None):
    """Ring attention whose per-step block runs the FUSED flash kernel
    (Pallas on TPU; XLA fallback elsewhere) instead of materializing the
    [T_local, T_local] block scores. Per-step partial results merge
    exactly via their log-sum-exps:

        out = Σ_j exp(lse_j - lse_total) · out_j

    The rotation schedule makes causality STATIC per step: at step 0
    every device attends its OWN diagonal block (causal kernel); later
    steps see strictly-past blocks (merged via lse) or strictly-future
    blocks (fully masked — the kernel is SKIPPED via lax.cond, matching
    the dense body's ~half-FLOP causal saving). Staged
    behind MXTPU_RING_FLASH (see registry.policy_key) pending on-chip
    measurement; numerics are pinned against the dense path either way.
    """
    from ..ops.pallas.flash_attention import (_BLOCK_K, _BLOCK_Q,
                                              flash_attention_with_lse)
    # no blocks named: the kernels' own pair
    block_q, block_k = block_q or _BLOCK_Q, block_k or _BLOCK_K

    n = jax.lax.psum(1, axis_name)  # concrete inside shard_map
    idx = jax.lax.axis_index(axis_name)
    b, h, t_local, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def merge(o_a, lse_a, o_b, lse_b):
        m = jnp.maximum(lse_a, lse_b)
        wa = jnp.exp(lse_a - m)
        wb = jnp.exp(lse_b - m)
        den = jnp.maximum(wa + wb, 1e-30)
        o = (o_a * wa[..., None] + o_b * wb[..., None]) / den[..., None]
        return o, m + jnp.log(den)

    o_run = jnp.zeros((b, h, t_local, d), jnp.float32)
    lse_run = jnp.full((b, h, t_local), _NEG_INF, jnp.float32)
    k_c, v_c = k, v
    for j in range(n):
        if causal and j > 0:
            # strictly-future blocks (src > idx) are fully masked: skip
            # the kernel entirely, as the dense ring body does
            src = (idx - j) % n

            def _attend(args):
                o_r, lse_r, k_b, v_b = args
                out_j, lse_j = flash_attention_with_lse(
                    q, k_b, v_b, causal=False, scale=scale,
                    block_q=block_q, block_k=block_k)
                return merge(o_r, lse_r, out_j.astype(jnp.float32), lse_j)

            o_run, lse_run = jax.lax.cond(
                src < idx, _attend, lambda args: (args[0], args[1]),
                (o_run, lse_run, k_c, v_c))
        else:
            out_j, lse_j = flash_attention_with_lse(
                q, k_c, v_c, causal=causal, scale=scale,
                block_q=block_q, block_k=block_k)
            o_run, lse_run = merge(o_run, lse_run,
                                   out_j.astype(jnp.float32), lse_j)
        if j < n - 1:
            k_c = jax.lax.ppermute(k_c, axis_name, perm)
            v_c = jax.lax.ppermute(v_c, axis_name, perm)
    return o_run.astype(q.dtype)


def _dense_attention(q, k, v, causal=False, scale=None):
    """Single-device reference path (the degenerate 1-shard ring) — one
    implementation shared with flash_attention's fallback."""
    from ..ops.pallas.flash_attention import _xla_attention
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _xla_attention(q, k, v, causal, scale)


def ring_self_attention(q, k, v, mesh=None, seq_axis="sp", batch_axis=None,
                        causal=False, scale=None):
    """Sequence-parallel attention over a mesh (dense fallback when mesh is
    None or lacks the sequence axis).

    q, k, v: [B, H, T, D] *global* arrays (or tracers inside a jitted sharded
    program). The sequence axis T is sharded over ``seq_axis``; the batch axis
    optionally over ``batch_axis``.
    """
    if mesh is None or seq_axis not in mesh.shape or mesh.shape[seq_axis] == 1:
        # single-shard path: fused flash kernel (Pallas on TPU, XLA fallback)
        from ..ops.pallas import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale)
    spec = P(batch_axis, None, seq_axis, None)
    import os
    body = ring_flash_attention \
        if os.environ.get("MXTPU_RING_FLASH", "0") == "1" else ring_attention
    fn = functools.partial(body, axis_name=seq_axis, causal=causal,
                           scale=scale)
    from .shmap import shard_map
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


# mx.nd-level op so eager autograd tapes through attention like any other op
# (the registry's _apply path, ref: Imperative::Invoke)
from ..ops.registry import register as _register  # noqa: E402

ring_attention_nd = _register("_contrib_ring_attention")(
    lambda q, k, v, mesh=None, seq_axis="sp", batch_axis=None, causal=False,
    scale=None: ring_self_attention(q, k, v, mesh=mesh, seq_axis=seq_axis,
                                    batch_axis=batch_axis, causal=causal,
                                    scale=scale))
