"""Two gated delta rules in their chunked form, as pairs of Pallas
kernels: Kimi Delta Attention (Kimi Linear, arXiv:2510.26692 §3-4), whose
decay is a vector a head, bounded in [-5, 0) (``kda_fwd`` / ``kda_bwd``,
:func:`kda_attention`), and Gated DeltaNet (Yang et al., arXiv:2412.06464),
whose decay is ONE number a head, unbounded, and whose key heads each serve
several value heads (``gdn_fwd`` / ``gdn_bwd``, :func:`gated_delta_rule`).

**What the two share** is one object each, not a copy: the chunk's
arithmetic after the decay (:func:`_prep`'s WY factors through
:func:`_inverse_t`, :func:`_apply`), the product table (:data:`_PASSES`,
:func:`_mm`), both kernel bodies, the plan of a call (:func:`_plan`: chunks
of 64 in blocks of four, padding, the by-head views), the saved chunk
states, the names a recomputed caller keeps, the plain path and the
counting. **What they do not:** how ``e^{G_t - G_s}`` enters the chunk's
two triangular products. A channel's decay stands INSIDE the sum over
channels, so it is split over the two factors, a sub-chunk at a time
against its middle token, and that needs the bound (:func:`_channel_decay`,
described below). A head's decay stands OUTSIDE the sum: a [C, C] matrix
formed from the differences ``G_t - G_s`` themselves, which are <= 0 where
they are read, so there is no bound, no sub-chunk and no ``e^{-G_s}``
(:func:`_head_decay`; -1,300 over a chunk underflows to 0, which is
right). And the heads: Gated DeltaNet's q and k are fetched at head ``j //
r`` by the kernels' index maps (never repeated in HBM), and a key head's
cotangents leave a value head each in float32 and are summed outside. A
:class:`_Rule` names the rest: the kernels, the counters, the kept names,
how ``g`` is laid.

Kimi Delta Attention, the first rule:

One head keeps a float32 state ``S`` in ``R^{K x V}``, ``S_0 = 0``:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t * scale,          a_t = exp(g_t), g_t in [-5, 0) a channel

Token by token that is T dependent steps. Over a chunk of ``C`` tokens with
``G_t = sum_{r <= t} g_r`` (inside the chunk) the same numbers are

    U = T (Diag(b) V - Diag(b) (K * e^G) S_0),   T = (I + A)^{-1}
    A[t, s] = b_t sum_c k_t[c] k_s[c] e^{G_t[c] - G_s[c]}        (s < t)
    O = scale ((Q * e^G) S_0 + Aqk U),  Aqk as A with q_t for k_t, s <= t, no b
    S_C = Diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T U

(the WY representation of the chunk's Householder-like factors), so only
the state passes from chunk to chunk. ``e^{G_t - G_s}`` is never formed
from ``e^{G_t}`` and ``e^{-G_s}`` over a whole chunk (``G`` reaches -320 at
the gate's bound of -5 a step, and ``e^{320}`` is no float32): rows are
taken a sub-chunk of ``_SUB`` = 16 at a time against the cumulative decay
at that sub-chunk's MIDDLE token, so every exponent lies in [-40, 40]
wherever the product is read: the "safe gate" of the open implementation
(flash-linear-attention, ``fla/ops/kda``, which measures from the
sub-chunk's start and so reaches ``e^{80}`` and, on the other side,
``e^{-80}`` times a small entry of ``k``, a subnormal that the TPU flushes
to zero), and the reason the configuration bounds ``g`` below by -5. What
float32 can give at that end is set by the exponent itself: ``G_t - G_mid``
near 40 is a float32 number with an ulp of 4e-6, so a decayed term carries
a relative error of some 1e-5 where the token-by-token product of sixteen
``e^{g}`` carries 1e-6. The inverse of the unit lower
triangular ``I + A`` is a substitution inside the 16 x 16 diagonal blocks
(on the VPU, the blocks side by side) and two doublings by block products.

The kernels (either rule's; KDA's names and sizes here): ``kda_fwd``
walks a head's chunks in order, the state in
VMEM; under differentiation it also writes the state at every chunk's
start (``[K, V]`` float32 a chunk and head: 268 MB a layer at 8,192
positions, 32 heads of 128 and chunks of 64). ``kda_bwd`` walks them
backwards, carries the state's cotangent in VMEM, and differentiates one
chunk at a time from its inputs and its saved start state: the chunk's
forward is computed again inside it and nothing else of the forward is
kept. The forward rule names ``o`` and the chunk states
(:data:`KEPT_NAMES`), so a caller recomputed under ``jax.checkpoint`` with
``save_only_these_names(*KEPT_NAMES)`` holds them (335 MB a layer at those
sizes) and its backward's second forward does not run ``kda_fwd`` again
(``HybridLM(recompute=True)``; PERF.md §6, PR 46); anywhere else a name
lowers to nothing. The chunk's arithmetic is ONE pair of plain functions
(:func:`_prep`, :func:`_apply`) which the forward kernel calls, the
backward kernel differentiates (``jax.vjp`` inside the kernel body) and
the plain path scans: the three cannot drift apart. The state, the decay
and every sum are float32 whatever the inputs' dtype; the products' MXU
passes follow it (``_PASSES``).

Off the TPU (or at a head width the lanes do not tile) the plain path runs
the same chunk functions under ``vmap`` and a ``lax.scan`` over chunks: it
holds a state a chunk, never a state a token, and counts in
``kda_attention.fallbacks`` (``gated_delta.fallbacks``) by reason.
``MXTPU_FLASH_INTERPRET=1`` runs the kernels through the Pallas
interpreter (the tier-1 parity path).
"""
from __future__ import annotations

import functools
import importlib
import typing

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

# the module (the package exports the function under the same name): its
# platform check and interpreter flag are this kernel's too
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["kda_attention", "gated_delta_rule", "KEPT_NAMES",
           "GDN_KEPT_NAMES"]

_SUB = 16            # tokens a sub-chunk
_CHUNK = 64          # tokens a chunk
_BLOCK_CHUNKS = 4    # chunks a grid step of either kernel
# the gate's bound times half a sub-chunk, and a little: no exponent that is
# read reaches it (a tie with the cap would halve ``g``'s gradient there)
_EXP_CAP = 5.0 * _SUB / 2 + 4.0

_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


# MXU passes of each product of a chunk, by what the product is and by the
# inputs' dtype: 6 is the float32 contraction (``highest``), 3 splits both
# operands in two bf16 pieces and drops the smallest cross term (some 16
# bits), 1 rounds both operands to bf16. Accumulation is float32
# throughout; a product's two backward products take its passes.
# ``cumsum``'s left operand is 0 / 1, exact in bf16: its 3 passes split the
# right operand alone, in three pieces, and lose nothing. float32 inputs
# get float32 products (parity with the recurrence: 4e-6 on the chip);
# bf16 inputs get what their own rounding is worth: the products with the
# state in one pass, as the flash kernels take theirs, and the chunk's
# triangular factors, which an inverse amplifies, in three (3e-3 of the
# largest output, 6.5 ms a forward call at the Ling cell's shapes where
# float32 products take 8.7: PERF.md section 6, PR 41)
_PASSES = {
    True: {"cumsum": 3, "gram": 6, "merge": 6, "wy": 6, "state": 6},
    False: {"cumsum": 3, "gram": 3, "merge": 3, "wy": 3, "state": 1},
}


def _dot(a, b, dims, precision=jax.lax.Precision.DEFAULT):
    """bf16 operands in one pass (DEFAULT, said out loud: a global
    ``jax_default_matmul_precision`` would ask Mosaic for a multi-pass bf16
    contraction it cannot lower); off the TPU the same numbers from the
    rounded operands widened again (XLA:CPU has no bf16 x bf16 = f32 dot)."""
    if a.dtype == jnp.bfloat16 and _fa._platform() != "tpu":
        a, b, precision = a.astype(_F32), b.astype(_F32), \
            jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=_F32)


def _pieces(x, n):
    out = []
    for _ in range(n):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(_F32)
    return out


def _product(a, b, dims, passes, exact_lhs=False):
    if passes == 6:
        return _dot(a, b, dims, jax.lax.Precision.HIGHEST)
    if exact_lhs:
        a = a.astype(jnp.bfloat16)
        return sum(_dot(a, p, dims) for p in _pieces(b, passes))
    if passes == 1:
        return _dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims)
    (a1, a2), (b1, b2) = _pieces(a, 2), _pieces(b, 2)
    return _dot(a1, b1, dims) + (_dot(a1, b2, dims) + _dot(a2, b1, dims))


# how each operand's cotangent is a product of the other and the result's:
# dims -> ((lhs, rhs, dims) for d_a, (lhs, rhs, dims) for d_b), with a = 0,
# b = 1, the cotangent = 2
_TRANSPOSES = {_NN: ((2, 1, _NT), (0, 2, _TN)),
               _NT: ((2, 1, _NN), (2, 0, _TN)),
               _TN: ((1, 2, _NT), (0, 2, _NN))}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _mm_at(a, b, dims, passes, exact_lhs):
    return _product(a, b, dims, passes, exact_lhs)


def _mm_at_bwd(dims, passes, exact_lhs, kept, ct):
    xs = kept + (ct,)
    (la, ra, da), (lb, rb, db) = _TRANSPOSES[dims]
    # an exact left operand is a constant of the kernel: no cotangent read
    d_a = jnp.zeros_like(kept[0]) if exact_lhs else _product(
        xs[la], xs[ra], da, passes)
    return d_a, _product(xs[lb], xs[rb], db, passes,
                         exact_lhs and lb == 0)


_mm_at.defvjp(lambda a, b, dims, passes, exact_lhs:
              (_product(a, b, dims, passes, exact_lhs), (a, b)), _mm_at_bwd)


def _mm(wide, kind, a, b, dims=_NN, exact_lhs=False):
    """The product ``kind`` of a chunk whose inputs are float32 (``wide``)
    or narrower."""
    return _mm_at(a, b, dims, _PASSES[wide][kind], exact_lhs)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


# ---------------------------------------------------- (I + A)^{-1}, transposed
def _inverse_t_impl(a, sub, wide):
    """``((I + a)^{-1})^T`` of a strictly lower triangular ``a`` [C, C], C =
    ``sub`` times a power of two. Inside a diagonal block row ``r`` of the
    transposed inverse is ``e_r - sum_{s > r} a[s, r] X[s, :]`` (from the
    last row up), whose coefficients are a COLUMN of ``a``, which lies along
    the sublanes as the rows of ``X`` do: no transposed copy of ``a`` is
    needed. Then ``T <- T - T a_off T`` doubles the blocks, ``a_off`` the
    part of ``a`` between two neighbouring blocks."""
    c = a.shape[0]
    lane = _iota((sub, c), 1)
    row = _iota((sub, c), 0)
    blocks = []
    for i in range(c // sub):
        a_i = a[i * sub:(i + 1) * sub]                        # [sub, C]
        x = jnp.zeros((sub, c), _F32)
        for r in reversed(range(sub)):
            coef = jnp.sum(jnp.where(lane == i * sub + r, a_i, 0.0), axis=1,
                           keepdims=True)                     # a[:, r]
            new = (lane[:1] == i * sub + r).astype(_F32) \
                - jnp.sum(coef * x, axis=0, keepdims=True)
            x = jnp.where(row == r, new, x)
        blocks.append(x)
    tt = jnp.concatenate(blocks, axis=0) if len(blocks) > 1 else blocks[0]
    ri, ci = _iota((c, c), 0), _iota((c, c), 1)
    size = sub
    while size < c:
        off = jnp.where((ri // (2 * size) == ci // (2 * size))
                        & (ri // size != ci // size), a, 0.0)
        # (T - T off T)^T = Tt - Tt off^T Tt
        tt = tt - _mm(wide, "merge", _mm(wide, "merge", tt, off, _NT), tt)
        size *= 2
    return tt


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _inverse_t(a, sub, wide):
    return _inverse_t_impl(a, sub, wide)


def _inverse_t_fwd(a, sub, wide):
    tt = _inverse_t_impl(a, sub, wide)
    return tt, tt


def _inverse_t_bwd(sub, wide, tt, ct):
    # T = M^{-1}: dT = -T dM T, so the cotangent of ``a`` under a cotangent
    # ``ct`` of T^T is -Tt ct^T Tt; the caller masks it to the lower part
    return (-_mm(wide, "merge", _mm(wide, "merge", tt, ct, _NT), tt),)


_inverse_t.defvjp(_inverse_t_fwd, _inverse_t_bwd)


# ------------------------------------------------------------ one chunk
def _channel_decay(q, k, g, ri, ci, sub, wide):
    """A decay a CHANNEL (``g`` [C, K]): the chunk's two products whose
    factors carry ``e^{G_t - G_s}`` INSIDE the sum over channels, by
    sub-chunks against the middle token's ``G``. -> (``akk``, ``aqk`` [C,
    C], whole and unscaled, the running sums ``G`` [C, K], their rows'
    index)."""
    c, dk = k.shape
    rk = _iota((c, dk), 0)
    gc = _mm(wide, "cumsum", (ci <= ri).astype(_F32), g, _NN, True)  # G, incl.
    # each sub-chunk's rows against G at its middle token: exponents within
    # 5 * sub / 2 either way wherever a product is read
    mids = [jnp.sum(jnp.where(rk == i * sub + sub // 2 - 1, gc, 0.0), axis=0,
                    keepdims=True) for i in range(c // sub)]
    ref = mids[0]
    for i in range(1, c // sub):
        ref = jnp.where(rk >= i * sub, mids[i], ref)
    er = jnp.exp(gc - ref)
    kr, qr = k * er, q * er
    akk, aqk = [], []
    for i in range(c // sub):
        lo, hi = i * sub, (i + 1) * sub
        # columns up to this sub-chunk's end: an earlier sub-chunk's
        # exponent is < 0, its own within the cap; later columns are above
        # the diagonal and read as zero
        kc = jnp.where(rk < hi, k * jnp.exp(
            jnp.minimum(mids[i] - gc, _EXP_CAP)), 0.0)
        m = _mm(wide, "gram",
                jnp.concatenate([kr[lo:hi], qr[lo:hi]], axis=0), kc, _NT)
        akk.append(m[:sub])
        aqk.append(m[sub:])
    akk = jnp.concatenate(akk, axis=0) if len(akk) > 1 else akk[0]
    aqk = jnp.concatenate(aqk, axis=0) if len(aqk) > 1 else aqk[0]
    return akk, aqk, gc, rk


def _head_decay(q, k, g_row, ri, ci, wide):
    """ONE decay a head (``g_row`` [1, C], as ``beta`` comes): ``e^{G_t -
    G_s}`` is a [C, C] matrix OUTSIDE the sum over channels, formed from
    the differences ``G_t - G_s`` (<= 0 on and under the diagonal, the part
    that is read; above it the entry is written as 0 and its exponent never
    taken), so no bound on ``g`` is needed: a decay past float32's range
    underflows to 0, which is the right answer, and ``e^{-G_s}`` alone,
    which overflows there, is never formed. The running sums are taken on
    the VPU in float32 (a masked row sum: no product's rounding enters an
    exponent). Same returns as :func:`_channel_decay`, ``G`` a column [C,
    1]."""
    c = k.shape[0]
    below = ci <= ri
    g_col = jnp.sum(jnp.where(below, g_row, 0.0), axis=1,
                    keepdims=True)                          # G_t [C, 1]
    g_at = jnp.sum(jnp.where(ri == ci, g_col, 0.0), axis=0,
                   keepdims=True)                           # G_s [1, C]
    decay = jnp.where(below, jnp.exp(jnp.where(below, g_col - g_at, 0.0)),
                      0.0)
    m = _mm(wide, "gram", jnp.concatenate([k, q], axis=0), k, _NT)
    return m[:c] * decay, m[c:] * decay, g_col, _iota((c, 1), 0)


def _prep(q, k, v, g, b_row, scale, sub, wide):
    """What a chunk contributes whatever state it starts from. ``q``, ``k``
    [C, K], ``v`` [C, V] float32, ``b_row`` [1, C]; the log-decay ``g`` [C,
    K] (a channel: Kimi Delta Attention) or [1, C] (a head: Gated
    DeltaNet), which is all the two rules differ in. Returns ``w`` [C, K]
    and ``u`` [C, V] (the chunk's pseudo-values are ``u - w S_0``), ``aqk``
    [C, C], ``qg`` [C, K] (both scaled), ``kend`` [C, K] and the chunk's
    whole log-decay ``gl`` [1, K] or [1, 1]."""
    c, dk = k.shape
    ri, ci = _iota((c, c), 0), _iota((c, c), 1)
    if g.shape == k.shape:
        akk, aqk, gc, rk = _channel_decay(q, k, g, ri, ci, sub, wide)
    else:
        akk, aqk, gc, rk = _head_decay(q, k, g, ri, ci, wide)
    b_col = jnp.sum(jnp.where(ri == ci, b_row, 0.0), axis=1, keepdims=True)
    tt = _inverse_t(jnp.where(ci < ri, akk, 0.0) * b_col, sub, wide)
    eg = jnp.exp(gc)
    wu = _mm(wide, "wy", tt, jnp.concatenate([k * eg, v], axis=1) * b_col,
             _TN)
    gl = jnp.sum(jnp.where(rk == c - 1, gc, 0.0), axis=0, keepdims=True)
    return (wu[:, :dk], wu[:, dk:], jnp.where(ci <= ri, aqk, 0.0) * scale,
            q * eg * scale, k * jnp.exp(gl - gc), gl)


def _apply(wide, s0, w, u, aqk, qg, kend, gl):
    """The chunk from the state ``s0`` [K, V] at its start: -> (o [C, V],
    the state at its end)."""
    dk = s0.shape[0]
    mm = functools.partial(_mm, wide, "state")
    un = u - mm(w, s0)
    o = mm(qg, s0) + mm(aqk, un)
    if gl.shape[1] > 1:                     # a channel's, down the rows
        eye = _iota((dk, dk), 0) == _iota((dk, dk), 1)
        decay = jnp.sum(jnp.where(eye, jnp.exp(gl), 0.0), axis=1,
                        keepdims=True)
    else:
        decay = jnp.exp(gl)                 # [1, 1]: one decay a head
    return o, decay * s0 + mm(kend, un, _TN)


def _chunk(q, k, v, g, b_row, s0, scale, sub, wide):
    return _apply(wide, s0, *_prep(q, k, v, g, b_row, scale, sub, wide))


# ------------------------------------------------------------- kernels
class _Rule(typing.NamedTuple):
    """Which of the two delta rules a call runs: all that differs outside
    :func:`_prep` is what things are called and how ``g`` is laid."""
    kernels: str        # the pair is ``<kernels>_fwd`` / ``<kernels>_bwd``
    counters: str       # ``<counters>.calls`` / ``.chunks`` / ``.fallbacks``
    kept: tuple         # the names of ``o`` and the chunk states
    head_decay: bool    # ``g`` [B, T, H], laid as ``beta`` is


_KDA = _Rule("kda", "kda_attention", ("kda_o", "kda_states"), False)
_GDN = _Rule("gdn", "gated_delta", ("gdn_o", "gdn_states"), True)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, nb, chunk,
                sub, scale, wide, head_decay):
    s_ref = rest[-1]                        # the state, carried in VMEM
    st_ref = rest[0] if len(rest) > 1 else None
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, _F32)

    # what does not wait for the state, for every chunk of the block: the
    # scheduler interleaves the chunks' chains
    parts = []
    for j in range(nb):
        at, row = pl.ds(j * chunk, chunk), pl.ds(n * nb + j, 1)
        parts.append(_prep(
            q_ref[0, at, :].astype(_F32), k_ref[0, at, :].astype(_F32),
            v_ref[0, at, :].astype(_F32),
            g_ref[0, row if head_decay else at, :].astype(_F32),
            b_ref[0, row, :].astype(_F32), scale, sub, wide))
    s = s_ref[...]
    for j in range(nb):
        if st_ref is not None:
            st_ref[0, j] = s
        o, s = _apply(wide, s, *parts[j])
        o_ref[0, pl.ds(j * chunk, chunk), :] = o.astype(o_ref.dtype)
    s_ref[...] = s


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, nb, n_blocks,
                chunk, sub, scale, wide, head_decay):
    n = pl.program_id(1)                    # block n_blocks - 1 - n

    @pl.when(n == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, _F32)

    ds = ds_ref[...]
    first = (n_blocks - 1 - n) * nb
    for j in reversed(range(nb)):
        at, row = pl.ds(j * chunk, chunk), pl.ds(first + j, 1)
        g_at = row if head_decay else at
        _, vjp = jax.vjp(
            functools.partial(_chunk, scale=scale, sub=sub, wide=wide),
            q_ref[0, at, :].astype(_F32), k_ref[0, at, :].astype(_F32),
            v_ref[0, at, :].astype(_F32), g_ref[0, g_at, :].astype(_F32),
            b_ref[0, row, :].astype(_F32), st_ref[0, j])
        dq, dk, dv, dg, db, ds = vjp((do_ref[0, at, :].astype(_F32), ds))
        dq_ref[0, at, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, at, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, at, :] = dv.astype(dv_ref.dtype)
        dg_ref[0, g_at, :] = dg.astype(dg_ref.dtype)
        db_ref[0, row, :] = db.astype(db_ref.dtype)
    ds_ref[...] = ds


def _params(interpret):
    if interpret:       # Mosaic-only hints: the interpreter takes none
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=48 * 2**20)}


def _plan(rule, q, k, v, g, beta, chunk):
    """What both kernels' calls share. The kernels' views: q, k, v [B, T',
    . * width] as they come, and so a channel's g; what is one number a
    head and token (beta; a head's g) as [B * H, T' / C, C] float32; T' is
    T padded to whole blocks of ``nb`` chunks with tokens that change
    nothing (k = 0, beta = 0, g = 0). -> (the views in the kernels' order,
    nb, T', the block of a by-head view, which is a head's whole row of
    chunks, resident while the head's blocks pass)."""
    b, t, h = beta.shape
    nb = min(_BLOCK_CHUNKS, -(-t // chunk))
    pad = -t % (chunk * nb)
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (q, k, v, g, beta))
    tp = t + pad

    def by_head(x):
        return x.astype(_F32).transpose(0, 2, 1).reshape(
            b * h, tp // chunk, chunk)

    whole = pl.BlockSpec((1, tp // chunk, chunk), lambda bh, n: (bh, 0, 0))
    return (q, k, v, by_head(g) if rule.head_decay else g,
            by_head(beta)), nb, tp, whole


def _by_token(x, b, t):
    """A by-head view [B * H, T' / C, C] back as [B, T, H]."""
    return x.reshape(b, -1, x.shape[1] * x.shape[2]).transpose(
        0, 2, 1)[:, :t]


def _specs(h, rows, widths, at=lambda n: n, share=1):
    """A head's ``rows`` tokens of [B, T', . * width] arrays, block
    ``at(n)`` at grid step (bh, n); ``share`` heads of the grid read one
    head of the array (head ``j // share``: the array is never repeated)."""
    return [pl.BlockSpec((1, rows, w),
                         lambda bh, n: (bh // h, at(n), bh % h // share))
            for w in widths]


def _operand_specs(rule, h, rows, dk, dv, share, whole, at=lambda n: n):
    """q, k (at ``h / share`` heads), v, g, beta."""
    return _specs(h, rows, (dk, dk), at, share) + _specs(h, rows, (dv,), at) \
        + [whole if rule.head_decay else _specs(h, rows, (dk,), at)[0], whole]


def _kernel_args(rule, kernel, q, chunk, scale, **more):
    interpret = _fa._interpret()
    return dict(
        kernel=functools.partial(kernel, chunk=chunk, sub=min(_SUB, chunk),
                                 scale=scale, wide=q.dtype == _F32,
                                 head_decay=rule.head_decay, **more),
        interpret=interpret, **_params(interpret))


def _widths(k, v, beta, key_heads):
    """-> (value heads, value heads a key head, K, V)."""
    h = beta.shape[-1]
    return h, h // key_heads, k.shape[-1] // key_heads, v.shape[-1] // h


def _forward_pallas(rule, q, k, v, g, beta, key_heads, chunk, scale, save):
    from jax.experimental.pallas import tpu as pltpu
    b, t, _ = beta.shape
    h, share, dk, dv = _widths(k, v, beta, key_heads)
    views, nb, tp, whole = _plan(rule, q, k, v, g, beta, chunk)
    n_chunks, rows = tp // chunk, chunk * nb
    out_specs = _specs(h, rows, (dv,))
    out_shape = [jax.ShapeDtypeStruct((b, tp, h * dv), q.dtype)]
    if save:
        out_specs.append(pl.BlockSpec((1, nb, dk, dv),
                                      lambda bh, n: (bh, n, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b * h, n_chunks, dk, dv),
                                              _F32))
    call = _kernel_args(rule, _fwd_kernel, q, chunk, scale, nb=nb)
    outs = pl.pallas_call(
        call.pop("kernel"), grid=(b * h, n_chunks // nb),
        in_specs=_operand_specs(rule, h, rows, dk, dv, share, whole),
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        name=rule.kernels + "_fwd", **call,
    )(*views)
    return outs[0][:, :t], (outs[1] if save else None)


def _backward_pallas(rule, q, k, v, g, beta, key_heads, states, do, chunk,
                     scale):
    from jax.experimental.pallas import tpu as pltpu
    b, t, _ = beta.shape
    h, share, dk, dv = _widths(k, v, beta, key_heads)
    views, nb, tp, whole = _plan(rule, q, k, v, g, beta, chunk)
    do2 = jnp.pad(do, ((0, 0), (0, tp - t), (0, 0)))
    rows, n_blocks = chunk * nb, tp // (chunk * nb)
    back = lambda n: n_blocks - 1 - n                       # noqa: E731
    call = _kernel_args(rule, _bwd_kernel, q, chunk, scale, nb=nb,
                        n_blocks=n_blocks)
    # a key head's two cotangents come a VALUE head each (a grid step owns
    # its blocks), in float32 where several are then summed
    dqk = jax.ShapeDtypeStruct((b, tp, h * dk),
                               q.dtype if share == 1 else _F32)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        call.pop("kernel"), grid=(b * h, n_blocks),
        in_specs=_operand_specs(rule, h, rows, dk, dv, share, whole, back)
        + [pl.BlockSpec((1, nb, dk, dv), lambda bh, n: (bh, back(n), 0, 0))]
        + _specs(h, rows, (dv,), back),
        out_specs=_operand_specs(rule, h, rows, dk, dv, 1, whole, back),
        out_shape=[dqk, dqk] + [jax.ShapeDtypeStruct(x.shape, x.dtype)
                                for x in views[2:]],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        name=rule.kernels + "_bwd", **call,
    )(*views, states, do2)
    if share > 1:
        dq, dk_ = (x.reshape(b, tp, key_heads, share, dk).sum(3).reshape(
            b, tp, key_heads * dk).astype(q.dtype) for x in (dq, dk_))
    dg = _by_token(dg, b, t) if rule.head_decay else dg[:, :t]
    return (dq[:, :t], dk_[:, :t], dv_[:, :t], dg,
            _by_token(db, b, t).astype(beta.dtype))


# ------------------------------------------------------------- plain path
def _plain(rule, q, k, v, g, beta, key_heads, chunk, scale):
    """The same chunk functions under ``vmap`` (batch, heads, chunks) and a
    ``lax.scan`` over chunks: a state a chunk, nothing a token. (Here, and
    here alone, q and k are repeated to the value heads.)"""
    b, t, h = beta.shape
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(x, heads=h):      # [B, T', heads * W] -> [N, B, H, C, W]
        x = x.astype(_F32).reshape(b, n, chunk, heads, -1).transpose(
            1, 0, 3, 2, 4)
        return x if heads == h else jnp.repeat(x, h // heads, axis=2)

    def rows(x):                # [B, T', H] -> [N, B, H, 1, C]
        return chunks(x).transpose(0, 1, 2, 4, 3)

    wide = q.dtype == _F32
    prep = functools.partial(_prep, scale=scale, sub=min(_SUB, chunk),
                             wide=wide)
    over = lambda f: jax.vmap(jax.vmap(f))                  # noqa: E731
    parts = jax.vmap(over(prep))(
        chunks(q, key_heads), chunks(k, key_heads), chunks(v),
        rows(g) if rule.head_decay else chunks(g), rows(beta))
    s0 = jnp.zeros((b, h, k.shape[-1] // key_heads, v.shape[-1] // h), _F32)
    _, o = jax.lax.scan(
        lambda s, p: over(functools.partial(_apply, wide))(s, *p)[::-1], s0,
        parts)
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, -1)
    return o[:, :t].astype(q.dtype)


def _scale(k, key_heads):
    return 1.0 / ((k.shape[-1] // key_heads) ** 0.5)


def _refusal(k, v, beta, key_heads):
    """Why this call cannot take the kernels, or None."""
    if _fa._platform() != "tpu" and not _fa._interpret():
        return "platform is not tpu"
    _, _, dk, dv = _widths(k, v, beta, key_heads)
    if not _fa._interpret() and (dk % 128 or dv % 128):
        return "a head's width is not a multiple of 128 lanes"
    return None


def _count(rule, k, chunk, reason):
    from ... import telemetry
    telemetry.inc(rule.counters + ".calls")
    telemetry.inc(rule.counters + ".chunks", -(-k.shape[1] // chunk))
    if reason is not None:
        telemetry.inc(rule.counters + ".fallbacks", tag=reason)


def _forward(rule, q, k, v, g, beta, key_heads, chunk, save):
    sub = min(_SUB, chunk)
    if chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError("%s: a chunk of %d is not %d times a power of two"
                         % (rule.counters, chunk, sub))
    if beta.shape[-1] % key_heads:
        raise ValueError("%s: %d value heads do not divide over %d key heads"
                         % (rule.counters, beta.shape[-1], key_heads))
    scale = _scale(k, key_heads)
    reason = _refusal(k, v, beta, key_heads)
    _count(rule, k, chunk, reason)
    if reason is not None:
        return _plain(rule, q, k, v, g, beta, key_heads, chunk, scale), None
    return _forward_pallas(rule, q, k, v, g, beta, key_heads, chunk, scale,
                           save)


def _fwd_rule(rule, q, k, v, g, beta, key_heads, chunk):
    o, states = _forward(rule, q, k, v, g, beta, key_heads, chunk, True)
    o = checkpoint_name(o, rule.kept[0])
    if states is not None:      # the plain path has none
        states = checkpoint_name(states, rule.kept[1])
    return o, (q, k, v, g, beta, states)


def _bwd_rule(rule, key_heads, chunk, kept, do):
    q, k, v, g, beta, states = kept
    scale = _scale(k, key_heads)
    if states is None:      # the plain path, computed again
        _, vjp = jax.vjp(lambda *x: _plain(rule, *x, key_heads, chunk, scale),
                         q, k, v, g, beta)
        return vjp(do)
    return _backward_pallas(rule, q, k, v, g, beta, key_heads, states, do,
                            chunk, scale)


# What a forward rule gives that is dear to make again, by the names a
# caller's ``jax.checkpoint(..., policy=save_only_these_names(*KEPT_NAMES,
# *GDN_KEPT_NAMES))`` keeps (as ``flash_attention.KEPT_NAMES``): ``states``
# is the backward kernel's; ``o`` is not in the residual, but what follows
# the call in a recomputed block (the head's norm, the gate, the output
# projection) reads it, so unless it is kept too the kernel runs again just
# to give it.
KEPT_NAMES = _KDA.kept
GDN_KEPT_NAMES = _GDN.kept


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_attention(q, k, v, g, beta, chunk=_CHUNK):
    """``o`` [B, T, H * V] of the recurrence above from ``q``, ``k`` [B, T,
    H * K], ``v`` [B, T, H * V], the log-decay ``g`` [B, T, H * K]
    (float32, in [-5, 0): the exponent's bound above rests on it) and
    ``beta`` [B, T, H], which says how many heads there are: the heads lie
    side by side along the last axis, as the projections leave them, and
    the kernels fetch a head's columns themselves (a [B, T, H, K] view is
    another tiling on the chip, and a copy). The output is scaled by ``1 /
    sqrt(K)``. ``chunk`` is 16 times a
    power of two (or under 16: one sub-chunk). Kernels ``kda_fwd`` /
    ``kda_bwd`` in a trace; counted at trace time in
    ``kda_attention.calls`` / ``.chunks`` (a head's chunks a call) /
    ``.fallbacks`` (by reason: a call on the plain path)."""
    return _forward(_KDA, q, k, v, g, beta, beta.shape[-1], chunk, False)[0]


kda_attention.defvjp(
    lambda q, k, v, g, beta, chunk: _fwd_rule(
        _KDA, q, k, v, g, beta, beta.shape[-1], chunk),
    lambda chunk, kept, do: _bwd_rule(
        _KDA, kept[4].shape[-1], chunk, kept, do))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gated_delta_rule(q, k, v, g, beta, key_heads, chunk=_CHUNK):
    """Gated DeltaNet's recurrence (the module's second rule): ``o`` [B, T,
    H * V] from ``q``, ``k`` [B, T, key_heads * K], ``v`` [B, T, H * V],
    the log-decay ``g`` [B, T, H] (float32, <= 0, NO lower bound) and
    ``beta`` [B, T, H]; value head ``j`` reads key head ``j // (H /
    key_heads)``, fetched there by the kernels' index maps. Scaled by ``1 /
    sqrt(K)``; ``chunk`` as :func:`kda_attention`'s. Kernels ``gdn_fwd`` /
    ``gdn_bwd`` in a trace; counted at trace time in ``gated_delta.calls``
    / ``.chunks`` / ``.fallbacks`` (by reason)."""
    return _forward(_GDN, q, k, v, g, beta, key_heads, chunk, False)[0]


gated_delta_rule.defvjp(functools.partial(_fwd_rule, _GDN),
                        functools.partial(_bwd_rule, _GDN))
