"""Mixture-of-experts with expert parallelism (the GSPMD MoE formulation).

The reference has no MoE / expert parallelism (SURVEY §2.3 "Parallelism
NOT present"). This is the TPU-native design: a switch (top-1) FFN layer
expressed as dense einsums over a dispatch tensor — the GSPMD/Switch
Transformer recipe — with the stacked expert weights sharded over an
``expert`` mesh axis. Under ``jit`` on such a mesh, XLA lowers the
dispatch/combine einsums to all-to-all collectives over ICI; on one device
the same program is just dense math, so numerics are identical at any
mesh size (tests prove parity against a per-token reference).

Routing: top-1 with capacity. Each expert processes at most
``C = ceil(T / E * capacity_factor)`` tokens; tokens over capacity are
DROPPED (output zero, the standard Switch behavior — the residual path of
the surrounding block carries them). The auxiliary load-balancing loss of
Switch Transformer (mean fraction * mean router prob, scaled by E) is
returned alongside the output (scaled by E, per the paper).

:func:`routed_ffn` is the other kind of layer: top-k of many small gated
experts, no capacity and nothing dropped, told which of the router's
experts it holds (one chip's share under expert parallelism). The (token,
slot) pairs routed here are sorted by expert and go through grouped
products over exactly the rows each expert was chosen for; on one chip it
runs without an exchange. What a step pays follows the rows routed HERE,
not all T*k: the held part exists at a short ladder of static row counts
and a ``lax.switch`` runs the lowest that holds the step's live rows
(:func:`piece_plan` is that decision, public and pure); the last rung is
all T*k rows, so the worst case still runs and no shape is dynamic.

A rung's rows are summed into their tokens, forward (``moe.combine``) and in
the backward's transpose of the dispatch, in float32, in one of two
spellings (:func:`_sum_by_token`). A scatter-add by token id costs by the
rung's rows (its indices may collide, so XLA:TPU adds a row at a time); a
gather of every token's row a slot, summed over the k slots, costs by the
pairs T*k, whatever the rung, at a fifth to a half of a scatter-added
row. Both counts are static for a branch of the switch, so each branch and
direction takes the cheaper by :func:`_sums_by_gather`: the float32 rows of
a backward scatter-add on a rung under four ninths of the pairs, the bf16
rows of a forward only under a seventh, and the last rung of every ladder
(and a layer that holds every expert) gathers both ways.

Under differentiation the forward switch keeps its rung's two up products
(``xs w_gate`` and ``xs w_up``, in the weights' dtype) for a hand-written
backward switch that computes no grouped product twice: six a branch. A
switch's results have one shape whatever branch ran, so the kept pair is
laid out once, at the last rung's ``[T*k, F]``, and a lower rung fills its
first rows and zero-fills the rest; everything else the backward reads
(the gathered rows, ``act(gate) * up``) it makes again from x and the pair.
The gate's activation (``silu`` or ``relu``) and the router's score
(``sigmoid`` or ``softmax``) are properties of a model, named by its
configuration; a router may read another tensor than the experts do
(``router_x``: a router placed ahead of attention).

What the router decided carries names (:data:`KEPT_NAMES`,
``jax.ad_checkpoint.checkpoint_name``): its float32 product, its choice,
the scores picked there, and the sorted plan's order, sizes and rung. A caller that recomputes the
layer under ``jax.checkpoint`` with a policy that keeps those names
(``hybrid_lm.kept_policy``) routes once a step: the second forward runs no
router product, no top-k, no gather of the picked scores and no argsort,
and reads the very choice the first made. Under no checkpoint a name is the identity and lowers to
nothing.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["switch_ffn", "routed_ffn", "route_top_k", "piece_plan",
           "shard_experts", "KEPT_NAMES"]

# what a routed layer names of its router's decision, for a caller's
# ``jax.checkpoint(..., policy=save_only_these_names(*KEPT_NAMES))`` to keep
# (as ``flash_attention.KEPT_NAMES``): the product ``x W^T`` (T, E) float32,
# the choice (T, k) int32 and the scores picked there (T, k) float32 of
# :func:`route_top_k`; the order (T*k,) int32, the sizes (held,) and the
# rung () of :func:`piece_plan`. O(T*E) float32 and O(T*k) a layer,
# against a float32 product, a full sort of (T, E) (what ``lax.top_k`` is
# on a TPU), a gather of T*k scalars (10 ns each on a v5e: dearer than the
# sort) and an argsort of T*k to make them again. The product is named BEFORE the score function: jax's
# derivative rules of ``logistic`` and of the softmax read the function's
# own output, which carries no name, so scores named after it were kept
# AND their product made again for the rule; from the kept product the
# score function is one pass over (T, E)
KEPT_NAMES = ("route_logits", "route_choice", "route_picked", "route_order",
              "route_sizes", "route_rung")
_LOGITS, _CHOICE, _PICKED, _ORDER, _SIZES, _RUNG = KEPT_NAMES


def switch_ffn(x, router_w, w1, b1, w2, b2, capacity_factor=1.25):
    """Top-1 switch FFN layer.

    Parameters
    ----------
    x : (T, D) tokens.
    router_w : (D, E) router projection.
    w1, b1 : (E, D, H), (E, H) — expert up-projections.
    w2, b2 : (E, H, D), (E, D) — expert down-projections.
    capacity_factor : per-expert capacity C = ceil(T/E * factor).

    Returns ``(out, aux_loss)``: (T, D) combined expert outputs (dropped
    tokens are zero) and the scalar load-balancing loss.
    """
    t, d = x.shape
    e = router_w.shape[1]
    cap = int(-(-t * capacity_factor // e))  # ceil

    logits = x @ router_w                      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)             # (T,)
    expert = jnp.argmax(probs, axis=-1)        # (T,)

    # capacity: position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert, e, dtype=x.dtype)        # (T, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0          # (T, E)
    keep = (pos >= 0) & (pos < cap)
    pos_cap = jnp.where(keep, pos, 0).astype(jnp.int32)
    slot = jax.nn.one_hot(jnp.sum(pos_cap, axis=-1), cap,
                          dtype=x.dtype)                     # (T, C)
    dispatch = (onehot * keep)[:, :, None] * slot[:, None, :]  # (T, E, C)

    # dispatch -> expert batches (E, C, D): the all-to-all under GSPMD
    xin = jnp.einsum("tec,td->ecd", dispatch, x)
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", xin, w1) + b1[:, None, :])
    xout = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]

    combine = dispatch * gate[:, None, None]                 # (T, E, C)
    out = jnp.einsum("tec,ecd->td", combine, xout)

    # Switch aux loss: E * sum_e( fraction_e * mean_prob_e )
    fraction = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(fraction * mean_prob)
    return out, aux


def route_top_k(x, router_w, score_bias, top_k, scale=1.0, score="sigmoid",
                n_group=1, topk_group=1):
    """A top-k router: ``s = score(x W^T)`` with the product in float32
    (``router_w`` is (E, D), a Dense weight); the ``top_k`` experts are
    chosen by ``s + score_bias`` (the selection bias steers the choice
    only and takes no gradient); their weights are ``s`` at the chosen
    experts, normalised to sum to one, times ``scale``. Returns ``(idx,
    w)``, both (T, top_k): int32 experts and float32 weights.

    ``score`` names the published router, a property of the model:
    ``"sigmoid"`` is DeepSeek-V3's (arXiv:2412.19437 §2.1.2), each expert
    scored alone (kanana-2, LFM2 with its expert bias); ``"softmax"`` is the softmax over all E logits in float32, its
    ``top_k`` largest renormalised, which equals the softmax over the
    chosen logits alone (Mixtral's, and SmallThinker's with
    ``moe_primary_router_apply_softmax`` and ``norm_topk_prob``); a model
    without a selection bias holds that leaf at zero.

    ``n_group`` > 1 is that paper's group limit (its released ``noaux_tc``
    gate): the E experts are ``n_group`` equal groups in order, a group's
    score is the sum of its two largest ``s + score_bias``, only the
    ``topk_group`` best groups stay, and the ``top_k`` are chosen among
    their experts (the others' biased scores read -inf). The weights are
    still ``s`` at the chosen experts over their sum.

    The product, ``idx`` and the scores picked at ``idx`` are named
    (:data:`KEPT_NAMES`): a recomputed caller whose policy keeps them makes
    again the scores (their backward reads ``s``) and the weights'
    normalisation, and neither the product, any ``top_k`` nor the gather."""
    if score not in ("sigmoid", "softmax"):
        raise MXNetError("route_top_k: score %r is neither 'sigmoid' nor "
                         "'softmax'" % (score,))
    with jax.named_scope("moe.route"):
        s = checkpoint_name(jnp.einsum(
            "td,ed->te", x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), _LOGITS)
        s = jax.nn.sigmoid(s) if score == "sigmoid" else jax.nn.softmax(s, -1)
        biased = s + jax.lax.stop_gradient(score_bias.astype(jnp.float32))
        if n_group > 1:
            biased = _group_limited(biased, n_group, topk_group)
        idx = checkpoint_name(jax.lax.top_k(biased, top_k)[1], _CHOICE)
        w = checkpoint_name(jnp.take_along_axis(s, idx, axis=-1), _PICKED)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return idx, w


def _group_limited(biased, n_group, topk_group):
    """(T, E) biased scores with -inf outside each token's ``topk_group``
    best of ``n_group`` groups (a group's score: its two largest)."""
    t, e = biased.shape
    if e % n_group or not 0 < topk_group <= n_group or e // n_group < 2:
        raise MXNetError("route_top_k: %d experts do not make %d groups of "
                         "two or more, %d of them kept"
                         % (e, n_group, topk_group))
    by_group = jnp.sum(jax.lax.top_k(
        biased.reshape(t, n_group, e // n_group), 2)[0], axis=-1)
    _, kept = jax.lax.top_k(by_group, topk_group)           # (T, topk_group)
    stays = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :],
                    axis=1)                                 # (T, n_group)
    return jnp.where(jnp.repeat(stays, e // n_group, axis=1), biased,
                     -jnp.inf)


# rows a grid step of XLA:TPU's grouped matmul kernel takes (its tile
# table at [T*k, D] has ceil(T*k / 512) + groups - 1 entries)
_ROW_TILE = 512

# what a sum by token costs on one v5e at 2,048-wide rows, in microseconds,
# on a LOWER rung, a sixth to a third of the pairs, where the choice is open
# (the last rung's gather wins two to one whatever these say): each of the
# rung's float32 rows scatter-added by token id (XLA:TPU sorts the ids and
# adds a row at a time: 0.114-0.146), and each (token, slot) pair gathered,
# by the bytes of its element: 0.020-0.023 in bf16, 0.030-0.063 in float32.
# A gather is not paid by the byte, and it is at these rates only while the
# rung's rows take under 128 MiB (PERF.md section 6, PR 35). The forms cross
# at rows / pairs = 0.15 for bf16 rows and 0.44 for float32 ones
_SCATTER_ADD_ROW_US = 0.135
_GATHER_PAIR_US = {2: 0.020, 4: 0.060}


def _sums_by_gather(rows, pairs, itemsize):
    """Whether a rung of ``rows`` rows sums them into their tokens by a
    gather over all ``pairs`` (token, slot) pairs of ``itemsize``-byte
    elements rather than by a scatter-add of its rows: the scatter-add's
    cost follows the rung, the gather's the pairs, and both are static for
    a branch, so this is the whole decision (:func:`_sum_by_token`)."""
    pair_us = _GATHER_PAIR_US[2 if itemsize <= 2 else 4]
    return rows * _SCATTER_ADD_ROW_US > pairs * pair_us


class PiecePlan(NamedTuple):
    """What :func:`piece_plan` decides for one batch. ``rungs`` is static
    (Python ints); the rest are device values."""
    order: jax.Array     # (T*k,) the (token, slot) pairs, live ones first
    sizes: jax.Array     # (held,) rows of each expert held
    n_live: jax.Array    # () pairs routed here: sum(sizes)
    rung: jax.Array      # () index into ``rungs`` of the row count run
    rungs: tuple         # the row counts the held part exists at

    @property
    def rows(self):
        """() the rows the held part runs over: ``rungs[rung]``."""
        return jnp.asarray(self.rungs, jnp.int32)[self.rung]


def _rungs(pairs, held, total):
    """The static row counts the held part is built at, smallest first:
    a third above the share of the ``pairs`` (token, slot) rows that
    ``held`` of ``total`` equally loaded experts draw, in whole row tiles
    of the grouped kernel, then doubling while a rung still saves half the
    rows, then all of them. Every rung is compiled (each its own grouped
    kernels: about 5 s of a cold compile and 7 MB of code a rung and layer
    at the kanana cell's widths), so the ladder is short. All experts held
    is the one rung ``pairs``."""
    rung = -(-pairs * held * 4 // (total * 3 * _ROW_TILE)) * _ROW_TILE
    rungs = []
    while rung * 2 <= pairs:    # a rung above half would save under half
        rungs.append(rung)
        rung *= 2
    return tuple(rungs) + (pairs,)


def piece_plan(idx, first_expert, held, total):
    """Which rows the held part of :func:`routed_ffn` runs over, from the
    router's choices ``idx`` (T, k) alone: the pairs that chose one of the
    ``held`` experts from ``first_expert`` on, sorted by expert (stable, so
    a group's tokens ascend), ahead of the pairs routed elsewhere; each
    expert's rows; and the smallest of the static row counts
    (:func:`_rungs`) that holds every live row. The layer runs exactly
    ``rungs[rung]`` rows: this function is the whole of that decision.
    ``order``, ``sizes`` and ``rung`` are named (:data:`KEPT_NAMES`), so a
    recomputed caller whose policy keeps them sorts once; ``n_live`` is
    the sizes' sum, made again."""
    rungs = _rungs(idx.size, held, total)
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(-1) - first_expert
        # pairs of experts held elsewhere sort behind the last group
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = checkpoint_name(
            jnp.argsort(key, stable=True).astype(jnp.int32), _ORDER)
        sizes = checkpoint_name(
            jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32), _SIZES)
        n_live = jnp.sum(sizes)
        rung = checkpoint_name(
            jnp.sum(n_live > jnp.asarray(rungs[:-1], jnp.int32),
                    dtype=jnp.int32), _RUNG)
    return PiecePlan(order, sizes, n_live, rung, rungs)


# the grouped product over sorted rows, that against a weight as it lies
# (contracted on its last dimension: a backward's ``a @ b[e].T``), and the
# one over the sorted rows as the contracted dimension that gives a
# weight's gradient: the kernel's three forms, and how ``ragged_dot`` says
# each (the second only after a transposed copy of the weights)
# (the names are the kernel's own, ``ops/pallas/grouped_matmul.py``; that
# module is imported where a product is built and not with this one:
# ``import mxtpu`` reaches this file, and Pallas is a second's import that
# a program without a routed layer never needs)
_ROWS, _ROWS_T, _WEIGHTS = "rows", "rows_t", "weights"
_RAGGED = {
    _ROWS: jax.lax.RaggedDotDimensionNumbers(     # a[rows of e] @ b[e]
        (((1,), (1,)), ((), ())), lhs_ragged_dimensions=(0,),
        rhs_group_dimensions=(0,)),
    _WEIGHTS: jax.lax.RaggedDotDimensionNumbers(  # a[rows].T @ b[rows]
        (((0,), (0,)), ((), ())), lhs_ragged_dimensions=(0,),
        rhs_group_dimensions=()),
}


class _Groups:
    """The groups of a branch's sorted rows: each one's rows, and the
    kernel's tile table over them, built at the first product the kernel
    takes and shared by the branch's others (3 forward, 6 backward)."""

    def __init__(self, sizes, rows):
        self.sizes, self.rows, self._table = sizes, rows, None

    @property
    def table(self):
        from ..ops.pallas import grouped_matmul as gmm
        if self._table is None:
            self._table = gmm.tile_table(
                self.sizes, self.rows,
                gmm.row_tile(self.rows, self.sizes.shape[0]))
        return self._table


def _grouped(a, b, groups, form=_ROWS):
    """``a[rows of group e] @ b[e]`` for every group, rows sorted by group
    (``_ROWS_T``: ``@ b[e].T``, ``b`` taken as it lies; ``_WEIGHTS``: every
    group's ``a[rows].T @ b[rows]``), under the framework's MXU policy:
    operands in one pass, a float32 accumulator, one rounding. The one
    place the layer chooses who multiplies: the Pallas grouped-matmul
    kernel (``ops/pallas/grouped_matmul.py``: the accumulator stays in
    VMEM, the result is written once in the operands' dtype, no weight is
    copied transposed) wherever it applies, else ``ragged_dot_general``,
    XLA:TPU's grouped kernel, which leaves float32 for a second pass to
    round. Both visit only the row tiles the groups cover. Rows past the
    last group cost nothing and are NOT WRITTEN: they hold what the
    memory held (NaN, on a chip that has run anything else), so a caller
    selects them away (``where``) before any arithmetic and never
    multiplies them by zero. Counted at trace time:
    ``moe.grouped_mm.pallas`` (a product the kernel took),
    ``moe.grouped_mm.xla`` (one left to ``ragged_dot``, by reason:
    ``platform`` / ``dtype`` / ``lanes``); 0 is the number to expect of
    the latter in a timed program."""
    from .. import telemetry
    from ..ops.pallas import grouped_matmul as gmm
    from ..ops.precision_util import contract_acc
    reason = gmm.refusal(a, b)
    if reason is None:
        telemetry.inc("moe.grouped_mm.pallas")
        return gmm.grouped_matmul(a, b, groups.table, form)
    telemetry.inc("moe.grouped_mm.xla", tag=reason)
    if form == _ROWS_T:
        b, form = jnp.swapaxes(b, 1, 2), _ROWS
    return contract_acc(
        lambda a, b, **kw: jax.lax.ragged_dot_general(
            a, b, groups.sizes, _RAGGED[form], **kw), a, b)


def routed_ffn(x, router_w, score_bias, w_gate, w_up, w_down, top_k,
               first_expert=0, scale=1.0, grouped=True, router_x=None,
               score="sigmoid", activation="silu", n_group=1, topk_group=1):
    """Top-k routed gated FFN over the experts HELD here: a contiguous
    range of the router's experts (expert parallelism's share of a layer;
    all of them when ``w_gate`` holds as many as the router scores).

    Every token is routed over ALL ``router_w.shape[0]`` experts and keeps
    every one of its ``top_k`` choices: there is no capacity and nothing is
    dropped. The (token, slot) pairs that chose an expert held here are
    sorted by expert (:func:`piece_plan`; ``moe.dispatch`` gathers their
    tokens), run through ``w_down[e](act(x w_gate[e]) * (x w_up[e]))`` as
    grouped products over exactly the rows of each expert
    (``moe.experts``), and added into their tokens' rows in float32 under
    the router's weights (``moe.combine``: on a rung that lays out under a
    seventh of the pairs a scatter-add by token id, no un-permute over
    every pair; on a larger one a gather of each token's row a slot and a
    sum over the k slots: :func:`_sum_by_token`). A choice of an expert that
    is not held adds nothing; its weight still counts in the normalisation,
    so the shares of all holders add up to the whole layer.

    All of it runs over ``rungs[rung]`` sorted rows, the lowest of the
    static row counts of :func:`_rungs` that holds this step's live rows:
    a chip that holds 16 of 128 experts lays out 8,192 rows of the 49,152
    pairs while its experts draw up to a third over their even share, and
    the last rung is every pair. The ladder follows from T, k, held, total
    and the grouped kernel's row tile; nothing sets it from outside.

    Parameters
    ----------
    x : (T, D) tokens.
    router_w, score_bias : (E, D), (E,) — see :func:`route_top_k`.
    w_gate, w_up : (H, D, F) — the held experts' gate and up projections.
    w_down : (H, F, D).
    first_expert : index, among the router's E, of the first expert held.
    router_x : (T, D) or None — what the router scores, where that is not
        what the experts read (a router placed ahead of attention reads
        the layer's input; the experts the normalised state after it). The
        weights' gradient then goes through the router to ``router_x``,
        not to ``x``. Counted in ``moe.router_ahead``.
    score : the router's score function (:func:`route_top_k`).
    n_group, topk_group : the router's group limit (:func:`route_top_k`;
        1: none). Counted in ``moe.group_limited``.
    activation : the gate's: ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU:
        ``relu(x w_gate) * (x w_up)``). Like ``score`` a property of the
        model, named by its configuration.
    grouped : False computes every held expert on every token under a
        mask (T*H expert passes instead of the T*k/E*H chosen): the plain
        form, kept as the in-program check of the grouped one. Counted in
        ``moe.grouped_mm.dense``.

    Returns (T, D): the held experts' part of the layer's output.

    Counted at trace time (``telemetry``), beside ``moe.layers``:
    ``moe.rows_total`` (T*k, what the grouped path lays out at most) and
    ``moe.piece_rows`` (its lowest rung, what it lays out at least); under
    differentiation ``moe.kept_bytes`` (what the forward keeps for the
    backward: :func:`_held_part`) and ``moe.bwd_products`` (the grouped
    products of one backward branch); ``moe.sum_by_token.gather`` /
    ``moe.sum_by_token.scatter``, one for each branch and direction built
    (:func:`_sum_by_token`).
    """
    from .. import telemetry
    t, d = x.shape
    held, total = w_gate.shape[0], router_w.shape[0]
    if not 0 <= first_expert <= total - held:
        raise MXNetError("experts %d..%d are not among the router's %d"
                         % (first_expert, first_expert + held - 1, total))
    if activation not in _GATES:
        raise MXNetError("routed_ffn: activation %r is none of %s"
                         % (activation, sorted(_GATES)))
    telemetry.inc("moe.layers")
    telemetry.inc("moe.experts_held", held)
    telemetry.inc("moe.experts_total", total)
    if grouped:
        telemetry.inc("moe.grouped_mm.grouped")
    else:
        telemetry.inc("moe.grouped_mm.dense")
    if router_x is not None:
        telemetry.inc("moe.router_ahead")
    if score == "softmax":
        telemetry.inc("moe.score.softmax")
    if n_group > 1:
        telemetry.inc("moe.group_limited")
    idx, w = route_top_k(x if router_x is None else router_x, router_w,
                         score_bias, top_k, scale, score, n_group, topk_group)
    if not grouped:
        from ..ops.precision_util import contract_acc
        local = idx - first_expert
        out = jnp.zeros((t, d), jnp.float32)
        for e in range(held):
            w_e = jnp.sum(jnp.where(local == e, w, 0.0), axis=-1)
            y = _expert(x, w_gate[e], w_up[e], w_down[e],
                        lambda a, b: contract_acc(jnp.matmul, a, b),
                        _GATES[activation])
            out = out + w_e[:, None] * y.astype(jnp.float32)
        return out.astype(x.dtype)

    plan = piece_plan(idx, first_expert, held, total)
    telemetry.inc("moe.rows_total", t * top_k)
    telemetry.inc("moe.piece_rows", plan.rungs[0])
    out = _held_part(top_k, plan.rungs, activation, x, w, w_gate, w_up,
                     w_down, plan.order, plan.sizes, plan.rung)
    return out.astype(x.dtype)


# the gate's activation by the name a configuration gives it
_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _expert(xs, e_gate, e_up, e_down, mm, act):
    return mm(act(mm(xs, e_gate)) * mm(xs, e_up), e_down)


def _sum_by_token(ys, top_k, order, sizes, w=None):
    """(T, D) float32: each token's rows of ``ys`` (the first sorted pairs'
    rows; those past the live ones hold nothing defined and are selected
    away before any arithmetic), under the router's weights ``w`` (T, k) if
    given, summed in float32 (a sum in bf16 would round after every one of
    a token's up to k rows). One sum in two spellings, chosen for each rung
    and direction from static shapes alone (:func:`_sums_by_gather`):

    * a scatter-add of the rung's rows by token id. Its indices may
      collide, so XLA:TPU adds a row at a time and the cost follows the
      rung's rows: the cheaper form where a rung lays out a small share of
      the pairs (16 of 128 experts held: the float32 rows of the backward
      at 8,192 and at 16,384 rows of 49,152 pairs);
    * a gather a slot: for each of the k slots the row of every token's
      pair, (T, D), added up slot by slot (k gathers summed as they come
      ran 2 ms a layer faster in lfm2's backward than one (k, T, D) gather
      and a sum over it), from ``pos``, the inverse of ``order`` (one unique
      int32 scatter of T*k scalars, built only in the branches that take
      this form). A pair is live where ``pos < sum(sizes)``; a dead pair's
      clipped index lands on some row of the rung and is masked. The cost
      follows the pairs whatever the rung, at a fifth to a half of a
      scatter-added row: the cheaper form where the rung IS most of the
      pairs (the last rung of every ladder; every expert held). ``ys`` is
      gathered in its own dtype and widened after the select.

    Counted at trace time, one for each branch and direction built:
    ``moe.sum_by_token.gather`` / ``moe.sum_by_token.scatter``."""
    from .. import telemetry
    f32 = jnp.float32
    rows, pairs = ys.shape[0], order.shape[0]
    n_live = jnp.sum(sizes)
    if _sums_by_gather(rows, pairs, ys.dtype.itemsize):
        telemetry.inc("moe.sum_by_token.gather")
        return _gathered_sum(ys, top_k, order, n_live, w)
    telemetry.inc("moe.sum_by_token.scatter")
    here = order[:rows]
    y = jnp.where((jnp.arange(rows) < n_live)[:, None], ys, 0).astype(f32)
    if w is not None:
        y = _weights_here(w, here) * y
    return jnp.zeros((pairs // top_k,) + ys.shape[1:], f32).at[
        here // top_k].add(y)


@functools.partial(jax.jit, static_argnums=1, inline=True)
def _gathered_sum(ys, top_k, order, n_live, w):
    """The gathering form of :func:`_sum_by_token`. Jitted (and inlined
    where it is called) so that a step traces it once for each rung's
    shape and not once a layer: the k slots' worth of small array
    operations are some forty nested traces a call, each an event for
    every ``jax.monitoring`` listener, and cost lfm2's set-up 1.5 s and
    kanana's 4 before."""
    rows, pairs = ys.shape[0], order.shape[0]
    pos = jnp.zeros(pairs, jnp.int32).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32), unique_indices=True)
    pos = pos.reshape(-1, top_k)
    out = 0.
    for slot in range(top_k):
        at = pos[:, slot]
        y = ys.at[jnp.minimum(at, rows - 1)].get(mode="promise_in_bounds")
        y = jnp.where((at < n_live)[:, None], y, 0).astype(jnp.float32)
        out = out + (y if w is None else w[:, slot, None] * y)
    return out


def _weights_here(w, pairs):
    """(rows, 1): the router's weights of the sorted ``pairs``."""
    return w.reshape(-1).at[pairs].get(unique_indices=True)[:, None]


def _rows_here(rows, top_k, x, order, sizes):
    """The first ``rows`` sorted pairs: their (token, slot) ids, tokens, the
    mask of the live ones (rows, 1), and their tokens' rows of x with the
    rows past the live ones zero."""
    pairs = order[:rows]
    tok = pairs // top_k
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    return pairs, tok, live, jnp.where(live, x[tok], 0)


def _held_rows(rows, top_k, keep, x, w, w_gate, w_up, w_down, order, sizes,
               activation="silu"):
    """The held experts' part over the first ``rows`` sorted pairs (every
    live one is among them): (T, D) float32; with ``keep`` also the two up
    products, padded to all T*k rows, for :func:`_held_rows_bwd`."""
    with jax.named_scope("moe.dispatch"):
        xs = _rows_here(rows, top_k, x, order, sizes)[-1]
    groups = _Groups(sizes, rows)
    with jax.named_scope("moe.experts"):
        gate = _grouped(xs, w_gate, groups)
        up = _grouped(xs, w_up, groups)
        ys = _grouped(_GATES[activation](gate) * up, w_down, groups)
    with jax.named_scope("moe.combine"):
        # float32 under the router's weights, summed by token. A row past
        # the live ones holds nothing defined: it is zeroed BEFORE it meets
        # its weight (0 * NaN is NaN)
        out = _sum_by_token(ys, top_k, order, sizes, w)
    if not keep:
        return out
    tail = ((0, order.shape[0] - rows), (0, 0))
    return out, jnp.pad(gate, tail), jnp.pad(up, tail)


def _held_rows_bwd(rows, top_k, g, gate, up, x, w, w_gate, w_up, w_down,
                   order, sizes, activation="silu"):
    """The transpose of :func:`_held_rows` at ``rows`` rows over the up
    products its forward kept: the cotangents of x, w and the three expert
    leaves from ``g``, (T, D) float32, in six grouped products. The
    router weight's gradient ``<ys, gy>`` is had as ``<h, gy w_down^T>``,
    the product the backward makes anyway, so ``ys = h w_down`` is never
    formed again. Every row past the live ones is selected away before
    any arithmetic, in what was kept too: the grouped kernel wrote none."""
    from .. import telemetry
    f32 = jnp.float32
    last = rows == order.shape[0]       # the rung every ladder has

    def mm(a, b, form):
        if last:
            telemetry.inc("moe.bwd_products")
        return _grouped(a, b, groups, form)

    def back(a, b):         # a[rows of e] @ b[e].T, b as it lies
        return jnp.where(live, mm(a, b, _ROWS_T), 0).astype(f32)

    with jax.named_scope("moe.dispatch"):
        pairs, tok, live, xs = _rows_here(rows, top_k, x, order, sizes)
        w_row = _weights_here(w, pairs)
        gy = jnp.where(live, g[tok], 0)
    groups = _Groups(sizes, rows)
    with jax.named_scope("moe.experts"):
        gate = jnp.where(live, gate[:rows], 0)
        up = jnp.where(live, up[:rows], 0)
        dt = gate.dtype
        h = _GATES[activation](gate) * up
        t = back(gy.astype(dt), w_down)
        d_w_row = jnp.sum(h.astype(f32) * t, axis=-1)
        dw_down = mm(h, (w_row * gy).astype(dt), _WEIGHTS)
        dh, gate32 = w_row * t, gate.astype(f32)
        if activation == "silu":
            sig = jax.nn.sigmoid(gate32)
            d_gate = (dh * up.astype(f32) * sig
                      * (1 + gate32 * (1 - sig))).astype(dt)
            d_up = (dh * gate32 * sig).astype(dt)
        else:       # relu: its slope is the gate's sign, 0 at 0 as jax's
            d_gate = jnp.where(gate32 > 0, dh * up.astype(f32), 0).astype(dt)
            d_up = (dh * jnp.maximum(gate32, 0)).astype(dt)
        dxs = back(d_gate, w_gate) + back(d_up, w_up)
        dw_gate, dw_up = mm(xs, d_gate, _WEIGHTS), mm(xs, d_up, _WEIGHTS)
    with jax.named_scope("moe.dispatch"):
        dx = _sum_by_token(dxs, top_k, order, sizes).astype(x.dtype)
    with jax.named_scope("moe.combine"):
        dw = jnp.zeros(w.size, w.dtype).at[pairs].set(
            d_w_row.astype(w.dtype), unique_indices=True).reshape(w.shape)
    return (dx, dw, dw_gate.astype(w_gate.dtype), dw_up.astype(w_up.dtype),
            dw_down.astype(w_down.dtype))


def _switch(rung, rungs, part, static, *operands, **named):
    """``part(rows, *static, *operands, **named)`` at ``rows =
    rungs[rung]``."""
    return jax.lax.switch(
        rung, [functools.partial(part, rows, *static, **named)
               for rows in rungs], *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_part(top_k, rungs, activation, x, w, w_gate, w_up, w_down, order,
               sizes, rung):
    """:func:`_held_rows` at the row count ``rungs[rung]``: one
    ``lax.switch`` over the static counts, so a step pays for the rung its
    live rows fit and the worst case (every pair live) still runs them all.

    Under differentiation the forward switch also returns its rung's two up
    products, ``xs w_gate`` and ``xs w_up``, in the weights' dtype, and the
    backward is a second switch over :func:`_held_rows_bwd`, written by
    hand so that no grouped product is computed twice (six a branch; the
    transpose of a recomputed forward is nine). A switch's result has ONE
    shape, whichever branch ran, so the pair is laid out at the last rung's
    ``[T*k, F]``: a lower rung fills its first ``rows`` rows and zero-fills
    the rest, at most one write of the pair. That is why only the pair
    crosses (2F a row, bf16 in the cells): the gathered rows, ``act * up``
    and everything float32 or D wide are cheaper to make again in the
    branch than to lay out at T*k rows. Without a gradient nothing is kept.
    Counted at trace time: ``moe.kept_bytes``, ``moe.bwd_products``."""
    return _switch(rung, rungs, _held_rows, (top_k, False), x, w, w_gate,
                   w_up, w_down, order, sizes, activation=activation)


def _held_part_fwd(top_k, rungs, activation, *args):
    from .. import telemetry
    *operands, rung = args
    out, gate, up = _switch(rung, rungs, _held_rows, (top_k, True),
                            *operands, activation=activation)
    telemetry.inc("moe.kept_bytes", gate.nbytes + up.nbytes)
    return out, (gate, up) + args


def _held_part_bwd(top_k, rungs, activation, kept, g):
    *operands, rung = kept
    return _switch(rung, rungs, _held_rows_bwd, (top_k,), g, *operands,
                   activation=activation) + (None, None, None)


_held_part.defvjp(_held_part_fwd, _held_part_bwd)


def shard_experts(params, mesh, num_experts, expert_axis="expert"):
    """Place expert-stacked weights on the expert axis; everything else
    (e.g. the router) replicated. A leaf is expert-stacked iff its leading
    dim EQUALS ``num_experts`` — an explicit count, not a divisibility
    heuristic, so a (D, E) router with D divisible by the axis can never
    be mis-sharded over its feature dim."""
    if expert_axis not in mesh.shape:
        raise MXNetError("mesh has no %r axis; axes: %s"
                         % (expert_axis, tuple(mesh.shape)))
    size = mesh.shape[expert_axis]
    if num_experts % size:
        raise MXNetError("num_experts (%d) must divide over the %r axis "
                         "(%d)" % (num_experts, expert_axis, size))

    def place(leaf):
        if leaf.ndim >= 2 and leaf.shape[0] == num_experts:
            return jax.device_put(leaf,
                                  NamedSharding(mesh, P(expert_axis)))
        return jax.device_put(leaf, NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(place, params)
