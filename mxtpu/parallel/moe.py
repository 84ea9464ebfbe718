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
experts it holds (one chip's share under expert parallelism). Its tokens
are sorted by expert and go through grouped products over exactly the rows
each expert was chosen for; on one chip it runs without an exchange.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError

__all__ = ["switch_ffn", "routed_ffn", "route_top_k", "shard_experts"]


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


def route_top_k(x, router_w, score_bias, top_k, scale=1.0):
    """The router of DeepSeek-V3 (arXiv:2412.19437 §2.1.2) without its
    group limit: ``s = sigmoid(x W^T)`` with the product in float32
    (``router_w`` is (E, D), a Dense weight); the ``top_k`` experts are
    chosen by ``s + score_bias`` (the selection bias steers the choice
    only and takes no gradient); their weights are ``s`` at the chosen
    experts, normalised to sum to one, times ``scale``. Returns ``(idx,
    w)``, both (T, top_k): int32 experts and float32 weights."""
    with jax.named_scope("moe.route"):
        s = jax.nn.sigmoid(jnp.einsum(
            "td,ed->te", x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(
            s + jax.lax.stop_gradient(score_bias.astype(jnp.float32)), top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return idx, w


@jax.custom_vjp
def _dispatch(x, order, inverse):
    """Row ``i`` of the result is token ``order[i] // k``: the (token,
    slot) pairs in sorted order. ``order`` permutes the T*k pairs and
    ``inverse`` undoes it, so the transpose is a gather too (then a sum
    over each token's k slots), not a scatter-add with repeated rows."""
    return x[order // (order.shape[0] // x.shape[0])]


def _dispatch_fwd(x, order, inverse):
    return _dispatch(x, order, inverse), (inverse, x.shape[0])


def _dispatch_bwd(kept, g):
    inverse, t = kept
    return g[inverse].reshape(t, -1, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unpermute(y, order, inverse):
    """``y[inverse]``: sorted rows back into (token, slot) order; its
    transpose is ``g[order]``."""
    return y[inverse]


_unpermute.defvjp(lambda y, order, inverse: (y[inverse], order),
                  lambda order, g: (g[order], None, None))


def _grouped(xs, w, sizes):
    """``xs[rows of group e] @ w[e]`` for every group, rows sorted by
    group: ``jax.lax.ragged_dot``, which XLA:TPU lowers to one grouped
    matmul kernel that visits only the row tiles the groups cover (rows
    past the last group cost nothing and hold nothing defined), under the
    framework's MXU policy (``contract_acc``)."""
    from ..ops.precision_util import contract_acc
    return contract_acc(
        lambda a, b, **kw: jax.lax.ragged_dot(a, b, sizes, **kw), xs, w)


def routed_ffn(x, router_w, score_bias, w_gate, w_up, w_down, top_k,
               first_expert=0, scale=1.0, grouped=True):
    """Top-k routed gated FFN over the experts HELD here: a contiguous
    range of the router's experts (expert parallelism's share of a layer;
    all of them when ``w_gate`` holds as many as the router scores).

    Every token is routed over ALL ``router_w.shape[0]`` experts and keeps
    every one of its ``top_k`` choices: there is no capacity and nothing is
    dropped. The (token, slot) pairs that chose an expert held here are
    sorted by expert (`moe.dispatch`), run through ``w_down[e](silu(x
    w_gate[e]) * (x w_up[e]))`` as grouped products over exactly the rows
    of each expert (`moe.experts`), and summed back per token under the
    router's weights (`moe.combine`). A choice of an expert that is not
    held adds nothing; its weight still counts in the normalisation, so
    the shares of all holders add up to the whole layer.

    Parameters
    ----------
    x : (T, D) tokens.
    router_w, score_bias : (E, D), (E,) — see :func:`route_top_k`.
    w_gate, w_up : (H, D, F) — the held experts' gate and up projections.
    w_down : (H, F, D).
    first_expert : index, among the router's E, of the first expert held.
    grouped : False computes every held expert on every token under a
        mask (T*H expert passes instead of the T*k/E*H chosen): the plain
        form, kept as the in-program check of the grouped one. Counted in
        ``moe.grouped_mm.dense``.

    Returns (T, D): the held experts' part of the layer's output.
    """
    from .. import telemetry
    t, d = x.shape
    held, total = w_gate.shape[0], router_w.shape[0]
    if not 0 <= first_expert <= total - held:
        raise MXNetError("experts %d..%d are not among the router's %d"
                         % (first_expert, first_expert + held - 1, total))
    telemetry.inc("moe.layers")
    telemetry.inc("moe.experts_held", held)
    telemetry.inc("moe.experts_total", total)
    if grouped:
        telemetry.inc("moe.grouped_mm.grouped")
    else:
        telemetry.inc("moe.grouped_mm.dense")
    idx, w = route_top_k(x, router_w, score_bias, top_k, scale)
    local = idx - first_expert
    mine = (local >= 0) & (local < held)                     # (T, k)

    def expert(xs, e_gate, e_up, e_down, mm):
        return mm(jax.nn.silu(mm(xs, e_gate)) * mm(xs, e_up), e_down)

    if not grouped:
        from ..ops.precision_util import contract_acc
        out = jnp.zeros((t, d), jnp.float32)
        for e in range(held):
            w_e = jnp.sum(jnp.where(local == e, w, 0.0), axis=-1)
            y = expert(x, w_gate[e], w_up[e], w_down[e],
                       lambda a, b: contract_acc(jnp.matmul, a, b))
            out = out + w_e[:, None] * y.astype(jnp.float32)
        return out.astype(x.dtype)

    def held_part(x, w, w_gate, w_up, w_down):
        with jax.named_scope("moe.dispatch"):
            # pairs of experts held elsewhere sort behind the last group
            key = jnp.where(mine, local, held).reshape(-1)   # (T*k,)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=jnp.int32))
            sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :],
                            axis=0, dtype=jnp.int32)
            live = jnp.arange(order.shape[0]) < jnp.sum(sizes)
            xs = jnp.where(live[:, None], _dispatch(x, order, inverse), 0)
        with jax.named_scope("moe.experts"):
            ys = expert(xs, w_gate, w_up, w_down,
                        lambda a, b: _grouped(a, b, sizes))
        with jax.named_scope("moe.combine"):
            y = jnp.where(mine.reshape(-1, 1),
                          _unpermute(ys, order, inverse), 0)
            # one multiply-and-reduce fusion: no float32 copy of y
            return jnp.sum(w[:, :, None] * y.reshape(t, top_k, d).astype(
                jnp.float32), axis=1)

    # T*k rows are laid out whatever share of them is routed here (the
    # worst case is all), so nothing of this part is kept for the backward
    # pass: it is computed again from x, the weights and the choices
    out = jax.checkpoint(held_part)(x, w, w_gate, w_up, w_down)
    return out.astype(x.dtype)


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
