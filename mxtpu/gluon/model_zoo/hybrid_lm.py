"""Decoder-only language model whose layers differ in kind: each layer
names its sequence operator and its feed-forward by position.

No reference counterpart. Pre-norm blocks, every norm an RMSNorm, no bias
anywhere: ``h = x + op(norm1(x)); y = h + ffn(norm2(h))``.

* ``op`` is one of :data:`OPERATORS`: ``conv`` (the gated short
  convolution of LFM2, :class:`~mxtpu.gluon.nn.ShortConv`),
  ``full_attention`` (:class:`GroupedQueryAttention`: causal attention of
  ``num_heads`` query heads over ``num_kv_heads`` key/value heads; by
  default an RMSNorm over each query and key head and rotary over the
  whole head), ``window_attention`` (the same block, for the layers of a
  window / global stack that see a sliding window: its keyword arguments
  carry the ``window``), ``sparse_attention`` (the same block with a
  :class:`SparseIndexer` for a child: each query attends to the ``topk``
  keys a learned indexer scores highest, DeepSeek-Sparse-Attention's
  selection), ``latent_attention``
  (:class:`~mxtpu.gluon.model_zoo.latent_moe.MultiHeadLatentAttention`),
  ``kda`` (:class:`KimiDeltaAttention`: a gated delta rule with a decay a
  channel, a recurrent state a head and no softmax) or ``gated_delta_net``
  (:class:`GatedDeltaNet`: the delta rule with ONE decay a head, unbounded,
  and more value heads than key heads). The two delta rules share their
  filters (op ``_contrib_kda_conv``: taps, SiLU, a head's L2 norm), the
  chunked kernels' plan (:mod:`mxtpu.ops.pallas.kda`) and the head norm's
  place; they differ in the decay (its shape, its gate's equation, its
  bound), in the heads (a key head serves several value heads here) and in
  the output gate (``sigmoid`` there, ``silu`` here);
* ``ffn`` is a gated MLP in the first ``dense_layers`` blocks (there may
  be none) and a :class:`~mxtpu.gluon.contrib.nn.RoutedMoE` after them
  (which may hold one chip's share of each layer's experts); with
  ``router_ahead`` its router reads the layer's input ``x``, ahead of the
  operator, while its experts read ``norm2(h)``;
* a final norm and a vocabulary head, tied to the embedding by default;
* with ``recompute`` each block is recomputed in the backward from its
  input (``jax.checkpoint``), and of a block's inside only what its Pallas
  kernels' forwards and its routed layer name is kept (:func:`kept_policy`:
  attention's output and log-sum-exp rows where no window cuts the call,
  either delta rule's output and chunk states, the router's product, its
  choice and the sorted plan), so the second forward runs everything but
  those kernels, the router's product, its ``top_k`` and the plan's sort;
* with ``zero_centered`` every norm of the stack scales by ``1 + w``.

``HybridLM(layers=["conv", "full_attention", "conv", ...])`` is LFM2's
stack (``model_type: lfm2_moe``); ``layers=["full_attention",
"window_attention", "window_attention", "window_attention"]`` with
``router_ahead`` and no dense layer a period of SmallThinker's;
:class:`~mxtpu.gluon.model_zoo.latent_moe.LatentMoELM` is the same model
with ``latent_attention`` in every layer. Trains under
:class:`mxtpu.parallel.ShardedTrainStep`.
"""
from __future__ import annotations

import jax

from ...ndarray import NDArray
from ..block import HybridBlock, _IN_TRACE
from .. import nn

__all__ = ["HybridLM", "DecoderBlock", "GroupedQueryAttention",
           "KimiDeltaAttention", "GatedDeltaNet", "SparseIndexer",
           "OPERATORS", "gate_heads", "gate_elements"]


def kept_policy():
    """What a recomputed block keeps beside its input, as a policy of
    ``jax.checkpoint``: the values its kernels' forward rules name, O(T)
    bytes that cost a kernel's whole run to make again, and what its
    routed layer names of its router's decision (``moe.KEPT_NAMES``: the
    float32 product (T, E), the choice, the sorted order, sizes and rung),
    which cost a 6-pass product, a full sort of (T, E) and an argsort of
    T*k to make again, so a step routes once and both forwards read one
    choice. A windowed attention call names nothing (the same bytes for a
    window's work, and with them Laguna's step does not load beside what
    its set-up holds: PERF.md §6, PR 46). The kernel files and the routed
    layer's own the names (and are imported here, not with the model
    zoo)."""
    from ...ops.pallas.flash_attention import KEPT_NAMES as flash
    from ...ops.pallas.kda import GDN_KEPT_NAMES as gdn, KEPT_NAMES as kda
    from ...parallel.moe import KEPT_NAMES as routed
    return jax.checkpoint_policies.save_only_these_names(
        *flash, *kda, *gdn, *routed)


class SparseIndexer(HybridBlock):
    """The indexer of DeepSeek-Sparse-Attention (DeepSeek-V3.2-Exp report,
    eq. 1-2): ``num_heads`` index heads of ``head_dim`` over ONE index key
    head, and a weight an index head; query ``t`` keeps the ``min(t + 1,
    topk)`` keys ``s <= t`` of largest ``I[t, s] = sum_j w[t, j] relu(qI[t,
    j] . kI[s])`` (op ``_contrib_index_select``: float32, exact, of equal
    scores the lower ``s``). No position encoding and no norm inside. Its
    three leaves take no gradient (``grad_req="null"``): the sets are
    integers, so no loss this block is trained under moves them; the
    objective that would (an index aligned to the attention's scores, its
    input detached) is a second loss this program does not run.

    Input [B, T, dim]; output the sets as int8 [B, T, T], keys first."""

    def __init__(self, dim, num_heads=16, head_dim=64, topk=2048, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {"num_heads": num_heads, "topk": topk}
        with self.name_scope():
            self.q_weight, self.k_weight, self.w_weight = (
                self.params.get(name, shape=(rows, dim), grad_req="null",
                                differentiable=False)
                for name, rows in (("q_weight", num_heads * head_dim),
                                   ("k_weight", head_dim),
                                   ("w_weight", num_heads)))

    def hybrid_forward(self, F, x, *, q_weight, k_weight, w_weight):
        return F._contrib_index_select(x, q_weight, k_weight, w_weight,
                                       **self._attrs)


def gate_heads(F, out, gate, x, head_dim):
    """The head-wise output gate of gated attention (arXiv:2505.06708):
    ``o_h <- o_h * sigmoid(x Wg)_h`` on ``out`` [B, T, H * head_dim], with
    ``gate`` the block that gives ``x Wg`` [B, T, H] from the layer's
    normed input ``x``, before the output projection. The one spelling for
    every block that has it (:class:`GroupedQueryAttention`,
    :class:`~mxtpu.gluon.model_zoo.latent_moe.MultiHeadLatentAttention`);
    scope ``head_gate``, counted in ``attention.head_gated``."""
    from ... import telemetry
    telemetry.inc("attention.head_gated")
    with jax.named_scope("head_gate"):
        heads = F.reshape(out, shape=(0, 0, -1, head_dim))
        return F.reshape(heads * F.expand_dims(F.sigmoid(gate(x)), -1),
                         shape=(0, 0, -1))


def gate_elements(F, out, gate):
    """The elementwise output gate of gated attention (arXiv:2505.06708 at
    its finest granularity): ``o <- o * sigmoid(gate)`` on ``out`` [B, T, H
    * head_dim] with ``gate`` of the same shape, a projection of the
    layer's normed input that the caller has made (the second half of a
    double-width query projection in :class:`GroupedQueryAttention`),
    before the output projection. The one spelling for every block that
    has it; scope ``element_gate``, counted in
    ``attention.element_gated``."""
    from ... import telemetry
    telemetry.inc("attention.element_gated")
    with jax.named_scope("element_gate"):
        return out * F.sigmoid(gate)


class GroupedQueryAttention(HybridBlock):
    """Causal grouped-query attention (Ainslie et al., arXiv:2305.13245):
    ``num_heads`` query heads of ``head_dim`` (``dim // num_heads`` unless
    given) read ``num_kv_heads`` key / value heads, query head ``j`` the
    head ``j // (num_heads / num_kv_heads)``, before the flash kernels,
    which take K and V at their own heads.

    The mask: key ``j`` is visible to query ``i`` iff ``j <= i``; with
    ``window = W > 0`` iff ``i - W < j <= i`` (``W`` keys, the query's own
    among them). ``qk_norm``: queries and keys go through an RMSNorm over a
    head's entries (one learned scale each; without it the block has no
    such leaves). ``rope``: rotary turns them, over the whole head or, with
    ``rotary_dim = R > 0``, over a head's first ``R`` entries alone, by
    ``rope_theta``'s plain table or, with ``rope_scaling`` (the keys of a
    ``rope_type: yarn`` group), YaRN's; without ``rope`` the layer carries
    no position encoding at all. ``head_gate``: each head's output is
    scaled by ``sigmoid(x Wgate)_h`` before the output projection
    (:func:`gate_heads`; ``Wgate`` is ``dim x num_heads``).
    ``element_gate``: the query projection is twice as wide, a head's
    ``head_dim`` of query followed by its ``head_dim`` of gate, and the
    output is scaled entry by entry by the sigmoid of that gate
    (:func:`gate_elements`). ``zero_centered``: the two head norms scale by
    ``1 + w``. The defaults are LFM2's attention layer.

    ``topk = K > 0`` (with ``index_heads`` and ``index_head_dim``): sparse
    attention. A :class:`SparseIndexer` reads the block's input and keeps
    ``min(i + 1, K)`` keys a query, one set for all heads, and the softmax
    runs over the set alone; projections, norms and rotary are unchanged."""

    def __init__(self, dim, num_heads, num_kv_heads, rope_theta=10000.0,
                 epsilon=1e-6, head_dim=None, qk_norm=True, rope=True,
                 window=0, topk=0, index_heads=16, index_head_dim=64,
                 rotary_dim=0, rope_scaling=None, head_gate=False,
                 element_gate=False, zero_centered=False, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads do not divide over %d key/value"
                             " heads" % (num_heads, num_kv_heads))
        self._head_dim = head_dim = head_dim or dim // num_heads
        self._attrs = {"rope_theta": rope_theta, "window": window,
                       "rope": rope, "rotary_dim": rotary_dim,
                       "rope_scaling": rope_scaling}
        if topk and (window or not rope or rotary_dim or rope_scaling):
            raise ValueError("sparse attention (topk=%d) is written with "
                             "plain rotary over the whole head and without "
                             "a window" % topk)
        self._topk = topk
        self._qk_norm = qk_norm
        self._element_gate = element_gate
        with self.name_scope():
            self.q = nn.Dense((2 if element_gate else 1) * num_heads
                              * head_dim, use_bias=False,
                              flatten=False, prefix="q_")
            self.k = nn.Dense(num_kv_heads * head_dim, use_bias=False,
                              flatten=False, prefix="k_")
            self.v = nn.Dense(num_kv_heads * head_dim, use_bias=False,
                              flatten=False, prefix="v_")
            if qk_norm:
                self.q_norm, self.k_norm = (
                    nn.RMSNorm(epsilon=epsilon, zero_centered=zero_centered,
                               prefix=p) for p in ("qnorm_", "knorm_"))
            self.gate = nn.Dense(num_heads, use_bias=False, flatten=False,
                                 prefix="gate_") if head_gate else None
            self.proj = nn.Dense(dim, use_bias=False, flatten=False,
                                 prefix="proj_")
            if topk:
                self.indexer = SparseIndexer(dim, index_heads, index_head_dim,
                                             topk, prefix="indexer_")

    def hybrid_forward(self, F, x):
        heads = (0, 0, -1, self._head_dim)        # [B, T, H, head_dim]
        q = self.q(x)
        if self._element_gate:      # a head's query, then its gate
            q = F.reshape(q, shape=(0, 0, -1, 2 * self._head_dim))
            q, gate = (F.slice_axis(q, axis=-1, begin=b,
                                    end=b + self._head_dim)
                       for b in (0, self._head_dim))
        q = F.reshape(q, shape=heads)
        if self._qk_norm:
            q = self.q_norm(q)
        k = F.reshape(self.k(x), shape=heads)
        if self._qk_norm:
            k = self.k_norm(k)
        if self._topk:
            out = F._contrib_sparse_attention(
                q, k, self.v(x), self.indexer(x),
                rope_theta=self._attrs["rope_theta"], topk=self._topk)
        else:
            out = F._contrib_grouped_attention(q, k, self.v(x), **self._attrs)
        if self.gate is not None:
            out = gate_heads(F, out, self.gate, x, self._head_dim)
        if self._element_gate:
            out = gate_elements(F, out, F.reshape(gate, shape=(0, 0, -1)))
        return self.proj(out)


def _dense(units, prefix):
    """A projection of the delta-rule blocks: no bias, the last axis."""
    return nn.Dense(units, use_bias=False, flatten=False, prefix=prefix)


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692 §3-4): linear
    attention whose state a head, ``S`` in ``R^{head_dim x head_dim}``,
    follows a gated delta rule with a decay a channel, ``S_t = (I - b_t
    k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t /
    sqrt(head_dim)``; no softmax, no position encoding.

    ``q``, ``k``, ``v`` are projections of the input through a causal
    depthwise filter of ``conv_size`` taps and SiLU, ``q`` and ``k`` then
    L2-normed by head (op ``_contrib_kda_conv``); the log-decay is ``g =
    lower_bound * sigmoid(exp(a_log[h]) * (x Wf + dt_bias))`` in float32
    (op ``_contrib_kda_gate``: the bounded gate that the chunked kernels'
    exponents rest on), ``b = sigmoid(b(x))`` a head; the output goes
    through an RMSNorm over each head's entries (one learned scale of
    ``head_dim``), an elementwise gate ``sigmoid(g(x))`` and the output
    projection. The decay's and the gate's projections are full rank. The
    recurrence runs by chunks through a Pallas kernel pair (op
    ``_contrib_kda_attention``). No bias anywhere.

    Input [B, T, dim]; output [B, T, dim]."""

    def __init__(self, dim, num_heads, head_dim, conv_size=4,
                 lower_bound=-5.0, epsilon=1e-6, chunk=64, **kwargs):
        super().__init__(**kwargs)
        self._head_dim, self._chunk = head_dim, chunk
        self._lower_bound = lower_bound
        width = num_heads * head_dim

        with self.name_scope():
            self.q, self.k, self.v = (_dense(width, n) for n in
                                      ("q_", "k_", "v_"))
            self.q_conv, self.k_conv, self.v_conv = (
                self.params.get(n + "_conv_weight", shape=(width, conv_size))
                for n in "qkv")
            self.a_log = self.params.get("a_log", shape=(num_heads,))
            self.dt_bias = self.params.get("dt_bias", shape=(width,))
            # the decay's projection, a leaf of the block: its product's
            # float32 result goes into the gate unrounded
            self.f_weight = self.params.get("f_weight", shape=(width, dim))
            self.b = _dense(num_heads, "b_")
            self.g = _dense(width, "g_")
            self.o_norm = nn.RMSNorm(epsilon=epsilon, prefix="onorm_")
            self.proj = _dense(dim, "proj_")

    def hybrid_forward(self, F, x, *, q_conv, k_conv, v_conv, a_log,
                       dt_bias, f_weight):
        hd = self._head_dim
        o = F._contrib_kda_attention(
            F._contrib_kda_conv(self.q(x), q_conv, head_dim=hd),
            F._contrib_kda_conv(self.k(x), k_conv, head_dim=hd),
            F._contrib_kda_conv(self.v(x), v_conv),
            F._contrib_kda_gate(x, f_weight, a_log, dt_bias,
                                lower_bound=self._lower_bound),
            F.sigmoid(self.b(x)), chunk=self._chunk)
        o = self.o_norm(F.reshape(o, shape=(0, 0, -1, hd)))  # [B, T, H, hd]
        return self.proj(F.reshape(o, shape=(0, 0, -1)) * F.sigmoid(self.g(x)))


class GatedDeltaNet(HybridBlock):
    """Gated DeltaNet (Yang et al., arXiv:2412.06464): linear attention
    whose state a value head, ``S`` in ``R^{head_dim x value_head_dim}``,
    decays by one number a head and token and is corrected by a delta
    rule: ``S' = exp(g_t) S_{t-1}; S_t = S' + k_t (b_t (v_t - S'^T
    k_t))^T``, ``o_t = S_t^T q_t / sqrt(head_dim)``; no softmax, no
    position encoding. ``num_heads`` value heads read ``num_key_heads`` key
    heads, value head ``j`` the head ``j // (num_heads / num_key_heads)``.

    ``q``, ``k``, ``v`` are projections of the input through a causal
    depthwise filter of ``conv_size`` taps and SiLU, ``q`` and ``k`` then
    L2-normed by head (op ``_contrib_kda_conv``, the filter
    :class:`KimiDeltaAttention` has); the log-decay is ``g = -exp(a_log[h])
    * softplus(x Wa + dt_bias[h])`` in float32 (op ``_contrib_gdn_gate``:
    unbounded below, which the kernels' decay of differences takes), ``b =
    sigmoid(b(x))`` a head; the output goes through an RMSNorm over each
    head's entries (one learned scale of ``value_head_dim``, a plain
    scale whatever the stack's other norms do), times ``silu(z(x))`` entry
    by entry (scope ``gated_norm``), and the output projection. The
    recurrence runs by chunks through a Pallas kernel pair (op
    ``_contrib_gated_delta_rule``). No bias anywhere.

    Input [B, T, dim]; output [B, T, dim]."""

    def __init__(self, dim, num_heads, num_key_heads, head_dim,
                 value_head_dim=None, conv_size=4, epsilon=1e-6, chunk=64,
                 **kwargs):
        super().__init__(**kwargs)
        self._key_heads, self._head_dim, self._chunk = \
            num_key_heads, head_dim, chunk
        self._value_dim = value_head_dim = value_head_dim or head_dim
        keys, values = num_key_heads * head_dim, num_heads * value_head_dim

        with self.name_scope():
            self.q, self.k = _dense(keys, "q_"), _dense(keys, "k_")
            self.v, self.z = _dense(values, "v_"), _dense(values, "z_")
            self.q_conv, self.k_conv, self.v_conv = (
                self.params.get(n + "_conv_weight", shape=(width, conv_size))
                for n, width in (("q", keys), ("k", keys), ("v", values)))
            self.a_log = self.params.get("a_log", shape=(num_heads,))
            self.dt_bias = self.params.get("dt_bias", shape=(num_heads,))
            # the decay's projection, a leaf of the block: its product's
            # float32 result goes into the gate unrounded
            self.a_weight = self.params.get("a_weight",
                                            shape=(num_heads, dim))
            self.b = _dense(num_heads, "b_")
            self.o_norm = nn.RMSNorm(epsilon=epsilon, prefix="onorm_")
            self.proj = _dense(dim, "proj_")

    def hybrid_forward(self, F, x, *, q_conv, k_conv, v_conv, a_log,
                       dt_bias, a_weight):
        hd = self._head_dim
        o = F._contrib_gated_delta_rule(
            F._contrib_kda_conv(self.q(x), q_conv, head_dim=hd),
            F._contrib_kda_conv(self.k(x), k_conv, head_dim=hd),
            F._contrib_kda_conv(self.v(x), v_conv),
            F._contrib_gdn_gate(x, a_weight, a_log, dt_bias),
            F.sigmoid(self.b(x)), key_heads=self._key_heads,
            chunk=self._chunk)
        with jax.named_scope("gated_norm"):
            o = self.o_norm(F.reshape(o, shape=(0, 0, -1, self._value_dim)))
            o = F.reshape(o, shape=(0, 0, -1)) * F.Activation(
                self.z(x), act_type="silu")
        return self.proj(o)


def _latent_attention(dim, **kwargs):
    from .latent_moe import MultiHeadLatentAttention
    return MultiHeadLatentAttention(dim, **kwargs)


# kind of sequence operator -> (its block's constructor after ``dim``, the
# prefix of its parameters in a decoder block)
OPERATORS = {
    "conv": (nn.ShortConv, "conv_"),
    "full_attention": (GroupedQueryAttention, "attn_"),
    # the same block under its own name, so that a stack can give its
    # windowed layers other keyword arguments than its global ones
    "window_attention": (GroupedQueryAttention, "attn_"),
    # and with a ``topk`` among them: each query's keys picked by an indexer
    "sparse_attention": (GroupedQueryAttention, "attn_"),
    "latent_attention": (_latent_attention, "attn_"),
    "kda": (KimiDeltaAttention, "kda_"),
    "gated_delta_net": (GatedDeltaNet, "gdn_"),
}


class DecoderBlock(HybridBlock):
    """``h = x + op(norm1(x)); y = h + ffn(norm2(h))``. ``operator`` is
    ``(kind, keyword arguments)`` of one of :data:`OPERATORS`; ``ffn`` is a
    gated MLP (``moe=None``) or routed experts (``moe``: the keyword
    arguments of :class:`~mxtpu.gluon.contrib.nn.RoutedMoE` after
    ``dim``). ``router_ahead`` (routed experts only): the router reads the
    layer's own input, ``y = h + ffn(norm2(h), router_x=x)``: a router
    placed before attention, whose choice does not wait for it.
    ``zero_centered``: both norms scale by ``1 + w``."""

    def __init__(self, dim, operator, dense_hidden=0, moe=None,
                 epsilon=1e-6, router_ahead=False, zero_centered=False,
                 **kwargs):
        super().__init__(**kwargs)
        kind, op_kwargs = operator
        make, prefix = OPERATORS[kind]
        self._router_ahead = router_ahead and moe is not None
        with self.name_scope():
            self.norm1 = nn.RMSNorm(epsilon=epsilon, prefix="norm1_",
                                    zero_centered=zero_centered)
            self.op = make(dim, prefix=prefix, **op_kwargs)
            self.norm2 = nn.RMSNorm(epsilon=epsilon, prefix="norm2_",
                                    zero_centered=zero_centered)
            if moe is None:
                self.ffn = nn.GatedMLP(dim, dense_hidden, prefix="mlp_")
            else:
                from ..contrib.nn import RoutedMoE
                self.ffn = RoutedMoE(dim, prefix="moe_", **moe)

    def hybrid_forward(self, F, x):
        h = x + self.op(self.norm1(x))
        if self._router_ahead:
            return h + self.ffn(self.norm2(h), x)
        return h + self.ffn(self.norm2(h))


class HybridLM(HybridBlock):
    """Embed -> one :class:`DecoderBlock` a layer -> RMSNorm -> vocabulary
    head. Input: int token ids [B, T]; output: logits [B, T, vocab].

    ``layers``: each layer's operator kind, in order (``layer_types`` of an
    ``lfm2_moe`` configuration). ``operators``: kind -> the keyword
    arguments of its block (:data:`OPERATORS`), e.g. ``{"conv":
    {"kernel_size": 3}, "full_attention": {"num_heads": 32,
    "num_kv_heads": 8, "rope_theta": 1e6, "epsilon": 1e-5}}``. The first
    ``dense_layers`` blocks (0: none) have a gated MLP of ``dense_hidden``,
    the rest ``moe`` (``hidden, num_experts, top_k`` and optionally
    ``experts_held, first_expert, scale, shared_hidden, shared_gate, score,
    activation``), whose routers read their layer's input with
    ``router_ahead``. ``tie_head``: the head reads the embedding's weight,
    whose gradient is the sum of both uses. ``zero_centered``: the blocks'
    norms and the final norm scale by ``1 + w`` (an operator's own norms
    follow its own keyword arguments). ``recompute``: in a traced
    forward each block runs under ``jax.checkpoint``, which keeps the
    block's input and what its attention (unwindowed) and delta-rule
    kernels' forward rules and its routed layer's router name
    (:func:`kept_policy`), so a differentiated step holds one block's
    activations at a time and runs every block's forward twice (the expert
    layer's own ``custom_vjp`` rule among it) but for those kernels and
    the router's product, ``top_k`` and sort, whose second run is dead
    code once their outputs are held; a property of the model, set where
    the model is built, and counted at trace time in
    ``train_step.blocks_recomputed``.
    """

    def __init__(self, vocab_size, dim, layers, operators, dense_hidden, moe,
                 dense_layers=1, epsilon=1e-6, tie_head=True,
                 router_ahead=False, recompute=False, zero_centered=False,
                 **kwargs):
        super().__init__(**kwargs)
        self._recompute = recompute
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, dim, prefix="wte_")
            self.blocks = nn.HybridSequential(prefix="h_")
            with self.blocks.name_scope():
                for i, kind in enumerate(layers):
                    self.blocks.add(DecoderBlock(
                        dim, (kind, operators.get(kind, {})),
                        dense_hidden=dense_hidden,
                        moe=None if i < dense_layers else moe,
                        epsilon=epsilon, router_ahead=router_ahead,
                        zero_centered=zero_centered))
            self.norm_f = nn.RMSNorm(epsilon=epsilon, prefix="normf_",
                                     zero_centered=zero_centered)
            # tied: the head is a Dense over the embedding's own weight
            self.head = nn.Dense(
                vocab_size, use_bias=False, flatten=False,
                **({"in_units": dim, "params": self.embed.params}
                   if tie_head else {"prefix": "head_"}))

    def hybrid_forward(self, F, tokens):
        if not (self._recompute and _IN_TRACE.active):
            return self.head(self.norm_f(self.blocks(self.embed(tokens))))
        from ... import telemetry
        x, kept = self.embed(tokens), kept_policy()
        # the blocks are called one by one, not through the stack: its
        # name is given here, so that a layer's path reads the same
        # (``h_/decoderblock3_/...``) recomputed or not
        with jax.named_scope(self.blocks._own_name):
            for block in self.blocks._children.values():
                telemetry.inc("train_step.blocks_recomputed")
                x = NDArray(jax.checkpoint(
                    lambda data, block=block: block(NDArray(data))._data,
                    policy=kept)(x._data))
        return self.head(self.norm_f(x))
