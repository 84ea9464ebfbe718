"""Contrib layers (ref: python/mxnet/gluon/contrib/nn/basic_layers.py)."""
from __future__ import annotations

import jax

from ...block import HybridBlock
from ...nn import BatchNorm, Dense, GatedMLP, HybridSequential, Embedding

__all__ = ["Concurrent", "HybridConcurrent", "Identity", "SparseEmbedding",
           "SyncBatchNorm", "SwitchMoE", "RoutedMoE"]


class Concurrent(HybridSequential):
    """Run children on the same input, concat outputs (ref: Concurrent)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def hybrid_forward(self, F, x):
        out = [block(x) for block in self._children.values()]
        return F.concat(*out, dim=self.axis)


class HybridConcurrent(Concurrent):
    pass


class Identity(HybridBlock):
    def hybrid_forward(self, F, x):
        return x


class SparseEmbedding(HybridBlock):
    """Embedding backed by row_sparse gradients (ref: SparseEmbedding).

    TPU note: gradients stay dense under jit (XLA scatter-add); the
    row_sparse benefit of the reference (PS bandwidth) is subsumed by the
    collective data plane, so this is API parity over the same Embedding op.
    """

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim,
                        "dtype": dtype, "sparse_grad": True}
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer, grad_stype="row_sparse")

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, **{k: v for k, v in self._kwargs.items()
                                         if k != "sparse_grad"})

    def __repr__(self):
        return "SparseEmbedding({input_dim} -> {output_dim}, {dtype})".format(
            **self._kwargs)


class SyncBatchNorm(BatchNorm):
    """Cross-device synchronized BatchNorm (ref: contrib SyncBatchNorm over
    src/operator/contrib/sync_batch_norm.cc — a barrier/broadcast protocol
    across GPU workers).

    TPU-native: inside a jitted sharded step, batch statistics are GLOBAL
    means over the full (mesh-sharded) batch automatically — GSPMD inserts the
    cross-replica reduction, so plain BatchNorm *is* SyncBatchNorm on the
    mesh. Kept as a distinct class for API parity; `num_devices` is accepted
    and ignored.
    """

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, center=True, scale=True, use_global_stats=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", **kwargs):
        super().__init__(axis=1, momentum=momentum, epsilon=epsilon,
                         center=center, scale=scale,
                         use_global_stats=use_global_stats,
                         beta_initializer=beta_initializer,
                         gamma_initializer=gamma_initializer,
                         running_mean_initializer=running_mean_initializer,
                         running_variance_initializer=running_variance_initializer,
                         in_channels=in_channels, **kwargs)


class SwitchMoE(HybridBlock):
    """Top-1 switch mixture-of-experts FFN layer (no reference counterpart
    — SURVEY §2.3 lists MoE/expert parallelism as absent upstream).

    Wraps the registered ``_contrib_switch_moe`` op (mxtpu.parallel.moe
    switch_ffn): router + E expert FFNs as dispatch/combine einsums so
    GSPMD lowers routing to all-to-all when the expert weights live on an
    ``expert`` mesh axis (place them with ``mxtpu.parallel.shard_experts``
    or ShardedTrainStep param_specs).

    Returns ``(out, aux_loss)`` — the Switch load-balancing loss is a REAL
    second output (not a side-channel attribute), so it survives
    hybridize()/export and its gradient flows when added to the objective.

    Input (..., dim) is flattened to tokens and restored, so the layer
    drops into transformer blocks shaped (batch, seq, dim).
    """

    def __init__(self, dim, hidden, num_experts, capacity_factor=1.25,
                 **kwargs):
        super().__init__(**kwargs)
        self._dim, self._hidden = dim, hidden
        self._num_experts = num_experts
        self._capacity_factor = capacity_factor
        with self.name_scope():
            self.router = self.params.get("router", shape=(dim, num_experts))
            self.w1 = self.params.get("w1", shape=(num_experts, dim, hidden))
            self.b1 = self.params.get("b1", shape=(num_experts, hidden),
                                      init="zeros")
            self.w2 = self.params.get("w2", shape=(num_experts, hidden, dim))
            self.b2 = self.params.get("b2", shape=(num_experts, dim),
                                      init="zeros")

    def hybrid_forward(self, F, x, router, w1, b1, w2, b2):
        if x.shape[-1] != self._dim:
            raise ValueError(
                "SwitchMoE(dim=%d) got input with last axis %d"
                % (self._dim, x.shape[-1]))
        return F._contrib_switch_moe(x, router, w1, b1, w2, b2,
                                     capacity_factor=self._capacity_factor)

    def __repr__(self):
        return "SwitchMoE(dim=%d, hidden=%d, experts=%d)" % (
            self._dim, self._hidden, self._num_experts)


class RoutedMoE(HybridBlock):
    """Top-k routed gated experts with optional shared experts, as
    DeepSeek-V3 has them (arXiv:2412.19437 §2.1.2): sigmoid scores, a
    selection bias that takes no gradient, weights normalised over the
    chosen experts and scaled; no capacity, no token dropped. ``score``
    (``"softmax"``: the softmax over all experts, its chosen renormalised)
    and ``activation`` (``"relu"``: ReGLU experts) name another published
    layer (mxtpu.parallel.moe.route_top_k, routed_ffn); ``n_group`` > 1
    with ``topk_group`` is that paper's group limit on the choice. Called
    with a second input, the router scores that and the experts read the
    first (a router placed ahead of attention reads its layer's input).
    ``shared_gate``: the shared experts' output is scaled by ``sigmoid(x
    w_sg)``, one number a token (``w_sg``: ``dim x 1``), before it is
    added.

    ``experts_held`` of the router's ``num_experts`` live in this block,
    starting at ``first_expert``: one chip's share of the layer under
    expert parallelism (all of them by default). The block routes over
    all ``num_experts`` and returns its own experts' part of the routed
    sum plus the shared experts' output, which every holder computes
    alike. Wraps the registered ``_contrib_routed_moe`` op
    (mxtpu.parallel.moe.routed_ffn).

    Input (..., dim) is flattened to tokens and restored.
    """

    def __init__(self, dim, hidden, num_experts, top_k, experts_held=None,
                 first_expert=0, scale=1.0, shared_hidden=0, grouped=True,
                 score="sigmoid", activation="silu", n_group=1,
                 topk_group=1, shared_gate=False, **kwargs):
        super().__init__(**kwargs)
        if shared_gate and not shared_hidden:
            raise ValueError("RoutedMoE: a gate on a shared expert that is "
                             "not there (shared_hidden=0)")
        held = num_experts if experts_held is None else experts_held
        self._dim, self._hidden = dim, hidden
        self._num_experts, self._held = num_experts, held
        self._attrs = {"top_k": top_k, "first_expert": first_expert,
                       "scale": scale, "grouped": grouped, "score": score,
                       "activation": activation}
        if n_group > 1:     # the router's group limit (route_top_k)
            self._attrs.update(n_group=n_group, topk_group=topk_group)
        with self.name_scope():
            self.router = self.params.get("router_weight",
                                          shape=(num_experts, dim))
            self.score_bias = self.params.get(
                "score_bias", shape=(num_experts,), init="zeros",
                grad_req="null", differentiable=False)
            self.w_gate = self.params.get("w_gate", shape=(held, dim, hidden))
            self.w_up = self.params.get("w_up", shape=(held, dim, hidden))
            self.w_down = self.params.get("w_down",
                                          shape=(held, hidden, dim))
            self.shared_gate = Dense(
                1, use_bias=False, flatten=False, prefix="sgate_") \
                if shared_gate else None
            self.shared = GatedMLP(dim, shared_hidden, prefix="shared_") \
                if shared_hidden else None

    def hybrid_forward(self, F, x, router_x=None, *, router, score_bias,
                       w_gate, w_up, w_down):
        if x.shape[-1] != self._dim:
            raise ValueError("RoutedMoE(dim=%d) got input with last axis %d"
                             % (self._dim, x.shape[-1]))
        ahead = {} if router_x is None else {"router_data": router_x}
        out = F._contrib_routed_moe(x, router, score_bias, w_gate, w_up,
                                    w_down, **ahead, **self._attrs)
        if self.shared is None:
            return out
        with jax.named_scope("moe.shared"):
            shared = self.shared(x)
            if self.shared_gate is None:
                return out + shared
        with jax.named_scope("moe.shared_gate"):
            return out + shared * F.sigmoid(self.shared_gate(x))

    def __repr__(self):
        return "RoutedMoE(dim=%d, hidden=%d, experts=%d of %d, top_k=%d)" % (
            self._dim, self._hidden, self._held, self._num_experts,
            self._attrs["top_k"])
