"""Decoder-only language model with multi-head latent attention and routed
experts: the layer of DeepSeek-V2/V3 (arXiv:2405.04434, arXiv:2412.19437;
``model_type: deepseek_v3`` configurations).

No reference counterpart. Pre-norm blocks, every norm an RMSNorm, no bias
anywhere:

* attention keeps ONE low-rank latent a token for keys and values and one
  rotary key shared by all heads (:class:`MultiHeadLatentAttention`); keys
  and queries are ``nope + rope`` wide, values ``v`` wide, and both flash
  kernels take the two widths;
* the first ``dense_layers`` blocks have a gated MLP, the rest a
  :class:`~mxtpu.gluon.contrib.nn.RoutedMoE`: top-k of ``num_experts`` small
  gated experts by sigmoid scores, nothing dropped, plus shared experts. A
  block may hold a contiguous range of each layer's experts (``experts_held``
  from ``first_expert``): one chip's share under expert parallelism;
* rotary positions on the ``rope`` part of each head, no position table;
* a final norm and an untied vocabulary head.

Trains under :class:`mxtpu.parallel.ShardedTrainStep` like
:class:`~mxtpu.gluon.model_zoo.transformer.TransformerLM`.
"""
from __future__ import annotations

from ..block import HybridBlock
from .. import nn
from .hybrid_lm import HybridLM, gate_heads

__all__ = ["LatentMoELM", "MultiHeadLatentAttention"]


class MultiHeadLatentAttention(HybridBlock):
    """Causal latent attention without a query rank: ``q = x Wq``; ``x
    Wkva`` gives the latent ``c`` (``kv_rank`` wide) and the shared rotary
    key; ``norm(c) Wkvb`` gives each head's position-free key and value.
    ``head_gate``: each head's output is scaled by ``sigmoid(x Wgate)_h``
    before the output projection (gated attention, arXiv:2505.06708, at its
    head-wise granularity: ``Wgate`` is ``dim x num_heads``;
    :func:`~mxtpu.gluon.model_zoo.hybrid_lm.gate_heads`)."""

    def __init__(self, dim, num_heads, kv_rank, nope_dim, rope_dim, v_dim,
                 rope_theta=10000.0, rope_interleave=False, epsilon=1e-6,
                 causal=True, head_gate=False, **kwargs):
        super().__init__(**kwargs)
        self._kv_rank, self._rope_dim = kv_rank, rope_dim
        self._v_dim = v_dim
        self._attrs = {"num_heads": num_heads, "nope_dim": nope_dim,
                       "rope_dim": rope_dim, "v_dim": v_dim,
                       "rope_theta": rope_theta,
                       "rope_interleave": rope_interleave, "causal": causal}
        with self.name_scope():
            self.q = nn.Dense(num_heads * (nope_dim + rope_dim),
                              use_bias=False, flatten=False, prefix="q_")
            self.kv_a = nn.Dense(kv_rank + rope_dim, use_bias=False,
                                 flatten=False, prefix="kva_")
            self.kv_norm = nn.RMSNorm(epsilon=epsilon, prefix="kvnorm_")
            self.kv_b = nn.Dense(num_heads * (nope_dim + v_dim),
                                 use_bias=False, flatten=False, prefix="kvb_")
            self.gate = nn.Dense(num_heads, use_bias=False, flatten=False,
                                 prefix="gate_") if head_gate else None
            self.proj = nn.Dense(dim, use_bias=False, flatten=False,
                                 prefix="proj_")

    def hybrid_forward(self, F, x):
        r = self._kv_rank
        ckr = self.kv_a(x)                                   # [B, T, r + rope]
        c = F.slice_axis(ckr, axis=-1, begin=0, end=r)
        k_rope = F.slice_axis(ckr, axis=-1, begin=r, end=r + self._rope_dim)
        out = F._contrib_latent_attention(
            self.q(x), self.kv_b(self.kv_norm(c)), k_rope, **self._attrs)
        if self.gate is not None:
            out = gate_heads(F, out, self.gate, x, self._v_dim)
        return self.proj(out)


class LatentMoELM(HybridLM):
    """Embed → ``dense_layers`` dense blocks → routed-expert blocks → RMSNorm
    → vocabulary head (untied), latent attention in every layer: a
    :class:`~mxtpu.gluon.model_zoo.hybrid_lm.HybridLM` of one operator
    kind (each layer a :class:`~mxtpu.gluon.model_zoo.hybrid_lm.DecoderBlock`
    whose ``op`` is a :class:`MultiHeadLatentAttention`). Input: int token
    ids [B, T]; output: logits [B, T, vocab].

    ``attention``: ``num_heads, kv_rank, nope_dim, rope_dim, v_dim`` and
    optionally ``rope_theta, rope_interleave``. ``moe``: ``hidden,
    num_experts, top_k`` and optionally ``experts_held, first_expert, scale,
    shared_hidden`` (:class:`~mxtpu.gluon.contrib.nn.RoutedMoE`).
    """

    def __init__(self, vocab_size, dim, num_layers, attention, dense_hidden,
                 moe, dense_layers=1, epsilon=1e-6, **kwargs):
        super().__init__(
            vocab_size, dim, ["latent_attention"] * num_layers,
            {"latent_attention": dict(attention, epsilon=epsilon)},
            dense_hidden, moe, dense_layers=dense_layers, epsilon=epsilon,
            tie_head=False, **kwargs)
