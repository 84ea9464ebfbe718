"""Operations the algorithm needs, MAC = 2, from the configuration's shapes
at the PUBLISHED widths. Per token and layer, by the layer's operator
(``benchmark/reference/ling3_flash.py:kinds``): Kimi Delta Attention's six
projections and its ``beta``, its three filters, the recurrence at its own
count (the decay of the state, ``k^T S``, the rank-one update and ``S^T
q``: ``7 K V`` a token and head, whatever form a kernel has: a chunked form
does more), its norms and gates; or latent attention's four projections,
its head gate and the causal pairs; then the dense gated MLP (the first
``first_k_dense_replace`` layers) or the router, the shared expert and the
routed experts at their expectation here: ``num_experts_per_tok`` choices a
token, of which the share ``num_experts_held / num_experts`` falls on an
expert held. Then the vocabulary head over the slice. Training is 3 x
forward: the model's operations. The step recomputes every block's forward
in its backward (``recompute``), so the device does about 4 x forward;
nothing recomputed is counted."""
from benchmark.reference.ling3_flash import kinds


def _causal_pairs(cfg):
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def _attention_macs_per_pair(cfg):
    return cfg["num_attention_heads"] * (cfg["qk_head_dim"]
                                         + cfg["v_head_dim"])


def _recurrence_flops_per_token(cfg):
    return 7 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2


def forward_flops(cfg):
    d, t, h = cfg["hidden_size"], cfg["seq_len"], cfg["num_attention_heads"]
    width = h * cfg["head_dim"]
    # MACs a token: six projections, beta, three filters
    kda = 6 * d * width + d * h + 3 * width * cfg["short_conv_kernel_size"]
    # operations a token that are no MAC: the recurrence; the two L2 norms,
    # the head norm, the decay's gate and the output gate, some 20 a channel
    kda_ops = _recurrence_flops_per_token(cfg) + 20 * width
    mla = (d * h * cfg["qk_head_dim"]
           + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                        + cfg["v_head_dim"])
           + d * h + h * cfg["v_head_dim"] * d)
    dense = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    moe = (d * cfg["num_experts"] + cfg["num_shared_experts"] * 3 * d
           * cfg["moe_shared_expert_intermediate_size"]
           + cfg["num_experts_per_tok"] * cfg["num_experts_held"]
           / cfg["num_experts"] * expert)
    ops = kinds(cfg)
    n, n_dense = len(ops), cfg["first_k_dense_replace"]
    n_kda = ops.count("kda")
    macs = (n_kda * kda + (n - n_kda) * mla + n_dense * dense
            + (n - n_dense) * moe + d * cfg["vocab_size"])
    return (2 * macs + n_kda * kda_ops) * t \
        + 2 * (n - n_kda) * _causal_pairs(cfg) * _attention_macs_per_pair(cfg)


def train_flops_per_sample(cfg):
    return 3 * forward_flops(cfg)


def flash_fwd_flops(cfg):
    """One call of the forward kernel (the latent layer, one sequence): q
    k^T and p v over the causal pairs."""
    return 2 * _causal_pairs(cfg) * _attention_macs_per_pair(cfg)


def flash_bwd_flops(cfg):
    """One call of the backward kernel: its five products over the causal
    pairs, three over the key width, two over the value width."""
    return 2 * _causal_pairs(cfg) * cfg["num_attention_heads"] * (
        3 * cfg["qk_head_dim"] + 2 * cfg["v_head_dim"])


def _channels(cfg):
    return cfg["sequences_per_step"] * cfg["seq_len"] \
        * cfg["num_attention_heads"] * cfg["head_dim"]


def kda_fwd_flops(cfg):
    """One forward call of the operator (one layer, a step's sequences):
    the recurrence's own operations."""
    return cfg["sequences_per_step"] * cfg["seq_len"] \
        * _recurrence_flops_per_token(cfg)


def kda_fwd_bytes(cfg):
    """What any form of the forward must move: read q, k, v (2 bytes a
    channel each) and the log-decay (4), write o (2); beta (2 a head)."""
    return _channels(cfg) * (2 + 2 + 2 + 4 + 2) \
        + 2 * _channels(cfg) // cfg["head_dim"]


def kda_bwd_flops(cfg):
    """One backward call: twice the forward's (each product of the
    recurrence has two in its transpose); nothing recomputed is counted."""
    return 2 * kda_fwd_flops(cfg)


def kda_bwd_bytes(cfg):
    """Read q, k, v, the log-decay and the output's cotangent; write the
    cotangents of q, k, v (2 bytes a channel each) and of the log-decay
    (4); beta and its cotangent."""
    return _channels(cfg) * ((2 + 2 + 2 + 4 + 2) + (2 + 2 + 2 + 4)) \
        + 4 * _channels(cfg) // cfg["head_dim"]
