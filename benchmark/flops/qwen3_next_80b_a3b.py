"""Operations the algorithm needs, MAC = 2, from the configuration's shapes
at the PUBLISHED widths. Per token and layer, by the layer's operator
(``benchmark/reference/qwen3_next_80b_a3b.py:kinds``): Gated DeltaNet's
six projections (q and k at the key heads, v and z at the value heads, the
two one-a-head ones), its three filters, the recurrence at its own count
(the decay of the state, ``S^T k``, the rank-one update and ``S^T q``: ``7
K V`` a token and VALUE head, whatever form a kernel has: a chunked form
does more), its norms and gates; or gated attention's projections (the
query's twice as wide: its gate) and the causal pairs at the query heads;
then the router's logits, the gated shared expert and the routed experts
at their expectation here: ``num_experts_per_tok`` choices a token, of
which the share ``num_experts_held / num_experts`` falls on an expert
held. Then the vocabulary head over the slice. Training is 3 x forward;
nothing recomputed is counted."""
from benchmark.reference.qwen3_next_80b_a3b import kinds


def _causal_pairs(cfg):
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def _recurrence_flops_per_token(cfg):
    return 7 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def _widths(cfg):
    """(the key heads' channels, the value heads' channels)."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def forward_flops(cfg):
    """One sequence forward."""
    d, t, hd = cfg["hidden_size"], cfg["seq_len"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    keys, values = _widths(cfg)
    hv = cfg["linear_num_value_heads"]
    # MACs a token: q, k, v, z, b, a, out; three filters
    gdn = d * (2 * keys + 2 * values + 2 * hv) + values * d \
        + (2 * keys + values) * cfg["linear_conv_kernel_dim"]
    # operations a token that are no MAC: the recurrence; the two L2 norms,
    # the head norm, the SiLU gate, some 20 a channel
    gdn_ops = _recurrence_flops_per_token(cfg) + 20 * values
    attn = d * 2 * h * hd + 2 * d * kv * hd + h * hd * d
    moe = d * cfg["num_experts"] + d \
        + 3 * d * cfg["shared_expert_intermediate_size"] \
        + cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"] * 3 * d * cfg["moe_intermediate_size"]
    ops = kinds(cfg)
    n, n_gdn = len(ops), ops.count("gated_delta_net")
    macs = n_gdn * gdn + (n - n_gdn) * attn + n * moe + d * cfg["vocab_size"]
    return (2 * macs + n_gdn * gdn_ops) * t \
        + 2 * (n - n_gdn) * _causal_pairs(cfg) * h * 2 * hd


def train_flops_per_sample(cfg):
    return 3 * forward_flops(cfg)


def _flash(cfg, products):
    """One call of a flash kernel: the attention layer, a step's sequences,
    ``products`` matmuls ``head_dim`` deep over the causal pairs of every
    query head."""
    return 2 * cfg["sequences_per_step"] * _causal_pairs(cfg) \
        * cfg["num_attention_heads"] * products * cfg["head_dim"]


def flash_fwd_flops(cfg):
    """q k^T and p v over the causal pairs."""
    return _flash(cfg, 2)


def flash_bwd_flops(cfg):
    """The backward kernel's five products over the causal pairs."""
    return _flash(cfg, 5)


def _tokens(cfg):
    return cfg["sequences_per_step"] * cfg["seq_len"]


def gdn_fwd_flops(cfg):
    """One forward call of the recurrence (one layer, a step's sequences):
    its own operations."""
    return _tokens(cfg) * _recurrence_flops_per_token(cfg)


def gdn_fwd_bytes(cfg):
    """What any form of the forward must move: read q, k at the key heads
    and v at the value heads (2 bytes a channel), write o (2); the
    log-decay and beta, one float32 a value head each as the kernels take
    them."""
    keys, values = _widths(cfg)
    return _tokens(cfg) * (2 * (2 * keys + 2 * values)
                           + 2 * 4 * cfg["linear_num_value_heads"])


def gdn_bwd_flops(cfg):
    """One backward call: twice the forward's (each product of the
    recurrence has two in its transpose); nothing recomputed is counted."""
    return 2 * gdn_fwd_flops(cfg)


def gdn_bwd_bytes(cfg):
    """Read q, k, v, the output's cotangent, the log-decay and beta; write
    the cotangents of q, k (at the key heads), v, the log-decay and
    beta."""
    keys, values = _widths(cfg)
    return _tokens(cfg) * (2 * (2 * keys + 2 * values)
                           + 2 * (2 * keys + values)
                           + 4 * 4 * cfg["linear_num_value_heads"])
