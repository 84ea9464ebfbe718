"""Operations the algorithm needs, MAC = 2, from the configuration's shapes
at the PUBLISHED head widths (keys and queries ``qk_head_dim``, values
``v_head_dim``; not as the program pads them). Per token and layer: latent
attention's four projections; causal attention over the (T + 1) / 2 keys a
query sees on average; then the dense gated MLP (the first
``first_k_dense_replace`` layers) or the router, the shared experts and the
routed experts at their expectation here: ``num_experts_per_tok`` choices
a token, of which the share ``n_routed_experts_held / n_routed_experts``
falls on an expert held. Then the vocabulary head. Training is 3 x forward;
nothing recomputed is counted."""


def _causal_pairs(cfg):
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def _attention_macs_per_pair(cfg):
    return cfg["num_attention_heads"] * (cfg["qk_head_dim"]
                                         + cfg["v_head_dim"])


def forward_flops(cfg):
    d, t, h = cfg["hidden_size"], cfg["seq_len"], cfg["num_attention_heads"]
    mla = (d * h * cfg["qk_head_dim"]
           + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                        + cfg["v_head_dim"])
           + h * cfg["v_head_dim"] * d)
    dense = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    moe = (d * cfg["n_routed_experts"] + cfg["n_shared_experts"] * expert
           + cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"]
           / cfg["n_routed_experts"] * expert)
    n, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    per_token = n * mla + n_dense * dense + (n - n_dense) * moe \
        + d * cfg["vocab_size"]
    return 2 * (per_token * t
                + n * _causal_pairs(cfg) * _attention_macs_per_pair(cfg))


def train_flops_per_sample(cfg):
    return 3 * forward_flops(cfg)


def flash_fwd_flops(cfg):
    """One call of the forward kernel (one layer, one sequence): q k^T and
    p v over the causal pairs."""
    return 2 * _causal_pairs(cfg) * _attention_macs_per_pair(cfg)


def flash_bwd_flops(cfg):
    """One call of the backward kernel: its five products over the causal
    pairs (s = k q^T again from the saved log-sum-exp, dv = p^T g, dp = v
    g^T, dk = ds^T q, dq = ds k): three over the key width, two over the
    value width."""
    return 2 * _causal_pairs(cfg) * cfg["num_attention_heads"] * (
        3 * cfg["qk_head_dim"] + 2 * cfg["v_head_dim"])
