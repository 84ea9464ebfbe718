"""Operations the algorithm needs, MAC = 2, from the configuration's shapes
at the PUBLISHED head width (``hidden_size / num_attention_heads`` = 64; not
as the program pads it). Per token and layer: a conv layer's two
projections (d -> 3d, d -> d) and its taps; an attention layer's four
projections (q and o over all query heads, k and v over the key/value
heads) and causal attention over the (T + 1) / 2 keys a query sees on
average, at the QUERY heads (sharing K and V saves bytes, not operations);
then the dense gated MLP (the first ``num_dense_layers`` layers) or the
router and the routed experts at their expectation here:
``num_experts_per_tok`` choices a token, of which the share
``num_experts_held / num_experts`` falls on an expert held. Then the
vocabulary head. Training is 3 x forward; nothing recomputed is counted."""


def _kinds(cfg):
    first = cfg["first_layer_held"]
    return cfg["layer_types"][first:first + cfg["num_hidden_layers"]]


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _causal_pairs(cfg):
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def forward_flops(cfg):
    """One sequence forward."""
    d, t = cfg["hidden_size"], cfg["seq_len"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        _head_dim(cfg)
    conv = d * 3 * d + d * d + d * cfg["conv_L_cache"]
    attn = 2 * d * h * hd + 2 * d * hk * hd
    dense = 3 * d * cfg["intermediate_size"]
    moe = d * cfg["num_experts"] + cfg["num_experts_per_tok"] \
        * cfg["num_experts_held"] / cfg["num_experts"] \
        * 3 * d * cfg["moe_intermediate_size"]
    kinds = _kinds(cfg)
    n_attn = kinds.count("full_attention")
    n, n_dense = len(kinds), cfg["num_dense_layers"]
    per_token = (n - n_attn) * conv + n_attn * attn + n_dense * dense \
        + (n - n_dense) * moe + d * cfg["vocab_size"]
    return 2 * (per_token * t + n_attn * _causal_pairs(cfg) * h * 2 * hd)


def train_flops_per_sample(cfg):
    return 3 * forward_flops(cfg)


def flash_fwd_flops(cfg):
    """One call of the forward kernel: one attention layer, and ALL of a
    step's ``sequences_per_step`` sequences, which the kernel takes in one
    call (its grid's first axis is batch x query heads): q k^T and p v
    over the causal pairs of every query head."""
    return 2 * cfg["sequences_per_step"] * _causal_pairs(cfg) \
        * cfg["num_attention_heads"] * 2 * _head_dim(cfg)


def flash_bwd_flops(cfg):
    """One call of the backward kernel (one layer, a step's sequences, as
    above): its five products over the causal pairs (s = k q^T again from
    the saved log-sum-exp, dv = p^T g, dp = v g^T, dk = ds^T q, dq = ds
    k), each ``head_dim`` deep, at the query heads."""
    return 2 * cfg["sequences_per_step"] * _causal_pairs(cfg) \
        * cfg["num_attention_heads"] * 5 * _head_dim(cfg)
