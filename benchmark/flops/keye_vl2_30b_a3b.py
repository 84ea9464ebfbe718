"""Operations the algorithm needs, MAC = 2, from the configuration's shapes
at the PUBLISHED widths. Per token and layer: attention's four projections
(q and o over all query heads of ``head_dim``, k and v over the key/value
heads), the indexer's three (``indexer_num_heads`` index queries of
``indexer_head_dim``, one index key, a weight a head), the router's logits,
and the routed experts at their expectation here: ``num_experts_per_tok``
choices a token, of which the share ``num_experts_held / num_experts``
falls on an expert held. The index score over the causal pairs, ``T (T +
1) / 2`` (a key ahead needs none), every index head. Attention over the
SELECTED pairs only, ``sum_t min(t + 1, topk)``, at the query heads
(sharing K and V saves bytes, not operations), whatever form the kernel
has: one that visits every causal pair reads low. Then the vocabulary head
over the slice held. Training is 3 x forward, but for the index score,
which has no backward; nothing recomputed is counted."""


def causal_pairs(cfg):
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def selected_pairs(cfg):
    """``sum_t min(t + 1, topk)``: 31,458,304 of the 134,225,920 causal
    pairs at 2,048 of 16,384."""
    t = cfg["seq_len"]
    k = min(cfg["sa_config"]["topk"], t)
    return k * t - k * (k - 1) // 2


def index_score_flops(cfg):
    """One layer's index score: ``qI . kI`` over the causal pairs."""
    sa = cfg["sa_config"]
    return 2 * cfg["sequences_per_step"] * causal_pairs(cfg) \
        * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def _differentiated_forward(cfg):
    """One sequence forward, the parts that have a backward."""
    d, t = cfg["hidden_size"], cfg["seq_len"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    total = cfg["num_experts"]
    attn = 2 * d * h * hd + 2 * d * hk * hd
    moe = d * total + cfg["num_experts_per_tok"] \
        * cfg["num_experts_held"] / total \
        * 3 * d * cfg["moe_intermediate_size"]
    per_token = cfg["num_hidden_layers"] * (attn + moe) \
        + d * cfg["vocab_size"]
    pairs = cfg["num_hidden_layers"] * selected_pairs(cfg)
    return 2 * (per_token * t + pairs * h * 2 * hd)


def _indexer_forward(cfg):
    """One sequence forward, the indexer: its projections and score."""
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    proj = cfg["hidden_size"] * (hi * di + di + hi)
    return cfg["num_hidden_layers"] * (
        2 * proj * cfg["seq_len"] + 2 * causal_pairs(cfg) * hi * di)


def forward_flops(cfg):
    return _differentiated_forward(cfg) + _indexer_forward(cfg)


def train_flops_per_sample(cfg):
    return 3 * _differentiated_forward(cfg) + _indexer_forward(cfg)


def _kernel(cfg, products):
    """One call of a sparse kernel: one layer, all of a step's
    ``sequences_per_step`` sequences, ``products`` matmuls ``head_dim``
    deep over the selected pairs of every query head."""
    return 2 * cfg["sequences_per_step"] * selected_pairs(cfg) \
        * cfg["num_attention_heads"] * products * cfg["head_dim"]


def sparse_attn_fwd_flops(cfg):
    """A forward call (``sparse_attention_fwd``): q k^T and p v."""
    return _kernel(cfg, 2)


def sparse_attn_bwd_flops(cfg):
    """A backward call (``sparse_attention_bwd``): its five products (s = k
    q^T again from the saved log-sum-exp, dv = p^T g, dp = v g^T, dk = ds^T
    q, dq = ds k)."""
    return _kernel(cfg, 5)
