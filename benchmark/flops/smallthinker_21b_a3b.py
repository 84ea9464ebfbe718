"""Operations the algorithm needs, MAC = 2, from the configuration's shapes
at the PUBLISHED widths. Per token and layer: attention's four projections
(q and o over all query heads of ``head_dim``, k and v over the key/value
heads), the router's logits, and the routed experts at their expectation
here: ``moe_num_active_primary_experts`` choices a token, of which the
share ``moe_num_primary_experts_held / moe_num_primary_experts`` falls on
an expert held. Attention over the pairs (query, visible key) of each
layer's own mask, at the QUERY heads (sharing K and V saves bytes, not
operations): a global layer's ``T (T + 1) / 2`` causal pairs, a windowed
layer's ``W T - W (W - 1) / 2`` (query i sees ``min(i + 1, W)`` keys), so
that a kernel which visits pairs it need not reads low. Then the
vocabulary head over the slice held. Training is 3 x forward; nothing
recomputed is counted."""


def _layout(cfg):
    first = cfg["first_layer_held"]
    return cfg["sliding_window_layout"][first:first
                                        + cfg["num_hidden_layers"]]


def causal_pairs(cfg):
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def window_pairs(cfg):
    t, w = cfg["seq_len"], min(cfg["sliding_window_size"], cfg["seq_len"])
    return w * t - w * (w - 1) // 2


def forward_flops(cfg):
    """One sequence forward."""
    d, t = cfg["hidden_size"], cfg["seq_len"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    total = cfg["moe_num_primary_experts"]
    attn = 2 * d * h * hd + 2 * d * hk * hd
    moe = d * total + cfg["moe_num_active_primary_experts"] \
        * cfg["moe_num_primary_experts_held"] / total \
        * 3 * d * cfg["moe_ffn_hidden_size"]
    layout = _layout(cfg)
    pairs = sum(window_pairs(cfg) if windowed else causal_pairs(cfg)
                for windowed in layout)
    per_token = len(layout) * (attn + moe) + d * cfg["vocab_size"]
    return 2 * (per_token * t + pairs * h * 2 * hd)


def train_flops_per_sample(cfg):
    return 3 * forward_flops(cfg)


def _kernel(cfg, pairs, products):
    """One call of a flash kernel: one layer, all of a step's
    ``sequences_per_step`` sequences (the grid's first axis is batch x
    query heads), ``products`` matmuls ``head_dim`` deep over ``pairs``
    (query, key) pairs of every query head."""
    return 2 * cfg["sequences_per_step"] * pairs \
        * cfg["num_attention_heads"] * products * cfg["head_dim"]


def flash_fwd_flops(cfg):
    """The GLOBAL layer's forward call (``flash_attention_fwd`` in this
    cell): q k^T and p v over the causal pairs."""
    return _kernel(cfg, causal_pairs(cfg), 2)


def flash_bwd_flops(cfg):
    """The global layer's backward call (``flash_attention_bwd``): its five
    products (s = k q^T again from the saved log-sum-exp, dv = p^T g, dp =
    v g^T, dk = ds^T q, dq = ds k) over the causal pairs."""
    return _kernel(cfg, causal_pairs(cfg), 5)


def flash_window_fwd_flops(cfg):
    """A windowed layer's forward call (``flash_window_fwd``): the two
    products over the WINDOW'S pairs."""
    return _kernel(cfg, window_pairs(cfg), 2)


def flash_window_bwd_flops(cfg):
    """A windowed layer's backward call (``flash_window_bwd``): the five
    products over the window's pairs."""
    return _kernel(cfg, window_pairs(cfg), 5)
