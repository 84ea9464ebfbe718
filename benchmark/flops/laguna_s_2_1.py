"""Operations the algorithm needs, MAC = 2, from the configuration's shapes
at the PUBLISHED widths. Per token and layer: attention's projections (q,
the head gate and o over the LAYER'S OWN query heads of ``head_dim``:
``num_attention_heads_per_layer``; k and v over the key/value heads), then
the dense layer's SwiGLU, or the router's logits, the shared expert and
the routed experts at their expectation here: ``num_experts_per_tok``
choices a token, of which the share ``num_experts_held / num_experts``
falls on an expert held. Attention over the pairs (query, visible key) of
each layer's own mask, at the QUERY heads (sharing K and V saves bytes,
not operations): a full layer's ``T (T + 1) / 2`` causal pairs, a windowed
layer's ``W T - W (W - 1) / 2`` (query i sees ``min(i + 1, W)`` keys), so
that a kernel which visits pairs it need not reads low. Then the
vocabulary head over the slice held. Training is 3 x forward; nothing
recomputed is counted."""


def _layers(cfg):
    """Each kept layer as ``(windowed, query heads, dense)``."""
    first = cfg["first_layer_held"]
    return [(cfg["layer_types"][l] == "sliding_attention",
             cfg["num_attention_heads_per_layer"][l],
             l in cfg["mlp_only_layers"])
            for l in range(first, first + cfg["num_hidden_layers"])]


def _heads(cfg, windowed):
    found = {h for w, h, _ in _layers(cfg) if w == windowed}
    if len(found) != 1:
        raise ValueError("layers of one kind differ in their heads: %r"
                         % sorted(found))
    return found.pop()


def causal_pairs(cfg):
    t = cfg["seq_len"]
    return t * (t + 1) // 2


def window_pairs(cfg):
    t, w = cfg["seq_len"], min(cfg["sliding_window"], cfg["seq_len"])
    return w * t - w * (w - 1) // 2


def forward_flops(cfg):
    """One sequence forward."""
    d, t, hd = cfg["hidden_size"], cfg["seq_len"], cfg["head_dim"]
    hk = cfg["num_key_value_heads"]
    moe = d * cfg["num_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] \
        + cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"] * 3 * d * cfg["moe_intermediate_size"]
    per_token, pairs = d * cfg["vocab_size"], 0
    for windowed, h, dense in _layers(cfg):
        per_token += 2 * d * h * hd + 2 * d * hk * hd + d * h
        per_token += 3 * d * cfg["intermediate_size"] if dense else moe
        pairs += (window_pairs(cfg) if windowed else causal_pairs(cfg)) \
            * h * 2 * hd
    return 2 * (per_token * t + pairs)


def train_flops_per_sample(cfg):
    return 3 * forward_flops(cfg)


def _kernel(cfg, windowed, products):
    """One call of a flash kernel: one layer, all of a step's
    ``sequences_per_step`` sequences (the grid's first axis is batch x
    query heads), ``products`` matmuls ``head_dim`` deep over the mask's
    (query, key) pairs of every query head of that kind of layer."""
    pairs = window_pairs(cfg) if windowed else causal_pairs(cfg)
    return 2 * cfg["sequences_per_step"] * pairs * _heads(cfg, windowed) \
        * products * cfg["head_dim"]


def flash_fwd_flops(cfg):
    """A FULL layer's forward call (``flash_attention_fwd`` in this cell,
    48 query heads): q k^T and p v over the causal pairs."""
    return _kernel(cfg, False, 2)


def flash_bwd_flops(cfg):
    """A full layer's backward call (``flash_attention_bwd``): its five
    products (s = k q^T again from the saved log-sum-exp, dv = p^T g, dp =
    v g^T, dk = ds^T q, dq = ds k) over the causal pairs."""
    return _kernel(cfg, False, 5)


def flash_window_fwd_flops(cfg):
    """A windowed layer's forward call (``flash_window_fwd``, 72 query
    heads): the two products over the WINDOW'S pairs."""
    return _kernel(cfg, True, 2)


def flash_window_bwd_flops(cfg):
    """A windowed layer's backward call (``flash_window_bwd``): the five
    products over the window's pairs."""
    return _kernel(cfg, True, 5)
