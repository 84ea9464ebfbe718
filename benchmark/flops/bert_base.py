"""Operations the algorithm needs for one sequence, MAC = 2, from the
configuration's shapes (the arithmetic of ``bench.py``'s ``bench_bert_base``
with the MLP's width read from the configuration): for each token and layer
the QKV and output projections (4 d^2), the MLP (2 d ff) and attention over
the sequence (QK^T and AV, 2 s d), and the vocabulary head (d V). Training
is 3 x forward. The head dimension is counted as published, not as the
program pads it."""


def forward_flops(cfg):
    d, s = cfg["hidden_size"], cfg["seq_len"]
    per_token = cfg["num_hidden_layers"] * (
        4 * d * d + 2 * d * cfg["intermediate_size"] + 2 * s * d) \
        + d * cfg["vocab_size"]
    return 2 * per_token * s


def train_flops_per_sample(cfg):
    return 3 * forward_flops(cfg)
