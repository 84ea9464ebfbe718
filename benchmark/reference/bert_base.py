"""The encoder the program builds for its BERT-base cell,
``TransformerLM(causal=False)``: token + position embeddings, pre-norm
blocks (LayerNorm -> fused QKV -> softmax attention -> projection; LayerNorm
-> ReLU MLP), a final LayerNorm and an untied vocabulary head, with the
cross-entropy over every position. Sizes are BERT-Base's (google-research/
bert ``bert_config.json``); how the block departs from BERT's own is listed
in the configuration file. Plain float32 ``jax.numpy`` at ``highest``
matmul precision; leaves in the order of the program's
``collect_params()``.
"""
import math

import jax
import jax.numpy as jnp

from . import common

EPS = 1e-5


def param_specs(cfg):
    dt, d, v = cfg["dtype"], cfg["hidden_size"], cfg["vocab_size"]
    ff = cfg["intermediate_size"]
    std = cfg["initializer_range"]

    def ln(name):
        return [(name + "_gamma", (d,), dt, True, "uniform", (0.9, 1.1)),
                (name + "_beta", (d,), dt, True, "normal", std)]

    def dense(name, out, inp, bias):
        rows = [(name + "_weight", (out, inp), dt, True, "normal", std)]
        if bias:
            rows.append((name + "_bias", (out,), dt, True, "normal", std))
        return rows

    specs = [("wte_weight", (v, d), dt, True, "normal", std),
             ("wpe_weight", (cfg["max_position_embeddings"], d), dt, True,
              "normal", std)]
    for i in range(cfg["num_hidden_layers"]):
        p = "h%d" % i
        specs += ln(p + "_ln1") + dense(p + "_qkv", 3 * d, d, False) \
            + dense(p + "_proj", d, d, False) + ln(p + "_ln2") \
            + dense(p + "_mlp1", ff, d, True) + dense(p + "_mlp2", d, ff, True)
    return specs + ln("ln_f") + dense("head", v, d, False)


def sample_inputs(cfg, key, n):
    """``n`` seeded sequences of the timed length, a label on every
    position (labels as the program's loss takes them)."""
    kx, ky = jax.random.split(key)
    shape = (n, cfg["seq_len"])
    x = jax.random.randint(kx, shape, 0, cfg["vocab_size"], jnp.int32)
    y = jax.random.randint(ky, shape, 0, cfg["vocab_size"]).astype(
        jnp.float32)
    return x, y


def forward(cfg, params, tokens, precision="float32"):
    product = common.product(precision)

    def einsum(spec):
        return product(lambda a, b: jnp.einsum(spec, a, b,
                                               precision=common.HIGHEST))
    f32 = [p.astype(jnp.float32) for p in params]
    heads = cfg["num_attention_heads"]
    b, t = tokens.shape
    d = cfg["hidden_size"]

    def ln(x, g, beta):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + EPS) * g + beta

    def dense(x, w, bias=None):
        y = einsum("...i,oi->...o")(x, w)
        return y if bias is None else y + bias

    def block(x, p):
        g1, b1, wqkv, wproj, g2, b2, w1, c1, w2, c2 = p
        qkv = dense(ln(x, g1, b1), wqkv).reshape(b, t, 3, heads, d // heads)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        s = einsum("bhqd,bhkd->bhqk")(q, k) / math.sqrt(d // heads)
        a = einsum("bhqk,bhkd->bhqd")(jax.nn.softmax(s, -1), v)
        x = x + dense(a.transpose(0, 2, 1, 3).reshape(b, t, d), wproj)
        h = jax.nn.relu(dense(ln(x, g2, b2), w1, c1))
        return x + dense(h, w2, c2)

    x = f32[0][tokens] + f32[1][jnp.arange(t)]
    at = 2
    for _ in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(block)(x, f32[at:at + 10])
        at += 10
    return dense(ln(x, f32[at], f32[at + 1]), f32[at + 2])


def forward_loss(cfg):
    def fn(params, x, y, precision):
        logits = forward(cfg, params, x, precision)
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(
            logp, y.astype(jnp.int32)[..., None], -1)[..., 0]
        return -jnp.mean(picked), {}
    return fn
