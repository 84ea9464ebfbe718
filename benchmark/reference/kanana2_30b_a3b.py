"""kanana-2-30b-a3b-instruct-2601 (``model_type: deepseek_v3``) as its
``config.json`` and the DeepSeek-V2/V3 papers (arXiv:2405.04434 §2.1,
arXiv:2412.19437 §2.1) describe the layer, cut as the configuration file
says. Plain float32 ``jax.numpy`` at ``highest`` matmul precision; imports
nothing of the program; leaves in the order of the program's
``collect_params()``.

x is [B, T, d]; every norm is an RMSNorm (eps ``rms_norm_eps``); no bias.

* layer: ``h = x + MLA(norm1(x))``; ``y = h + FFN(norm2(h))``; FFN is a
  SwiGLU of width ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers and the expert layer after them; then a
  final norm and the untied head; loss: mean next-token cross-entropy.
* MLA (no query rank): ``q = x Wq`` -> [T, H, nope + rope]; ``x Wkva`` ->
  the latent ``c`` (``kv_lora_rank``) and ONE rotary key ``k_r`` (rope)
  shared by all heads; ``norm(c) Wkvb`` -> [T, H, nope + v] = ``k_nope |
  v``. Rotary (``rope_theta``, pairs (2i, 2i+1): ``rope_interleave``) on
  q's last ``rope`` entries and on ``k_r``; ``k = [k_nope | k_r]``; causal
  softmax of ``q k^T / sqrt(nope + rope)`` times ``v``; then ``Wo``.
  The turned pairs are written out as [all first entries | all second
  entries], a fixed permutation of the rotary entries applied to q and k
  alike, which changes no score (the published code does the same).
* expert layer: ``s = sigmoid(x Wg^T)``, the product in float32 whatever
  the precision; the ``num_experts_per_tok`` experts are the top of ``s +
  b`` (``b`` takes no gradient; ``n_group = topk_group = 1``: no group
  limit); weights are ``s`` at the chosen experts over their sum + 1e-20,
  times ``routed_scaling_factor``; output: the weighted sum of the chosen
  experts plus the shared expert (``n_shared_experts`` experts side by
  side = one SwiGLU of their summed width). No capacity. Only experts
  ``first_expert_held`` .. + ``n_routed_experts_held`` exist here; a choice
  of another adds nothing. The experts are a loop (a ``lax.scan``, so that
  the step compiles one expert and not sixteen) over those held, each
  applied to EVERY token under its mask: nothing of the program's gather.

At the cell's size it is computed in blocks so that it fits: a layer at a
time under ``jax.checkpoint``, attention one head at a time (the float32
scores of all 32 heads of one layer would be 8.6 GB), weights cast to
float32 where they are used.
"""
import math

import jax
import jax.numpy as jnp

from . import common

_ATTN = 6       # leaves of latent attention with its norm, a layer
_DENSE = 3
_MOE = 8


def param_specs(cfg):
    dt, d, v = cfg["dtype"], cfg["hidden_size"], cfg["vocab_size"]
    std, h = cfg["initializer_range"], cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    held, ew = cfg["n_routed_experts_held"], cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * ew
    b = cfg["score_bias_range"]

    def w(name, *shape):
        return (name, shape, dt, True, "normal", std)

    def norm(name, n):
        return (name + "_gamma", (n,), dt, True, "uniform", (0.9, 1.1))

    def mlp(p, width):
        return [w(p + "gate_weight", width, d), w(p + "up_weight", width, d),
                w(p + "down_weight", d, width)]

    specs = [w("wte_weight", v, d)]
    for i in range(cfg["num_hidden_layers"]):
        p = "h%d_" % i
        specs += [norm(p + "norm1", d),
                  w(p + "attn_q_weight", h * cfg["qk_head_dim"], d),
                  w(p + "attn_kva_weight", rank + rope, d),
                  norm(p + "attn_kvnorm", rank),
                  w(p + "attn_kvb_weight",
                    h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]), rank),
                  w(p + "attn_proj_weight", d, h * cfg["v_head_dim"]),
                  norm(p + "norm2", d)]
        if i < cfg["first_k_dense_replace"]:
            specs += mlp(p + "mlp_", cfg["intermediate_size"])
        else:
            specs += [w(p + "moe_router_weight", cfg["n_routed_experts"], d),
                      (p + "moe_score_bias", (cfg["n_routed_experts"],), dt,
                       False, "uniform", (-b, b)),
                      w(p + "moe_w_gate", held, d, ew),
                      w(p + "moe_w_up", held, d, ew),
                      w(p + "moe_w_down", held, ew, d)] \
                + mlp(p + "moe_shared_", shared)
    return specs + [norm("normf", d), w("head_weight", v, d)]


def sample_inputs(cfg, key, n):
    """``n`` seeded sequences of the timed length, ids uniform over the
    vocabulary's slice; the label of a position is the next token."""
    ids = jax.random.randint(key, (n, cfg["seq_len"] + 1), 0,
                             cfg["vocab_size"], jnp.int32)
    return ids[:, :-1], ids[:, 1:].astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


def _rotary(x, theta):
    """[B, T, ..., R] -> the pairs (2i, 2i+1) turned by pos * theta^(-2i/R),
    written as [first entries | second entries]."""
    r = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32)
                                   / r)[None, :]
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (r // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def route(cfg, x, wg, bias):
    """-> (chosen experts [.., k], their weights [.., k])."""
    s = jax.nn.sigmoid(jnp.einsum(
        "...d,ed->...e", x.astype(jnp.float32), wg.astype(jnp.float32),
        precision=common.HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(
        bias.astype(jnp.float32)), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]


def _ops(cfg, precision, storage=None):
    """The layer's parts as functions: ``dense``, ``mla``, ``swiglu``,
    ``experts``. ``storage`` (a dtype) rounds every product's operands and
    result to it: the configuration's own arithmetic, for counting the
    selections it moves."""
    product = common.product(precision)

    def einsum(spec):
        op = product(lambda a, b: jnp.einsum(spec, a, b,
                                             precision=common.HIGHEST))
        if storage is None:
            return op
        return lambda a, b: op(a.astype(storage), b.astype(storage)).astype(
            storage).astype(jnp.float32)

    dense = einsum("...i,oi->...o")
    h, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    first, held = cfg["first_expert_held"], cfg["n_routed_experts_held"]

    def mla(x, g1, wq, wkva, gc, wkvb, wo):
        b, t, _ = x.shape
        xn = _rms(x, g1, eps)
        q = dense(xn, wq).reshape(b, t, h, nope + rope)
        q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)],
                            -1)
        ckr = dense(xn, wkva)
        k_r = _rotary(ckr[..., rank:], theta)                # [B, T, rope]
        kv = dense(_rms(ckr[..., :rank], gc, eps), wkvb).reshape(
            b, t, h, nope + vd)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

        def head(qh, kvh):                 # one head: [B, T, .]
            kh = jnp.concatenate([kvh[..., :nope], k_r], -1)
            s = einsum("bqd,bkd->bqk")(qh, kh) / math.sqrt(nope + rope)
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            return einsum("bqk,bkd->bqd")(p, kvh[..., nope:])

        out = jax.lax.map(lambda a: jax.checkpoint(head)(*a),
                          (jnp.moveaxis(q, 2, 0), jnp.moveaxis(kv, 2, 0)))
        return dense(jnp.moveaxis(out, 0, 2).reshape(b, t, h * vd), wo)

    def swiglu(x, wg, wu, wd):
        return dense(jax.nn.silu(dense(x, wg)) * dense(x, wu), wd)

    def experts(x, wg, bias, eg, eu, ed, sg, su, sd):
        """-> (shared + the held experts' part, the chosen experts)."""
        idx, w = route(cfg, x, wg, bias)
        y = swiglu(x, sg, su, sd)
        mm = einsum("...i,io->...o")

        def one(y, e):                     # expert e on EVERY token
            ge, ue, de, at = e
            w_e = jnp.sum(jnp.where(idx == at, w, 0.0), -1)
            return y + w_e[..., None] * mm(
                jax.nn.silu(mm(x, ge)) * mm(x, ue), de), None

        y, _ = jax.lax.scan(jax.checkpoint(one), y,
                            (eg, eu, ed, first + jnp.arange(held)))
        return y, idx

    return dense, mla, swiglu, experts


def expert_layer(cfg, x, leaves, precision="float32"):
    """The expert layer alone on (normalised) tokens ``x``; ``leaves``: its
    eight, in ``param_specs``' order."""
    return _ops(cfg, precision)[3](x.astype(jnp.float32), *leaves)[0]


def forward(cfg, params, tokens, precision="float32", storage=None):
    """-> (logits, the chosen experts [expert layer, B, T, k])."""
    dense, mla, swiglu, experts = _ops(cfg, precision, storage)
    eps = cfg["rms_norm_eps"]

    def dense_block(x, *p):
        x = x + mla(x, *p[:_ATTN])
        return x + swiglu(_rms(x, p[_ATTN], eps), *p[_ATTN + 1:]), None

    def moe_block(x, *p):
        x = x + mla(x, *p[:_ATTN])
        y, idx = experts(_rms(x, p[_ATTN], eps), *p[_ATTN + 1:])
        return x + y, idx

    x = params[0].astype(jnp.float32)[tokens]
    n_dense, at = cfg["first_k_dense_replace"], 1
    for _ in range(n_dense):
        n = _ATTN + 1 + _DENSE
        x, _ = jax.checkpoint(dense_block)(x, *params[at:at + n])
        at += n
    # the expert layers are alike: one scan over their stacked leaves, so
    # that the step compiles one of them
    n, n_moe = _ATTN + 1 + _MOE, cfg["num_hidden_layers"] - n_dense
    stacked = [jnp.stack([params[at + l * n + k] for l in range(n_moe)])
               for k in range(n)]
    x, chosen = jax.lax.scan(
        jax.checkpoint(lambda x, leaves: moe_block(x, *leaves)), x, stacked)
    at += n * n_moe
    return dense(_rms(x, params[at], eps), params[at + 1]), chosen


def forward_loss(cfg):
    def fn(params, x, y, precision):
        logits, _ = forward(cfg, params, x, precision)
        logp = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(
            logp, y.astype(jnp.int32)[..., None], -1)[..., 0]
        return -jnp.mean(picked), {}
    return fn


def selection_flip_share(cfg, params, tokens):
    """Share of the (token, slot) choices of all expert layers that a
    forward in the configuration's dtype (operands and results of every
    product rounded to it) makes otherwise than the float32 forward, on the
    same weights and tokens: a choice counts as moved when the expert
    chosen in float32 is not among that token's choices in the dtype."""
    _, want = forward(cfg, params, tokens)
    _, got = forward(cfg, params, tokens, storage=jnp.dtype(cfg["dtype"]))
    return jnp.mean(~jnp.any(want[..., :, None] == got[..., None, :], -1))
