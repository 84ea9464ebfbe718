"""LFM2-8B-A1B (LiquidAI, ``model_type: lfm2_moe``) as its ``config.json``
and the published block (transformers' ``lfm2_moe``) describe the layers,
cut as the configuration file says. Plain float32 ``jax.numpy`` at
``highest`` matmul precision; imports nothing of the program; leaves in the
order of the program's ``collect_params()``.

u is [B, T, d]; every norm is an RMSNorm (eps ``norm_eps``, a plain scale);
no bias anywhere (``conv_bias: false``).

* layer ``i``: ``h = u + op_i(norm1(u))``; ``y = h + ffn_i(norm2(h))``;
  ``op_i`` is ``layer_types[i]``; ``ffn_i`` is a SwiGLU of width
  ``intermediate_size`` in the first ``num_dense_layers`` layers and the
  expert layer after them; then a final norm and the head TIED to the
  embedding (one leaf, its gradient the sum of both uses); loss: mean
  next-token cross-entropy.
* ``conv``: ``(B, C, x) = split3(n W_in^T)`` (``W_in``: d -> 3d); ``z = B
  * x``; ``c[t] = w[:, 0] z[t-2] + w[:, 1] z[t-1] + w[:, 2] z[t]`` for each
  channel, ``z`` zero before the sequence's start (``conv_L_cache`` = 3
  taps, depthwise, causal: three shifted products, written out); ``op =
  (C * c) W_out^T``. No activation.
* ``full_attention``: ``q = n Wq^T`` as ``num_attention_heads`` heads of
  ``head_dim`` = d / heads, ``k``, ``v`` as ``num_key_value_heads`` heads;
  q and k each through an RMSNorm over their ``head_dim`` entries (one
  learned scale for q, one for k); rotary over the whole head, halves
  turned (entry i with i + head_dim/2; not interleaved), ``rope_theta``;
  K and V REPEATED to the query heads the plain way (query head j reads
  key/value head j // group); causal softmax of ``q k^T / sqrt(head_dim)``
  times ``v``; then ``Wo``.
* expert layer: ``s = sigmoid(m Wr^T)``, the product in float32 whatever
  the precision; the ``num_experts_per_tok`` experts are the top of ``s +
  expert_bias`` (the bias takes no gradient); weights are ``s`` at the
  chosen experts over their sum + ``router_epsilon``, times
  ``routed_scaling_factor``; no shared expert, no capacity. Only experts
  ``first_expert_held`` .. + ``num_experts_held`` exist here; a choice of
  another adds nothing. The experts are a ``lax.scan`` over those held,
  each applied to EVERY token under its mask: nothing of the program's
  gather.

At the cell's size (two sequences of 8,192 beside 508 M float32 parameters
with their gradients and Adam state) it is computed in blocks so that it
fits: every layer under ``jax.checkpoint`` (only a layer's input is kept
for the backward; consecutive layers of one kind are one ``lax.scan``
over their stacked leaves, so the step compiles one of them); attention
one query head at a time (``lax.map`` over
the repeated heads, each rematerialised: the float32 scores of all 32
heads of two sequences would be 17 GB); the experts one at a time (a
rematerialised scan, so one expert's [B, T, 1792] activations exist at a
time); the loss in row blocks of the logits (``lax.map`` over blocks of
2,048 positions, rematerialised: the float32 [16384, 16384] logits with
their softmax would be 2 GB twice over); weights cast to float32 where
they are used.
"""
import math

import jax
import jax.numpy as jnp

from . import common

_CONV = 3       # leaves of the conv operator: taps, in, out
_ATTN = 6       # of attention: q, k, v, q norm, k norm, out
_DENSE = 3
_MOE = 5
_LOSS_ROWS = 2048


def _kinds(cfg):
    first = cfg["first_layer_held"]
    return cfg["layer_types"][first:first + cfg["num_hidden_layers"]]


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_specs(cfg):
    dt, d, v = cfg["dtype"], cfg["hidden_size"], cfg["vocab_size"]
    std, h = cfg["initializer_range"], cfg["num_attention_heads"]
    hk, hd = cfg["num_key_value_heads"], _head_dim(cfg)
    held, ew = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    b = cfg["expert_bias_range"]

    def w(name, *shape):
        return (name, shape, dt, True, "normal", std)

    def norm(name, n):
        return (name + "_gamma", (n,), dt, True, "uniform", (0.9, 1.1))

    specs = [w("wte_weight", v, d)]            # the head reads it too
    for i, kind in enumerate(_kinds(cfg)):
        p = "h%d_" % i
        specs.append(norm(p + "norm1", d))
        if kind == "conv":
            specs += [w(p + "conv_weight", d, cfg["conv_L_cache"]),
                      w(p + "conv_in_weight", 3 * d, d),
                      w(p + "conv_out_weight", d, d)]
        elif kind == "full_attention":
            specs += [w(p + "attn_q_weight", h * hd, d),
                      w(p + "attn_k_weight", hk * hd, d),
                      w(p + "attn_v_weight", hk * hd, d),
                      norm(p + "attn_qnorm", hd), norm(p + "attn_knorm", hd),
                      w(p + "attn_proj_weight", d, h * hd)]
        else:
            raise ValueError("unknown layer type %r" % (kind,))
        specs.append(norm(p + "norm2", d))
        if i < cfg["num_dense_layers"]:
            width = cfg["intermediate_size"]
            specs += [w(p + "mlp_gate_weight", width, d),
                      w(p + "mlp_up_weight", width, d),
                      w(p + "mlp_down_weight", d, width)]
        else:
            specs += [w(p + "moe_router_weight", cfg["num_experts"], d),
                      (p + "moe_score_bias", (cfg["num_experts"],), dt,
                       False, "uniform", (-b, b)),
                      w(p + "moe_w_gate", held, d, ew),
                      w(p + "moe_w_up", held, d, ew),
                      w(p + "moe_w_down", held, ew, d)]
    return specs + [norm("normf", d)]


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
    """[B, T, H, D] -> entry i paired with entry i + D/2, the pair turned
    by pos * theta^(-2i/D) (``rotate_half``: not interleaved)."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32)
                                   / d)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def route(cfg, x, wr, bias):
    """-> (chosen experts [.., k], their weights [.., k])."""
    s = jax.nn.sigmoid(jnp.einsum(
        "...d,ed->...e", x.astype(jnp.float32), wr.astype(jnp.float32),
        precision=common.HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(
        bias.astype(jnp.float32)), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    return idx, w / (jnp.sum(w, -1, keepdims=True) + cfg["router_epsilon"]) \
        * cfg["routed_scaling_factor"]


def _ops(cfg, precision, storage=None):
    """The layer's parts as functions: ``dense``, ``conv``, ``attention``,
    ``swiglu``, ``experts``. ``storage`` (a dtype) rounds every product's
    operands and result to it: the configuration's own arithmetic, for
    counting the selections it moves."""
    product = common.product(precision)

    def einsum(spec):
        op = product(lambda a, b: jnp.einsum(spec, a, b,
                                             precision=common.HIGHEST))
        if storage is None:
            # cast outside the product: the tied leaf's two uses then both
            # hand back a cotangent in the leaf's own dtype
            return lambda a, b: op(a.astype(jnp.float32),
                                   b.astype(jnp.float32))
        return lambda a, b: op(a.astype(storage), b.astype(storage)).astype(
            storage).astype(jnp.float32)

    dense = einsum("...i,oi->...o")
    d, eps = cfg["hidden_size"], cfg["norm_eps"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        _head_dim(cfg)
    theta, taps = float(cfg["rope_theta"]), cfg["conv_L_cache"]
    first, held = cfg["first_expert_held"], cfg["num_experts_held"]

    def conv(x, g1, w, w_in, w_out):
        bcx = dense(_rms(x, g1, eps), w_in)
        gate_in, gate_out, xs = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        z = gate_in * xs
        t = z.shape[1]
        w = w.astype(jnp.float32)
        c = 0.0
        for j in range(taps):          # tap j reads z[t - (taps - 1) + j]
            back = taps - 1 - j
            c = c + w[:, j] * jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
        return dense(gate_out * c, w_out)

    def attention(x, g1, wq, wk, wv, gq, gk, wo):
        b, t, _ = x.shape
        xn = _rms(x, g1, eps)
        q = _rotary(_rms(dense(xn, wq).reshape(b, t, h, hd), gq, eps), theta)
        k = _rotary(_rms(dense(xn, wk).reshape(b, t, hk, hd), gk, eps), theta)
        v = dense(xn, wv).reshape(b, t, hk, hd)
        # K and V at the query heads, the plain way
        k, v = jnp.repeat(k, h // hk, axis=2), jnp.repeat(v, h // hk, axis=2)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

        def head(qh, kh, vh):              # one head: [B, T, hd]
            s = einsum("bqd,bkd->bqk")(qh, kh) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            return einsum("bqk,bkd->bqd")(p, vh)

        out = jax.lax.map(lambda a: jax.checkpoint(head)(*a),
                          tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
        return dense(jnp.moveaxis(out, 0, 2).reshape(b, t, h * hd), wo)

    def swiglu(x, wg, wu, wd):
        return dense(jax.nn.silu(dense(x, wg)) * dense(x, wu), wd)

    def experts(x, wr, bias, eg, eu, ed):
        """-> (the held experts' part, the chosen experts)."""
        idx, w = route(cfg, x, wr, bias)
        mm = einsum("...i,io->...o")

        def one(y, e):                     # expert e on EVERY token
            ge, ue, de, at = e
            w_e = jnp.sum(jnp.where(idx == at, w, 0.0), -1)
            return y + w_e[..., None] * mm(
                jax.nn.silu(mm(x, ge)) * mm(x, ue), de), None

        y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                            (eg, eu, ed, first + jnp.arange(held)))
        return y, idx

    return dense, {"conv": conv, "full_attention": attention}, swiglu, experts


def expert_layer(cfg, x, leaves, precision="float32"):
    """The expert layer alone on (normalised) tokens ``x``; ``leaves``: its
    five, in ``param_specs``' order."""
    return _ops(cfg, precision)[3](x.astype(jnp.float32), *leaves)[0]


def hidden(cfg, params, tokens, precision="float32", storage=None):
    """-> (the final norm's output [B, T, d], the chosen experts of each
    expert layer [B, T, k])."""
    dense, operators, swiglu, experts = _ops(cfg, precision, storage)
    eps = cfg["norm_eps"]
    n_op = {"conv": _CONV, "full_attention": _ATTN}

    def block(kind, routed):
        def fn(x, *p):
            n = 1 + n_op[kind]
            x = x + operators[kind](x, *p[:n])
            m = _rms(x, p[n], eps)
            if not routed:
                return x + swiglu(m, *p[n + 1:]), None
            y, idx = experts(m, *p[n + 1:])
            return x + y, idx
        return fn

    x = params[0].astype(jnp.float32)[tokens]
    kinds = [(kind, i >= cfg["num_dense_layers"])
             for i, kind in enumerate(_kinds(cfg))]
    at, chosen, i = 1, [], 0
    while i < len(kinds):
        kind, routed = kinds[i]
        run = 1                  # consecutive layers alike: one scan over
        while kinds[i + run:i + run + 1] == [kinds[i]]:    # stacked leaves,
            run += 1             # so that the step compiles one of them
        n = 2 + n_op[kind] + (_MOE if routed else _DENSE)
        fn = jax.checkpoint(block(kind, routed))
        stacked = [jnp.stack([params[at + l * n + k] for l in range(run)])
                   for k in range(n)]
        x, idx = jax.lax.scan(lambda x, leaves: fn(x, *leaves), x, stacked)
        at, i = at + run * n, i + run
        if routed:
            chosen.extend(idx)
    return _rms(x, params[at], eps), chosen


def forward(cfg, params, tokens, precision="float32", storage=None):
    """-> (logits [B, T, vocab] through the tied head, the chosen
    experts)."""
    x, chosen = hidden(cfg, params, tokens, precision, storage)
    return _ops(cfg, precision, storage)[0](x, params[0]), chosen


def forward_loss(cfg):
    def fn(params, x, y, precision):
        dense = _ops(cfg, precision)[0]
        hid, _ = hidden(cfg, params, x, precision)
        d = hid.shape[-1]
        rows = hid.reshape(-1, d)
        labels = y.astype(jnp.int32).reshape(-1)
        n = rows.shape[0]
        block = _LOSS_ROWS if n % _LOSS_ROWS == 0 else n

        def picked(a):                     # one block of positions
            r, lab = a
            logp = jax.nn.log_softmax(dense(r, params[0]), -1)
            return jnp.sum(jnp.take_along_axis(logp, lab[:, None], -1))

        total = jax.lax.map(jax.checkpoint(picked),
                            (rows.reshape(-1, block, d),
                             labels.reshape(-1, block)))
        return -jnp.sum(total) / n, {}
    return fn


def selection_flip_share(cfg, params, tokens):
    """Share of the (token, slot) choices of all expert layers that a
    forward in the configuration's dtype (operands and results of every
    product rounded to it) makes otherwise than the float32 forward, on the
    same weights and tokens: a choice counts as moved when the expert
    chosen in float32 is not among that token's choices in the dtype."""
    want = jnp.stack(hidden(cfg, params, tokens)[1])
    got = jnp.stack(hidden(cfg, params, tokens,
                           storage=jnp.dtype(cfg["dtype"]))[1])
    return jnp.mean(~jnp.any(want[..., :, None] == got[..., None, :], -1))
