"""Ling-3.0-flash (inclusionAI, ``model_type: bailing_hybrid``) as its
``config.json``, Kimi Linear (arXiv:2510.26692 §3-4: Kimi Delta Attention),
DeepSeek-V2/V3 (arXiv:2405.04434 §2.1, arXiv:2412.19437 §2.1: latent
attention, the group-limited sigmoid router) and gated attention
(arXiv:2505.06708: the head-wise output gate) describe the layers, cut as
the configuration file says (published layers 1-7). Plain float32
``jax.numpy`` at ``highest`` matmul precision; imports nothing of the
program; leaves in the order of the program's ``collect_params()``.

u is [B, T, d]; every norm is an RMSNorm (eps ``rms_norm_eps``); no bias.

* layer ``i``: ``h = u + A_i(norm1(u))``; ``y = h + E_i(norm2(h))``. ``A_i``
  is latent attention where ``(i + first_layer_held + 1) %
  layer_group_size == 0`` and Kimi Delta Attention elsewhere; ``E_i`` is a
  SwiGLU of ``intermediate_size`` in the first ``first_k_dense_replace``
  layers and the expert layer after them; then a final norm and the untied
  head; loss: mean next-token cross-entropy over the vocabulary's slice.
* KDA, ``H`` heads of ``K = head_dim``: ``q~ = n Wq``, ``k~ = n Wk``, ``v~ =
  n Wv`` [T, H K]; a causal depthwise filter of ``short_conv_kernel_size``
  taps then SiLU on each: ``c(z)[t] = sum_j w[:, j] z[t - (taps - 1) +
  j]``, ``z`` zero before the start; by head ``q = q' / sqrt(sum q'^2 +
  1e-6)``, ``k`` likewise, ``v = silu(c_v(v~))``; the log-decay ``g_t =
  kda_lower_bound * sigmoid(exp(A_log[h]) * (n Wf + dt_bias)_t)`` a
  channel, ``a_t = exp(g_t)``; ``b_t = sigmoid(n Wb)`` a head; TOKEN BY
  TOKEN, ``S_0 = 0`` in ``R^{K x K}``:

      S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
      o_t = S_t^T q_t / sqrt(K)

  (a ``lax.scan`` over positions carrying ``S`` for all heads, in stretches
  of ``_STRETCH`` tokens each under ``jax.checkpoint``, so that the
  backward keeps a state a stretch and not a state a token: 17 GB a layer
  at 8,192 positions); then ``(RMSNorm_head(o) * sigmoid(n Wg)) Wo``, the
  norm over each head's K entries with one learned scale of K. The
  recurrence, the decay and the filters are float32 whatever the
  precision; the seven projections are products like any other.
* latent attention (no query rank) as in ``kanana2_30b_a3b.py``: ``q = n
  Wq`` -> [T, H, nope + rope]; ``n Wkva`` -> the latent ``c`` and ONE rotary
  key; ``norm(c) Wkvb`` -> [T, H, nope + v]; rotary (``rope_theta``, pairs
  (2i, 2i+1), written out as [first entries | second entries]) on q's last
  ``rope`` entries and the shared key; causal softmax of ``q k^T /
  sqrt(nope + rope)`` times ``v``, one head at a time; then each head's
  output times ``sigmoid(n Wgate)_h`` (``Wgate``: d x H) and ``Wo``.
* expert layer: ``s = sigmoid(m Wr^T)`` over all ``num_experts``, the
  product in float32 whatever the precision; ``s' = s + bias`` (the bias
  takes no gradient); the experts are ``n_group`` groups in order, a
  group's score the sum of its two largest ``s'``, the ``topk_group`` best
  groups stay and the ``num_experts_per_tok`` largest ``s'`` among their
  experts are chosen; weights ``s`` at the chosen over their sum + 1e-20,
  times ``routed_scaling_factor``; plus one shared SwiGLU of
  ``moe_shared_expert_intermediate_size`` for every token. Only experts
  ``first_expert_held`` .. + ``num_experts_held`` exist here; a choice of
  another adds nothing. The experts are a ``lax.scan`` over those held,
  each applied to EVERY token under its mask: nothing of the program's
  gather.

At the cell's size it is computed in blocks so that it fits: a layer at a
time under ``jax.checkpoint`` (consecutive layers alike are one
``lax.scan`` over their stacked leaves), attention one head at a time, the
experts one at a time, the loss in row blocks of the logits; weights cast
to float32 where they are used.
"""
import math

import jax
import jax.numpy as jnp

from . import common

_KDA = 13       # leaves of a KDA operator: 3 filters, A_log, dt_bias, f, q,
#                 k, v, b, g, the head norm, out
_MLA = 6        # of latent attention: q, kva, kv norm, kvb, gate, out
_DENSE = 3
_MOE = 8
_LOSS_ROWS = 2048
_STRETCH = 64   # tokens of the recurrence under one checkpoint


def kinds(cfg):
    """Each kept layer's operator, by its PUBLISHED place."""
    first, period = cfg["first_layer_held"], cfg["layer_group_size"]
    return ["latent_attention" if (first + i + 1) % period == 0 else "kda"
            for i in range(cfg["num_hidden_layers"])]


def param_specs(cfg):
    dt, d, v = cfg["dtype"], cfg["hidden_size"], cfg["vocab_size"]
    std, h = cfg["initializer_range"], cfg["num_attention_heads"]
    hd, taps = cfg["head_dim"], cfg["short_conv_kernel_size"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    held, ew = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    b = cfg["score_bias_range"]

    def w(name, *shape, std=std):
        return (name, shape, dt, True, "normal", std)

    def norm(name, n):
        return (name + "_gamma", (n,), dt, True, "uniform", (0.9, 1.1))

    def mlp(p, width):
        return [w(p + "gate_weight", width, d), w(p + "up_weight", width, d),
                w(p + "down_weight", d, width)]

    specs = [w("wte_weight", v, d, std=cfg["embedding_initializer_range"])]
    for i, kind in enumerate(kinds(cfg)):
        p = "h%d_" % i
        specs.append(norm(p + "norm1", d))
        if kind == "kda":
            r = cfg["conv_initializer_range"]
            specs += [(p + "kda_%s_conv_weight" % n, (h * hd, taps), dt, True,
                       "uniform", (-r, r)) for n in "qkv"]
            specs += [(p + "kda_a_log", (h,), dt, True, "uniform",
                       tuple(math.log(x) for x in cfg["a_init_range"])),
                      (p + "kda_dt_bias", (h * hd,), dt, True, "uniform",
                       tuple(math.log(math.expm1(x))
                             for x in cfg["dt_init_range"]))]
            specs += [w(p + "kda_%s_weight" % n, h * hd, d) for n in "fqkv"]
            specs += [w(p + "kda_b_weight", h, d),
                      w(p + "kda_g_weight", h * hd, d),
                      norm(p + "kda_onorm", hd),
                      w(p + "kda_proj_weight", d, h * hd)]
        else:
            specs += [w(p + "attn_q_weight", h * cfg["qk_head_dim"], d),
                      w(p + "attn_kva_weight", rank + rope, d),
                      norm(p + "attn_kvnorm", rank),
                      w(p + "attn_kvb_weight",
                        h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]),
                        rank),
                      w(p + "attn_gate_weight", h, d),
                      w(p + "attn_proj_weight", d, h * cfg["v_head_dim"])]
        specs.append(norm(p + "norm2", d))
        if i < cfg["first_k_dense_replace"]:
            specs += mlp(p + "mlp_", cfg["intermediate_size"])
        else:
            specs += [w(p + "moe_router_weight", cfg["num_experts"], d),
                      (p + "moe_score_bias", (cfg["num_experts"],), dt,
                       False, "uniform", (-b, b)),
                      w(p + "moe_w_gate", held, d, ew),
                      w(p + "moe_w_up", held, d, ew),
                      w(p + "moe_w_down", held, ew, d)] \
                + mlp(p + "moe_shared_",
                      cfg["moe_shared_expert_intermediate_size"])
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


def route(cfg, x, wr, bias):
    """-> (chosen experts [.., k], their weights [.., k])."""
    s = jax.nn.sigmoid(jnp.einsum(
        "...d,ed->...e", x.astype(jnp.float32), wr.astype(jnp.float32),
        precision=common.HIGHEST))
    biased = s + jax.lax.stop_gradient(bias.astype(jnp.float32))
    groups, e = cfg["n_group"], s.shape[-1]
    if groups > 1:
        by_group = jnp.sum(jax.lax.top_k(
            biased.reshape(s.shape[:-1] + (groups, e // groups)), 2)[0], -1)
        _, kept = jax.lax.top_k(by_group, cfg["topk_group"])
        stays = jnp.any(kept[..., :, None] == jnp.arange(groups), -2)
        biased = jnp.where(jnp.repeat(stays, e // groups, -1), biased,
                           -jnp.inf)
    _, idx = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: ``q``, ``k``, ``g`` [B, T, H, K],
    ``v`` [B, T, H, V], ``beta`` [B, T, H], float32 -> ``o`` [B, T, H, V]."""
    b, t, h, dk = k.shape
    scale = 1.0 / math.sqrt(dk)

    def token(s, x):                       # s: [B, H, K, V]
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=common.HIGHEST)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t,
                             precision=common.HIGHEST) * scale

    stretch = _STRETCH if t % _STRETCH == 0 else t
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(
        (t // stretch, stretch) + x.shape[:1] + x.shape[2:])
        for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        jax.checkpoint(lambda s, x: jax.lax.scan(token, s, x)),
        jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _ops(cfg, precision, storage=None):
    """The layer's parts as functions: ``dense``, the operators by kind,
    ``swiglu``, ``experts``. ``storage`` (a dtype) rounds every product's
    operands and result to it: the configuration's own arithmetic, for
    counting the selections it moves."""
    product = common.product(precision)

    def einsum(spec):
        op = product(lambda a, b: jnp.einsum(spec, a, b,
                                             precision=common.HIGHEST))
        if storage is None:
            return lambda a, b: op(a.astype(jnp.float32),
                                   b.astype(jnp.float32))
        return lambda a, b: op(a.astype(storage), b.astype(storage)).astype(
            storage).astype(jnp.float32)

    dense = einsum("...i,oi->...o")
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank, eps = cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    theta, taps = float(cfg["rope_theta"]), cfg["short_conv_kernel_size"]
    first, held = cfg["first_expert_held"], cfg["num_experts_held"]
    bound = float(cfg["kda_lower_bound"])

    def filtered(z, w):
        """silu of the causal depthwise filter along the positions."""
        t, w = z.shape[1], w.astype(jnp.float32)
        c = 0.0
        for j in range(taps):          # tap j reads z[t - (taps - 1) + j]
            back = taps - 1 - j
            c = c + w[:, j] * jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
        return jax.nn.silu(c)

    def kda(x, g1, cq, ck, cv, a_log, dt_bias, wf, wq, wk, wv, wb, wg, go,
            wo):
        b, t, _ = x.shape
        n = _rms(x, g1, eps)

        def heads(z):
            return z.reshape(b, t, h, hd)

        def unit(z):
            return z * jax.lax.rsqrt(jnp.sum(jnp.square(z), -1,
                                             keepdims=True) + 1e-6)

        q = unit(heads(filtered(dense(n, wq), cq)))
        k = unit(heads(filtered(dense(n, wk), ck)))
        v = heads(filtered(dense(n, wv), cv))
        rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
        g = bound * jax.nn.sigmoid(rate * heads(
            dense(n, wf) + dt_bias.astype(jnp.float32)))
        o = delta_rule(q, k, v, g, jax.nn.sigmoid(dense(n, wb)))
        o = _rms(o, go, eps).reshape(b, t, h * hd)
        return dense(o * jax.nn.sigmoid(dense(n, wg)), wo)

    def mla(x, g1, wq, wkva, gc, wkvb, wgate, wo):
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
        out = jnp.moveaxis(out, 0, 2) \
            * jax.nn.sigmoid(dense(xn, wgate))[..., None]
        return dense(out.reshape(b, t, h * vd), wo)

    def swiglu(x, wg, wu, wd):
        return dense(jax.nn.silu(dense(x, wg)) * dense(x, wu), wd)

    def experts(x, wr, bias, eg, eu, ed, sg, su, sd):
        """-> (shared + the held experts' part, the chosen experts)."""
        idx, w = route(cfg, x, wr, bias)
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

    return dense, {"kda": kda, "latent_attention": mla}, swiglu, experts


def expert_layer(cfg, x, leaves, precision="float32"):
    """The expert layer alone on (normalised) tokens ``x``; ``leaves``: its
    eight, in ``param_specs``' order."""
    return _ops(cfg, precision)[3](x.astype(jnp.float32), *leaves)[0]


def hidden(cfg, params, tokens, precision="float32", storage=None):
    """-> (the final norm's output [B, T, d], the chosen experts of each
    expert layer [B, T, k])."""
    dense, operators, swiglu, experts = _ops(cfg, precision, storage)
    eps = cfg["rms_norm_eps"]
    n_op = {"kda": _KDA, "latent_attention": _MLA}

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
    layers = [(kind, i >= cfg["first_k_dense_replace"])
              for i, kind in enumerate(kinds(cfg))]
    at, chosen, i = 1, [], 0
    while i < len(layers):
        kind, routed = layers[i]
        run = 1                  # consecutive layers alike: one scan over
        while layers[i + run:i + run + 1] == [layers[i]]:  # stacked leaves,
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
    """-> (logits [B, T, vocab] through the untied head, the chosen
    experts)."""
    x, chosen = hidden(cfg, params, tokens, precision, storage)
    return _ops(cfg, precision, storage)[0](x, params[-1]), chosen


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
            logp = jax.nn.log_softmax(dense(r, params[-1]), -1)
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
