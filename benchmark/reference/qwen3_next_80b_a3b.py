"""Qwen3-Next-80B-A3B-Instruct (Qwen, ``model_type: qwen3_next``) as its
``config.json``, Gated Delta Networks (Yang et al., arXiv:2412.06464: the
recurrence, Mamba-2's decay gate) and gated attention (arXiv:2505.06708:
the elementwise output gate) describe the layers, cut as the configuration
file says (published layers 0-3). Plain float32 ``jax.numpy`` at
``highest`` matmul precision; imports nothing of the program; leaves in the
order of the program's ``collect_params()``.

x is [B, T, d]; no bias anywhere. ``N(x; w) = x / sqrt(mean(x^2) +
rms_norm_eps) * (1 + w)``: every norm but one is ZERO-CENTRED, its leaf the
scale's distance from one. Layer ``i``: ``h = x + A_i(N(x; w1)); y = h +
E(N(h; w2))``; ``A_i`` is gated attention where ``(i + first_layer_held +
1) % full_attention_interval == 0`` and Gated DeltaNet elsewhere; every
layer has the expert layer ``E`` (``mlp_only_layers []``,
``decoder_sparse_step 1``); then ``N(.; wf)``, the untied head, and the
mean next-token cross-entropy over every position of the vocabulary's
slice.

* Gated DeltaNet, ``Hk = linear_num_key_heads`` key heads of ``K =
  linear_key_head_dim``, ``H = linear_num_value_heads`` value heads of ``V
  = linear_value_head_dim``, ``u = N(x; w1)``: ``q~ = u Wq``, ``k~ = u Wk``
  [T, Hk K], ``v~ = u Wv``, ``z = u Wz`` [T, H V], ``b = u Wb``, ``a = u
  Wa`` [T, H] (the source fuses the first four as ``in_proj_qkvz`` and the
  last two as ``in_proj_ba``, rows interleaved by key head: with seeded
  weights a permutation of rows). A causal depthwise filter of
  ``linear_conv_kernel_dim`` taps then SiLU on each of q~, k~, v~: ``c(x)[t]
  = sum_j w[:, j] x[t - (taps - 1) + j]``, ``x`` zero before the start; by
  head ``q = q' / sqrt(sum q'^2 + 1e-6)``, ``k`` likewise, ``v =
  silu(c_v(v~))``; ``beta_t = sigmoid(b_t)``; ``g_t = -exp(A_log) *
  softplus(a_t + dt_bias)`` a value head (<= 0, no bound); value head ``j``
  reads key head ``j // (H / Hk)`` (``repeat_interleave``); TOKEN BY TOKEN,
  ``S_0 = 0`` in ``R^{K x V}``:

      S' = exp(g_t) S_{t-1}
      S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
      o_t = S_t^T q_t / sqrt(K)

  (a ``lax.scan`` over positions carrying ``S`` for all heads, in stretches
  of ``_STRETCH`` tokens each under ``jax.checkpoint``); then ``o_t /
  sqrt(mean_head(o_t^2) + eps) * w_o`` over a head's ``V`` entries with a
  PLAIN scale ``w_o`` (the one norm that is not zero-centred), times
  ``silu(z_t)``, then ``Wo``. The recurrence, the decay and the filters
  are float32 whatever the precision; the seven projections are products
  like any other.
* gated attention, ``H = num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``D = head_dim``: ``u Wq`` is
  [T, H, 2 D], a head's first ``D`` entries its query, the last ``D`` its
  gate; ``k, v = u Wk, u Wv``; ``N`` over each query and key head's
  entries (zero-centred, one leaf of ``D`` each); rotary over a head's
  first ``R = D * partial_rotary_factor`` entries, halves rotated (entry i
  with i + R/2), pair i by ``pos * rope_theta^(-2i/R)``, the rest
  untouched; K and V repeated to the query heads the plain way; causal
  softmax of ``q k^T / sqrt(D)`` times ``v``; the output times
  ``sigmoid(gate)`` entry by entry; ``Wo``.
* expert layer on ``m = N(h; w2)``: ``p = softmax(m Wr^T)`` over all
  ``num_experts``, the product in float32 whatever the precision; the
  ``num_experts_per_tok`` largest are chosen (the program's selection bias
  is a leaf held at zero); weights ``p`` at the chosen over their sum
  (``norm_topk_prob``; + 1e-20), no scale; ``E(m) = sum over the chosen e
  held here of w_e SwiGLU_e(m) + sigmoid(m w_sg) * SwiGLU_shared(m)``, the
  shared expert's gate one number a token. Only experts
  ``first_expert_held`` .. + ``num_experts_held`` exist here; a choice of
  another adds nothing. The experts are a ``lax.scan`` over those held,
  each applied to EVERY token under its mask: nothing of the program's
  gather.

At the cell's size it is computed in blocks so that it fits: a layer at a
time under ``jax.checkpoint`` (consecutive layers alike are one
``lax.scan`` over their stacked leaves), attention one query head and one
block of 2,048 queries at a time, the experts one at a time, the loss in
row blocks of the logits; weights cast to float32 where they are used.
"""
import math

import jax
import jax.numpy as jnp

from . import common

_GDN = 13       # 3 filters, A_log, dt_bias, a, q, k, v, z, b, the head
#                 norm, out
_ATTN = 6       # q (with its gate), k, v, q norm, k norm, out
_MOE = 9        # router, bias, experts' gate, up, down, the shared expert's
#                 gate, and its gate, up, down
_Q_ROWS = 2048
_LOSS_ROWS = 2048
_STRETCH = 64   # tokens of the recurrence under one checkpoint


def kinds(cfg):
    """Each kept layer's operator, by its PUBLISHED place."""
    first, period = cfg["first_layer_held"], cfg["full_attention_interval"]
    return ["full_attention" if (first + i + 1) % period == 0
            else "gated_delta_net" for i in range(cfg["num_hidden_layers"])]


def param_specs(cfg):
    dt, d, v = cfg["dtype"], cfg["hidden_size"], cfg["vocab_size"]
    std, taps = cfg["initializer_range"], cfg["linear_conv_kernel_dim"]
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    held, ew = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    r = cfg["conv_initializer_range"]

    def w(name, *shape, std=std):
        return (name, shape, dt, True, "normal", std)

    def uniform(name, shape, lo, hi, trainable=True):
        return (name, shape, dt, trainable, "uniform", (lo, hi))

    def centred(name, n):           # the leaf of a scale ``1 + w``
        return uniform(name + "_gamma", (n,), *cfg["norm_offset_range"])

    def mlp(p, width):
        return [w(p + "gate_weight", width, d), w(p + "up_weight", width, d),
                w(p + "down_weight", d, width)]

    specs = [w("wte_weight", v, d, std=cfg["embedding_initializer_range"])]
    for i, kind in enumerate(kinds(cfg)):
        p = "h%d_" % i
        specs.append(centred(p + "norm1", d))
        if kind == "gated_delta_net":
            specs += [uniform(p + "gdn_%s_conv_weight" % n, (rows, taps),
                              -r, r)
                      for n, rows in (("q", hk * dk), ("k", hk * dk),
                                      ("v", hv * dv))]
            specs += [uniform(p + "gdn_a_log", (hv,),
                              *(math.log(x) for x in cfg["a_init_range"])),
                      uniform(p + "gdn_dt_bias", (hv,),
                              *cfg["dt_bias_init_range"]),
                      w(p + "gdn_a_weight", hv, d),
                      w(p + "gdn_q_weight", hk * dk, d),
                      w(p + "gdn_k_weight", hk * dk, d),
                      w(p + "gdn_v_weight", hv * dv, d),
                      w(p + "gdn_z_weight", hv * dv, d),
                      w(p + "gdn_b_weight", hv, d),
                      uniform(p + "gdn_onorm_gamma", (dv,), 0.9, 1.1),
                      w(p + "gdn_proj_weight", d, hv * dv)]
        else:
            specs += [w(p + "attn_q_weight", 2 * h * hd, d),
                      w(p + "attn_k_weight", kv * hd, d),
                      w(p + "attn_v_weight", kv * hd, d),
                      centred(p + "attn_qnorm", hd),
                      centred(p + "attn_knorm", hd),
                      w(p + "attn_proj_weight", d, h * hd)]
        specs += [centred(p + "norm2", d),
                  w(p + "moe_router_weight", cfg["num_experts"], d),
                  # the source names no selection bias: held at zero
                  uniform(p + "moe_score_bias", (cfg["num_experts"],), 0.0,
                          0.0, trainable=False),
                  w(p + "moe_w_gate", held, d, ew),
                  w(p + "moe_w_up", held, d, ew),
                  w(p + "moe_w_down", held, ew, d),
                  w(p + "moe_sgate_weight", 1, d)] \
            + mlp(p + "moe_shared_", cfg["shared_expert_intermediate_size"])
    return specs + [centred("normf", d), w("head_weight", v, d)]


def sample_inputs(cfg, key, n):
    """``n`` seeded sequences of the timed length, ids uniform over the
    vocabulary's slice; the label of a position is the next token."""
    ids = jax.random.randint(key, (n, cfg["seq_len"] + 1), 0,
                             cfg["vocab_size"], jnp.int32)
    return ids[:, :-1], ids[:, 1:].astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _norm(x, w, eps):
    """The zero-centred norm: the scale is ``1 + w``."""
    return _rms(x, 1.0 + w.astype(jnp.float32), eps)


def _rotary(x, theta, turned):
    """[B, T, H, D] -> its first ``turned`` entries turned (entry i paired
    with entry i + turned/2: ``rotate_half``), the rest as they were."""
    half = turned // 2
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32)
                                   * 2.0 / turned)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:turned]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., turned:]], -1)


def route(cfg, m, wr, bias):
    """-> (chosen experts [.., k], their weights [.., k])."""
    p = jax.nn.softmax(jnp.einsum(
        "...d,ed->...e", m.astype(jnp.float32), wr.astype(jnp.float32),
        precision=common.HIGHEST), -1)
    _, idx = jax.lax.top_k(p + jax.lax.stop_gradient(
        bias.astype(jnp.float32)), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(p, idx, -1)
    if not cfg["norm_topk_prob"]:
        raise ValueError("only the renormalised weights are written")
    return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-20)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: ``q``, ``k`` [B, T, H, K] (already
    at the value heads), ``v`` [B, T, H, V], ``g``, ``beta`` [B, T, H],
    float32 -> ``o`` [B, T, H, V]."""
    b, t, h, dk = k.shape
    scale = 1.0 / math.sqrt(dk)

    def token(s, x):                       # s: [B, H, K, V]
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None, None] * s
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=common.HIGHEST)
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - seen))[..., None, :]
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
    ``experts``. ``storage`` (a dtype) rounds every product's operands and
    result to it: the configuration's own arithmetic, for counting the
    selections it moves."""
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
    eps, taps = cfg["rms_norm_eps"], cfg["linear_conv_kernel_dim"]
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    theta = float(cfg["rope_theta"])
    turned = int(hd * cfg["partial_rotary_factor"])
    first, held = cfg["first_expert_held"], cfg["num_experts_held"]

    def filtered(z, w):
        """silu of the causal depthwise filter along the positions."""
        t, w = z.shape[1], w.astype(jnp.float32)
        c = 0.0
        for j in range(taps):          # tap j reads z[t - (taps - 1) + j]
            back = taps - 1 - j
            c = c + w[:, j] * jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
        return jax.nn.silu(c)

    def gated_delta_net(x, w1, cq, ck, cv, a_log, dt_bias, wa, wq, wk, wv,
                        wz, wb, go, wo):
        b, t, _ = x.shape
        u = _norm(x, w1, eps)

        def unit(z):
            return z * jax.lax.rsqrt(jnp.sum(jnp.square(z), -1,
                                             keepdims=True) + 1e-6)

        q = unit(filtered(dense(u, wq), cq).reshape(b, t, hk, dk))
        k = unit(filtered(dense(u, wk), ck).reshape(b, t, hk, dk))
        v = filtered(dense(u, wv), cv).reshape(b, t, hv, dv)
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
            dense(u, wa) + dt_bias.astype(jnp.float32))
        # value head j reads key head j // (H / Hk)
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        o = delta_rule(q, k, v, g, jax.nn.sigmoid(dense(u, wb)))
        o = _rms(o, go.astype(jnp.float32), eps).reshape(b, t, hv * dv)
        return dense(o * jax.nn.silu(dense(u, wz)), wo)

    def attention(x, w1, wq, wk, wv, gq, gk, wo):
        b, t, _ = x.shape
        u = _norm(x, w1, eps)
        qg = dense(u, wq).reshape(b, t, h, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        q = _rotary(_norm(q, gq, eps), theta, turned)
        k = _rotary(_norm(dense(u, wk).reshape(b, t, kv, hd), gk, eps),
                    theta, turned)
        v = dense(u, wv).reshape(b, t, kv, hd)
        # K and V at the query heads, the plain way
        k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
        rows = _Q_ROWS if t % _Q_ROWS == 0 else t
        key_pos = jnp.arange(t)[None, :]

        def head(a):                       # one head: [B, T, hd] each
            qh, kh, vh = a

            def block(c):                  # ``rows`` queries, all keys
                qb, at = c
                seen = key_pos <= at + jnp.arange(rows)[:, None]
                s = einsum("bqd,bkd->bqk")(qb, kh) / math.sqrt(hd)
                p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
                return einsum("bqk,bkd->bqd")(p, vh)

            out = jax.lax.map(
                jax.checkpoint(block),
                (jnp.moveaxis(qh.reshape(b, -1, rows, hd), 1, 0),
                 jnp.arange(0, t, rows)))
            return jnp.moveaxis(out, 0, 1).reshape(b, t, hd)

        out = jax.lax.map(jax.checkpoint(head),
                          tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
        out = jnp.moveaxis(out, 0, 2) * jax.nn.sigmoid(gate)  # [B, T, H, hd]
        return dense(out.reshape(b, t, h * hd), wo)

    def swiglu(x, wg, wu, wd):
        return dense(jax.nn.silu(dense(x, wg)) * dense(x, wu), wd)

    def experts(m, wr, bias, eg, eu, ed, wsg, sg, su, sd):
        """-> (the gated shared expert + the held experts' part, the chosen
        experts)."""
        idx, w = route(cfg, m, wr, bias)
        mm = einsum("...i,io->...o")

        def one(y, e):                     # expert e on EVERY token
            ge, ue, de, at = e
            w_e = jnp.sum(jnp.where(idx == at, w, 0.0), -1)
            return y + w_e[..., None] * mm(
                jax.nn.silu(mm(m, ge)) * mm(m, ue), de), None

        shared = jax.nn.sigmoid(dense(m, wsg)) * swiglu(m, sg, su, sd)
        y, _ = jax.lax.scan(jax.checkpoint(one), shared,
                            (eg, eu, ed, first + jnp.arange(held)))
        return y, idx

    return dense, {"gated_delta_net": gated_delta_net,
                   "full_attention": attention}, experts


def expert_layer(cfg, m, leaves, precision="float32"):
    """The expert layer alone on (normalised) tokens ``m``; ``leaves``: its
    nine, in ``param_specs``' order."""
    return _ops(cfg, precision)[2](m.astype(jnp.float32), *leaves)[0]


def hidden(cfg, params, tokens, precision="float32", storage=None):
    """-> (the final norm's output [B, T, d], the chosen experts of each
    layer [B, T, k])."""
    _, operators, experts = _ops(cfg, precision, storage)
    eps = cfg["rms_norm_eps"]
    n_op = {"gated_delta_net": _GDN, "full_attention": _ATTN}

    def block(kind):
        def fn(x, *p):
            n = 1 + n_op[kind]
            h = x + operators[kind](x, *p[:n])
            y, idx = experts(_norm(h, p[n], eps), *p[n + 1:])
            return h + y, idx
        return fn

    x = params[0].astype(jnp.float32)[tokens]
    layers = kinds(cfg)
    at, chosen, i = 1, [], 0
    while i < len(layers):
        kind = layers[i]
        run = 1                  # consecutive layers alike: one scan over
        while layers[i + run:i + run + 1] == [kind]:        # stacked leaves,
            run += 1             # so that the step compiles one of them
        n = 2 + n_op[kind] + _MOE
        fn = jax.checkpoint(block(kind))
        stacked = [jnp.stack([params[at + l * n + k] for l in range(run)])
                   for k in range(n)]
        x, idx = jax.lax.scan(lambda x, leaves: fn(x, *leaves), x, stacked)
        at, i = at + run * n, i + run
        chosen.extend(idx)
    return _norm(x, params[at], eps), chosen


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


def selection_counts(cfg, params, tokens):
    """Of step 1's forward on the same weights and tokens, by layer:
    ``(rows, flipped)``. ``rows`` [layers]: the (token, slot) choices that
    fall on an expert held here, in float32 (an even share is ``tokens *
    num_experts_per_tok * num_experts_held / num_experts``). ``flipped``:
    the share of all layers' choices that a forward in the configuration's
    dtype (operands and results of every product rounded to it) makes
    otherwise than the float32 forward: a choice counts as moved when the
    expert chosen in float32 is not among that token's choices in the
    dtype."""
    want = jnp.stack(hidden(cfg, params, tokens)[1])
    got = jnp.stack(hidden(cfg, params, tokens,
                           storage=jnp.dtype(cfg["dtype"]))[1])
    first = cfg["first_expert_held"]
    here = (want >= first) & (want < first + cfg["num_experts_held"])
    return jnp.sum(here, axis=tuple(range(1, want.ndim))), \
        jnp.mean(~jnp.any(want[..., :, None] == got[..., None, :], -1))
