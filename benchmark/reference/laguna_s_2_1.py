"""Laguna-S-2.1 (poolside, ``model_type: laguna``) as its ``config.json``
describes the layers, cut as the configuration file says. Plain float32
``jax.numpy`` at ``highest`` matmul precision; imports nothing of the
program; leaves in the order of the program's ``collect_params()``.

x is [B, T, d]; every norm is an RMSNorm (eps ``rms_norm_eps``, a plain
scale); no bias anywhere. Layer ``l`` of the source (``assumed`` in the
configuration file names what ``config.json`` leaves open):

* ``u = norm1(x)``; ``q = u Wq^T`` as ``H = num_attention_heads_per_layer
  [l]`` heads of ``head_dim`` (48 on a ``full_attention`` layer, 72 on a
  ``sliding_attention`` one), ``k``, ``v`` as ``num_key_value_heads``
  heads; an RMSNorm over each query and key head's entries (one learned
  scale of ``head_dim`` each). Rotary, halves turned (entry i with i +
  R/2), by ``rope_parameters[layer_types[l]]``: over the first ``R =
  head_dim * partial_rotary_factor`` entries, the rest untouched; ``rope_
  type default``: pair i by ``pos * theta^(-2i/R)``; ``rope_type yarn``
  (arXiv:2309.00071 as transformers computes it): ``e_i = theta^(-2i/R)``,
  ``n_i = e_i / factor``, ``r_i = clip((i - low) / (high - low), 0, 1)``
  with ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``, ``c(b)
  = R ln(original_max_position_embeddings / (2 pi b)) / (2 ln theta)``,
  pair i by ``pos * (n_i r_i + e_i (1 - r_i))``, cos and sin times
  ``attention_factor``. K and V are REPEATED to the query heads the plain
  way (query head j reads key/value head ``j // (H / num_key_value_
  heads)``); softmax of ``q k^T / sqrt(head_dim)`` over the visible keys
  (key j visible to query i iff ``j <= i``, and on a ``sliding_attention``
  layer iff ``i - sliding_window < j <= i``), times ``v``; each head's
  output times ``sigmoid(u Wg^T)_h`` (``gating: per-head``); ``h = x + o
  Wo^T``.
* ``m = norm2(h)``; a layer in ``mlp_only_layers``: ``y = h + Wd (silu(Wg
  m) * (Wu m))``, ``intermediate_size`` wide. Every other: ``s =
  sigmoid(m Wr^T)``, the product in float32 whatever the precision; the
  ``num_experts_per_tok`` experts are the top of ``s + b`` (``b`` a leaf
  held at zero, no gradient); weights ``s`` at the chosen over their sum +
  1e-20 (``norm_topk_prob``), times ``moe_routed_scaling_factor``; ``y = h
  + sum over the chosen e held here of w_e E_e(m) + S(m)``, experts and
  the shared expert SwiGLU of ``moe_intermediate_size`` /
  ``shared_expert_intermediate_size``. Only experts ``first_expert_held``
  .. + ``num_experts_held`` exist here; a choice of another adds nothing.
  The experts are a ``lax.scan`` over those held, each applied to EVERY
  token under its mask: nothing of the program's gather.

Then the final norm, the untied head, and the mean next-token
cross-entropy over every position.

At the cell's size (one sequence of 16,384 beside 811 M parameters and
their gradients) it is computed in blocks so that it fits: every layer
under ``jax.checkpoint`` (consecutive layers alike are one ``lax.scan``
over their stacked leaves); attention one query head and one block of
2,048 queries at a time against all keys (``lax.map`` over heads, then
over query blocks, each rematerialised); the experts one at a time; the
loss in row blocks of 2,048 positions.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common

_ATTN = 9       # norm1, q, k, v, q norm, k norm, gate, out, norm2
_DENSE = 3      # gate, up, down
_MOE = 8        # router, bias, experts' gate, up, down, shared gate, up, down
_Q_ROWS = 2048
_LOSS_ROWS = 2048


def layers(cfg):
    """Each kept layer as ``(windowed, query heads, dense)``."""
    first, n = cfg["first_layer_held"], cfg["num_hidden_layers"]
    return [(cfg["layer_types"][l] == "sliding_attention",
             cfg["num_attention_heads_per_layer"][l],
             l in cfg["mlp_only_layers"]) for l in range(first, first + n)]


def param_specs(cfg):
    dt, d, v = cfg["dtype"], cfg["hidden_size"], cfg["vocab_size"]
    std, hk, hd = cfg["initializer_range"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    total, held = cfg["num_experts"], cfg["num_experts_held"]
    ew, sw = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]

    def w(name, *shape, std=std):
        return (name, shape, dt, True, "normal", std)

    def norm(name, n):
        return (name + "_gamma", (n,), dt, True, "uniform", (0.9, 1.1))

    def mlp(p, width):
        return [w(p + "gate_weight", width, d), w(p + "up_weight", width, d),
                w(p + "down_weight", d, width)]

    specs = [w("wte_weight", v, d, std=cfg["embedding_initializer_range"])]
    for i, (_, h, dense) in enumerate(layers(cfg)):
        p = "h%d_" % i
        specs += [norm(p + "norm1", d),
                  w(p + "attn_q_weight", h * hd, d),
                  w(p + "attn_k_weight", hk * hd, d),
                  w(p + "attn_v_weight", hk * hd, d),
                  norm(p + "attn_qnorm", hd), norm(p + "attn_knorm", hd),
                  w(p + "attn_gate_weight", h, d),
                  w(p + "attn_proj_weight", d, h * hd),
                  norm(p + "norm2", d)]
        if dense:
            specs += mlp(p + "mlp_", cfg["intermediate_size"])
        else:
            specs += [w(p + "moe_router_weight", total, d),
                      # the source names no selection bias: held at zero
                      (p + "moe_score_bias", (total,), dt, False, "uniform",
                       (0.0, 0.0)),
                      w(p + "moe_w_gate", held, d, ew),
                      w(p + "moe_w_up", held, d, ew),
                      w(p + "moe_w_down", held, ew, d)] \
                + mlp(p + "moe_shared_", sw)
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


def rope_table(rope, head_dim):
    """``(inv_freq [R/2] float32, the factor on cos and sin, R)`` of one
    ``rope_parameters`` group: the formulas of the module's docstring, in
    float64 on the host."""
    turned = int(head_dim * rope["partial_rotary_factor"])
    theta = float(rope["rope_theta"])
    i = np.arange(turned // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / turned)
    if rope["rope_type"] == "default":
        return e.astype(np.float32), 1.0, turned
    if rope["rope_type"] != "yarn":
        raise ValueError("rope_type %r is not written" % rope["rope_type"])

    def c(b):
        return turned * math.log(rope["original_max_position_embeddings"]
                                 / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), turned - 1)
    r = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    inv_freq = e / rope["factor"] * r + e * (1.0 - r)
    return inv_freq.astype(np.float32), float(rope["attention_factor"]), \
        turned


def _rotary(x, rope):
    """[B, T, H, D] -> its first R entries turned (entry i paired with
    entry i + R/2: ``rotate_half``), the last D - R as they were."""
    inv_freq, factor, turned = rope_table(rope, x.shape[-1])
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * jnp.asarray(inv_freq)[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    a, b = x[..., :turned // 2], x[..., turned // 2:turned]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., turned:]], -1)


def route(cfg, m, wr, bias):
    """-> (chosen experts [.., k], their weights [.., k])."""
    s = jax.nn.sigmoid(jnp.einsum(
        "...d,ed->...e", m.astype(jnp.float32), wr.astype(jnp.float32),
        precision=common.HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(
        bias.astype(jnp.float32)), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if not cfg["norm_topk_prob"]:
        raise ValueError("only the normalised weights are written")
    return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * cfg["moe_routed_scaling_factor"]


def _ops(cfg, precision, storage=None):
    """The layer's parts as functions: ``dense``, ``attention``,
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
    eps = cfg["rms_norm_eps"]
    hk, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    span = cfg["sliding_window"]
    first, held = cfg["first_expert_held"], cfg["num_experts_held"]

    def attention(windowed, x, g1, wq, wk, wv, gq, gk, wg, wo):
        b, t, _ = x.shape
        h = wq.shape[0] // hd
        rope = cfg["rope_parameters"][
            "sliding_attention" if windowed else "full_attention"]
        u = _rms(x, g1, eps)
        q = _rotary(_rms(dense(u, wq).reshape(b, t, h, hd), gq, eps), rope)
        k = _rotary(_rms(dense(u, wk).reshape(b, t, hk, hd), gk, eps), rope)
        v = dense(u, wv).reshape(b, t, hk, hd)
        # K and V at the query heads, the plain way
        k, v = jnp.repeat(k, h // hk, axis=2), jnp.repeat(v, h // hk, axis=2)
        rows = _Q_ROWS if t % _Q_ROWS == 0 else t
        key_pos = jnp.arange(t)[None, :]

        def head(a):                       # one head: [B, T, hd] each
            qh, kh, vh = a

            def block(c):                  # ``rows`` queries, all keys
                qb, at = c
                i = at + jnp.arange(rows)[:, None]
                seen = key_pos <= i
                if windowed:
                    seen = seen & (key_pos > i - span)
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
        out = jnp.moveaxis(out, 0, 2)                       # [B, T, H, hd]
        if cfg["gating"] != "per-head":
            raise ValueError("only the per-head gate is written")
        out = out * jax.nn.sigmoid(dense(u, wg))[..., None]
        return dense(out.reshape(b, t, h * hd), wo)

    def swiglu(x, wg, wu, wd):
        return dense(jax.nn.silu(dense(x, wg)) * dense(x, wu), wd)

    def experts(m, wr, bias, eg, eu, ed, sg, su, sd):
        """-> (shared + the held experts' part, the chosen experts)."""
        idx, w = route(cfg, m, wr, bias)
        mm = einsum("...i,io->...o")

        def one(y, e):                     # expert e on EVERY token
            ge, ue, de, at = e
            w_e = jnp.sum(jnp.where(idx == at, w, 0.0), -1)
            return y + w_e[..., None] * mm(
                jax.nn.silu(mm(m, ge)) * mm(m, ue), de), None

        y, _ = jax.lax.scan(jax.checkpoint(one), swiglu(m, sg, su, sd),
                            (eg, eu, ed, first + jnp.arange(held)))
        return y, idx

    return dense, attention, swiglu, experts


def expert_layer(cfg, m, leaves, precision="float32"):
    """The expert layer alone on (normalised) tokens ``m``; ``leaves``: its
    eight, in ``param_specs``' order."""
    return _ops(cfg, precision)[3](m.astype(jnp.float32), *leaves)[0]


def hidden(cfg, params, tokens, precision="float32", storage=None):
    """-> (the final norm's output [B, T, d], the chosen experts of each
    expert layer [B, T, k])."""
    _, attention, swiglu, experts = _ops(cfg, precision, storage)
    eps = cfg["rms_norm_eps"]

    def block(windowed, dense):
        def fn(x, *p):
            h = x + attention(windowed, x, *p[:_ATTN - 1])
            m = _rms(h, p[_ATTN - 1], eps)
            if dense:
                return h + swiglu(m, *p[_ATTN:]), jnp.zeros((), jnp.int32)
            y, idx = experts(m, *p[_ATTN:])
            return h + y, idx
        return fn

    x = params[0].astype(jnp.float32)[tokens]
    kinds = layers(cfg)
    at, chosen, i = 1, [], 0
    while i < len(kinds):
        run = 1                  # consecutive layers alike: one scan over
        while kinds[i + run:i + run + 1] == [kinds[i]]:     # stacked leaves,
            run += 1             # so that the step compiles one of them
        windowed, _, dense = kinds[i]
        n = _ATTN + (_DENSE if dense else _MOE)
        fn = jax.checkpoint(block(windowed, dense))
        stacked = [jnp.stack([params[at + l * n + k] for l in range(run)])
                   for k in range(n)]
        x, idx = jax.lax.scan(lambda x, leaves: fn(x, *leaves), x, stacked)
        at, i = at + run * n, i + run
        if not dense:
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


def selection_counts(cfg, params, tokens):
    """Of step 1's forward on the same weights and tokens, by expert layer:
    ``(rows, flipped)``. ``rows`` [expert layers]: the (token, slot)
    choices that fall on an expert held here, in float32 (an even share is
    ``tokens * num_experts_per_tok * num_experts_held / num_experts``).
    ``flipped``: the share of all expert layers' choices that a forward in
    the configuration's dtype (operands and results of every product
    rounded to it) makes otherwise than the float32 forward: a choice
    counts as moved when the expert chosen in float32 is not among that
    token's choices in the dtype."""
    want = jnp.stack(hidden(cfg, params, tokens)[1])
    got = jnp.stack(hidden(cfg, params, tokens,
                           storage=jnp.dtype(cfg["dtype"]))[1])
    first = cfg["first_expert_held"]
    here = (want >= first) & (want < first + cfg["num_experts_held"])
    return jnp.sum(here, axis=tuple(range(1, want.ndim))), \
        jnp.mean(~jnp.any(want[..., :, None] == got[..., None, :], -1))
