"""SmallThinker-21BA3B-Instruct (PowerInfer, 2025-07) as its ``config.json``
describes the layers, cut as the configuration file says. Plain float32
``jax.numpy`` at ``highest`` matmul precision; imports nothing of the
program; leaves in the order of the program's ``collect_params()``.

x is [B, T, d]; every norm is an RMSNorm (eps ``rms_norm_eps``, a plain
scale); no bias anywhere. One layer (``assumed`` in the configuration file
names what the row of ``config.json`` leaves open):

* ``r = x Wr^T``: ``moe_num_primary_experts`` logits a token, the product
  in float32 whatever the precision. The router reads the LAYER'S INPUT,
  ahead of attention.
* ``a = norm1(x)``; ``q = a Wq^T`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k``, ``v`` as ``num_key_value_heads`` heads; no per-head
  norm. A layer whose ``sliding_window_layout`` entry is 1 turns q and k by
  rotary over the whole head (halves turned: entry i with i + head_dim/2,
  ``rope_theta``) and sees a window: key j is visible to query i iff ``i -
  sliding_window_size < j <= i``. A layer whose entry is 0 is global and
  carries NO position encoding: nothing turns, nothing is added, key j is
  visible iff ``j <= i``. (``rope_layout`` equals ``sliding_window_layout``
  in the source; another pairing is refused.) K and V are REPEATED to the
  query heads the plain way (query head j reads key/value head j // group);
  softmax of ``q k^T / sqrt(head_dim)`` over the visible keys, position by
  position, times ``v``; ``h = x + o Wo^T``.
* ``m = norm2(h)``; the ``moe_num_active_primary_experts`` experts are the
  largest of ``r`` (+ a selection bias held at zero, which takes no
  gradient); their weights are the softmax over the CHOSEN logits alone
  (= the softmax over all of them, the chosen renormalised:
  ``moe_primary_router_apply_softmax``, ``norm_topk_prob``); ``y = h + sum
  over the chosen e held here of w_e Wd_e (relu(Wg_e m) * (Wu_e m))``. Only
  experts ``first_expert_held`` .. + ``moe_num_primary_experts_held`` exist
  here; a choice of another adds nothing. The experts are a ``lax.scan``
  over those held, each applied to EVERY token under its mask: nothing of
  the program's gather.

Then the final norm, the untied head, and the mean next-token
cross-entropy over every position.

At the cell's size (one sequence of 16,384 beside 370 M parameters with
their gradients and Adam state) it is computed in blocks so that it fits:
every layer under ``jax.checkpoint`` (consecutive layers of one kind are
one ``lax.scan`` over their stacked leaves); attention one query head and
one block of 2,048 queries at a time against all keys (``lax.map`` over
heads, then over query blocks, each rematerialised: a head's float32
[16384, 16384] scores would be 1.07 GB, a block's are 134 MB); the experts
one at a time; the loss in row blocks of 2,048 positions.
"""
import math

import jax
import jax.numpy as jnp

from . import common

_LAYER = 11     # norm1, q, k, v, out, norm2, router, bias, gate, up, down
_Q_ROWS = 2048
_LOSS_ROWS = 2048


def _layout(cfg):
    """Each kept layer's ``sliding_window_layout`` entry: 1 a windowed
    layer with rotary, 0 a global one without."""
    first, n = cfg["first_layer_held"], cfg["num_hidden_layers"]
    layout = cfg["sliding_window_layout"][first:first + n]
    if layout != cfg["rope_layout"][first:first + n]:
        raise ValueError("rope_layout differs from sliding_window_layout: "
                         "the source turns exactly its windowed layers")
    return layout


def param_specs(cfg):
    dt, d, v = cfg["dtype"], cfg["hidden_size"], cfg["vocab_size"]
    std, h, hk = cfg["initializer_range"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, total = cfg["head_dim"], cfg["moe_num_primary_experts"]
    held, ew = cfg["moe_num_primary_experts_held"], cfg["moe_ffn_hidden_size"]

    def w(name, *shape, std=std):
        return (name, shape, dt, True, "normal", std)

    def norm(name, n):
        return (name + "_gamma", (n,), dt, True, "uniform", (0.9, 1.1))

    # the embedding alone is drawn wider (the configuration file says why)
    specs = [w("wte_weight", v, d, std=cfg["embedding_initializer_range"])]
    for i in range(len(_layout(cfg))):
        p = "h%d_" % i
        specs += [norm(p + "norm1", d),
                  w(p + "attn_q_weight", h * hd, d),
                  w(p + "attn_k_weight", hk * hd, d),
                  w(p + "attn_v_weight", hk * hd, d),
                  w(p + "attn_proj_weight", d, h * hd),
                  norm(p + "norm2", d),
                  w(p + "moe_router_weight", total, d),
                  # the source has no selection bias: the leaf is held at 0
                  (p + "moe_score_bias", (total,), dt, False, "uniform",
                   (0.0, 0.0)),
                  w(p + "moe_w_gate", held, d, ew),
                  w(p + "moe_w_up", held, d, ew),
                  w(p + "moe_w_down", held, ew, d)]
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
    """-> (chosen experts [.., k], their weights [.., k]) from the tensor
    the router reads."""
    r = jnp.einsum("...d,ed->...e", x.astype(jnp.float32),
                   wr.astype(jnp.float32), precision=common.HIGHEST)
    if not cfg["moe_primary_router_apply_softmax"]:
        raise ValueError("only the softmax router of the source is written")
    _, idx = jax.lax.top_k(r + jax.lax.stop_gradient(
        bias.astype(jnp.float32)), cfg["moe_num_active_primary_experts"])
    # the softmax over the chosen logits: all 64, the chosen renormalised
    return idx, jax.nn.softmax(jnp.take_along_axis(r, idx, -1), -1)


def _ops(cfg, precision, storage=None):
    """The layer's parts as functions: ``dense``, ``attention``,
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
    eps = cfg["rms_norm_eps"]
    h, hk, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    theta, span = float(cfg["rope_theta"]), cfg["sliding_window_size"]
    first, held = cfg["first_expert_held"], cfg["moe_num_primary_experts_held"]

    def attention(windowed, x, g1, wq, wk, wv, wo):
        b, t, _ = x.shape
        xn = _rms(x, g1, eps)
        q = dense(xn, wq).reshape(b, t, h, hd)
        k = dense(xn, wk).reshape(b, t, hk, hd)
        v = dense(xn, wv).reshape(b, t, hk, hd)
        if windowed:
            q, k = _rotary(q, theta), _rotary(k, theta)
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
        return dense(jnp.moveaxis(out, 0, 2).reshape(b, t, h * hd), wo)

    def experts(m, router_x, wr, bias, eg, eu, ed):
        """-> (the held experts' part, the chosen experts): the experts
        read ``m``, the router ``router_x``."""
        idx, w = route(cfg, router_x, wr, bias)
        mm = einsum("...i,io->...o")

        def one(y, e):                     # expert e on EVERY token
            ge, ue, de, at = e
            w_e = jnp.sum(jnp.where(idx == at, w, 0.0), -1)
            return y + w_e[..., None] * mm(
                jax.nn.relu(mm(m, ge)) * mm(m, ue), de), None

        y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(m),
                            (eg, eu, ed, first + jnp.arange(held)))
        return y, idx

    return dense, attention, experts


def expert_layer(cfg, m, router_x, leaves, precision="float32"):
    """The expert layer alone: the experts read ``m``, the router
    ``router_x``; ``leaves``: its five, in ``param_specs``' order."""
    return _ops(cfg, precision)[2](m.astype(jnp.float32),
                                   router_x.astype(jnp.float32), *leaves)[0]


def hidden(cfg, params, tokens, precision="float32", storage=None):
    """-> (the final norm's output [B, T, d], the chosen experts of each
    layer [B, T, k])."""
    _, attention, experts = _ops(cfg, precision, storage)
    eps = cfg["rms_norm_eps"]

    def block(windowed):
        def fn(x, *p):
            h = x + attention(windowed, x, *p[:5])
            y, idx = experts(_rms(h, p[5], eps), x, *p[6:])
            return h + y, idx
        return fn

    x = params[0].astype(jnp.float32)[tokens]
    layout = _layout(cfg)
    at, chosen, i = 1, [], 0
    while i < len(layout):
        run = 1                  # consecutive layers alike: one scan over
        while layout[i + run:i + run + 1] == [layout[i]]:   # stacked leaves,
            run += 1             # so that the step compiles one of them
        fn = jax.checkpoint(block(bool(layout[i])))
        stacked = [jnp.stack([params[at + l * _LAYER + k]
                              for l in range(run)]) for k in range(_LAYER)]
        x, idx = jax.lax.scan(lambda x, leaves: fn(x, *leaves), x, stacked)
        at, i = at + run * _LAYER, i + run
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
