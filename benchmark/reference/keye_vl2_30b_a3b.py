"""The language model of Keye-VL-2.0-30B-A3B (Kwai-Keye, 2026-06) as its
``config.json`` describes the layers, cut as the configuration file says.
Plain float32 ``jax.numpy`` at ``highest`` matmul precision; imports nothing
of the program; leaves in the order of the program's ``collect_params()``.

x is [B, T, d]; every norm is an RMSNorm (eps ``rms_norm_eps``, a plain
scale); no bias anywhere. One layer (``assumed`` in the configuration file
names what ``config.json`` leaves open), ``h = x + A(norm1(x))``, ``y = h +
E(norm2(h))``:

``A(u)``:

* ``q = u Wq^T`` as ``num_attention_heads`` heads of ``head_dim``, ``k = u
  Wk^T``, ``v = u Wv^T`` as ``num_key_value_heads`` heads; an RMSNorm over
  each query and key head with one learned scale each; rotary over the
  whole head (halves turned: entry i with i + head_dim/2, ``rope_theta``).
  K and V are REPEATED to the query heads the plain way (query head j reads
  key/value head j // group).
* the indexer (``sa_config``; DeepSeek-V3.2-Exp report, eq. 1-2, without
  position encoding or norm): ``qI = u WqI^T`` as ``indexer_num_heads``
  heads of ``indexer_head_dim``, ``kI = u WkI^T`` (one index key head), ``w
  = u WwI^T``, all three in float32 whatever the precision; ``I[t, s] =
  sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``.
* ``S_t``: the ``min(t + 1, topk)`` keys ``s <= t`` of largest ``I[t, s]``,
  by ``jax.lax.top_k`` itself (of equal scores the lower ``s``): the edge
  of the set is the last value it returns, and of the keys level with the
  edge those up to the last index it returned among them. One set a
  query, shared by all heads.
* ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // group]
  / sqrt(head_dim)) v[s, h // group]``; ``A(u) = concat_h(o) Wo^T``.
* the set is made of comparisons, so no gradient reaches ``WqI``, ``WkI``
  or ``WwI``: autodiff reads exactly zero for them, unaided.

``E(m)``: ``r = m Wr^T`` over all ``num_experts`` logits, the product in
float32; the ``num_experts_per_tok`` largest (+ a selection bias held at
zero); weights the softmax over the CHOSEN logits alone (= the softmax over
all, the chosen renormalised: ``norm_topk_prob``); ``sum over the chosen e
held here of w_e Wd_e (silu(Wg_e m) * (Wu_e m))``. Only experts
``first_expert_held`` .. + ``num_experts_held`` exist here; a choice of
another adds nothing. A ``lax.scan`` over those held, each applied to EVERY
token under its mask: nothing of the program's gather.

Then the final norm, the untied head, and the mean next-token
cross-entropy over every position.

At the cell's size it is computed in blocks so that it fits: the layers
are one ``lax.scan`` over their stacked leaves, each under
``jax.checkpoint``; a layer's sets first, 512 queries at a time ([512, 16,
16384] float32 index products are 537 MB), kept as one boolean [T, T];
then attention one query head and one block of 2,048 queries at a time
against all keys (a block's scores are 134 MB), each rematerialised; the
experts one at a time; the loss in row blocks of 2,048 positions.
"""
import math

import jax
import jax.numpy as jnp

from . import common

# norm1, q, k, v, q norm, k norm, out, index q, index k, index w, norm2,
# router, bias, gate, up, down
_LAYER = 16
_Q_ROWS = 2048
_INDEX_ROWS = 512
_LOSS_ROWS = 2048


def param_specs(cfg):
    dt, d, v = cfg["dtype"], cfg["hidden_size"], cfg["vocab_size"]
    std, h, hk = cfg["initializer_range"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, total = cfg["head_dim"], cfg["num_experts"]
    held, ew = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]

    def w(name, *shape, std=std, train=True):
        return (name, shape, dt, train, "normal", std)

    def norm(name, n):
        return (name + "_gamma", (n,), dt, True, "uniform", (0.9, 1.1))

    # the embedding alone is drawn wider (the configuration file says why)
    specs = [w("wte_weight", v, d, std=cfg["embedding_initializer_range"])]
    for i in range(cfg["num_hidden_layers"]):
        p = "h%d_" % i
        specs += [norm(p + "norm1", d),
                  w(p + "attn_q_weight", h * hd, d),
                  w(p + "attn_k_weight", hk * hd, d),
                  w(p + "attn_v_weight", hk * hd, d),
                  norm(p + "attn_qnorm", hd),
                  norm(p + "attn_knorm", hd),
                  w(p + "attn_proj_weight", d, h * hd),
                  # the indexer's leaves are held fixed: no gradient of the
                  # language loss reaches them
                  w(p + "attn_indexer_q_weight", hi * di, d, train=False),
                  w(p + "attn_indexer_k_weight", di, d, train=False),
                  w(p + "attn_indexer_w_weight", hi, d, train=False),
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


def index_scores(cfg, u, wq, wk, ww):
    """-> (qI [B, T, Hi, Di], kI [B, T, Di], w [B, T, Hi]): the indexer's
    three products, float32 whatever the precision."""
    sa = cfg["sa_config"]

    def proj(w):
        return jnp.einsum("btd,od->bto", u.astype(jnp.float32),
                          w.astype(jnp.float32), precision=common.HIGHEST)

    b, t, _ = u.shape
    return (proj(wq).reshape(b, t, sa["indexer_num_heads"],
                             sa["indexer_head_dim"]), proj(wk), proj(ww))


def selected(cfg, u, wq, wk, ww):
    """-> bool [B, T, T], QUERIES first: [b, t, s] iff key ``s`` is in
    query ``t``'s set. ``u`` is what the indexer reads (``norm1(x)``)."""
    q_idx, k_idx, w_idx = index_scores(cfg, u, wq, wk, ww)
    b, t = u.shape[:2]
    keep = min(cfg["sa_config"]["topk"], t)
    rows = _INDEX_ROWS if t % _INDEX_ROWS == 0 else t
    key_pos = jnp.arange(t)[None, None, :]

    def block(c):                          # ``rows`` queries, all keys
        qb, wb, at = c
        s = jnp.einsum("bqjd,bsd->bqjs", qb, k_idx, precision=common.HIGHEST)
        score = jnp.sum(wb[..., None] * jax.nn.relu(s), axis=2)
        causal = key_pos <= (at + jnp.arange(rows))[None, :, None]
        score = jnp.where(causal, score, -jnp.inf)
        vals, idx = jax.lax.top_k(score, keep)
        edge = vals[..., -1:]
        # of the keys level with the edge, top_k took the lowest positions
        last = jnp.max(jnp.where(vals == edge, idx, -1), -1, keepdims=True)
        return causal & ((score > edge) | ((score == edge)
                                           & (key_pos <= last)))

    def by_block(a):
        return jnp.moveaxis(a.reshape((b, -1, rows) + a.shape[2:]), 1, 0)

    out = jax.lax.map(block, (by_block(q_idx), by_block(w_idx),
                              jnp.arange(0, t, rows)))
    return jax.lax.stop_gradient(jnp.moveaxis(out, 0, 1).reshape(b, t, t))


def route(cfg, m, wr, bias):
    """-> (chosen experts [.., k], their weights [.., k])."""
    r = jnp.einsum("...d,ed->...e", m.astype(jnp.float32),
                   wr.astype(jnp.float32), precision=common.HIGHEST)
    _, idx = jax.lax.top_k(r + jax.lax.stop_gradient(
        bias.astype(jnp.float32)), cfg["num_experts_per_tok"])
    if not cfg["norm_topk_prob"]:
        raise ValueError("only the renormalised router of the source is "
                         "written")
    # the softmax over the chosen logits: all 128, the chosen renormalised
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
    theta = float(cfg["rope_theta"])
    first, held = cfg["first_expert_held"], cfg["num_experts_held"]

    def attention(x, g1, wq, wk, wv, gq, gk, wo, iq, ik, iw):
        """-> (A(norm1(x)), the sets [B, T, T] bool, queries first)."""
        b, t, _ = x.shape
        u = _rms(x, g1, eps)
        q = _rms(dense(u, wq).reshape(b, t, h, hd), gq, eps)
        k = _rms(dense(u, wk).reshape(b, t, hk, hd), gk, eps)
        v = dense(u, wv).reshape(b, t, hk, hd)
        q, k = _rotary(q, theta), _rotary(k, theta)
        # K and V at the query heads, the plain way
        k, v = jnp.repeat(k, h // hk, axis=2), jnp.repeat(v, h // hk, axis=2)
        # the indexer reads the activations as the dtype stores them
        sets = selected(cfg, u if storage is None
                        else u.astype(storage).astype(jnp.float32),
                        iq, ik, iw)
        rows = _Q_ROWS if t % _Q_ROWS == 0 else t

        def head(a):                       # one head: [B, T, hd] each
            qh, kh, vh = a

            def block(c):                  # ``rows`` queries, all keys
                qb, at = c
                seen = jax.lax.dynamic_slice_in_dim(sets, at, rows, axis=1)
                s = einsum("bqd,bkd->bqk")(qb, kh) / math.sqrt(hd)
                p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
                return einsum("bqk,bkd->bqd")(p, vh)

            out = jax.lax.map(
                jax.checkpoint(block),
                (jnp.moveaxis(qh.reshape(b, -1, rows, hd), 1, 0),
                 jnp.arange(0, t, rows)))
            return jnp.moveaxis(out, 0, 1).reshape(b, t, hd)

        out = jax.lax.map(jax.checkpoint(head),
                          tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
        return dense(jnp.moveaxis(out, 0, 2).reshape(b, t, h * hd), wo), sets

    def experts(m, wr, bias, eg, eu, ed):
        """-> (the held experts' part, the chosen experts)."""
        idx, w = route(cfg, m, wr, bias)
        mm = einsum("...i,io->...o")

        def one(y, e):                     # expert e on EVERY token
            ge, ue, de, at = e
            w_e = jnp.sum(jnp.where(idx == at, w, 0.0), -1)
            return y + w_e[..., None] * mm(
                jax.nn.silu(mm(m, ge)) * mm(m, ue), de), None

        y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(m),
                            (eg, eu, ed, first + jnp.arange(held)))
        return y, idx

    return dense, attention, experts


def expert_layer(cfg, m, leaves, precision="float32"):
    """The expert layer alone on ``m``; ``leaves``: its five (router, bias,
    gate, up, down), in ``param_specs``' order."""
    return _ops(cfg, precision)[2](m.astype(jnp.float32), *leaves)[0]


def hidden(cfg, params, tokens, precision="float32", storage=None,
           keep_sets=False):
    """-> (the final norm's output [B, T, d], the chosen experts of each
    layer [L, B, T, k], and with ``keep_sets`` each layer's sets [L, B, T,
    T], else None)."""
    _, attention, experts = _ops(cfg, precision, storage)
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def block(x, *p):
        a, sets = attention(x, *p[:10])
        h = x + a
        y, idx = experts(_rms(h, p[10], eps), *p[11:])
        return h + y, (idx, sets if keep_sets else None)

    x = params[0].astype(jnp.float32)[tokens]
    n = cfg["num_hidden_layers"]
    stacked = [jnp.stack([params[1 + l * _LAYER + k] for l in range(n)])
               for k in range(_LAYER)]
    x, (chosen, sets) = jax.lax.scan(lambda x, leaves: block(x, *leaves), x,
                                     stacked)
    return _rms(x, params[1 + n * _LAYER], eps), chosen, sets


def forward(cfg, params, tokens, precision="float32", storage=None):
    """-> (logits [B, T, vocab] through the untied head, the chosen
    experts)."""
    x, chosen, _ = hidden(cfg, params, tokens, precision, storage)
    return _ops(cfg, precision, storage)[0](x, params[-1]), chosen


def forward_loss(cfg):
    def fn(params, x, y, precision):
        dense = _ops(cfg, precision)[0]
        hid = hidden(cfg, params, x, precision)[0]
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


def selection_flip_shares(cfg, params, tokens):
    """-> (experts, keys): the shares of step 1's selections that a forward
    in the configuration's dtype (operands and results of every product
    rounded to it; the router's and the indexer's products float32 from
    the rounded activations) makes otherwise than the float32 forward, on
    the same weights and tokens. Experts: a (token, slot) choice counts as
    moved when the expert chosen in float32 is not among that token's
    choices in the dtype. Keys: of the (query, key) pairs in the float32
    sets of all layers, those not in the dtype's sets."""
    _, want, want_sets = hidden(cfg, params, tokens, keep_sets=True)
    _, got, got_sets = hidden(cfg, params, tokens, keep_sets=True,
                              storage=jnp.dtype(cfg["dtype"]))
    experts = jnp.mean(~jnp.any(want[..., :, None] == got[..., None, :], -1))
    keys = jnp.sum(want_sets & ~got_sets) / jnp.sum(want_sets)
    return experts, keys
