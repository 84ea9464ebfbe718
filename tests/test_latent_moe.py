"""``LatentMoELM`` (latent attention, routed experts, RMSNorm, rotary, a
gated MLP) and ``parallel.moe.routed_ffn`` against the plain reference the
benchmark keeps (``benchmark/reference/kanana2_30b_a3b.py``), at the
configuration's rehearsal sizes, in float32 on seeded weights."""
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import gluon
from mxtpu.parallel import ShardedTrainStep
from mxtpu.parallel import moe

from benchmark.models import kanana2_30b_a3b as model
from benchmark.reference import common as ref_common
from benchmark.reference import kanana2_30b_a3b as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "kanana2_30b_a3b.json")) as f:
    CFG = json.load(f)
CFG.update(CFG["rehearsal"], dtype="float32")
SPECS = ref.param_specs(CFG)
TRAINABLE = [s[0] for s in SPECS if s[3]]
ADAM = {"name": "adam", "learning_rate": 1e-3}


def _gap(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


@pytest.fixture(scope="module")
def case():
    """The model with the reference's seeded leaves, two sequences, and the
    reference's logits, loss and gradients on them."""
    leaves = ref_common.init_params(SPECS, 5)
    x, y = ref.sample_inputs(CFG, jax.random.PRNGKey(9), 2)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    t_idx = [i for i, s in enumerate(SPECS) if s[3]]
    loss_fn = ref.forward_loss(CFG)

    def of(train):
        full = list(leaves)
        for i, w in zip(t_idx, train):
            full[i] = w
        return loss_fn(full, x, y, "float32")[0]

    loss, grads = jax.value_and_grad(of)([leaves[i] for i in t_idx])
    return {"net": net, "leaves": leaves, "x": x, "y": y,
            "logits": ref.forward(CFG, leaves, x)[0], "loss": float(loss),
            "grads": dict(zip(TRAINABLE, grads))}


@pytest.fixture(scope="module")
def program_grads(case):
    """The program's loss and gradients by its eager autograd."""
    from mxtpu import autograd
    net = case["net"]
    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = mx.nd.NDArray(case["x"]), mx.nd.NDArray(case["y"])
    with autograd.record():
        loss = loss_blk(net(x).reshape((-1, CFG["vocab_size"])),
                        y.reshape((-1,))).mean()
    loss.backward()
    params = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    return float(loss.asnumpy()), {
        n: p.grad().asnumpy() for n, p in zip(TRAINABLE, params)}


def test_leaves_are_the_references(case):
    params = list(case["net"].collect_params().values())
    assert [tuple(p.shape) for p in params] == [tuple(s[1]) for s in SPECS]
    assert [p.grad_req != "null" for p in params] == [s[3] for s in SPECS]


def test_logits_match_the_reference(case):
    got = case["net"](mx.nd.NDArray(case["x"])).asnumpy()
    assert got.shape == (2, CFG["seq_len"], CFG["vocab_size"])
    assert _gap(got, case["logits"]) <= 1e-5


def test_loss_matches_the_reference(case, program_grads):
    assert abs(program_grads[0] - case["loss"]) <= 1e-5 * case["loss"]


@pytest.mark.parametrize("leaf", TRAINABLE)
def test_gradient_matches_the_reference(case, program_grads, leaf):
    assert _gap(program_grads[1][leaf], case["grads"][leaf]) <= 2e-4


def test_three_adam_steps_match_the_reference(case):
    """``ShardedTrainStep`` on one device against the reference's own
    training loop: each step's loss and every leaf after three steps."""
    leaves = ref_common.init_params(SPECS, 6)
    net = model.build(CFG, SPECS, leaves)
    model._FIRST.clear()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(block, tokens, labels):
        return loss_blk(block(tokens).reshape((-1, CFG["vocab_size"])),
                        labels.reshape((-1,)))

    step = ShardedTrainStep(net, None, mesh, optimizer="adam",
                            optimizer_params={"learning_rate": 1e-3},
                            forward=forward)
    batches = [ref.sample_inputs(CFG, jax.random.PRNGKey(k), 2)
               for k in (1, 2, 3)]
    start = [np.asarray(w) for w in leaves]
    losses = [float(step(mx.nd.NDArray(x), mx.nd.NDArray(y)).asnumpy())
              for x, y in batches]
    want = ref_common.train_reference(ref.forward_loss(CFG), SPECS, ADAM, 6,
                                      batches, "float32")
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-5)
    got = ref_common.delta_norms(
        [p.data()._data for p in net.collect_params().values()], start)
    gaps = ref_common.leaf_gaps(np.asarray(got), want["delta_norms"])
    assert float(np.max(gaps)) <= 2e-3, gaps
    # the selection bias is held fixed
    frozen = [i for i, s in enumerate(SPECS) if not s[3]]
    assert frozen and all(np.asarray(got)[i] == 0.0 for i in frozen)


# ------------------------------------------------------- the expert layer
E, K, D, F_ = 16, 3, 32, 12          # experts, choices a token, widths


def _layer(seed, t=40, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = jax.random.normal
    x = n(ks[0], (t, D), jnp.float32).astype(dtype)
    leaves = [0.3 * n(ks[1], (E, D)),                          # router
              jax.random.uniform(ks[2], (E,), jnp.float32, -0.01, 0.01),
              0.2 * n(ks[3], (E, D, F_)), 0.2 * n(ks[4], (E, D, F_)),
              0.2 * n(ks[5], (E, F_, D)),
              0.2 * n(ks[6], (2 * F_, D)), 0.2 * n(ks[7], (2 * F_, D)),
              0.2 * n(ks[8], (D, 2 * F_))]
    return x, leaves


def _cfg(held=E, first=0):
    return {"num_experts_per_tok": K, "routed_scaling_factor": 2.448,
            "n_routed_experts_held": held, "first_expert_held": first,
            "num_attention_heads": 1, "qk_nope_head_dim": 1,
            "qk_rope_head_dim": 2, "v_head_dim": 1, "kv_lora_rank": 1,
            "rms_norm_eps": 1e-6, "rope_theta": 1.0}


def _shared(x, leaves):
    wg, wu, wd = leaves[5:]
    return (jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd.T


def _routed(x, leaves, first=0, held=E, grouped=True):
    router, bias, eg, eu, ed = leaves[:5]
    part = slice(first, first + held)
    return moe.routed_ffn(x, router, bias, eg[part], eu[part], ed[part],
                          top_k=K, first_expert=first, scale=2.448,
                          grouped=grouped)


@pytest.mark.parametrize("shares", [1, 2, 8, 16])
def test_shares_add_up_to_the_whole_layer(shares):
    """model-configs §4: the routed parts that ``shares`` holders of
    ``E / shares`` experts each give, with the shared expert (which every
    holder computes alike) counted once, add up to the uncut layer's output
    and to the reference's over all experts."""
    x, leaves = _layer(3)
    held = E // shares
    parts = sum(_routed(x, leaves, first=i * held, held=held)
                for i in range(shares))
    whole = _routed(x, leaves) + _shared(x, leaves)
    want = ref.expert_layer(_cfg(), x, leaves)
    assert _gap(parts + _shared(x, leaves), whole) <= 1e-5
    assert _gap(whole, want) <= 1e-5


@pytest.mark.parametrize("first", [0, 4, 12])
def test_a_share_is_the_references_share(first):
    x, leaves = _layer(4)
    got = _routed(x, leaves, first=first, held=4) + _shared(x, leaves)
    part = slice(first, first + 4)
    want = ref.expert_layer(
        _cfg(4, first), x,
        leaves[:2] + [w[part] for w in leaves[2:5]] + leaves[5:])
    assert _gap(got, want) <= 1e-5


@pytest.mark.parametrize("held,first", [(E, 0), (K, 5), (4, 4)])
def test_no_token_is_dropped_when_all_choose_the_same(held, first):
    """A selection bias that makes EVERY token choose experts 5, 6, 7:
    each of the three then gets all T tokens, T*k rows in all. Nothing is
    cut to a capacity: the grouped path equals the masked one and the
    reference, and without the held experts' part the output changes."""
    x, leaves = _layer(5, t=64)
    leaves[1] = jnp.zeros(E).at[5:5 + K].set(10.0)
    idx, _ = moe.route_top_k(x, leaves[0], leaves[1], K)
    assert set(np.asarray(idx).ravel()) == {5, 6, 7}
    got = _routed(x, leaves, first=first, held=held)
    plain = _routed(x, leaves, first=first, held=held, grouped=False)
    part = slice(first, first + held)
    want = ref.expert_layer(
        _cfg(held, first), x,
        leaves[:2] + [w[part] for w in leaves[2:5]] + leaves[5:]) \
        - _shared(x, leaves)
    assert _gap(got, plain) <= 1e-5
    assert _gap(got, want) <= 1e-5
    assert float(jnp.min(jnp.linalg.norm(got, axis=-1))) > 1e-3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_selections_are_the_references(seed, dtype):
    """With the router's product in float32 the program chooses exactly
    the reference's experts, with its weights, whatever x's dtype."""
    x, leaves = _layer(seed, t=256, dtype=jnp.dtype(dtype))
    idx, w = moe.route_top_k(x, leaves[0], leaves[1], K, 2.448)
    want_idx, want_w = ref.route(_cfg(), x, leaves[0], leaves[1])
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), rtol=1e-6)
    assert w.dtype == jnp.float32


def test_routed_gradients_match_the_masked_form():
    x, leaves = _layer(6)

    def loss(grouped):
        def f(x, router, eg, eu, ed):
            out = moe.routed_ffn(x, router, leaves[1], eg, eu, ed, top_k=K,
                                 first_expert=4, scale=2.448,
                                 grouped=grouped)
            return jnp.sum(jnp.sin(out))
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))(
            x, leaves[0], *(w[4:12] for w in leaves[2:5]))

    for a, b in zip(loss(True), loss(False)):
        assert _gap(a, b) <= 1e-5


# ---- the rows routed here: the ladder of row counts the held part runs at
T_, HELD, FIRST = 64, 2, 4      # 192 pairs; rungs 32, 64, 192 at a tile of 8


@pytest.fixture()
def small_tile(monkeypatch):
    """The grouped kernel's row tile is 512 on the chip; at 8 the toy
    layer has the same ladder of three rungs as the cell's."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    assert moe._rungs(T_ * K, HELD, E) == (32, 64, 192)
    assert moe._rungs(T_ * K, E, E) == (192,)


FORMS = {"predicate": None, "gather": True, "scatter": False}


@pytest.fixture(params=sorted(FORMS))
def sum_form(request, monkeypatch):
    """How the rungs sum their rows by token: as ``_sums_by_gather`` says
    (at the toy ladder, 32 / 64 / 192 of 192 pairs, the last rung gathers
    in both directions and bf16 rows at every rung), or every rung
    and direction forced to the gather, or to the scatter-add."""
    forced = FORMS[request.param]
    if forced is not None:
        monkeypatch.setattr(moe, "_sums_by_gather", lambda *shape: forced)
    return request.param


def _steered(n_live, held, first, dtype, seed=11):
    """A layer whose router reads its choices off x: token t chooses
    ``n_live`` experts held in all (spread as evenly as its held experts
    allow, first tokens first) and fills its k with experts held
    elsewhere, at three distinct scores."""
    x, leaves = _layer(seed, t=T_)
    leaves[0] = jnp.eye(E, D)
    leaves[1] = jnp.zeros(E)
    mine = list(range(first, first + held))
    other = [e for e in range(E) if e not in mine]
    head = np.full((T_, E), -6.0, np.float32)
    most = min(K, held)
    for t in range(T_):
        c = min(most, n_live // T_ + (t < n_live % T_))
        chosen = [mine[(t + j) % held] for j in range(c)] \
            + [other[(t + j) % len(other)] for j in range(K - c)]
        head[t, chosen] = [6.0, 5.0, 4.0]
    x = x.at[:, :E].set(head).astype(dtype)
    return x, leaves


def _routed_grads(x, leaves, held, first, grouped):
    part = slice(first, first + held)

    def f(x, router, eg, eu, ed):
        out = moe.routed_ffn(x, router, leaves[1], eg, eu, ed, top_k=K,
                             first_expert=first, scale=2.448,
                             grouped=grouped)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    args = (x, leaves[0].astype(x.dtype)) + tuple(
        w[part].astype(x.dtype) for w in leaves[2:5])
    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                         has_aux=True)(*args)
    return (out,) + grads


def _reference_grads(x, leaves, held, first):
    part = slice(first, first + held)

    def f(x, router, eg, eu, ed):
        out = ref.expert_layer(_cfg(held, first), x,
                               [router, leaves[1], eg, eu, ed] + leaves[5:])
        out = out - _shared(x, leaves)
        return jnp.sum(jnp.sin(out)), out

    f32 = lambda a: a.astype(jnp.float32)
    args = (f32(x), f32(leaves[0].astype(x.dtype))) + tuple(
        f32(w[part].astype(x.dtype)) for w in leaves[2:5])
    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                         has_aux=True)(*args)
    return (out,) + grads


LIVE = {  # name: (pairs routed here, experts held, the first, rows run)
    "none": (0, HELD, FIRST, 32), "eighth": (24, HELD, FIRST, 32),
    "one_under_the_edge": (31, HELD, FIRST, 32),
    "on_the_edge": (32, HELD, FIRST, 32),
    "one_over_the_edge": (33, HELD, FIRST, 64),
    "on_the_second_edge": (64, HELD, FIRST, 64),
    "over_the_second_edge": (65, HELD, FIRST, 192),
    "every_token_both_held": (128, HELD, FIRST, 192),
    "all_choose_the_same_three": (192, 3, 5, 192),     # experts 5, 6, 7
    "all_held": (192, E, 0, 192),
}


@functools.lru_cache(maxsize=None)
def _plain_and_reference(case, dtype):
    """A case's masked form and float32 reference: the same whatever form
    the grouped path's sums take, so made once."""
    n_live, held, first, _ = LIVE[case]
    x, leaves = _steered(n_live, held, first, jnp.dtype(dtype))
    return (_routed_grads(x, leaves, held, first, False),
            _reference_grads(x, leaves, held, first))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(LIVE))
def test_rows_routed_here_forward_and_gradients(small_tile, sum_form, case,
                                                dtype):
    """The output and the gradients of x, the router and the three expert
    leaves, at every share of live rows and on both sides of each rung's
    edge, against the masked form (the same arithmetic on every token) and
    the float32 reference; the plan runs the rung expected. Under the form
    of the sum by token the predicate picks, and under each form forced on
    every rung and direction."""
    n_live, held, first, rows = LIVE[case]
    x, leaves = _steered(n_live, held, first, jnp.dtype(dtype))
    idx, _ = moe.route_top_k(x, leaves[0], leaves[1], K)
    plan = moe.piece_plan(idx, first, held, E)
    assert int(plan.n_live) == n_live
    assert int(plan.rows) == rows
    got = _routed_grads(x, leaves, held, first, True)
    plain, want = _plain_and_reference(case, dtype)
    tol = 1e-5 if dtype == "float32" else 3e-2
    for a, b, c in zip(got, plain, want):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
        if n_live == 0:         # nothing routed here: exactly nothing
            assert not np.any(np.asarray(a, np.float32))
            assert not np.any(np.asarray(b, np.float32))
            assert float(jnp.max(jnp.abs(c))) <= 1e-5
        else:
            assert _gap(a, b) <= tol
            # in bf16 the reference is as far from the masked form
            assert _gap(a, c) <= (2e-4 if dtype == "float32"
                                  else max(tol, 1.5 * _gap(b, c)))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("held,first", [(HELD, FIRST), (4, 12), (E, 0)])
def test_piece_plan_against_a_count(small_tile, seed, held, first):
    """``piece_plan`` on a seeded router against NumPy: each expert's
    rows, the live pairs sorted by (expert, token), and the smallest rung
    that holds them."""
    x, leaves = _layer(seed, t=T_)
    idx, _ = moe.route_top_k(x, leaves[0], leaves[1], K)
    plan = moe.piece_plan(idx, first, held, E)
    flat = np.asarray(idx).reshape(-1) - first
    live = np.flatnonzero((flat >= 0) & (flat < held))
    np.testing.assert_array_equal(np.asarray(plan.sizes),
                                  np.bincount(flat[live], minlength=held))
    assert int(plan.n_live) == live.size > 0
    want = live[np.argsort(flat[live], kind="stable")]
    np.testing.assert_array_equal(np.asarray(plan.order)[:live.size], want)
    assert sorted(np.asarray(plan.order)) == list(range(T_ * K))
    assert plan.rungs == moe._rungs(T_ * K, held, E)
    assert int(plan.rows) == min(r for r in plan.rungs if r >= live.size)


def _dead_rows_poisoned(a, n_live):
    dead = jnp.arange(a.shape[0])[:, None] >= n_live
    return jnp.where(dead, jnp.nan, a)


@pytest.mark.parametrize("poisoned", ["products", "kept"])
@pytest.mark.parametrize("case", ["none", "eighth", "on_the_edge"])
def test_rows_past_the_last_group_hold_nothing_defined(small_tile, sum_form,
                                                       monkeypatch, case,
                                                       poisoned):
    """XLA:TPU's grouped kernel leaves the rows past the last group
    unwritten, in the product and in its transpose (on the chip they held
    NaN once other buffers had used the memory; XLA:CPU zeroes them). With
    those rows poisoned in every grouped result, forward and backward, the
    layer gives what it gave; and so it does with every row past the live
    ones of the KEPT products poisoned between forward and backward, the
    zero-filled tail included: the backward selects before it multiplies.
    Under both forms of the sum by token, in both directions: a dead pair's
    clipped index lands on a poisoned row and is masked."""
    n_live, held, first, _ = LIVE[case]
    x, leaves = _steered(n_live, held, first, jnp.float32)
    want = _routed_grads(x, leaves, held, first, True)
    if poisoned == "products":
        grouped = moe._grouped

        def poison(a, b, groups, *form):
            out = grouped(a, b, groups, *form)  # (rows, .) or (E, K, N)
            return _dead_rows_poisoned(out, jnp.sum(groups.sizes)) \
                if out.ndim == 2 else out

        monkeypatch.setattr(moe, "_grouped", poison)
    else:
        backward = moe._held_rows_bwd

        def poison(rows, top_k, g, gate, up, *operands, **named):
            n = jnp.sum(operands[-1])
            assert gate.shape == up.shape == (T_ * K, F_)
            return backward(rows, top_k, g, _dead_rows_poisoned(gate, n),
                            _dead_rows_poisoned(up, n), *operands, **named)

        monkeypatch.setattr(moe, "_held_rows_bwd", poison)
    got = _routed_grads(x, leaves, held, first, True)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert _gap(a, b) <= 1e-6 or not np.any(np.asarray(b))


def _held_operands(case, dtype):
    """The operands of the held part for a case of ``LIVE``: (x, w, the
    three expert leaves, order, sizes), the rung's rows, and a cotangent."""
    n_live, held, first, rows = LIVE[case]
    x, leaves = _steered(n_live, held, first, jnp.dtype(dtype))
    idx, w = moe.route_top_k(x, leaves[0], leaves[1], K, 2.448)
    plan = moe.piece_plan(idx, first, held, E)
    assert int(plan.rows) == rows
    experts = tuple(a[first:first + held].astype(x.dtype)
                    for a in leaves[2:5])
    g = jax.random.normal(jax.random.PRNGKey(13), x.shape, jnp.float32)
    return (x, w) + experts + (plan.order, plan.sizes), rows, g


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(LIVE))
def test_the_hand_written_backward_is_the_transpose(small_tile, case, dtype):
    """``_held_rows_bwd`` over the two products the forward kept, against
    ``jax.vjp`` of ``_held_rows`` at the same rung: the cotangents of x, the
    router's weights and the three expert leaves, at every share of live
    rows; what is kept is the rung's two up products in the leaves' dtype,
    at all T*k rows, zero past the rung."""
    (*diff, order, sizes), rows, g = _held_operands(case, dtype)
    out, gate, up = moe._held_rows(rows, K, True, *diff, order, sizes)
    want_out, transpose = jax.vjp(
        lambda *a: moe._held_rows(rows, K, False, *a, order, sizes), *diff)
    assert _gap(out, want_out) <= 1e-6 or not np.any(np.asarray(want_out))
    for kept in (gate, up):
        assert kept.shape == (T_ * K, F_) and kept.dtype == jnp.dtype(dtype)
        assert not np.any(np.asarray(kept[rows:], np.float32))
    got = moe._held_rows_bwd(rows, K, g, gate, up, *diff, order, sizes)
    tol = 1e-5 if dtype == "float32" else 3e-2
    for a, b, operand in zip(got, transpose(g), diff):
        assert a.shape == operand.shape and a.dtype == operand.dtype
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
        assert _gap(a, b) <= tol or not np.any(np.asarray(b, np.float32))


def _layer_loss(bias):
    def loss(x, router, eg, eu, ed):
        return jnp.sum(moe.routed_ffn(x, router, bias, eg, eu, ed, top_k=K,
                                      first_expert=FIRST)
                       .astype(jnp.float32))
    return loss


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_only_the_two_kept_products_leave_the_switch(small_tile, dtype):
    """The guard that the dead rows do not come back: in the compiled
    gradient of the layer at held < total, the arrays of T*k rows outside
    control flow are the two kept products, [T*k, F] in the weights' dtype
    (the forward conditional's results, the backward's operands); none is
    [T*k, D], and in bf16 none is float32."""
    from _hlo_text import arrays_outside_control_flow, conditionals
    x, leaves = _steered(24, HELD, FIRST, jnp.dtype(dtype))
    args = (x, leaves[0]) + tuple(w[FIRST:FIRST + HELD].astype(x.dtype)
                                  for w in leaves[2:5])
    text = jax.jit(jax.grad(_layer_loss(leaves[1]),
                            argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    switches = conditionals(text)
    assert len(switches) == 2               # one forward, one backward
    kept = "%s[%d,%d]" % ({"float32": "f32", "bfloat16": "bf16"}[dtype],
                          T_ * K, F_)
    results = [line.split(" conditional(")[0] for line in switches]
    assert sorted(r.count(kept) for r in results) == [0, 2]
    assert arrays_outside_control_flow(text, T_ * K, D) == []
    outside = arrays_outside_control_flow(text, T_ * K, F_)
    assert outside and all(
        line.count("[%d,%d]" % (T_ * K, F_)) == line.count(kept)
        for line in outside), outside


def test_a_forward_without_a_gradient_keeps_nothing(small_tile):
    """``jax.jit`` of the layer alone (as ``Predictor`` runs it): one
    conditional, and no array of T*k rows by F or D outside it."""
    from _hlo_text import arrays_outside_control_flow, conditionals
    x, leaves = _steered(24, HELD, FIRST, jnp.float32)
    args = (x, leaves[0]) + tuple(w[FIRST:FIRST + HELD] for w in leaves[2:5])
    text = jax.jit(_layer_loss(leaves[1])).lower(*args).compile().as_text()
    assert len(conditionals(text)) == 1
    for cols in (D, F_):
        assert arrays_outside_control_flow(text, T_ * K, cols) == []


def test_the_layer_counts_what_it_traced():
    from mxtpu import telemetry
    for name in ("moe.layers", "moe.experts_held", "moe.experts_total",
                 "moe.grouped_mm.grouped", "moe.grouped_mm.dense",
                 "moe.rows_total", "moe.piece_rows", "moe.kept_bytes",
                 "moe.bwd_products"):
        telemetry.reset_metric(name)
    x, leaves = _layer(7, t=1024)
    _routed(x, leaves, first=4, held=4)
    _routed(x, leaves, grouped=False)
    # laid out at most and at least, by the grouped layer alone: all T*k
    # pairs, and the lowest rung (a third over T*k * 4/16, in tiles of 512)
    assert telemetry.value("moe.rows_total") == 1024 * K
    assert telemetry.value("moe.piece_rows") == 1024
    assert telemetry.value("moe.layers") == 2
    assert telemetry.value("moe.experts_held") == 4 + E
    assert telemetry.value("moe.experts_total") == 2 * E
    assert telemetry.value("moe.grouped_mm.grouped") == 1
    assert telemetry.value("moe.grouped_mm.dense") == 1
    # a forward without a gradient keeps nothing and traces no backward
    assert telemetry.value("moe.kept_bytes") == 0
    assert telemetry.value("moe.bwd_products") == 0
    # under a gradient: the two up products at all T*k rows in the leaves'
    # dtype, and six grouped products a backward branch, once a layer
    jax.grad(lambda x: jnp.sum(_routed(x.astype(jnp.bfloat16),
                                       [w.astype(jnp.bfloat16)
                                        for w in leaves], first=4, held=4)
                               .astype(jnp.float32)))(x)
    assert telemetry.value("moe.kept_bytes") == 2 * 1024 * K * F_ * 2
    assert telemetry.value("moe.bwd_products") == 6
    assert telemetry.value("moe.layers") == 3


# (pairs, the ladder's rungs): lfm2's 8 of 32 experts at two sequences of
# 8,192, kanana's 16 of 128 at 8,192 tokens, smallthinker's 8 of 64 at
# 16,384, and every expert held (one rung: the pairs)
LADDERS = {"lfm2": (16384 * 4, 8, 32), "kanana": (8192 * 6, 16, 128),
           "smallthinker": (16384 * 6, 8, 64), "all_held": (8192 * 6, 16, 16)}


@pytest.mark.parametrize("cell", sorted(LADDERS))
def test_which_rungs_sum_by_a_gather(cell):
    """``_sums_by_gather`` at the expert cells' ladders, for the forward's
    bf16 rows and the backward's float32 ones: the last rung of every
    ladder (and so every expert held) gathers in both directions; a first
    rung a sixth of the pairs (kanana's, smallthinker's) gathers its bf16
    rows and scatter-adds its float32 ones, and so do their second rungs
    and lfm2's 22,016 of 65,536, a third. A form taken at a rung is taken
    at every larger one, and a gather of float32 rows implies the gather of
    bf16 ones."""
    pairs, held, total = LADDERS[cell]
    rungs = moe._rungs(pairs, held, total)
    assert rungs == {"lfm2": (22016, 65536), "kanana": (8192, 16384, 49152),
                     "smallthinker": (16384, 32768, 98304),
                     "all_held": (49152,)}[cell]
    forms = {size: [moe._sums_by_gather(rows, pairs, size) for rows in rungs]
             for size in (2, 4)}
    for size in (2, 4):
        assert forms[size][-1] is True
        assert forms[size] == sorted(forms[size])
    assert all(a or not b for a, b in zip(forms[2], forms[4]))
    assert all(forms[2])                        # from a sixth of the pairs
    assert not any(forms[4][:-1])               # up to a third of them
    # a rung an eighth of the pairs (1 of 16 experts held by the even
    # share: 3 of 32 by the ladder's rule) scatter-adds both ways
    assert not moe._sums_by_gather(pairs // 8, pairs, 2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_each_branch_and_direction_counts_its_form(small_tile, dtype):
    """``moe.sum_by_token.gather`` / ``.scatter``: one increment for each
    branch and direction built. The toy ladder is 32, 64, 192 of 192 pairs:
    a forward alone builds three branches, a gradient three more; the last
    rung gathers in both directions, the first scatter-adds its float32
    rows and gathers bf16 ones, and both counters say so."""
    from mxtpu import telemetry
    names = ("moe.sum_by_token.gather", "moe.sum_by_token.scatter")
    size = jnp.dtype(dtype).itemsize
    x, leaves = _steered(24, HELD, FIRST, jnp.dtype(dtype))
    args = (x, leaves[0]) + tuple(w[FIRST:FIRST + HELD].astype(x.dtype)
                                  for w in leaves[2:5])
    fwd = [moe._sums_by_gather(rows, T_ * K, size) for rows in (32, 64, 192)]
    bwd = [moe._sums_by_gather(rows, T_ * K, 4) for rows in (32, 64, 192)]
    assert fwd[-1] and bwd[-1] and not bwd[0]
    assert fwd[0] == (dtype == "bfloat16")

    def counted(fn):
        for name in names:
            telemetry.reset_metric(name)
        jax.block_until_ready(fn(*args))
        return tuple(telemetry.value(name) for name in names)

    loss = _layer_loss(leaves[1])
    assert counted(loss) == (sum(fwd), 3 - sum(fwd))
    assert counted(jax.grad(loss, argnums=(0, 1, 2, 3, 4))) == (
        sum(fwd) + sum(bwd), 6 - sum(fwd) - sum(bwd))


def test_a_range_outside_the_router_is_refused():
    x, leaves = _layer(8)
    router, bias, eg, eu, ed = leaves[:5]
    with pytest.raises(mx.MXNetError):
        moe.routed_ffn(x, router, bias, eg[:4], eu[:4], ed[:4], top_k=K,
                       first_expert=13)


# ------------------------------------------------------ the smaller parts
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rms_norm(dtype):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(3, 5, 16), dtype)
    g = jnp.asarray(rng.rand(16) + 0.5, dtype)
    got = mx.nd.RMSNorm(mx.nd.NDArray(x), mx.nd.NDArray(g)).asnumpy()
    x32 = np.asarray(x, np.float32)
    want = x32 / np.sqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(g, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=1e-5 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("interleave", [True, False])
def test_rotary_turns_pairs_and_keeps_scores(interleave):
    """Against the definition, pair by pair; and q . k depends on the
    distance of the positions only."""
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, 3, 8).astype(np.float32)

    def rotary(x, theta, interleave):
        return mx.nd.rotary_embedding(mx.nd.NDArray(x), theta=theta,
                                      interleave=interleave).asnumpy()

    got = np.asarray(rotary(jnp.asarray(x), 100.0, interleave))
    for i in range(4):
        a, b = (2 * i, 2 * i + 1) if interleave else (i, i + 4)
        ang = np.arange(6)[None, :, None] * 100.0 ** (-2.0 * i / 8)
        np.testing.assert_allclose(
            got[..., a], x[..., a] * np.cos(ang) - x[..., b] * np.sin(ang),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            got[..., b], x[..., b] * np.cos(ang) + x[..., a] * np.sin(ang),
            rtol=1e-5, atol=1e-6)
    same = np.broadcast_to(x[:, :1], x.shape)     # one vector at every place
    r = np.asarray(rotary(jnp.asarray(same), 100.0, interleave))
    np.testing.assert_allclose((r[:, 1] * r[:, 3]).sum(-1),
                               (r[:, 2] * r[:, 4]).sum(-1), rtol=1e-4)


def test_routed_moe_op_restores_the_input_shape():
    """The registered op flattens (..., D) to tokens and back."""
    x, leaves = _layer(9, t=24)
    got = mx.nd.routed_moe(mx.nd.NDArray(x.reshape(2, 12, D)),
                           *(mx.nd.NDArray(w) for w in leaves[:5]),
                           top_k=K, scale=2.448).asnumpy()
    assert got.shape == (2, 12, D)
    assert _gap(got.reshape(24, D), _routed(x, leaves)) <= 1e-6


@pytest.mark.parametrize("interleave", [True, False])
def test_latent_attention_op_is_attention_over_joined_keys(interleave):
    """``latent_attention`` against plain causal softmax attention over
    keys put together by hand: each head's own part and the ONE rotary
    part, turned, shared by all heads."""
    from mxtpu.ops.nn import rotary
    h, nope, rope, vd, t = 3, 8, 4, 6, 10
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, t, h * (nope + rope)))
    kv = jax.random.normal(ks[1], (2, t, h * (nope + vd)))
    k_rope = jax.random.normal(ks[2], (2, t, rope))
    got = mx.nd.latent_attention(
        *(mx.nd.NDArray(a) for a in (q, kv, k_rope)), num_heads=h,
        nope_dim=nope, rope_dim=rope, v_dim=vd, rope_theta=50.0,
        rope_interleave=interleave).asnumpy()
    q4, kv4 = q.reshape(2, t, h, -1), kv.reshape(2, t, h, -1)
    q4 = jnp.concatenate([q4[..., :nope],
                          rotary(q4[..., nope:], 50.0, interleave)], -1)
    k_r = jnp.broadcast_to(rotary(k_rope, 50.0, interleave)[:, :, None],
                           (2, t, h, rope))
    k4 = jnp.concatenate([kv4[..., :nope], k_r], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q4, k4) / np.sqrt(nope + rope)
    s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None], s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      kv4[..., nope:]).reshape(2, t, h * vd)
    assert _gap(got, want) <= 1e-5


def test_gated_mlp_is_swiglu():
    net = gluon.nn.GatedMLP(8, 12)
    net.initialize()
    x = mx.nd.array(np.random.RandomState(2).randn(3, 8))
    got = net(x).asnumpy()
    wg, wu, wd = (p.data().asnumpy() for p in net.collect_params().values())
    h = x.asnumpy() @ wg.T
    want = (h / (1 + np.exp(-h)) * (x.asnumpy() @ wu.T)) @ wd.T
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
