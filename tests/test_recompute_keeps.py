"""What a recomputed block keeps: the attention and KDA kernels name their
forwards' outputs inside their ``custom_vjp`` forward rules
(``flash_attention.KEPT_NAMES``, ``kda.KEPT_NAMES``), the routed layer
names what its router decided (``moe.KEPT_NAMES``), and ``HybridLM`` puts
its blocks under ``jax.checkpoint`` with the policy that keeps exactly
those (``hybrid_lm.kept_policy``). Here, a kernel or a routed layer at a
time: a function under that checkpoint holds the kernel's forward (the
router's product, its ``top_k``, its gather and the plan's sort) once when
differentiated where the bare checkpoint holds it twice, and both give the
same gradients bit for bit; and the benchmark's readers of what ran on the
device. The three recomputed models count theirs in ``test_ling3_flash.py``,
``test_laguna_s_2_1.py`` and ``test_qwen3_next.py``; that the names lower
to nothing under no checkpoint is in ``test_ling3_flash.py``."""
import importlib

import pytest

import jax
import jax.numpy as jnp

from mxtpu.gluon.model_zoo import hybrid_lm

from _jaxpr_count import calls, router_ops

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
kda = importlib.import_module("mxtpu.ops.pallas.kda")
moe = importlib.import_module("mxtpu.parallel.moe")

T = 256


def _attention(kind):
    """-> (a block around one attention call, its differentiable inputs):
    4 query heads over 2 key/value heads of 16, 256 positions in blocks of
    128; what follows the call reads its output, as a layer's gate and
    output projection do."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (1, 4, T, 16), jnp.float32)
    k, v = (jax.random.normal(key, (1, 2, T, 16), jnp.float32)
            for key in ks[1:3])
    w = 0.2 * jax.random.normal(ks[3], (16, 16), jnp.float32)
    if kind == "sparse":
        # each query's own key and a seeded half of those before it
        keep = jax.random.bernoulli(ks[4], 0.5, (1, T, T))
        sets = (jnp.tril(keep | jnp.eye(T, dtype=bool)[None])
                .transpose(0, 2, 1).astype(jnp.int8))

        def call(q, k, v):
            return fa.sparse_attention(q, k, v, sets, block_q=128,
                                       block_k=128)
    else:
        def call(q, k, v):
            return fa.flash_attention(q, k, v, True, block_q=128,
                                      block_k=128,
                                      window=40 if kind == "windowed" else 0)

    def block(q, k, v):
        return jnp.tanh(call(1.5 * q, k, v) @ w)
    return block, (q, k, v)


def _kda():
    """-> (a block around one KDA call, its inputs): 2 heads of 16, 128
    positions in chunks of 64, the decay inside the gate's bound."""
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    q, k, v = (jax.random.normal(key, (1, 128, 32), jnp.float32)
               for key in ks[:3])
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (1, 128, 32)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, 128, 2)))
    w = 0.2 * jax.random.normal(ks[5], (32, 32), jnp.float32)

    def block(q, k, v, g, beta):
        return jnp.tanh(kda.kda_attention(1.1 * q, k, v, g, beta, 64) @ w)
    return block, (q, k, v, g, beta)


# case -> (interpreter, the block's maker, what counts as a forward, its
# count under no checkpoint, whether the model's policy keeps it). KDA's
# plain path differentiates its scan in the backward rule, which is one
# forward scan more under every wrapping. A windowed call names nothing,
# so the policy changes nothing for it
CASES = {
    "flash-full": (True, lambda: _attention("full"),
                   "flash_attention_fwd", 1, True),
    "flash-windowed": (True, lambda: _attention("windowed"),
                       "flash_window_fwd", 1, False),
    "flash-sparse": (True, lambda: _attention("sparse"),
                     "sparse_attention_fwd", 1, True),
    "kda-plain": (False, _kda, "scan", 2, True),
    "kda-kernels": (True, _kda, "kda_fwd", 1, True),
}


def _wrapped(block, args, count):
    """-> ``count`` of the differentiated ``block``'s jaxpr under no
    checkpoint (``none``), the bare one and the model's policy (``kept``);
    the last two give the same gradients bit for bit, none of them zero."""
    policy = hybrid_lm.kept_policy()
    wraps = {"none": lambda f: f, "bare": jax.checkpoint,
             "kept": lambda f: jax.checkpoint(f, policy=policy)}
    grads = {name: jax.grad(lambda *a, wrap=wrap: jnp.sum(wrap(block)(*a)),
                            argnums=tuple(range(len(args))))
             for name, wrap in wraps.items()}
    bare, kept = grads["bare"](*args), grads["kept"](*args)
    for a, b in zip(bare, kept):
        assert bool(jnp.all(a == b))
    assert all(bool(jnp.any(a != 0)) for a in kept)
    return {name: count(jax.make_jaxpr(g)(*args))
            for name, g in grads.items()}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_kept_forward_runs_once(monkeypatch, case):
    interpret, make, forward, once, kept = CASES[case]
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1" if interpret else "0")
    counted = _wrapped(*make(), lambda closed: calls(closed)[forward])
    assert counted == {"none": once, "bare": once + 1,
                       "kept": once + (not kept)}


# a router's score, and its group limit (groups, groups kept): the two
# published routers, and DeepSeek-V3's limit, whose two ``top_k``s over the
# groups run once too
ROUTERS = {"softmax": ("softmax", 1, 1), "sigmoid": ("sigmoid", 1, 1),
           "sigmoid-groups": ("sigmoid", 4, 2)}
EXPERTS = 16


def _routed(score, n_group, topk_group):
    """-> (a block around one routed layer, its differentiable inputs): 48
    tokens of 32, 4 of 16 experts of 12 each, every expert held; what
    follows the layer reads its output."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (48, 32), jnp.float32)
    router_w = 0.3 * jax.random.normal(ks[1], (EXPERTS, 32), jnp.float32)
    w_gate, w_up = (0.2 * jax.random.normal(key, (EXPERTS, 32, 12))
                    for key in ks[2:4])
    w_down = 0.2 * jax.random.normal(ks[4], (EXPERTS, 12, 32))

    def block(x, router_w, w_gate, w_up, w_down):
        return jnp.tanh(moe.routed_ffn(
            1.5 * x, router_w, jnp.zeros(EXPERTS), w_gate, w_up, w_down, 4,
            score=score, n_group=n_group, topk_group=topk_group))
    return block, (x, router_w, w_gate, w_up, w_down)


@pytest.mark.parametrize("case", sorted(ROUTERS))
def test_a_kept_router_routes_once(case):
    """One routed layer, grouped: under the model's policy the
    differentiated function holds one choice, one sort, one gather of the
    picked scores and the ``HIGHEST`` product once, in the value; the bare
    checkpoint's recomputed part holds each again. The gradients are the
    same bits: the second forward reads the choice and the order the first
    made."""
    limit = 2 * (ROUTERS[case][1] > 1)      # the group limit's own top_ks
    counted = _wrapped(*_routed(*ROUTERS[case]),
                       lambda closed: router_ops(closed, EXPERTS))
    once = {"score": 1, "top_k.full": 1, "top_k": 1 + limit, "sort": 1,
            "picked": 1}
    assert counted == {"none": once, "kept": once,
                       "bare": {k: 2 * n for k, n in once.items()}}


# who owns names -> (what it exports, what that should be): each file
# exports its own and the model's policy is their union; nobody's are not
# kept
OWNERS = {
    "flash": (fa.KEPT_NAMES, ("flash_out", "flash_lse")),
    "kda": (kda.KEPT_NAMES, ("kda_o", "kda_states")),
    "gdn": (kda.GDN_KEPT_NAMES, ("gdn_o", "gdn_states")),
    "moe": (moe.KEPT_NAMES, ("route_logits", "route_choice", "route_picked",
                             "route_order", "route_sizes", "route_rung")),
    "nobody": (("another", "route_scores"), ("another", "route_scores")),
}


@pytest.mark.parametrize("owner", sorted(OWNERS))
def test_the_policy_keeps_the_kernels_names_and_no_other(owner):
    """Each kernel file and the routed layer's export their names; the
    model's policy is their union, and a value under another name is not
    kept."""
    names, want = OWNERS[owner]
    assert names == want
    from jax.ad_checkpoint import checkpoint_name

    def f(x, name):
        return jnp.sum(jnp.sin(checkpoint_name(jnp.sin(x), name)))

    def sines(name):
        kept = jax.checkpoint(lambda x: f(x, name),
                              policy=hybrid_lm.kept_policy())
        text = str(jax.make_jaxpr(jax.grad(kept))(jnp.ones(4)))
        return text.count(" sin ")
    for name in names:
        # kept: the inner sine is not run again
        assert sines(name) == 2 + (owner == "nobody"), name


def _ops(**kernels):
    """An operation table as ``trace_reduce.reduce`` gives it: each call an
    operation of its own, ``<kernel>`` then ``<kernel>.<n>``."""
    names = [k if i == 0 else "%s.%d" % (k, 3 * i + 1)
             for k, n in kernels.items() for i in range(n)]
    return dict({name: 0.01 for name in names}, **{
        "fusion.12": 0.5, "kda_conv_fwd.3": 0.1, "kda_conv_bwd": 0.1,
        "sparse_attention_fwd": 0.2})


@pytest.mark.parametrize("ops,want", [
    # ling3 a step: six KDA layers and a latent one, the parent's and ours
    (_ops(kda_fwd=12, flash_attention_fwd=2, kda_bwd=6,
          flash_attention_bwd=1), 2.0),
    (_ops(kda_fwd=6, flash_attention_fwd=1, kda_bwd=6,
          flash_attention_bwd=1), 1.0),
    # laguna: two full layers and three windowed ones, whose second
    # forward still runs
    (_ops(flash_attention_fwd=4, flash_window_fwd=6, flash_attention_bwd=2,
          flash_window_bwd=3), 2.0),
    (_ops(flash_attention_fwd=2, flash_window_fwd=6, flash_attention_bwd=2,
          flash_window_bwd=3), 1.6),
    (_ops(flash_attention_fwd=2), None),    # no backward: nothing to read
    (None, None),                           # no trace
], ids=["ling3-bare", "ling3-kept", "laguna-bare", "laguna-kept",
        "no-backward", "no-trace"])
def test_the_benchmark_counts_forwards_for_each_backward(ops, want):
    read = importlib.import_module("benchmark.run").reader(
        "kernel_forwards_per_backward.train")
    assert read({"trace": None if ops is None else {"ops": ops}}) == want


@pytest.mark.parametrize("ops,runs,want", [
    # ten steps traced: two sorts and what only looks like one
    ({"sort": 0.0143, "sort.7": 0.0128, "sortish_fusion": 0.5,
      "fusion.sort": 0.25, "kda_fwd.3": 0.1}, 10, 2.71),
    ({"sort.12": 0.004}, 4, 1.0),
    ({"fusion.12": 0.5}, 10, None),         # no sort ran: nothing to read
    ({"sort": 0.0143}, 0, None),            # no module's run
    (None, 10, None),                       # no trace
], ids=["two-sorts", "one-numbered", "no-sort", "no-run", "no-trace"])
def test_the_benchmark_sums_the_sorts_of_a_step(ops, runs, want):
    """``sort_ms.train``: the device milliseconds a step of the operations
    named ``sort`` or ``sort.<n>``, over the runs of the module that ran
    longest (the step's)."""
    read = importlib.import_module("benchmark.run").reader("sort_ms.train")
    trace = None if ops is None else {
        "ops": ops, "planes": 1,
        "modules": {"jit_step(1)": [0.4] * runs, "jit_small(2)": [1e-6] * 40}
        if runs else {}}
    got = read({"trace": trace})
    assert got is None if want is None else abs(got - want) < 1e-9
