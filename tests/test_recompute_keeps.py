"""What a recomputed block keeps: the attention and KDA kernels name their
forwards' outputs inside their ``custom_vjp`` forward rules
(``flash_attention.KEPT_NAMES``, ``kda.KEPT_NAMES``) and ``HybridLM`` puts
its blocks under ``jax.checkpoint`` with the policy that keeps exactly
those (``hybrid_lm.kept_policy``). Here, a kernel at a time: a function
under that checkpoint holds the kernel's forward once when differentiated
where the bare checkpoint holds it twice, and both give the same gradients
bit for bit; and the benchmark's reader of what ran on the device. The two
recomputed models count theirs in ``test_ling3_flash.py`` and
``test_laguna_s_2_1.py``; that the names lower to nothing under no
checkpoint is in ``test_ling3_flash.py``."""
import importlib

import pytest

import jax
import jax.numpy as jnp

from mxtpu.gluon.model_zoo import hybrid_lm

from _jaxpr_count import calls

fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
kda = importlib.import_module("mxtpu.ops.pallas.kda")

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


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_kept_forward_runs_once(monkeypatch, case):
    interpret, make, forward, once, kept = CASES[case]
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1" if interpret else "0")
    block, args = make()
    policy = hybrid_lm.kept_policy()
    wraps = {"none": lambda f: f, "bare": jax.checkpoint,
             "kept": lambda f: jax.checkpoint(f, policy=policy)}
    grads = {name: jax.grad(lambda *a, wrap=wrap: jnp.sum(wrap(block)(*a)),
                            argnums=tuple(range(len(args))))
             for name, wrap in wraps.items()}
    counted = {name: calls(jax.make_jaxpr(g)(*args))[forward]
               for name, g in grads.items()}
    assert counted == {"none": once, "bare": once + 1,
                       "kept": once + (not kept)}
    bare, kept = grads["bare"](*args), grads["kept"](*args)
    for a, b in zip(bare, kept):
        assert bool(jnp.all(a == b))
    assert all(bool(jnp.any(a != 0)) for a in kept)


def test_the_policy_keeps_the_kernels_names_and_no_other():
    """Each kernel file exports its names; the model's policy is their
    union, and a value under another name is not kept."""
    assert fa.KEPT_NAMES == ("flash_out", "flash_lse")
    assert kda.KEPT_NAMES == ("kda_o", "kda_states")
    from jax.ad_checkpoint import checkpoint_name

    def f(x, name):
        return jnp.sum(jnp.sin(checkpoint_name(jnp.sin(x), name)))

    def sines(name):
        kept = jax.checkpoint(lambda x: f(x, name),
                              policy=hybrid_lm.kept_policy())
        text = str(jax.make_jaxpr(jax.grad(kept))(jnp.ones(4)))
        return text.count(" sin ")
    for name in fa.KEPT_NAMES + kda.KEPT_NAMES:
        assert sines(name) == 2, name       # the inner sine is not run again
    assert sines("another") == 3


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
