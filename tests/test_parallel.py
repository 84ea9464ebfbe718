"""Tests for mxtpu.parallel — run on the 8-device virtual CPU mesh (conftest),
the analog of the reference's multi-process-localhost distributed tests
(SURVEY §4: tests/nightly/dist_sync_kvstore.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon import nn
from mxtpu.parallel import (ShardedTrainStep, data_parallel_mesh, make_mesh,
                            pure_forward, ring_self_attention)
from mxtpu.parallel.ring_attention import _dense_attention

pytestmark = pytest.mark.multidevice


def test_make_mesh():
    mesh = make_mesh({"data": 2, "sp": 2, "model": 2})
    assert mesh.shape == {"data": 2, "sp": 2, "model": 2}
    mesh = make_mesh({"data": -1})
    assert mesh.shape["data"] == 8
    with pytest.raises(ValueError):
        make_mesh({"data": 16})


def _mlp():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    return net


def test_pure_forward_matches_eager():
    net = _mlp()
    x = mx.nd.random.uniform(shape=(8, 10))
    eager = net(x).asnumpy()
    fn, params = pure_forward(net)
    out = jax.jit(fn)(params, x._data)
    np.testing.assert_allclose(np.asarray(out), eager, rtol=1e-5, atol=1e-5)


def test_sharded_train_step_dp_matches_single_device():
    """DP over 8 devices must match the single-logical-device update exactly
    (the reference's check_consistency cross-device comparison pattern)."""
    np.random.seed(0)
    x = np.random.uniform(size=(16, 10)).astype(np.float32)
    y = np.random.randint(0, 4, size=(16,)).astype(np.float32)

    def build():
        mx.random.seed(0)
        net = _mlp()
        net(mx.nd.array(x))  # settle shapes
        return net

    loss = gluon.loss.SoftmaxCrossEntropyLoss()

    # reference: plain autograd + Trainer on one device
    ref = build()
    trainer = gluon.Trainer(ref.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    for _ in range(3):
        with mx.autograd.record():
            l = loss(ref(mx.nd.array(x)), mx.nd.array(y))
        l.backward()
        # backward() of the (batch,)-shaped loss seeds ones => d sum(l_i);
        # step(batch) rescales to d mean(l_i), matching the sharded step
        trainer.step(16)
        ref_loss = l.mean().asnumpy()

    # sharded: same model, same data, 8-way DP
    net = build()
    mesh = data_parallel_mesh()
    step = ShardedTrainStep(net, loss, mesh, optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1,
                                              "momentum": 0.9})
    for _ in range(3):
        sharded_loss = step(mx.nd.array(x), mx.nd.array(y)).asnumpy()

    np.testing.assert_allclose(sharded_loss, ref_loss, rtol=1e-4, atol=1e-5)
    for p_ref, p_new in zip(ref.collect_params().values(),
                            net.collect_params().values()):
        np.testing.assert_allclose(p_new.data().asnumpy(),
                                   p_ref.data().asnumpy(),
                                   rtol=1e-4, atol=1e-5)


def test_sharded_train_step_tp():
    """Tensor-parallel placement: weights sharded over the model axis still
    produce the same loss trajectory as replicated."""
    np.random.seed(0)
    x = np.random.uniform(size=(8, 16)).astype(np.float32)
    y = np.random.randint(0, 8, size=(8,)).astype(np.float32)
    loss = gluon.loss.SoftmaxCrossEntropyLoss()

    def run(param_specs):
        mx.random.seed(0)
        net = nn.HybridSequential(prefix="tp_")
        with net.name_scope():
            net.add(nn.Dense(64, activation="relu"))
            net.add(nn.Dense(8))
        net.initialize()
        net(mx.nd.array(x))
        mesh = make_mesh({"data": 2, "model": 4})
        step = ShardedTrainStep(net, loss, mesh,
                                optimizer_params={"learning_rate": 0.05},
                                param_specs=param_specs)
        out = [step(mx.nd.array(x), mx.nd.array(y)).asnumpy() for _ in range(3)]
        return out

    replicated = run(())
    # Dense weight is [units, in]: shard the output dim (column parallel)
    sharded = run([(r".*dense0_weight", P("model", None)),
                   (r".*dense0_bias", P("model"))])
    np.testing.assert_allclose(sharded, replicated, rtol=1e-4, atol=1e-6)


def test_batchnorm_aux_updates_in_sharded_step():
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16))
        net.add(nn.BatchNorm())
        net.add(nn.Dense(4))
    net.initialize()
    x = mx.nd.random.uniform(shape=(16, 8))
    y = mx.nd.zeros((16,))
    net(x)
    bn_mean_before = [p.data().asnumpy().copy()
                      for n, p in net.collect_params().items()
                      if "running_mean" in n][0]
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    step = ShardedTrainStep(net, loss, data_parallel_mesh())
    step(x, y)
    bn_mean_after = [p.data().asnumpy()
                     for n, p in net.collect_params().items()
                     if "running_mean" in n][0]
    assert not np.allclose(bn_mean_before, bn_mean_after)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    """Ring attention over a 4-way sequence shard == dense attention."""
    np.random.seed(0)
    b, h, t, d = 2, 4, 32, 8
    q = jnp.asarray(np.random.normal(size=(b, h, t, d)).astype(np.float32))
    k = jnp.asarray(np.random.normal(size=(b, h, t, d)).astype(np.float32))
    v = jnp.asarray(np.random.normal(size=(b, h, t, d)).astype(np.float32))
    dense = _dense_attention(q, k, v, causal=causal)
    mesh = make_mesh({"data": 2, "sp": 4})
    ring = ring_self_attention(q, k, v, mesh=mesh, seq_axis="sp",
                               batch_axis="data", causal=causal)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grads_match_dense():
    np.random.seed(1)
    b, h, t, d = 1, 2, 16, 4
    q = jnp.asarray(np.random.normal(size=(b, h, t, d)).astype(np.float32))
    k = jnp.asarray(np.random.normal(size=(b, h, t, d)).astype(np.float32))
    v = jnp.asarray(np.random.normal(size=(b, h, t, d)).astype(np.float32))
    mesh = make_mesh({"sp": 4})

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        out = ring_self_attention(q, k, v, mesh=mesh, seq_axis="sp",
                                  causal=True)
        return jnp.sum(out ** 2)

    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for gd, gr in zip(g_dense, g_ring):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=2e-4, atol=2e-5)


def test_sharded_dropout_decorrelated_across_shards():
    """Parallel-PRNG story (ref kParallelRandom, src/resource.cc:87;
    mxtpu/random.py docstring): a dropout mask drawn over a batch-sharded
    tensor must be distinct on every data shard — GSPMD partitions the
    generator over the global shape, so no per-device PRNG resource is
    needed."""
    from jax.sharding import NamedSharding
    from mxtpu.ops.nn import Dropout
    from mxtpu import autograd
    from mxtpu.ndarray import NDArray

    mesh = make_mesh({"data": 8})
    x = jnp.ones((8, 4096), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("data")))
    prev = autograd.set_training(True)
    try:
        out = Dropout(NDArray(x), p=0.5)
    finally:
        autograd.set_training(prev)
    mask = np.asarray(out.asnumpy() != 0)
    rows = [mask[i] for i in range(8)]
    # each device's row must not equal any other's (same-key-per-shard
    # implementations fail this with probability ~1)
    for i in range(8):
        for j in range(i + 1, 8):
            assert not np.array_equal(rows[i], rows[j])


def test_zero1_sharded_weight_update_matches_replicated():
    """shard_weight_update=True (ZeRO-1, arXiv:2004.13336): optimizer state
    is sharded over the data axis, the loss trajectory is unchanged, and
    the state arrays are REALLY sharded (memory claim is structural)."""
    def build():
        np.random.seed(0)
        mx.random.seed(0)  # parameter init draws from the jax PRNG
        net = nn.HybridSequential()
        net.add(nn.Dense(64, activation="relu"), nn.Dense(16))
        net.initialize()
        x = mx.nd.array(np.random.randn(16, 32).astype(np.float32))
        y = mx.nd.array(np.random.randint(0, 16, (16,)).astype(np.float32))
        net(x)
        return net, x, y

    mesh = make_mesh({"data": 8})
    losses = {}
    for zero1 in (False, True):
        net, x, y = build()
        step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                mesh, optimizer="sgd",
                                optimizer_params={"learning_rate": 0.1,
                                                  "momentum": 0.9},
                                shard_weight_update=zero1)
        ls = [float(step(x, y).asnumpy()) for _ in range(5)]
        losses[zero1] = ls
        if zero1:
            # states live in the rule registry's structure (None | array |
            # tuple) since the optimizer adapters merged with optimizer_fused
            momenta = [s for st in step._opt_states
                       for s in jax.tree_util.tree_leaves(st)]
            sharded = [m for m in momenta
                       if any(ax is not None for ax in m.sharding.spec)]
            assert sharded, "no optimizer state was actually sharded"
            for m in sharded:
                assert m.sharding.spec[0] == "data"
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-5,
                               atol=1e-6)


def test_pipeline_apply_matches_sequential_fwd_and_grad():
    """GPipe-style pipeline over pipe x data (mxtpu/parallel/pipeline.py —
    beyond-reference feature, SURVEY §2.3 'Parallelism NOT present'):
    forward and grads must equal the sequential layer stack."""
    from jax.sharding import Mesh
    from mxtpu.parallel import pipeline_apply

    rng = np.random.RandomState(0)
    n_layers, d = 8, 16
    params = {"w": jnp.asarray(rng.randn(n_layers, d, d) * 0.2, jnp.float32),
              "b": jnp.asarray(rng.randn(n_layers, d) * 0.1, jnp.float32)}

    def layer(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    x = jnp.asarray(rng.randn(32, d), jnp.float32)
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("pipe", "data"))

    def seq(params, x):
        h, _ = jax.lax.scan(lambda h, p: (layer(p, h), None), x, params)
        return h

    out = pipeline_apply(layer, params, x, mesh, axis="pipe",
                         num_microbatches=8, batch_axis="data")
    np.testing.assert_allclose(np.asarray(out), np.asarray(seq(params, x)),
                               rtol=1e-5, atol=1e-5)

    g_pipe = jax.grad(lambda p: jnp.sum(pipeline_apply(
        layer, p, x, mesh, axis="pipe", num_microbatches=8,
        batch_axis="data") ** 2))(params)
    g_seq = jax.grad(lambda p: jnp.sum(seq(p, x) ** 2))(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_pipe[k]),
                                   np.asarray(g_seq[k]), rtol=1e-4,
                                   atol=1e-5)


def test_pipeline_apply_validations():
    from jax.sharding import Mesh
    from mxtpu.parallel import pipeline_apply

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("pipe", "data"))
    params = {"w": jnp.zeros((6, 4, 4))}  # 6 layers over 4 stages: invalid
    with pytest.raises(mx.MXNetError, match="must divide"):
        pipeline_apply(lambda p, h: h, params, jnp.zeros((8, 4)), mesh)
    params = {"w": jnp.zeros((4, 4, 4))}
    with pytest.raises(mx.MXNetError, match="microbatches"):
        pipeline_apply(lambda p, h: h, params, jnp.zeros((9, 4)), mesh,
                       num_microbatches=4)


def test_switch_moe_dense_and_expert_parallel_parity():
    """Top-1 switch MoE (mxtpu/parallel/moe.py — beyond-reference):
    einsum-dispatch output must equal a per-token reference, on one device
    AND with experts sharded over an expert mesh axis."""
    from jax.sharding import Mesh, NamedSharding
    from mxtpu.parallel import shard_experts, switch_ffn

    rng = np.random.RandomState(0)
    T, D, H, E = 32, 8, 16, 4
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    router = jnp.asarray(rng.randn(D, E) * 0.5, jnp.float32)
    w1 = jnp.asarray(rng.randn(E, D, H) * 0.2, jnp.float32)
    b1 = jnp.zeros((E, H), jnp.float32)
    w2 = jnp.asarray(rng.randn(E, H, D) * 0.2, jnp.float32)
    b2 = jnp.zeros((E, D), jnp.float32)

    out, aux = switch_ffn(x, router, w1, b1, w2, b2, capacity_factor=4.0)
    logits = np.asarray(x @ router)
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    ref = np.zeros((T, D), np.float32)
    for t in range(T):
        e_i = int(np.argmax(probs[t]))
        h = np.maximum(np.asarray(x[t]) @ np.asarray(w1[e_i]), 0)
        ref[t] = (h @ np.asarray(w2[e_i])) * probs[t].max()
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
    assert float(aux) >= 1.0  # Switch aux loss lower bound at balance

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("expert", "data"))
    params = shard_experts({"w1": w1, "b1": b1, "w2": w2, "b2": b2}, mesh,
                           num_experts=E)
    assert params["w1"].sharding.spec == P("expert")

    @jax.jit
    def run(x, router, p):
        return switch_ffn(x, router, p["w1"], p["b1"], p["w2"], p["b2"],
                          4.0)[0]

    x_sh = jax.device_put(x, NamedSharding(mesh, P("data")))
    np.testing.assert_allclose(np.asarray(run(x_sh, router, params)), ref,
                               rtol=1e-4, atol=1e-5)


def test_switch_moe_capacity_drops_tokens():
    from mxtpu.parallel import switch_ffn

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, 8), jnp.float32)
    router = jnp.asarray(rng.randn(8, 4) * 0.5, jnp.float32)
    w1 = jnp.asarray(rng.randn(4, 8, 16) * 0.2, jnp.float32)
    w2 = jnp.asarray(rng.randn(4, 16, 8) * 0.2, jnp.float32)
    out, _ = switch_ffn(x, router, w1, jnp.zeros((4, 16)), w2,
                        jnp.zeros((4, 8)), capacity_factor=0.25)
    dropped = int((np.abs(np.asarray(out)).sum(1) == 0).sum())
    assert dropped > 0  # over-capacity tokens are zeroed (Switch semantics)


def test_moe_transformer_lm_trains_expert_parallel():
    """Zoo TransformerLM(num_experts=4) under an expert x data sharded
    train step: the Switch aux loss joins the objective inside the trace
    and the loss decreases."""
    from mxtpu.gluon.model_zoo.transformer import (TransformerLM,
                                                   expert_parallel_rules)

    mx.random.seed(0)
    vocab = 64
    net = TransformerLM(vocab_size=vocab, dim=32, num_heads=4, num_layers=2,
                        max_len=64, num_experts=4)
    net.initialize()
    rng = np.random.RandomState(0)
    tokens = mx.nd.array(rng.randint(0, vocab, (4, 16)), dtype="int32")
    labels = mx.nd.array(rng.randint(0, vocab, (4, 16)), dtype="float32")
    net(tokens)
    assert float(net.aux_loss().asnumpy()) >= 1.0  # eager aux available

    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(block, tokens, labels):
        ce = loss_blk(block(tokens).reshape((-1, vocab)),
                      labels.reshape((-1,)))
        return ce + 0.01 * block.aux_loss()

    mesh = make_mesh({"data": 2, "expert": 4})
    step = ShardedTrainStep(net, None, mesh, optimizer="adam",
                            optimizer_params={"learning_rate": 1e-3},
                            param_specs=expert_parallel_rules("expert"),
                            batch_specs=[P("data"), P("data")],
                            forward=forward)
    l1 = float(step(tokens, labels).asnumpy())
    for _ in range(3):
        l2 = float(step(tokens, labels).asnumpy())
    assert l2 < l1
    # the expert weights really live on the expert axis
    moe_w1 = [d for p, d in zip(step._params, step._param_datas)
              if p.name.endswith("moe_w1")]
    assert moe_w1 and moe_w1[0].sharding.spec[0] == "expert"


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_attention_matches_dense(causal):
    """The flash-bodied ring (per-step fused blocks merged via lse) must
    reproduce full dense attention over the sharded sequence, forward AND
    gradients (the merge + whole-block visibility selects + g_lse path)."""
    from mxtpu.parallel.ring_attention import (_dense_attention,
                                               ring_flash_attention)

    mesh = make_mesh({"sp": 4})
    B, H, T, D = 2, 2, 32, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    spec = P(None, None, "sp", None)

    def ring(q_, k_, v_):
        from mxtpu.parallel.shmap import shard_map
        body = lambda a, b, c: ring_flash_attention(  # noqa: E731
            a, b, c, axis_name="sp", causal=causal)
        return shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q_, k_, v_)

    out = ring(q, k, v)
    ref = _dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    g = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    grads = jax.grad(lambda q_, k_, v_: jnp.sum(ring(q_, k_, v_) * g),
                     argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(
        lambda q_, k_, v_: jnp.sum(
            _dense_attention(q_, k_, v_, causal=causal) * g),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_ring_self_attention_flash_switch(monkeypatch):
    """MXTPU_RING_FLASH=1 routes ring_self_attention through the flash
    body with identical numerics."""
    mesh = make_mesh({"sp": 4})
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
    base = ring_self_attention(q, q, q, mesh=mesh, causal=True)
    monkeypatch.setenv("MXTPU_RING_FLASH", "1")
    flash = ring_self_attention(q, q, q, mesh=mesh, causal=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(base),
                               rtol=2e-4, atol=2e-4)


def test_sharded_train_step_policy_flip_recompiles(monkeypatch):
    """A registry.policy_key lever flip must rebuild the step executable
    (otherwise the trainer silently reuses an executable traced under the
    stale policy — the aliasing hazard at registry.py:90), and every build
    must report to the 'parallel.train_step' retrace site."""
    from mxtpu import telemetry

    np.random.seed(0)
    x = np.random.uniform(size=(8, 10)).astype(np.float32)
    y = np.random.randint(0, 4, size=(8,)).astype(np.float32)
    mx.random.seed(0)
    net = _mlp()
    net(mx.nd.array(x))  # settle shapes
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            data_parallel_mesh())

    def compiles():
        st = telemetry.retrace_stats("parallel.train_step")
        return st["compiles"] if st else 0

    before = compiles()
    step(mx.nd.array(x), mx.nd.array(y)).asnumpy()
    step(mx.nd.array(x), mx.nd.array(y)).asnumpy()
    assert compiles() == before + 1  # steady state: one build, then cached

    monkeypatch.setenv("MXTPU_NUMERICS_GUARD", "1")  # flip a policy_key lever
    step(mx.nd.array(x), mx.nd.array(y)).asnumpy()
    assert compiles() == before + 2  # exactly one rebuild per flip

    step(mx.nd.array(x), mx.nd.array(y)).asnumpy()
    assert compiles() == before + 2  # flipped policy is now the cached one
