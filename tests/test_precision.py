"""MXU precision policy guard (PERF.md root cause).

bf16 contractions must lower with precision DEFAULT (native one-pass MXU);
f32 contractions must keep HIGHEST (the honest-f32 global). A regression
here silently costs 3-6x conv throughput on TPU, which is exactly what
capped rounds 1-2 — so the policy is pinned by inspecting lowered
StableHLO, not by timing.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx


def _conv_precisions(dtype):
    from mxtpu.ops.registry import REGISTRY

    conv_fn = REGISTRY["Convolution"].fn  # raw jnp-level op
    x = jnp.zeros((1, 8, 8, 4), dtype)
    w = jnp.zeros((3, 3, 4, 8), dtype)
    lowered = jax.jit(lambda a, b: conv_fn(
        a, b, kernel=(3, 3), num_filter=8, no_bias=True,
        layout="NHWC")).lower(x, w)
    txt = lowered.as_text()
    return re.findall(r"precision_config = \[([^\]]*)\]", txt)


def test_bf16_conv_uses_default_precision():
    precs = _conv_precisions(jnp.bfloat16)
    assert precs and all("DEFAULT" in p for p in precs), precs


def test_f32_conv_keeps_highest_precision():
    precs = _conv_precisions(jnp.float32)
    assert precs and all("HIGHEST" in p for p in precs), precs


def test_mixed_dtype_falls_back_to_honest_precision():
    """bf16 weights with f32 activations must NOT downgrade to one-pass
    bf16 — the honest global wins when any operand is f32."""
    from mxtpu.ops.precision_util import mxu_precision
    from jax import lax

    assert mxu_precision(jnp.zeros((2,), jnp.bfloat16),
                         jnp.zeros((2,), jnp.float32)) is None
    assert mxu_precision(jnp.zeros((2,), jnp.bfloat16),
                         jnp.zeros((2,), jnp.bfloat16)) \
        == lax.Precision.DEFAULT


def test_whole_resnet_step_precision():
    """The exact bench model: every conv in the full train step must be
    DEFAULT under bf16 (158/158 were HIGHEST before the fix)."""
    from mxtpu import gluon
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh

    with mx.layout("NHWC"):
        net = vision.resnet18_v1()
    net.initialize()
    x = mx.nd.array(np.zeros((8, 224, 224, 3), np.float32))
    net(x)
    net.cast("bfloat16")
    x = x.astype("bfloat16")
    y = mx.nd.zeros((8,))
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            data_parallel_mesh(), optimizer="sgd")
    step(x, y)
    txt = step.lowered().as_text()
    convs = re.findall(r"convolution.*", txt)
    assert convs
    bad = [c for c in convs if "HIGHEST" in c]
    assert not bad, "%d/%d convs at HIGHEST precision" % (len(bad),
                                                          len(convs))


def _bn_training_output(x, hybridize):
    from mxtpu import autograd
    from mxtpu.gluon import nn
    net = nn.BatchNorm(in_channels=x.shape[1])
    net.initialize()
    if hybridize:
        net.hybridize()
    with autograd.record():
        out = net(mx.nd.array(x))
    return net, out.asnumpy()


@pytest.mark.parametrize("hybridize", [False, True])
def test_bn_onepass_stats_match_twopass(hybridize):
    """BatchNorm's single-read statistics (E[x^2] - mean^2, clamped at 0)
    against the two-pass ``jnp.var`` form, to f32 tolerance, in training
    mode, eager and hybridized."""
    from mxtpu.ops.nn import bn_batch_stats
    x = np.random.RandomState(0).uniform(-2, 2, (8, 6, 5, 5)) \
        .astype(np.float32)
    red = (0, 2, 3)
    mean, var = bn_batch_stats(jnp.asarray(x), red)
    np.testing.assert_allclose(mean, jnp.mean(x, axis=red), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(var, jnp.var(x, axis=red), rtol=1e-4,
                               atol=1e-5)
    # a constant channel: the cancellation floor holds the variance at 0
    flat = np.full((8, 6, 5, 5), 3.1, np.float32)
    assert float(jnp.min(bn_batch_stats(jnp.asarray(flat), red)[1])) >= 0.0

    net, one = _bn_training_output(x, hybridize)
    shape = (1, 6, 1, 1)
    two = (x - jnp.mean(x, axis=red).reshape(shape)) * jax.lax.rsqrt(
        jnp.var(x, axis=red).reshape(shape) + net._kwargs["eps"])
    np.testing.assert_allclose(one, two, rtol=1e-4, atol=1e-5)


def test_cached_op_recompiles_on_policy_flip(monkeypatch):
    """The cache-key guarantee itself: one SHARED hybridized net must
    recompile when a ``registry.policy_key`` lever flips (a stale reuse
    would make A/B measurements vacuous)."""
    x = np.random.RandomState(0).uniform(-2, 2, (8, 6, 5, 5)) \
        .astype(np.float32)
    from mxtpu import autograd
    monkeypatch.delenv("MXTPU_RNN_HOIST", raising=False)
    net, _ = _bn_training_output(x, True)
    n_jits = len(net._cached_op._jits)
    monkeypatch.setenv("MXTPU_RNN_HOIST", "0")
    with autograd.record():
        net(mx.nd.array(x))
    assert len(net._cached_op._jits) > n_jits, \
        "policy flip did not recompile the cached executable"
