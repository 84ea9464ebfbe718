"""A ``ShardedTrainStep`` that is dropped releases its block: the jitted
step stays in the compile service's store and in the executable ledger, and
must not keep the block's parameters and gradient buffers alive through its
closure (the benchmark frees the program before it runs the float32
reference on the same chip)."""
import gc
import weakref

import numpy as np
import pytest

import jax

import mxtpu as mx
from mxtpu import gluon
from mxtpu.parallel import ShardedTrainStep


def _step():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(96, activation="relu"), gluon.nn.Dense(10))
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0).randn(8, 48))
    y = mx.nd.array(np.arange(8) % 10)
    net(x)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh,
                            optimizer="adam",
                            optimizer_params={"learning_rate": 1e-3})
    return net, step, x, y


@pytest.mark.parametrize("steps", [1, 3])
def test_dropped_step_releases_its_block(steps):
    net, step, x, y = _step()
    for _ in range(steps):
        loss = float(step(x, y).asnumpy())
    assert np.isfinite(loss)
    weight = next(iter(net.collect_params().values())).data()._data
    block, array = weakref.ref(net), weakref.ref(weight)
    del net, step, weight
    gc.collect()
    assert block() is None and array() is None
    assert not [a for a in jax.live_arrays() if a.shape == (96, 48)]


def test_a_live_step_still_retraces_for_a_new_batch_shape():
    """The weak reference is live whenever the step is traced: a second
    batch shape traces it again through the same instance."""
    net, step, x, y = _step()
    first = float(step(x, y).asnumpy())
    x2 = mx.nd.array(np.random.RandomState(1).randn(16, 48))
    y2 = mx.nd.array(np.arange(16) % 10)
    second = float(step(x2, y2).asnumpy())
    assert np.isfinite(first) and np.isfinite(second)
