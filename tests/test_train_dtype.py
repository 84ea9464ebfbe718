"""Low-precision end-to-end training tier (ref: tests/python/train/
test_dtype.py — the fp16 training accuracy asserts, mapped to bf16, the
TPU design point). Exercises the one-pass bf16 convolution and the
f32-accumulate dot policy (precision_util.py) through a REAL training run with an
accuracy bar, not just op-level parity."""
import numpy as np

import mxtpu as mx
from mxtpu import autograd, gluon
from mxtpu.gluon import nn


def _blob_data(n=256, size=12, seed=0):
    """Two classes of images separable by a bright vs dark center blob."""
    rng = np.random.RandomState(seed)
    x = rng.uniform(-0.4, 0.4, (n, size, size, 3)).astype(np.float32)
    y = rng.randint(0, 2, n).astype(np.float32)
    c = size // 2
    for i in range(n):
        sign = 1.0 if y[i] else -1.0
        x[i, c - 2:c + 2, c - 2:c + 2] += sign * 0.8
    return x, y


def test_bf16_conv_training_reaches_accuracy():
    mx.random.seed(0)
    with mx.layout("NHWC"):
        net = nn.HybridSequential()
        net.add(nn.Conv2D(8, 3, padding=1, layout="NHWC",
                          activation="relu"),
                nn.Conv2D(8, 3, padding=1, layout="NHWC",
                          activation="relu"),
                nn.GlobalAvgPool2D(layout="NHWC"),
                nn.Dense(2))
    net.initialize(mx.init.Xavier())
    xf, y = _blob_data()
    net(mx.nd.array(xf[:8]))  # settle shapes
    net.cast("bfloat16")
    net.hybridize()

    # multi-precision: bf16 weights, f32 master copies (ref optimizer.py
    # mp_sgd_update pattern)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.2, "momentum": 0.9,
                             "multi_precision": True})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    bs = 32
    for epoch in range(4):
        for i in range(0, len(xf), bs):
            xb = mx.nd.array(xf[i:i + bs]).astype("bfloat16")
            yb = mx.nd.array(y[i:i + bs])
            with autograd.record():
                out = net(xb)
                loss = loss_fn(out, yb)
            loss.backward()
            trainer.step(bs)

    logits = net(mx.nd.array(xf).astype("bfloat16")).asnumpy()
    acc = float((logits.argmax(1) == y).mean())
    assert acc >= 0.95, "bf16 training accuracy %.3f < 0.95" % acc
    # weights really are stored bf16 (the fast path was exercised)
    w = list(net.collect_params().values())[0].data()
    assert str(w.dtype) == "bfloat16"
