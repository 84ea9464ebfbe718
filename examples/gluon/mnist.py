"""The canonical first Gluon example: an MLP on MNIST
(ref: example/gluon/mnist.py — same model, args, and loop shape).

TPU-native notes: ``net.hybridize()`` compiles the forward to one XLA
executable (the reference's CachedOp); everything else is the familiar
record/backward/Trainer.step loop. Runs on the real MNIST files when
present (``--data-dir``, idx format) and on a synthetic pattern set
otherwise, so the example is runnable in hermetic environments.

    python examples/gluon/mnist.py --epochs 2
"""
import argparse
import os

import numpy as np

import mxtpu as mx
from mxtpu import autograd, gluon
from mxtpu.gluon import nn


def build_net(hidden):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(hidden, activation="relu"))
        net.add(nn.Dense(64, activation="relu"))
        net.add(nn.Dense(10))
    return net


def load_data(args):
    """(train_x, train_y, val_x, val_y) as numpy, images flattened f32."""
    mnist_dir = args.data_dir
    imgs = os.path.join(mnist_dir, "train-images-idx3-ubyte.gz")
    if mnist_dir and os.path.exists(imgs):
        from mxtpu.gluon.data.vision import MNIST

        def flat(ds):
            # one bulk asnumpy of the dataset's image tensor — NOT
            # per-sample conversion (object arrays, device round-trips)
            x = ds._data.asnumpy().reshape(len(ds), -1) / 255.0
            return x.astype(np.float32), np.asarray(ds._label)

        tx, ty = flat(MNIST(root=mnist_dir, train=True))
        vx, vy = flat(MNIST(root=mnist_dir, train=False))
        return tx, ty, vx, vy
    # synthetic: 10 fixed class prototypes + noise — learnable in seconds
    rng = np.random.RandomState(42)
    protos = rng.uniform(0, 1, (10, 784)).astype(np.float32)

    def make(n):
        y = rng.randint(0, 10, n)
        x = protos[y] + rng.normal(0, 0.15, (n, 784)).astype(np.float32)
        return x.astype(np.float32), y

    tx, ty = make(args.synthetic_size)
    vx, vy = make(max(args.synthetic_size // 5, args.batch_size))
    return tx, ty, vx, vy


def evaluate(net, x, y, batch):
    metric = mx.metric.Accuracy()
    for i in range(0, len(x) - batch + 1, batch):
        out = net(mx.nd.array(x[i:i + batch]))
        metric.update([mx.nd.array(y[i:i + batch])], [out])
    return metric.get()[1]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=100)
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--data-dir", default="")
    p.add_argument("--synthetic-size", type=int, default=2000)
    p.add_argument("--no-hybridize", action="store_true")
    args = p.parse_args()

    tx, ty, vx, vy = load_data(args)
    net = build_net(args.hidden)
    net.initialize(mx.init.Xavier())
    if not args.no_hybridize:
        net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr})

    b = args.batch_size
    acc = evaluate(net, vx, vy, b)
    for epoch in range(args.epochs):
        cum = 0.0
        nb = 0
        for i in range(0, len(tx) - b + 1, b):
            data = mx.nd.array(tx[i:i + b])
            label = mx.nd.array(ty[i:i + b])
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(b)
            cum += float(loss.mean().asnumpy())
            nb += 1
        acc = evaluate(net, vx, vy, b)
        print("epoch %d loss %.4f val-acc %.4f" % (epoch, cum / max(nb, 1),
                                                   acc))
    return acc


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
