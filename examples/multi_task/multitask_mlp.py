"""Multi-task learning: one trunk, two heads, two losses
(ref: example/multi-task/example_multi_task.py — a shared body with a
classification head per task, losses summed before backward).

The synthetic task pair shares structure (both depend on the same latent
projection), so the shared trunk genuinely helps — the example asserts
both heads learn.

    python examples/multi_task/multitask_mlp.py --epochs 5
"""
import argparse

import numpy as np

import mxtpu as mx
from mxtpu import autograd, gluon
from mxtpu.gluon import nn
from mxtpu.gluon.block import HybridBlock


class MultiTaskNet(HybridBlock):
    def __init__(self, hidden, c1, c2, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.trunk = nn.HybridSequential()
            self.trunk.add(nn.Dense(hidden, activation="relu"))
            self.trunk.add(nn.Dense(hidden // 2, activation="relu"))
            self.head1 = nn.Dense(c1)
            self.head2 = nn.Dense(c2)

    def hybrid_forward(self, F, x):
        z = self.trunk(x)
        return self.head1(z), self.head2(z)


def make_data(rng, n, nin, c1, w):
    x = rng.normal(0, 1, (n, nin)).astype(np.float32)
    z = x @ w
    y1 = z[:, :c1].argmax(1).astype(np.float32)       # task 1: argmax class
    y2 = (z.sum(1) > 0).astype(np.float32)            # task 2: sign, binary
    return x, y1, y2


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--classes", type=int, default=5)
    p.add_argument("--train-size", type=int, default=2048)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--task2-weight", type=float, default=0.5)
    args = p.parse_args()

    rng = np.random.RandomState(0)
    nin, c1 = 32, args.classes
    w = rng.normal(0, 1, (nin, max(c1, 8))).astype(np.float32)
    tx, t1, t2 = make_data(rng, args.train_size, nin, c1, w)
    vx, v1, v2 = make_data(rng, max(512, args.batch_size), nin, c1, w)

    net = MultiTaskNet(args.hidden, c1, 2)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})

    b = args.batch_size
    acc1 = acc2 = 0.0
    for epoch in range(args.epochs):
        cum, nb = 0.0, 0
        for i in range(0, len(tx) - b + 1, b):
            data = mx.nd.array(tx[i:i + b])
            l1 = mx.nd.array(t1[i:i + b])
            l2 = mx.nd.array(t2[i:i + b])
            with autograd.record():
                o1, o2 = net(data)
                loss = ce(o1, l1) + args.task2_weight * ce(o2, l2)
            loss.backward()
            trainer.step(b)
            cum += float(loss.mean().asnumpy())
            nb += 1
        m1, m2 = mx.metric.Accuracy(), mx.metric.Accuracy()
        for i in range(0, len(vx) - b + 1, b):
            o1, o2 = net(mx.nd.array(vx[i:i + b]))
            m1.update([mx.nd.array(v1[i:i + b])], [o1])
            m2.update([mx.nd.array(v2[i:i + b])], [o2])
        acc1, acc2 = m1.get()[1], m2.get()[1]
        print("epoch %d loss %.4f task1-acc %.4f task2-acc %.4f"
              % (epoch, cum / max(nb, 1), acc1, acc2))
    return acc1, acc2


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
