"""Image-classification training example (ref: example/image-classification/
train_cifar10.py + train_mnist.py — the reference's most-used entry point).

Demonstrates the canonical training loop on a zoo model: Gluon Trainer +
autograd (the modern path) or Module.fit (the classic path), checkpoints,
Speedometer logging, and bf16/NHWC TPU defaults. Runs on synthetic CIFAR-10
shaped data by default (this environment has no dataset egress); pass
--data-dir with real CIFAR-10 RecordIO packs (made by tools/im2rec.py) to
train for real.

Usage:
    python examples/image_classification/train_cifar10.py \
        --model resnet18_v1 --epochs 2 --batch-size 128 [--module]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def synthetic_iter(batch_size, num_batches, image_size=32, classes=10,
                   layout="NHWC", seed=0):
    import mxtpu as mx

    rng = np.random.RandomState(seed)
    shape = ((batch_size, image_size, image_size, 3) if layout == "NHWC"
             else (batch_size, 3, image_size, image_size))
    data = rng.uniform(-1, 1, (num_batches,) + shape).astype(np.float32)
    label = rng.randint(0, classes, (num_batches, batch_size)) \
        .astype(np.float32)
    return mx.io.NDArrayIter(
        data={"data": data.reshape((-1,) + shape[1:])},
        label={"softmax_label": label.reshape(-1)},
        batch_size=batch_size)


def train_gluon(args):
    import mxtpu as mx
    from mxtpu import autograd, gluon
    from mxtpu.gluon.model_zoo import vision

    with mx.layout(args.layout):
        net = vision.get_model(args.model, classes=args.classes,
                               thumbnail=True)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    metric = mx.metric.Accuracy()

    it = synthetic_iter(args.batch_size, args.num_batches,
                        layout=args.layout, classes=args.classes)
    for epoch in range(args.epochs):
        it.reset()
        metric.reset()
        tic = time.time()
        n = 0
        for batch in it:
            x, y = batch.data[0], batch.label[0]
            if args.dtype != "float32":
                x = x.astype(args.dtype)
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(args.batch_size)
            metric.update([y], [out])
            n += args.batch_size
        name, acc = metric.get()
        print("epoch %d: %s=%.4f  %.1f samples/s"
              % (epoch, name, acc, n / (time.time() - tic)), flush=True)
    if args.save_prefix:
        net.export(args.save_prefix, epoch=args.epochs)
        print("exported to %s-symbol.json / -%04d.params"
              % (args.save_prefix, args.epochs))
    return net


def train_module(args):
    """The classic symbolic path (ref: train loop in
    example/image-classification/common/fit.py)."""
    import mxtpu as mx

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=32, kernel=(3, 3), pad=(1, 1),
                             name="conv1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, pool_type="max", kernel=(2, 2), stride=(2, 2))
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=args.classes, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    it = synthetic_iter(args.batch_size, args.num_batches, layout="NCHW",
                        classes=args.classes)
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
    mod.fit(it, num_epoch=args.epochs,
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr},
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 10),
            epoch_end_callback=(mx.callback.do_checkpoint(args.save_prefix)
                                if args.save_prefix else None))
    return mod


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet18_v1")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--num-batches", type=int, default=20,
                   help="synthetic batches per epoch")
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--layout", default="NHWC")
    p.add_argument("--save-prefix", default="")
    p.add_argument("--module", action="store_true",
                   help="use the classic Module/Symbol path")
    args = p.parse_args(argv)
    if args.module:
        train_module(args)
    else:
        train_gluon(args)


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
