"""DCGAN on synthetic images (ref: example/gluon/dcgan.py — same G/D
architectures scaled down, same two-optimizer adversarial loop).

Demonstrates multi-network training: two Blocks, two Trainers, the
real/fake label trick, and alternating updates — the loop structure the
reference's GAN examples established. Images are synthetic 32x32 blobs
(hermetic); swap ``make_batch`` for a DataLoader over real data.

    python examples/gluon/dcgan.py --epochs 1
"""
import argparse

import numpy as np

import mxtpu as mx
from mxtpu import autograd, gluon
from mxtpu.gluon import nn


def build_generator(ngf, nz):
    g = nn.HybridSequential()
    with g.name_scope():
        # nz -> 4x4 -> 8x8 -> 16x16 -> 32x32
        g.add(nn.Dense(ngf * 4 * 4 * 4, use_bias=False))
        g.add(nn.HybridLambda(lambda F, x: x.reshape((-1, ngf * 4, 4, 4))))
        g.add(nn.BatchNorm(), nn.Activation("relu"))
        g.add(nn.Conv2DTranspose(ngf * 2, 4, strides=2, padding=1,
                                 use_bias=False))
        g.add(nn.BatchNorm(), nn.Activation("relu"))
        g.add(nn.Conv2DTranspose(ngf, 4, strides=2, padding=1,
                                 use_bias=False))
        g.add(nn.BatchNorm(), nn.Activation("relu"))
        g.add(nn.Conv2DTranspose(3, 4, strides=2, padding=1, use_bias=False))
        g.add(nn.Activation("tanh"))
    return g


def build_discriminator(ndf):
    d = nn.HybridSequential()
    with d.name_scope():
        d.add(nn.Conv2D(ndf, 4, strides=2, padding=1, use_bias=False))
        d.add(nn.LeakyReLU(0.2))
        d.add(nn.Conv2D(ndf * 2, 4, strides=2, padding=1, use_bias=False))
        d.add(nn.BatchNorm(), nn.LeakyReLU(0.2))
        d.add(nn.Conv2D(ndf * 4, 4, strides=2, padding=1, use_bias=False))
        d.add(nn.BatchNorm(), nn.LeakyReLU(0.2))
        d.add(nn.Conv2D(1, 4, strides=1, padding=0, use_bias=False))
        d.add(nn.HybridLambda(lambda F, x: x.reshape((-1,))))
    return d


def make_batch(rng, batch):
    """Synthetic 'real' images: smooth colored gradients in [-1, 1]."""
    xs = np.linspace(-1, 1, 32, dtype=np.float32)
    gx, gy = np.meshgrid(xs, xs)
    imgs = np.empty((batch, 3, 32, 32), np.float32)
    for i in range(batch):
        a, b, c = rng.uniform(-1, 1, 3)
        for ch in range(3):
            imgs[i, ch] = np.tanh(a * gx + b * gy + 0.3 * c * (ch - 1))
    return imgs


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--batches-per-epoch", type=int, default=20)
    p.add_argument("--nz", type=int, default=32)
    p.add_argument("--ngf", type=int, default=16)
    p.add_argument("--ndf", type=int, default=16)
    p.add_argument("--lr", type=float, default=2e-4)
    args = p.parse_args()

    rng = np.random.RandomState(0)
    netG = build_generator(args.ngf, args.nz)
    netD = build_discriminator(args.ndf)
    netG.initialize(mx.init.Normal(0.02))
    netD.initialize(mx.init.Normal(0.02))

    loss_fn = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    trainerG = gluon.Trainer(netG.collect_params(), "adam",
                             {"learning_rate": args.lr, "beta1": 0.5})
    trainerD = gluon.Trainer(netD.collect_params(), "adam",
                             {"learning_rate": args.lr, "beta1": 0.5})

    b = args.batch_size
    n = max(args.batches_per_epoch, 1)
    sumD = sumG = 0.0
    real_label = mx.nd.ones((b,))
    fake_label = mx.nd.zeros((b,))
    for epoch in range(args.epochs):
        sumD = sumG = 0.0
        for _ in range(args.batches_per_epoch):
            real = mx.nd.array(make_batch(rng, b))
            noise = mx.nd.array(rng.normal(0, 1, (b, args.nz))
                                .astype(np.float32))
            # D step: maximize log D(x) + log(1 - D(G(z)))
            with autograd.record():
                out_real = netD(real)
                fake = netG(noise)
                out_fake = netD(fake.detach())
                lossD = loss_fn(out_real, real_label) \
                    + loss_fn(out_fake, fake_label)
            lossD.backward()
            trainerD.step(b)
            # G step: maximize log D(G(z))
            with autograd.record():
                out = netD(netG(noise))
                lossG = loss_fn(out, real_label)
            lossG.backward()
            trainerG.step(b)
            sumD += float(lossD.mean().asnumpy())
            sumG += float(lossG.mean().asnumpy())
        print("epoch %d lossD %.4f lossG %.4f" % (epoch, sumD / n, sumG / n))
    return sumD / n, sumG / n


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
