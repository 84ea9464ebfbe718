"""Multi-process data-parallel training example
(ref: the reference's distributed training entry points under
example/image-classification with ``--kv-store dist_sync`` +
tools/launch.py; docs/faq/distributed_training.md).

Run locally with the launcher (2 workers on this machine):

    python tools/launch.py -n 2 python examples/distributed/train_dist.py

On a real multi-host TPU pod, run this script once per host with no
launcher — ``mxtpu.distributed.init()`` autodetects the runtime.

What it shows: the symmetric worker bootstrap, a mesh spanning every
process, per-worker data sharding (each process feeds its LOCAL batch
slice, the reference's part_index/num_parts pattern), one
ShardedTrainStep whose gradient all-reduce spans hosts, and rank-0-only
checkpointing.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main():
    import mxtpu as mx
    from mxtpu import distributed, gluon
    from mxtpu.gluon import nn
    from mxtpu.parallel import ShardedTrainStep, make_mesh

    distributed.init()  # reads MXTPU_*/DMLC_* env; no-op single-process
    rank, nworkers = distributed.rank(), distributed.num_workers()

    mx.random.seed(7)  # same init on every worker (one logical model)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize()

    # per-worker shard of a synthetic dataset: the reference's
    # part_index/num_parts contract — each process loads ONLY its slice
    rng = np.random.RandomState(1234)
    all_x = rng.uniform(-1, 1, (512, 32)).astype(np.float32)
    all_y = (all_x[:, :10].sum(axis=1) > 0).astype(np.float32)
    local_x = all_x[rank::nworkers]
    local_y = all_y[rank::nworkers]

    x0 = mx.nd.array(local_x[:8])
    net(x0)  # settle shapes

    mesh = make_mesh({"data": -1})  # every device across every process
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            mesh, optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1,
                                              "momentum": 0.9})
    bs = 32
    for epoch in range(3):
        losses = []
        for i in range(0, len(local_x), bs):
            xb = mx.nd.array(local_x[i:i + bs])
            yb = mx.nd.array(local_y[i:i + bs])
            losses.append(float(step(xb, yb).asnumpy()))
        if rank == 0:
            print("epoch %d: loss %.4f (workers=%d)"
                  % (epoch, sum(losses) / len(losses), nworkers),
                  flush=True)

    distributed.barrier("epoch_end")
    if rank == 0:  # single-writer checkpoint, reference file format
        net.export("/tmp/train_dist_model", epoch=3)
        print("rank 0 exported checkpoint", flush=True)


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
