"""Model parallelism, TPU-style (ref: example/model-parallel/ — the
reference places layer groups on devices by hand with ``group2ctx`` and
auto-inserted cross-device copies; here the SAME intent is expressed as
GSPMD sharding rules and XLA inserts the collectives).

A wide MLP's first layer is column-parallel and its second row-parallel
over the mesh's ``model`` axis, while the batch is data-parallel over
``data`` — Megatron-style 2D parallelism in ~10 lines of placement
rules. Run on the 8-device virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python examples/model_parallel/tp_mlp.py --platform cpu
"""
import argparse

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--platform", default="")
    p.add_argument("--data-par", type=int, default=2)
    p.add_argument("--model-par", type=int, default=4)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--steps", type=int, default=10)
    args = p.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import mxtpu as mx
    from mxtpu import gluon
    from mxtpu.gluon import nn
    from mxtpu.parallel import ShardedTrainStep, make_mesh
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(0)
    nin, ncls = 64, 16
    w_true = rng.normal(0, 1, (nin, ncls)).astype(np.float32)
    pool_x = rng.normal(0, 1, (512, nin)).astype(np.float32)
    pool_y = (pool_x @ w_true).argmax(1).astype(np.float32)

    def batch(i):
        sl = np.arange(i * args.batch_size,
                       (i + 1) * args.batch_size) % len(pool_x)
        return pool_x[sl], pool_y[sl]

    net = nn.HybridSequential(prefix="tp_")
    with net.name_scope():
        net.add(nn.Dense(args.hidden, activation="relu"))
        net.add(nn.Dense(ncls))
    net.initialize()
    x0, _ = batch(0)
    net(mx.nd.array(x0))  # settle shapes

    mesh = make_mesh({"data": args.data_par, "model": args.model_par})
    # Dense weights are [units, in]: layer 1 shards its OUTPUT dim
    # (column parallel), layer 2 its INPUT dim (row parallel) — the
    # classic pairing that needs only one collective per layer pair
    rules = [
        (r".*dense0_weight", P("model", None)),
        (r".*dense0_bias", P("model")),
        (r".*dense1_weight", P(None, "model")),
    ]
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            mesh, optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1},
                            param_specs=rules)
    first = last = None
    for i in range(args.steps):
        x, y = batch(i)
        loss = float(step(mx.nd.array(x), mx.nd.array(y)).asnumpy())
        if first is None:
            first = loss
        last = loss
        print("step %d loss %.4f" % (i, loss))
    if first is not None:
        print("mesh %s  loss %.4f -> %.4f" % (dict(zip(mesh.axis_names,
                                                       mesh.devices.shape)),
                                              first, last))
    return first, last


if __name__ == "__main__":
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    main()
