"""Perf bisection for the resnet50 bench config (PERF.md evidence).

Times, as separately jitted programs on the real chip:
  fwd            - inference forward only
  fwd_bwd        - value_and_grad of loss (no optimizer)
  full_step      - the exact ShardedTrainStep bench path
and reports XLA cost-analysis flops for each.
"""
import os
import time

import numpy as np
import jax
import jax.numpy as jnp


def _sync(out):
    """Sync: fetch a few elements to host (PERF.md timing methodology).
    Device execution is queue-ordered, so fetching the LAST output waits
    for every step before it."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(jax.device_get(leaf.ravel()[:2]))


def timeit(fn, *args, steps=20):
    out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / steps


def flops_of(jfn, *args):
    """XLA cost-model FLOPs via the shared version-proof accessor
    (mxtpu/perf_model.py — list-of-dicts vs dict vs None handled there,
    not re-derived per tool)."""
    from mxtpu import perf_model
    c = jfn.lower(*args).compile()
    fl = perf_model.flops_of(c)
    return fl if fl is not None else 0.0


def main():
    from mxtpu import gluon
    from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh, pure_forward
    from perf_common import build_resnet

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    net, x, y = build_resnet(batch)

    # --- fwd only (train=False)
    fn, params = pure_forward(net)
    jfwd = jax.jit(fn)
    t_fwd = timeit(jfwd, params, x._data)
    f_fwd = flops_of(jfwd, params, x._data)
    print("fwd:       %7.2f ms  %6.1f GFLOP  (%5.1f TFLOP/s)"
          % (t_fwd * 1e3, f_fwd / 1e9, f_fwd / t_fwd / 1e12))

    # --- fwd+bwd (train=True), no optimizer
    fn_t, params_t = pure_forward(net, train=True)
    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_of(params_t, xd, yd):
        out = fn_t(params_t, xd)
        from mxtpu.ndarray import NDArray
        l = loss_blk(NDArray(out), NDArray(yd))
        return jnp.mean(l._data)

    jgrad = jax.jit(jax.value_and_grad(loss_of))
    t_bwd = timeit(jgrad, params_t, x._data, y._data)
    f_bwd = flops_of(jgrad, params_t, x._data, y._data)
    print("fwd+bwd:   %7.2f ms  %6.1f GFLOP  (%5.1f TFLOP/s)"
          % (t_bwd * 1e3, f_bwd / 1e9, f_bwd / t_bwd / 1e12))

    # --- full bench step
    step = ShardedTrainStep(net, loss_blk, data_parallel_mesh(),
                            optimizer="sgd",
                            optimizer_params={"learning_rate": 0.01,
                                              "momentum": 0.9})
    for _ in range(3):
        step(x, y).asnumpy()
    t0 = time.perf_counter()
    for _ in range(20):
        out = step(x, y)
    out.asnumpy()
    t_full = (time.perf_counter() - t0) / 20
    f_full = step.compiled_step_flops()
    print("full step: %7.2f ms  %6.1f GFLOP  (%5.1f TFLOP/s)  -> %.0f img/s"
          % (t_full * 1e3, f_full / 1e9, f_full / t_full / 1e12,
             batch / t_full))


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
