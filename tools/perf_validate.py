"""One-shot post-fix validation on the real chip: dispatch latency +
scan-fused on-chip step time. Run ``python bench.py``
separately for the full scoring numbers; append both to PERF.md."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    print("devices:", jax.devices(), flush=True)

    # 1) scan-fused on-chip step (the round-3 diagnosis method)
    import jax.numpy as jnp
    from mxtpu import gluon
    from mxtpu.ndarray import NDArray
    from mxtpu.parallel import pure_forward
    from perf_common import build_resnet, measure_rtt

    print("dispatch latency: %.1f ms" % (measure_rtt() * 1e3), flush=True)
    net, x, yl = build_resnet()
    fn_t, params_t = pure_forward(net, train=True)
    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_of(p, xd, yd):
        return jnp.mean(loss_blk(NDArray(fn_t(p, xd)), NDArray(yd))._data)

    def one_step(p, _):
        l, g = jax.value_and_grad(loss_of)(p, x._data, yl._data)
        return [(w - 0.01 * gw.astype(w.dtype)) for w, gw in zip(p, g)], l

    K = 10

    @jax.jit
    def multi(p):
        _, ls = jax.lax.scan(one_step, p, None, length=K)
        return ls[-1]

    float(multi(params_t))  # compile + run
    t0 = time.perf_counter()
    float(multi(params_t))
    dt = time.perf_counter() - t0
    batch = x.shape[0]
    print("scan(%d) fwd+bwd+sgd: %.2f ms/step -> %.0f img/s"
          % (K, dt / K * 1e3, batch * K / dt), flush=True)


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
