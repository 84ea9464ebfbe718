"""Isolate the effect of jax_default_matmul_precision and dtype mixing on
conv fwd/bwd time (scan-fused so dispatch latency amortizes)."""
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


_RTT = None


def timed(name, jfn, *args, K=None):
    global _RTT
    if _RTT is None:
        from perf_common import measure_rtt
        _RTT = measure_rtt()
    out = jfn(*args)
    # sync: host fetch (PERF.md timing methodology)
    np.asarray(jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:2]))
    t0 = time.perf_counter()
    out = jfn(*args)
    v = np.asarray(jax.device_get(out))
    dt = time.perf_counter() - t0 - _RTT  # subtract measured dispatch latency
    if K:
        dt /= K
    print("%-46s %8.2f ms" % (name, dt * 1e3))
    return v


def conv_stack(prec, dtype, bwd):
    # 8 chained 3x3 convs at 56x56x256 — MXU-heavy, resnet-like
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (128, 56, 56, 128), dtype)
    w = jax.random.normal(k, (3, 3, 128, 128), dtype)
    dn = ("NHWC", "HWIO", "NHWC")

    def f(x, w):
        for _ in range(8):
            x = lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                         dimension_numbers=dn,
                                         precision=prec)
        return jnp.sum(x * 1e-30)

    if bwd:
        g = jax.grad(f, argnums=(0, 1))

        def body(c, _):
            gx, gw = g(c[0], c[1])
            return (c[0] + gx * 0, c[1] + gw * 0), None

        jfn = jax.jit(lambda x, w: lax.scan(body, (x, w), None, length=5)[0][1])
        timed("conv8 %s prec=%s grad" % (dtype, prec), jfn, x, w, K=5)
    else:
        def body(c, _):
            return (f(c[0], c[1]) * 0 + c[0], c[1]), None

        jfn = jax.jit(lambda x, w: lax.scan(body, (x, w), None, length=5)[0][1])
        timed("conv8 %s prec=%s fwd" % (dtype, prec), jfn, x, w, K=5)


def main():
    print("default_matmul_precision =",
          jax.config.jax_default_matmul_precision)
    for dtype in ("bfloat16", "float32"):
        for prec in (None, "default", "highest"):
            conv_stack(prec, dtype, bwd=False)
            conv_stack(prec, dtype, bwd=True)


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
