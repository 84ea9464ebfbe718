"""Measure the chip's ACHIEVABLE bf16 matmul rate (the practical MXU
ceiling), not the datasheet peak.

Method: one jit dispatch runs a lax.scan of K chained NxN bf16 matmuls, so
per-dispatch latency and host sync amortize to nothing; sync is a host
fetch of a few result elements (see PERF.md "timing methodology").

The ratio achieved/nominal calibrates every MFU number in bench.py: if the
exposed chip sustains X TFLOP/s on an ideal 8k matmul, no model can exceed
X, and "% of achievable" is the number optimization work should move.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def probe(N, K=20, acc=None, prec=None, dtype=jnp.bfloat16):
    a = jax.random.normal(jax.random.PRNGKey(0), (N, N), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (N, N), dtype)

    def body(c, _):
        out = lax.dot(c, b, preferred_element_type=acc, precision=prec)
        # rescale so the chain neither overflows nor constant-folds
        return out.astype(dtype) * jnp.asarray(1e-3, dtype), None

    @jax.jit
    def run(a, b):
        c, _ = lax.scan(body, a, None, length=K)
        return c

    y = run(a, b)
    _ = np.asarray(y[0, :2])  # compile + settle
    t0 = time.perf_counter()
    y = run(a, b)
    _ = np.asarray(y[0, :2])  # true sync: host fetch
    dt = time.perf_counter() - t0
    fl = 2 * N ** 3 * K
    rate = fl / dt / 1e12
    print("N=%5d K=%2d acc=%-8s prec=%-8s %7.2f ms/matmul  %6.1f TFLOP/s"
          % (N, K, acc.__name__ if acc else None, prec, dt * 1e3 / K, rate),
          flush=True)
    return rate


def main():
    d = jax.devices()[0]
    print("device:", d.platform, getattr(d, "device_kind", "?"), flush=True)
    best = 0.0
    for n in (4096, 8192):
        best = max(best, probe(n))
    best = max(best, probe(8192, acc=jnp.float32))
    # the honest-f32 emulation floor (PERF.md ceiling table, f32 HIGHEST row)
    probe(8192, prec="highest", dtype=jnp.float32)
    # datasheet nominal from the ONE shared table (mxtpu/perf_model.py)
    # — the same denominator bench.py's mfu and the runtime perf.mfu
    # gauge divide by
    from mxtpu import perf_model
    nominal = perf_model.nominal_tflops(d) or 197.0
    print("achievable ceiling: %.1f TFLOP/s = %.0f%% of the %.0f TFLOP/s "
          "%s datasheet peak"
          % (best, 100 * best / nominal, nominal,
             getattr(d, "device_kind", "?")))


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
