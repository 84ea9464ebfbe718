"""Shared pieces for the perf diagnosis tools (perf_bisect/perf_prec/
perf_trace/perf_validate): ONE copy of the bench-identical resnet50 setup,
the scan-fused timing harness and a dispatch-latency measurement, so the
tools can't drift from bench.py."""
import os
import sys
import time

import numpy as np


def use_xla_cache():
    """The one compile-cache rule for the tools that run on the chip
    (mxtpu/compile_service.py): JAX_COMPILATION_CACHE_DIR if the
    environment sets it, else the fixed ``<checkout>/.jax_cache``. Called
    from each tool's ``__main__``; returns the directory in force."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from mxtpu import compile_service
    return compile_service.use_checkout_xla_cache()


def build_resnet(batch=None, layout=None, dtype="bfloat16"):
    """Build the exact resnet50 bench model + batch (mirrors
    bench.bench_resnet50). Returns (net, x, y)."""
    import mxtpu as mx
    from mxtpu.gluon.model_zoo import vision

    batch = batch or int(os.environ.get("BENCH_BATCH", "128"))
    layout = layout or os.environ.get("BENCH_LAYOUT", "NHWC")
    with mx.layout(layout):
        net = vision.resnet50_v1()
    net.initialize()
    shape = (batch, 224, 224, 3) if layout == "NHWC" else (batch, 3, 224, 224)
    x = mx.nd.array(np.random.uniform(-1, 1, size=shape), dtype="float32")
    net(x)  # settle deferred shapes
    if dtype != "float32":
        net.cast(dtype)
        x = x.astype(dtype)
    y = mx.nd.array(np.random.randint(0, 1000, size=(batch,)),
                    dtype="float32")
    return net, x, y


def timed_scan(step_fn, x0, K=8):
    """THE scan-fused timing harness (PERF.md methodology): K steps fused
    into ONE dispatch via lax.scan (one compile, one dispatch latency),
    synced by fetching result elements to the host — a sync on every
    backend (chip_smoke.py's ``sync`` phase checks ``block_until_ready``
    against it on the attached chip). ``step_fn: carry -> carry``; returns
    seconds per step. The single copy behind the perf tools and bench.py's
    flash_class config — a sync-idiom fix lands everywhere."""
    import jax

    @jax.jit
    def run(xd):
        c, _ = jax.lax.scan(lambda c, _: (step_fn(c), None), xd, None,
                            length=K)
        return c

    y = run(x0)
    np.asarray(jax.device_get(y.ravel()[:2]))  # warmup + compile
    t0 = time.perf_counter()
    y = run(x0)
    np.asarray(jax.device_get(y.ravel()[:2]))
    return (time.perf_counter() - t0) / K


def reinject(fn):
    """Wrap a ``carry -> output`` fn as ``carry -> carry`` for timed_scan
    by folding a cheap summary of the output back into the carry (keeps
    every scan step live without changing shapes)."""
    import jax.numpy as jnp

    def step(c):
        o = fn(c)
        return c + 0 * jnp.mean(o.astype(jnp.float32)).astype(c.dtype)
    return step


def measure_rtt(n=10):
    """Dispatch+sync latency of a trivial jitted op — the host-side floor
    to subtract from single-shot timings. Measured, never hardcoded."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda v: v + 1)
    v = jnp.ones((8, 8))
    jax.device_get(f(v))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.device_get(f(v))
    return (time.perf_counter() - t0) / n
