"""Localize the slow resnet50 forward (PERF.md gap #1): time truncated
prefixes of the exact bench model — stem only, stem+stage1, ... — fwd and
fwd+bwd, scan-fused into one dispatch. The per-stage *increments* attribute
step time to layer groups without needing the profiler."""
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def timed_scan(fn, args, K=8):
    """One jit dispatch of K chained applications; host-fetch sync."""
    def body(c, _):
        out = fn(c[0], *args[1:])
        # keep shapes: fold output back into the carry input cheaply
        return (c[0] + 0 * jnp.mean(out.astype(jnp.float32)).astype(c[0].dtype),
                ), None

    @jax.jit
    def run(x):
        c, _ = jax.lax.scan(body, (x,), None, length=K)
        return c[0]

    y = run(args[0])
    _ = np.asarray(jax.device_get(y.ravel()[:2]))
    t0 = time.perf_counter()
    y = run(args[0])
    _ = np.asarray(jax.device_get(y.ravel()[:2]))
    return (time.perf_counter() - t0) / K


def main():
    from mxtpu.parallel import pure_forward
    from perf_common import build_resnet

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    net, x, _y = build_resnet(batch)
    # resnet v1 body (mxtpu zoo): features = [stem convs..., stage1..4, pool]
    feats = list(net.features._children.values())
    # group prefix cut points: after stem (first 4 blocks: conv/bn/act/pool),
    # then after each residual stage
    names = [type(b).__name__ for b in feats]
    print("feature blocks:", names, flush=True)
    cuts = []
    seen_stage = 0
    for i, b in enumerate(feats):
        if type(b).__name__ in ("HybridSequential",):
            seen_stage += 1
            cuts.append((i + 1, "through stage%d" % seen_stage))
    if not cuts:
        cuts = [(len(feats), "full features")]
    cuts.insert(0, (cuts[0][0] - 1 if cuts else 4, "stem"))

    import mxtpu as mx
    prev = 0.0
    for upto, label in cuts + [(None, "full net (incl. dense)")]:
        if upto is None:
            fn, params = pure_forward(net, train=True)
        else:
            sub = mx.gluon.nn.HybridSequential()
            for b in feats[:upto]:
                sub.add(b)
            fn, params = pure_forward(sub, train=True)

        def f(xd, fn=fn, params=params):
            return fn(params, xd)

        dt = timed_scan(f, (x._data,))
        print("%-28s %7.2f ms  (+%.2f ms)" % (label, dt * 1e3,
                                              (dt - prev) * 1e3), flush=True)
        prev = dt

        def floss(xd, fn=fn, params=params):
            return jnp.sum(fn(params, xd).astype(jnp.float32)) * 1e-6

        g = jax.grad(lambda xd: floss(xd))
        dtb = timed_scan(lambda xd: g(xd), (x._data,))
        print("%-28s %7.2f ms fwd+bwd(x)" % ("", dtb * 1e3), flush=True)


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
