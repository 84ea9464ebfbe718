"""BatchNorm cost attribution (PERF.md round-4 plan item #2).

Two halves:

1. HLO fusion analysis (works anywhere, incl. CPU): jit a
   conv->BN->relu training block, dump the OPTIMIZED HLO, and report
   (a) whether mean and variance share ONE input-reading fusion
   (two sibling reduces fused = one stats read; separate = two),
   (b) whether the normalize arithmetic fused into the convolution's
   consumer fusion (no standalone elementwise pass over the activation),
   (c) total kFusion count and any naked (unfused) elementwise ops.
   Run with MXTPU_BN_ONEPASS=0 vs =1 to compare the staged lever.

2. On-chip timing (needs the real device): steps/sec of the block with
   BN vs without BN at resnet50 stage shapes — the measured per-BN cost
   the PERF.md table wants. Scan-fused, host-fetch synced.

Usage:
    python tools/perf_bn.py [--platform cpu] [--hlo-only]
"""
import argparse
import os
import re
import time

import numpy as np


def build_block(with_bn=True, train=True):
    import jax
    import jax.numpy as jnp

    from mxtpu.ops.registry import get_op

    conv = get_op("Convolution").fn

    # resnet50 stage-2 spatial/channel shape at batch 32 (a quarter of
    # the b128 bench batch, so CPU runs stay tractable; scale linearly)
    N, H, W, C = 32, 28, 28, 128
    x = jnp.ones((N, H, W, C), jnp.bfloat16)
    w = jnp.ones((3, 3, C, C), jnp.bfloat16) * 0.01  # HWIO (NHWC)
    g = jnp.ones((C,), jnp.float32)
    b = jnp.zeros((C,), jnp.float32)
    mm = jnp.zeros((C,), jnp.float32)
    mv = jnp.ones((C,), jnp.float32)

    def fwd(x, w):
        y = conv(x, w, None, kernel=(3, 3), num_filter=C, pad=(1, 1),
                 no_bias=True, layout="NHWC")
        if with_bn:
            # THE shipped stats implementation (shared helper), so this
            # tool can never drift from what BatchNorm compiles
            from mxtpu.ops.nn import bn_batch_stats
            shape = [1, 1, 1, C]
            xf = y.astype(jnp.float32)
            if train:
                mean, var = bn_batch_stats(xf, (0, 1, 2))
            else:
                mean, var = mm, mv
            inv = jax.lax.rsqrt(var + 1e-3)
            y = ((xf - mean.reshape(shape)) * (inv * g).reshape(shape)
                 + b.reshape(shape)).astype(y.dtype)
        return jax.nn.relu(y)

    return fwd, (x, w)


def analyze_hlo(train=True):
    import jax

    fwd, args = build_block(with_bn=True, train=train)
    lowered = jax.jit(fwd).lower(*args)
    hlo = lowered.compile().as_text()

    fusions = re.findall(r"^\s*(?:ROOT\s+)?%?\S+ = \S+ fusion\(", hlo,
                         re.M)
    reduces = re.findall(r" reduce\(|reduce-window\(", hlo)
    convs = re.findall(r"convolution\(|custom-call.*conv", hlo)
    # count fusion COMPUTATIONS containing a reduce (stats passes)
    stat_fusions = 0
    for m in re.finditer(r"^%?fused_[\w.]+ \([^)]*\) -> .*?\{(.*?)^\}",
                         hlo, re.S | re.M):
        if "reduce(" in m.group(1):
            stat_fusions += 1
    print("optimized-HLO summary (%s, MXTPU_BN_ONEPASS=%s):"
          % ("train" if train else "eval",
             # default mirrors ops/nn.py:_bn_onepass (1 as of round 5)
             os.environ.get("MXTPU_BN_ONEPASS", "1")))
    print("  fusion ops:          %d" % len(fusions))
    print("  fusions w/ reduce:   %d  (1 = mean+var share one stats read)"
          % stat_fusions)
    print("  conv calls:          %d" % len(convs))
    print("  raw reduce mentions: %d" % len(reduces))
    return hlo


def time_block(reps=20):
    import jax
    import jax.numpy as jnp

    for with_bn in (False, True):
        fwd, args = build_block(with_bn=with_bn)

        # scan over the forward so K iterations cost ONE dispatch
        f = jax.jit(lambda x, w: jax.lax.scan(
            lambda c, _: (fwd(c, w).astype(c.dtype), None), x, None,
            length=reps)[0])
        y = f(*args)
        np.asarray(jax.device_get(y.ravel()[:2]))  # warm + sync
        t0 = time.perf_counter()
        y = f(*args)
        np.asarray(jax.device_get(y.ravel()[:2]))
        dt = (time.perf_counter() - t0) / reps
        print("%-10s %.3f ms/iter" % ("conv+bn" if with_bn else "conv",
                                      dt * 1e3))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--hlo-only", action="store_true")
    ns = ap.parse_args()
    if ns.platform:
        import jax
        jax.config.update("jax_platforms", ns.platform)
    analyze_hlo(train=True)
    if not ns.hlo_only:
        time_block()


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
