"""Does f32 accumulation (preferred_element_type) speed up bf16 convs the
way it does matmuls (perf_peak.py: 102 -> 140 TFLOP/s)?

Times a resnet-like chained conv stack fwd and fwd+bwd, scan-fused into one
dispatch, with (a) plain bf16 conv, (b) f32-accumulate + cast back to bf16.
Sync is a host fetch (see perf_peak.py docstring).
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

DN = ("NHWC", "HWIO", "NHWC")


def timed(name, jfn, *args, K):
    y = jfn(*args)
    _ = np.asarray(jax.device_get(jax.tree_util.tree_leaves(y)[0].ravel()[:2]))
    t0 = time.perf_counter()
    y = jfn(*args)
    _ = np.asarray(jax.device_get(jax.tree_util.tree_leaves(y)[0].ravel()[:2]))
    dt = (time.perf_counter() - t0) / K
    print("%-38s %8.2f ms" % (name, dt * 1e3), flush=True)
    return dt


def stack(acc_f32, bwd, batch=128, hw=28, c=256, depth=8, K=5):
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, hw, hw, c),
                          jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, c, c), jnp.bfloat16)
    pet = jnp.float32 if acc_f32 else None

    def f(x, w):
        for _ in range(depth):
            x = lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                         dimension_numbers=DN,
                                         preferred_element_type=pet)
            x = x.astype(jnp.bfloat16) * jnp.bfloat16(0.1)
        return x

    if bwd:
        def lossf(x, w):
            return jnp.sum(f(x, w).astype(jnp.float32)) * 1e-30
        g = jax.grad(lossf, argnums=(0, 1))

        def body(c_, _):
            gx, gw = g(c_[0], c_[1])
            return (c_[0] + gx.astype(c_[0].dtype) * 0,
                    c_[1] + gw.astype(c_[1].dtype) * 0), None
    else:
        def body(c_, _):
            return (f(c_[0], c_[1]) * 0 + c_[0], c_[1]), None

    jfn = jax.jit(lambda x, w: lax.scan(body, (x, w), None, length=K)[0])
    # per-conv flops (fwd): 2 * batch*hw*hw*c * 3*3*c  per layer
    fl = 2 * batch * hw * hw * c * 9 * c * depth * (3 if bwd else 1)
    dt = timed("conv%d %dx%dx%d acc=%s %s" % (depth, hw, hw, c,
                                              "f32" if acc_f32 else "bf16",
                                              "fwd+bwd" if bwd else "fwd"),
               jfn, x, w, K=K)
    print("    -> %6.1f TFLOP/s" % (fl / dt / 1e12), flush=True)


def main():
    for bwd in (False, True):
        for acc in (False, True):
            stack(acc, bwd)


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
