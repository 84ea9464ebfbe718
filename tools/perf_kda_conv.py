#!/usr/bin/env python
"""What a Kimi-Delta-Attention layer's short filter (``_contrib_kda_conv``:
taps, SiLU, a head's L2 norm) costs on the chip, by who computes it: the
plain function under XLA (``ops/nn.py:_kda_conv_plain``: the value, and the
two gradients as ``jax.vjp`` of it, the forward again and its transpose)
against the Pallas pair (``mxtpu/ops/pallas/short_filter.py``:
``kda_conv_fwd``, ``kda_conv_bwd``), at the Ling cell's shape (``[1, 8192,
4096]`` bf16, 4 taps, heads of 128 and no norm) and, for the sizing of a
gated filter on the same taps (ROADMAP S4), at LFM2's (``[2, 8192, 2048]``,
3 taps, no norm). One JSON line a reading, all of them in
``chiprun_out/perf_kda_conv.jsonl``. Chip only:

    chiprun -- python tools/perf_kda_conv.py [--sweep] [shape ...]

``--sweep`` also times the kernels at other tiles than the ones their
shapes give them (``tiles=``: the measurement's own argument). A time is a
host clock around ``n`` dispatches that end in ``block_until_ready``;
``floor_pct`` is the bytes a pass has to move (the data in and out; the
data and the cotangent in and a gradient out) at 819 GB/s over that time;
``gap`` is the kernel's largest distance from the plain function's result
over that result's largest entry.
"""
import functools
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perf_moe_sums import timed  # noqa: E402
from mxtpu.ops.pallas import short_filter  # noqa: E402

ops_nn = importlib.import_module("mxtpu.ops.nn")

HBM_BYTES_PER_S = 819e9
SHAPES = {
    "ling3_qk": dict(shape=(1, 8192, 4096), taps=4, head_dim=128),
    "ling3_v": dict(shape=(1, 8192, 4096), taps=4, head_dim=0),
    "lfm2": dict(shape=(2, 8192, 2048), taps=3, head_dim=0),
}
SWEEP = [(256, 256), (256, 512), (512, 256), (512, 512), (512, 1024),
         (1024, 256), (1024, 512), (2048, 256)]


def _gap(got, want):
    got, want = (x.astype(jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def measure(name, case, out, sweep):
    shape, taps, head_dim = case["shape"], case["taps"], case["head_dim"]
    ks = jax.random.split(jax.random.PRNGKey(taps + head_dim), 3)
    x = jax.random.normal(ks[0], shape, jnp.float32).astype(jnp.bfloat16)
    w = (0.5 * jax.random.normal(ks[1], (shape[-1], taps), jnp.float32)
         ).astype(jnp.bfloat16)
    g = jax.random.normal(ks[2], shape, jnp.float32).astype(jnp.bfloat16)
    n_bytes = x.size * x.dtype.itemsize
    rec = {"shape": name, "dims": list(shape), "taps": taps,
           "head_dim": head_dim}

    def report(what, path, ms, passes, **more):
        out({**rec, "what": what, "path": path, "ms": ms, "floor_pct":
             100 * passes * n_bytes / HBM_BYTES_PER_S / (ms * 1e-3), **more})

    plain = functools.partial(ops_nn._kda_conv_plain, head_dim)
    xla_fwd = jax.jit(plain)
    xla_bwd = jax.jit(lambda x, w, g: jax.vjp(plain, x, w)[1](g))
    report("fwd", "xla", timed(xla_fwd, x, w), 2)
    report("bwd", "xla", timed(xla_bwd, x, w, g), 3)
    want, (want_dx, want_dw) = xla_fwd(x, w), xla_bwd(x, w, g)
    *given, unit = short_filter._tiles(shape[-2], shape[-1], head_dim, None)
    given = tuple(given)
    for tiles in [given] + [t for t in SWEEP if sweep and t != given]:
        if tiles[1] % unit or shape[-1] % tiles[1] or tiles[0] > shape[-2]:
            continue
        how = dict(head_dim=head_dim, tiles=tiles,
                   interpret=short_filter._fa._interpret())
        fwd = functools.partial(short_filter._forward, **how)
        bwd = functools.partial(short_filter._backward, **how)
        dx, dw = bwd(x, w, g)
        report("fwd", "pallas", timed(fwd, x, w), 2, tiles=list(tiles),
               gap=_gap(fwd(x, w), want))
        report("bwd", "pallas", timed(bwd, x, w, g), 3, tiles=list(tiles),
               gap_dx=_gap(dx, want_dx), gap_dw=_gap(dw, want_dw))


def main(argv):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("perf_kda_conv measures the chip; found %s"
                         % jax.devices()[0].platform)
    sweep = "--sweep" in argv
    names = [a for a in argv if not a.startswith("--")] or list(SHAPES)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "perf_kda_conv.jsonl"), "a") as f:
        def out(rec):
            print(json.dumps(rec), flush=True)
            f.write(json.dumps(rec) + "\n")
            f.flush()

        for name in names:
            measure(name, SHAPES[name], out, sweep)


if __name__ == "__main__":
    main(sys.argv[1:])
