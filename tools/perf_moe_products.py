#!/usr/bin/env python
"""What a routed layer's grouped products cost on the chip, by who
multiplies: XLA:TPU's ``ragged_dot`` (with the transposed copy of the
weights that ``a @ b[e].T`` costs it) against the Pallas grouped-matmul
kernel (``mxtpu/ops/pallas/grouped_matmul.py``), at the expert cells'
shapes, every form, every rung of each ladder at a cell's own share of live
rows; then the layer (``moe._held_part`` forward, and forward + backward
under a loss that reads the output) both ways. One JSON line a reading,
all of them in ``chiprun_out/perf_moe_products.jsonl``. Chip only:

    chiprun -- python tools/perf_moe_products.py [--sweep] [shape ...]

``--sweep`` also times the kernel at other tiles than the ones its shapes
give it (``_call(tiles=...)``: the measurement's own argument). A time is
a host clock around ``n`` dispatches that end in ``block_until_ready``;
``peak_pct`` is the live rows' operations over that time over 197 TFLOP/s.
"""
import contextlib
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perf_moe_sums import plan, timed  # noqa: E402
from mxtpu.ops.pallas import grouped_matmul as gmm  # noqa: E402
from mxtpu.parallel import moe  # noqa: E402

PEAK = 197e12
# tokens, model width, expert width, choices a token, experts held / scored,
# and the live rows to run each rung at (a cell's own range)
SHAPES = {
    "lfm2": dict(tokens=16384, dim=2048, width=1792, top_k=4, held=8,
                 total=32, activation="silu", live=(16384, 32768)),
    "kanana": dict(tokens=8192, dim=2048, width=768, top_k=6, held=16,
                   total=128, activation="silu", live=(6144, 12288, 24576)),
    "smallthinker": dict(tokens=16384, dim=2560, width=768, top_k=6, held=8,
                         total=64, activation="relu",
                         live=(12288, 24576, 49152)),
    "ling3": dict(tokens=8192, dim=2560, width=768, top_k=8, held=8,
                  total=512, activation="silu",
                  live=(1024, 2048, 4096, 8192, 16384, 32768)),
}


@contextlib.contextmanager
def forced_to_xla():
    """``moe._grouped`` leaves every product to ``ragged_dot`` (traced
    inside: a trace keeps the path it took)."""
    refusal, gmm.refusal = gmm.refusal, lambda a, b: "platform"
    try:
        yield
    finally:
        gmm.refusal = refusal


def operands(form, rows, held, d, f, wide_out):
    """(a, b) of a product of ``form``: ``wide_out`` says whether the
    result is the model's width (down, and gate/up's transposes' other)."""
    k, n = (f, d) if wide_out else (d, f)
    keys = jax.random.split(jax.random.PRNGKey(rows + k), 2)
    make = lambda key, *s: jax.random.normal(       # noqa: E731
        key, s, jnp.float32).astype(jnp.bfloat16)
    a = make(keys[0], rows, k)
    if form == gmm.WEIGHTS:
        return a, make(keys[1], rows, n), k, n
    return a, make(keys[1], held, *((n, k) if form == gmm.ROWS_T
                                    else (k, n))), k, n


def products(name, shape, out, sweep):
    d, f, held = shape["dim"], shape["width"], shape["held"]
    rungs = moe._rungs(shape["tokens"] * shape["top_k"], held, shape["total"])
    for rows, live in zip(rungs, shape["live"]):
        sizes = plan(shape, live, rows)[1]
        tm = gmm.row_tile(rows, held)
        for form in (gmm.ROWS, gmm.ROWS_T, gmm.WEIGHTS):
            for wide_out in (False, True):
                a, b, k, n = operands(form, rows, held, d, f, wide_out)
                rec = {"what": "product", "shape": name, "form": form,
                       "rows": rows, "live": live, "held": held, "k": k,
                       "n": n}

                def report(path, ms, **more):
                    out({**rec, "path": path, "ms": ms, "peak_pct":
                         100 * 2 * live * k * n / (ms * 1e-3) / PEAK, **more})

                with forced_to_xla():
                    xla = jax.jit(lambda a, b, sizes, form=form: moe._grouped(
                        a, b, moe._Groups(sizes, rows), form))
                    report("xla", timed(xla, a, b, sizes))
                tk, tn = gmm._tiles(k, n, 2, form)
                tilings = [(tm, tk, tn)]
                if sweep:
                    tilings += [(t, tk, tn) for t in (128, 256, 512)
                                if t != tm and t <= rows]
                    if form == gmm.WEIGHTS:
                        tilings += [(tm, gmm._divisor(k, tk // 2), tn),
                                    (tm, tk, gmm._divisor(n, tn // 2))]
                for tiles in tilings:
                    kernel = jax.jit(
                        lambda a, b, sizes, form=form, tiles=tiles: gmm._call(
                            a, b, gmm.tile_table(sizes, rows, tiles[0]),
                            form=form, tiles=tiles,
                            interpret=gmm._fa._interpret()))
                    report("pallas", timed(kernel, a, b, sizes),
                           tiles=list(tiles))


def layer(name, shape, out):
    t, d, f, k = (shape[n] for n in ("tokens", "dim", "width", "top_k"))
    held = shape["held"]
    rungs = moe._rungs(t * k, held, shape["total"])
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    leaf = lambda key, *s: (0.02 * jax.random.normal(       # noqa: E731
        key, s, jnp.float32)).astype(jnp.bfloat16)
    x = jax.random.normal(ks[0], (t, d), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.uniform(ks[1], (t, k), jnp.float32)
    experts = (leaf(ks[2], held, d, f), leaf(ks[3], held, d, f),
               leaf(ks[4], held, f, d))
    g = jax.random.normal(ks[5], (t, d), jnp.float32)
    plans = [plan(shape, n_live, rows) + (jnp.int32(i),)
             for i, (rows, n_live) in enumerate(zip(rungs, shape["live"]))]
    for path in ("xla", "pallas"):
        stack = contextlib.ExitStack()
        if path == "xla":
            stack.enter_context(forced_to_xla())

        # new functions a path: jit keeps a function's trace by identity
        def held_part(x, w, wg, wu, wd, order, sizes, rung):
            return moe._held_part(k, rungs, shape["activation"], x, w, wg, wu,
                                  wd, order, sizes, rung)

        def loss(*args):    # sin keeps the forward's sum live in the gradient
            return jnp.sum(jnp.sin(held_part(*args)) * g)

        for what, fn in (("layer_fwd", jax.jit(held_part)),
                         ("layer_fwd_bwd",
                          jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))))):
            for rows, n_live, p in zip(rungs, shape["live"], plans):
                out({"what": what, "shape": name, "rows": rows,
                     "pairs": t * k, "live": n_live, "path": path,
                     "ms": timed(fn, x, w, *experts, *p, n=10)})
        stack.close()


def main(argv):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("perf_moe_products measures the chip; found %s"
                         % jax.devices()[0].platform)
    sweep = "--sweep" in argv
    names = [a for a in argv if not a.startswith("--")] or sorted(SHAPES)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "perf_moe_products.jsonl"),
              "a") as f:
        def out(rec):
            print(json.dumps(rec), flush=True)
            f.write(json.dumps(rec) + "\n")
            f.flush()

        for name in names:
            products(name, SHAPES[name], out, sweep)
            layer(name, SHAPES[name], out)


if __name__ == "__main__":
    main(sys.argv[1:])
