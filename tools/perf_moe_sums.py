#!/usr/bin/env python
"""What a sum by token costs in each of its two forms, on the chip.

``parallel/moe.py:_sum_by_token`` sums a rung's rows into their tokens
either by a scatter-add of the rung's rows or by a gather over every (token,
slot) pair and a sum over the k slots; ``_sums_by_gather`` chooses from two
measured constants. This times both forms at the expert cells' shapes, the
sum alone (forward: bf16 rows under the router's weights; backward: float32
rows) and inside the layer (``_held_part`` forward, and forward + backward
under a loss that reads the output) at every rung of each ladder, and
prints one JSON line a reading plus the two constants fitted to the sums
alone. Chip only:

    chiprun -- python tools/perf_moe_sums.py [shape ...]

A time is a host clock around ``n`` dispatches that end in
``block_until_ready``; every reading here is a millisecond or more, so the
queue stays full.
"""
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from mxtpu.parallel import moe  # noqa: E402

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
    "all_held": dict(tokens=8192, dim=2048, width=768, top_k=6, held=16,
                     total=16, activation="silu", live=(49152,)),
}
FORMS = {"scatter": False, "gather": True}


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def plan(shape, n_live, seed):
    """A sorted order and group sizes with ``n_live`` pairs routed here."""
    rng = np.random.RandomState(seed)
    pairs = shape["tokens"] * shape["top_k"]
    key = np.full(pairs, shape["held"], np.int32)
    key[rng.permutation(pairs)[:n_live]] = rng.randint(
        0, shape["held"], n_live)
    order = np.argsort(key, kind="stable").astype(np.int32)
    sizes = np.bincount(key, minlength=shape["held"] + 1)[:shape["held"]]
    return jnp.asarray(order), jnp.asarray(sizes.astype(np.int32))


def force(fwd, bwd):
    """The forward sums bf16 rows and the backward float32 ones."""
    moe._sums_by_gather = lambda rows, pairs, itemsize: (
        fwd if itemsize == 2 else bwd)


def sums_alone(name, shape, out):
    t, d, k = shape["tokens"], shape["dim"], shape["top_k"]
    rungs = moe._rungs(t * k, shape["held"], shape["total"])
    w = jax.random.uniform(jax.random.PRNGKey(1), (t, k), jnp.float32)
    for rows, n_live in zip(rungs, shape["live"]):
        order, sizes = plan(shape, n_live, rows)
        for direction, dtype in (("fwd", jnp.bfloat16), ("bwd", jnp.float32)):
            ys = jax.random.normal(jax.random.PRNGKey(2), (rows, d), dtype)
            for form, gather in FORMS.items():
                force(gather, gather)
                if direction == "fwd":
                    fn = jax.jit(lambda ys, w, order, sizes: moe._sum_by_token(
                        ys, k, order, sizes, w))
                    ms = timed(fn, ys, w, order, sizes)
                else:
                    fn = jax.jit(lambda ys, order, sizes: moe._sum_by_token(
                        ys, k, order, sizes).astype(jnp.bfloat16))
                    ms = timed(fn, ys, order, sizes)
                out({"what": "sum_alone", "shape": name, "rows": rows,
                     "pairs": t * k, "dim": d, "direction": direction,
                     "form": form, "ms": ms})


def in_layer(name, shape, out):
    t, d, f, k = (shape[n] for n in ("tokens", "dim", "width", "top_k"))
    held = shape["held"]
    rungs = moe._rungs(t * k, held, shape["total"])
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    leaf = lambda key, *s: (0.02 * jax.random.normal(
        key, s, jnp.float32)).astype(jnp.bfloat16)
    x = jax.random.normal(ks[0], (t, d), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.uniform(ks[1], (t, k), jnp.float32)
    experts = (leaf(ks[2], held, d, f), leaf(ks[3], held, d, f),
               leaf(ks[4], held, f, d))
    g = jax.random.normal(ks[5], (t, d), jnp.float32)
    plans = [plan(shape, n_live, rows) + (jnp.int32(i),)
             for i, (rows, n_live) in enumerate(zip(rungs, shape["live"]))]

    for fwd, bwd in (("scatter", None), ("gather", None),
                     ("scatter", "scatter"), ("gather", "scatter"),
                     ("scatter", "gather"), ("gather", "gather")):
        force(FORMS[fwd], FORMS[bwd or "scatter"])

        # new functions a form: jit keeps a function's trace by identity
        def layer(x, w, wg, wu, wd, order, sizes, rung):
            return moe._held_part(k, rungs, shape["activation"], x, w, wg, wu,
                                  wd, order, sizes, rung)

        def loss(*args):    # sin keeps the forward's sum live in the gradient
            return jnp.sum(jnp.sin(layer(*args)) * g)

        fn = jax.jit(layer if bwd is None else
                     jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        for rows, n_live, p in zip(rungs, shape["live"], plans):
            out({"what": "layer_fwd" if bwd is None else "layer_fwd_bwd",
                 "shape": name, "rows": rows, "pairs": t * k, "live": n_live,
                 "fwd": fwd, "bwd": bwd,
                 "ms": timed(fn, x, w, *experts, *p, n=10)})


def fit(readings):
    """The two constants from the sums alone at 2,048-wide rows: us a
    scatter-added float32 row (backward readings), us a gathered pair and
    byte of its element (both directions)."""
    alone = [r for r in readings if r["what"] == "sum_alone"
             and r["dim"] == 2048]
    scatter = [1e3 * r["ms"] / r["rows"] for r in alone
               if r["form"] == "scatter" and r["direction"] == "bwd"]
    gather = [1e3 * r["ms"] / r["pairs"] / (2 if r["direction"] == "fwd"
                                            else 4)
              for r in alone if r["form"] == "gather"]
    return {"scatter_add_row_us": float(np.median(scatter)),
            "scatter_add_row_us_range": [min(scatter), max(scatter)],
            "gather_pair_byte_us": float(np.median(gather)),
            "gather_pair_byte_us_range": [min(gather), max(gather)]}


def main(argv):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("perf_moe_sums measures the chip; found %s"
                         % jax.devices()[0].platform)
    readings = []

    def out(rec):
        readings.append(rec)
        print(json.dumps(rec), flush=True)

    for name in argv or sorted(SHAPES):
        sums_alone(name, SHAPES[name], out)
        in_layer(name, SHAPES[name], out)
    print(json.dumps({"what": "fit", **fit(readings)}))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "perf_moe_sums.jsonl"), "w") as f:
        for rec in readings:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
