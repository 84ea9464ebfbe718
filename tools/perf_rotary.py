#!/usr/bin/env python
"""What turning q or k and laying it heads first costs on the chip, by who
computes it: the plain ``ops/nn.py:rotary`` and a transposition under XLA
(the value, and its transpose as ``jax.vjp`` of it) against the Pallas pair
(``mxtpu/ops/pallas/rotary.py``: ``rotary_turn``, ``rotary_unturn``), at
the shapes the cells turn (bf16, 16,384 positions, heads of 128): Laguna's
windowed layers (72 and 8 heads, the whole head), its full layers (48 and 8
heads, YaRN's table on 64 of 128), SmallThinker's (28 and 4) and Keye's (32
and 4). One JSON line a reading, all of them in
``chiprun_out/perf_rotary.jsonl``. Chip only:

    chiprun -- python tools/perf_rotary.py [--sweep] [shape ...]

``--sweep`` also times the kernels at other row blocks than the one they
take (the module's ``_ROWS``, set here before a pass is traced again). A time is a host clock
around ``n`` dispatches that end in ``block_until_ready``; ``floor_pct`` is
the bytes a pass has to move (the data in and out, the two tables once) at
819 GB/s over that time (the plain path and the kernel pass both include
the move between rows first and heads first, which the kernel leaves to
XLA); ``equal`` says that the kernel's result IS the
plain function's, every bit (the value in bf16 and float32; the transpose
against the float32 transpose rounded once, which the plain bf16 transpose
is not: it rounds three times), and ``off`` the share of entries that are
not.
"""
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perf_moe_sums import timed  # noqa: E402

ops_nn = importlib.import_module("mxtpu.ops.nn")
kernels = importlib.import_module("mxtpu.ops.pallas.rotary")

HBM_BYTES_PER_S = 819e9
YARN = {"factor": 32.0, "original_max_position_embeddings": 4096,
        "beta_fast": 64.0, "beta_slow": 1.0}
SHAPES = {
    "laguna_window_q": dict(heads=72, turn={"theta": 10000.0}),
    "laguna_window_k": dict(heads=8, turn={"theta": 10000.0}),
    "laguna_full_q": dict(heads=48, turn={
        "theta": 500000.0, "width": 64, "scaling": YARN}),
    "smallthinker_q": dict(heads=28, turn={"theta": 1.5e6}),
    "smallthinker_k": dict(heads=4, turn={"theta": 1.5e6}),
    "keye_q": dict(heads=32, turn={"theta": 1e6}),
}
SWEEP = [256, 512, 1024, 2048]
T = 16384


def _same(got, want):
    return {"equal": bool(jnp.all(got == want)),
            "off": float(jnp.mean(got != want))}


def measure(name, case, out, sweep):
    heads, turn = case["heads"], case["turn"]
    ks = jax.random.split(jax.random.PRNGKey(heads), 2)
    x = jax.random.normal(ks[0], (1, T, heads, 128),
                          jnp.float32).astype(jnp.bfloat16)
    g = jax.random.normal(ks[1], (1, heads, T, 128),
                          jnp.float32).astype(jnp.bfloat16)
    n_bytes = 2 * x.size * x.dtype.itemsize + 2 * T * 128 * 4
    rec = {"shape": name, "heads": heads, "turn": {
        k: v for k, v in turn.items() if k != "scaling"}}

    def report(what, path, ms, **more):
        out({**rec, "what": what, "path": path, "ms": ms, "floor_pct":
             100 * n_bytes / HBM_BYTES_PER_S / (ms * 1e-3), **more})

    def plain(x):
        return ops_nn.rotary(x, **turn).transpose(0, 2, 1, 3)

    xla_fwd = jax.jit(plain)
    xla_bwd = jax.jit(lambda x, g: jax.vjp(plain, x)[1](g)[0])
    report("turn", "xla", timed(xla_fwd, x))
    report("unturn", "xla", timed(xla_bwd, x, g))
    want = xla_fwd(x)
    want32 = jax.jit(lambda x: plain(x.astype(jnp.float32)))(x)
    once = jax.jit(lambda x, g: jax.vjp(
        lambda x: plain(x.astype(jnp.float32)).astype(x.dtype), x)[1](g)[0])(
            x, g)
    form = (turn["theta"], False, turn.get("width", 0),
            tuple(sorted(turn["scaling"].items()))
            if "scaling" in turn else None)
    interpret = kernels._fa._interpret()
    got32 = jax.jit(lambda x: ops_nn._rotary_kernels(x, form, interpret))(
        x.astype(jnp.float32))
    report("turn", "pallas float32", float("nan"), **_same(got32, want32))
    given = kernels._ROWS
    for rows in [given] + [r for r in SWEEP if sweep and r != given]:
        kernels._ROWS = rows    # read when the pass is traced
        ops_nn._rotary_kernel_pass.clear_cache()
        fwd, bwd = (jax.jit(lambda x, back=back: ops_nn._rotary_kernel_pass(
            x, form=form, back=back, interpret=interpret))
            for back in (False, True))
        report("turn", "pallas", timed(fwd, x), rows=rows,
               **_same(fwd(x), want))
        report("unturn", "pallas", timed(bwd, g), rows=rows,
               **_same(bwd(g), once))
    kernels._ROWS = given
    ops_nn._rotary_kernel_pass.clear_cache()


def main(argv):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("perf_rotary measures the chip; found %s"
                         % jax.devices()[0].platform)
    sweep = "--sweep" in argv
    names = [a for a in argv if not a.startswith("--")] or list(SHAPES)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "perf_rotary.jsonl"), "a") as f:
        def out(rec):
            print(json.dumps(rec), flush=True)
            f.write(json.dumps(rec) + "\n")
            f.flush()

        for name in names:
            measure(name, SHAPES[name], out, sweep)


if __name__ == "__main__":
    main(sys.argv[1:])
