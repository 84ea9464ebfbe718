"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main train and serve paths ONCE, end to end, in ONE process, on
one TPU, through the entry points a user calls, at the published widths of
the two models ``bench.py`` is built around (ResNet-50 v1 at 224x224x3 /
1000 classes / batch 128, BERT-base at 12 layers / 768 wide / 12 heads /
seq 512 / batch 16 / vocab 30,522; bf16, weights random from ``--seed``),
both flash kernels once more at latent attention's shape (32 heads of
8,192 positions, keys and queries 192 wide, values 128, causal) and at
grouped heads (2 sequences, 32 query heads over 8 key/value heads of 64,
8,192 positions, causal), and at a window / global stack's attention (28
query heads over 4 key/value heads of 128, 16,384 positions: once with a
sliding window of 4,096 keys, once without), one layer of sparse attention
(an indexer of 16 heads of 64 picks 2,048 of up to 16,384 keys a query,
exactly; 32 query heads over 4 key/value heads of 128 through the sparse
pair of kernels over those sets), and the
routed expert layer at one chip's share of kanana-2-30b-a3b's (8,192 tokens
of 2,048, top-6 of 128 experts of width 768, 16 held).

    python chip_smoke.py            # one chip, every phase below
    python chip_smoke.py --chips 4  # ONLY the cross-chip paths (one host)

Every phase prints one JSON line (``phase``, ``seconds``,
``compile_seconds`` and what it checked); the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The run fails, non-zero and without that line, the moment
``jax.devices()[0].platform`` is not ``tpu`` or any check fails — no phase
is wrapped in a ``try`` that lets the run go on.

Compile cache: ``JAX_COMPILATION_CACHE_DIR`` if the environment sets it,
else the fixed ``<checkout>/.jax_cache`` (mxtpu/compile_service.py — the one
rule, the one writer).

``run()`` is the whole program as a function: the tier-1 rehearsal
(tests/test_chip_smoke.py) calls it with ``TINY`` sizes on the CPU, where the
checks that only a chip can meet (compiled Mosaic kernel, peak table,
``memory_stats``) are skipped by what ``jax.devices()`` reports, not by an
option.
"""
import argparse
import gc
import json
import os
import shutil
import sys
import time
import urllib.request

import numpy as np

# ---------------------------------------------------------------- sizes
# FULL is what the driver runs: every width as published, depth uncut.
FULL = dict(
    sync_n=8192, sync_chain=32,
    resnet=dict(model="resnet50_v1", image=224, classes=1000),
    resnet_batch=128, gluon_batch=32, serve_batches=(1, 8),
    serve_requests=(1, 3, 8, 2),
    bert=dict(batch=16, seq=512, vocab=30522, dim=768, heads=12, layers=12),
    # latent attention's shape: keys and queries 192 wide, values 128
    latent=dict(heads=32, seq=8192, qk=192, v=128, check_heads=2),
    # grouped heads: 32 query heads over 8 key/value heads, two sequences
    grouped=dict(batch=2, heads=32, kv_heads=8, seq=8192, qk=64, v=64,
                 check_heads=4),
    # a window / global stack's attention: 28 query heads over 4 key/value
    # heads of 128, the model's whole context, 4,096 keys seen
    window=dict(heads=28, kv_heads=4, seq=16384, width=128, window=4096,
                check_heads=7),
    # one layer of sparse attention: 32 query heads over 4 key/value heads
    # of 128, the 2,048 keys a query an indexer of 16 heads of 64 picks
    sparse=dict(heads=32, kv_heads=4, seq=16384, width=128, dim=2048,
                index_heads=16, index_width=64, topk=2048, check_heads=8),
    # one layer of Kimi Delta Attention: 32 heads of 128 over 8,192
    # positions, chunks of 64; checked on the first heads and positions
    kda=dict(heads=32, seq=8192, width=128, chunk=64, check_heads=2,
             check_seq=1024),
    # that layer's short filter on one projection: four taps, heads of 128
    kda_conv=dict(batch=1, seq=8192, dim=4096, taps=4, head_dim=128),
    # one chip's share of a routed expert layer: 16 of 128 experts, top-6
    routed=dict(tokens=8192, dim=2048, width=768, held=16, total=128,
                top_k=6),
    train_steps=5, gluon_steps=3,
    # 1-device vs 4-device losses, same batch and seed: reduce-order
    # tolerance for bf16 parameters (the 4-way psum sums partial
    # gradients in another order), set before the first chip run
    parity_rtol=5e-2,
)
# TINY is the CPU rehearsal: same phases, same code, toy sizes.
TINY = dict(
    sync_n=256, sync_chain=4,
    resnet=dict(model="resnet18_v1", image=32, classes=10),
    resnet_batch=8, gluon_batch=8, serve_batches=(1, 4),
    serve_requests=(1, 3, 4, 2),
    bert=dict(batch=4, seq=128, vocab=512, dim=64, heads=2, layers=2),
    latent=dict(heads=2, seq=256, qk=24, v=16, check_heads=2),
    grouped=dict(batch=2, heads=4, kv_heads=2, seq=256, qk=16, v=16,
                 check_heads=2),
    window=dict(heads=14, kv_heads=2, seq=256, width=16, window=72,
                check_heads=7),
    sparse=dict(heads=8, kv_heads=2, seq=256, width=16, dim=64,
                index_heads=4, index_width=8, topk=32, check_heads=4),
    kda=dict(heads=2, seq=96, width=16, chunk=16, check_heads=2,
             check_seq=96),
    kda_conv=dict(batch=2, seq=96, dim=64, taps=4, head_dim=16),
    routed=dict(tokens=512, dim=64, width=32, held=2, total=16, top_k=3),
    train_steps=5, gluon_steps=3,
    # the toy memorizes its 8 images in three steps (loss 3.4 -> 0.02),
    # which amplifies one bf16 ULP of reduce order into tens of percent:
    # here the parity check rehearses control flow, it proves nothing
    parity_rtol=0.5,
)


# ------------------------------------------------------------ plumbing
def _compile_clock():
    """(seconds JAX spent tracing, lowering and compiling; backend compiles
    its persistent cache served instead) so far: JAX's own account, which
    the program keeps (``telemetry.watch_compiles``), not a guess from
    wall time."""
    from mxtpu import telemetry
    return (sum(telemetry.value(k) for k in
                ("compile.trace_s", "compile.lower_s", "compile.backend_s")),
            int(telemetry.value("compile.xla_cache_hits")))


def _check(cond, what):
    if not cond:
        raise AssertionError("chip_smoke: " + what)


def _retraces():
    """Compiles reported at every retrace-watchdog site (the program's own
    count — a disk-served executable is not one)."""
    from mxtpu import telemetry
    snap = telemetry.snapshot()["counters"]
    return int(sum(v for k, v in snap.items()
                   if k.startswith("retrace.") and isinstance(v, (int, float))
                   and k != "retrace.watchdog_trips"))


def _tagged_total(name):
    from mxtpu import telemetry
    return int(sum(telemetry.tagged(name).values()))


def _losses(step_fn, n):
    """``n`` calls of ``step_fn`` -> python floats (the fetch is the sync)."""
    return [float(np.asarray(step_fn().asnumpy(), np.float32).mean())
            for _ in range(n)]


def _check_training(losses, what):
    _check(all(np.isfinite(losses)), "%s: non-finite loss %s" % (what, losses))
    _check(losses[-1] < losses[0],
           "%s: loss did not fall on one fixed batch: %s" % (what, losses))


def _seed(seed):
    import mxtpu as mx
    np.random.seed(seed)
    mx.random.seed(seed)


# -------------------------------------------------------------- phases
def phase_device(on_tpu):
    import jax
    from mxtpu import _native, perf_model
    d = jax.devices()[0]
    _native.get_lib()
    rec = {"platform": d.platform, "kind": d.device_kind,
           "count": len(jax.devices()),
           "jax": jax.__version__,
           # a failed native build silently becomes the pure-Python path:
           # say so here, so a missing toolchain on this machine is seen
           "native_build_error": (str(_native.build_error())
                                  if _native.build_error() else None)}
    if on_tpu:
        stats = d.memory_stats()
        _check(stats and "bytes_limit" in stats,
               "device.memory_stats() missing: %r" % (stats,))
        rec["hbm_bytes_limit"] = int(stats["bytes_limit"])
        # raises LookupError for a device_kind that is in no table
        rec["peak_tflops"] = perf_model.peak_flops() / 1e12
        rec["peak_gbps"] = perf_model.peak_bandwidth() / 1e9
    return rec


def phase_sync(sizes, on_tpu):
    """Is ``block_until_ready`` a sound end of a timed region here? One
    jitted chain of large bf16 matmuls, timed two ways: ended by
    ``block_until_ready``, and ended by fetching one element to the host.
    They must agree; the enqueue alone must not."""
    import jax
    import jax.numpy as jnp
    n, chain = sizes["sync_n"], sizes["sync_chain"]

    @jax.jit
    def f(x, w):
        for _ in range(chain):
            x = jnp.dot(x, w, preferred_element_type=jnp.bfloat16) * 0.01
        return x

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (n, n), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(k, 1), (n, n), jnp.bfloat16)
    f(x, w).block_until_ready()                      # compile + warm
    blocks, fetches, enqueues = [], [], []
    for _ in range(5):
        t0 = time.perf_counter()
        y = f(x, w)
        enqueues.append(time.perf_counter() - t0)
        y.block_until_ready()
        blocks.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(f(x, w)[0, 0])
        fetches.append(time.perf_counter() - t0)
    block, fetch = float(np.median(blocks)), float(np.median(fetches))
    rec = {"matmul_n": n, "chain": chain,
           "block_until_ready_s": block, "fetch_to_host_s": fetch,
           "enqueue_only_s": float(np.median(enqueues)),
           "ratio_block_over_fetch": block / fetch}
    if on_tpu:
        rec["tflops_by_block"] = chain * 2 * n ** 3 / block / 1e12
        _check(abs(block - fetch) <= 0.15 * fetch + 0.002,
               "block_until_ready (%.4fs) and fetch-to-host (%.4fs) "
               "disagree: one of them is not a sync" % (block, fetch))
    return rec


def phase_train_resnet50(sizes, seed):
    import bench
    _seed(seed)
    step, (x, y) = bench.build_resnet50_step(
        sizes["resnet_batch"], "bfloat16", "NHWC", **sizes["resnet"])
    losses = _losses(lambda: step(x, y), sizes["train_steps"])
    _check_training(losses, "train_resnet50")
    return {"model": sizes["resnet"], "batch": sizes["resnet_batch"],
            "losses": losses}


def _flash_small_gaps(fa, seed):
    """Both flash kernels where the BERT step does not take them: causal,
    Tq != Tk, 3 x 4 blocks, a cotangent on lse (what ring attention
    differentiates). ``jax.vjp`` through the public function: out and lse
    against the plain float32 attention, dq, dk, dv against the float32
    blockwise oracle on the same residuals; each gap over its tensor's
    largest entry."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(seed % (2 ** 31))
    q, k, v, g = (jnp.asarray(rng.randn(2, 4, t, 64), jnp.bfloat16)
                  for t in (384, 512, 512, 384))
    g_lse = jnp.asarray(rng.randn(2, 4, 384), jnp.float32)
    (out, lse), vjp = jax.vjp(
        lambda q_, k_, v_: fa.flash_attention_with_lse(
            q_, k_, v_, True, None, 128, 128), q, k, v)
    oracle = fa._fa_backward_blockwise(q, k, v, out, lse, g, fa.Mask(True),
                                       0.125, 128, g_lse=g_lse)
    f32 = lambda x: np.asarray(x, np.float32)
    want = fa._xla_attention_lse(*(x.astype(jnp.float32) for x in (q, k, v)),
                                 fa.Mask(True), 0.125)
    return {name: float(np.max(np.abs(f32(a) - f32(b))) / np.max(np.abs(f32(b))))
            for name, a, b in zip(("out", "lse", "dq", "dk", "dv"),
                                  (out, lse) + vjp((g, g_lse)),
                                  want + oracle)}


def phase_train_bert_base(sizes, seed, on_tpu):
    import importlib

    import bench
    from mxtpu import telemetry
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    _seed(seed)
    fa.reset_dispatch_stats()
    ce = {path: "loss.softmax_ce." + path
          for path in ("one_pass", "materialized")}
    for name in ce.values():
        telemetry.reset_metric(name)
    step, (tokens, labels) = bench.build_bert_base_step(
        dtype="bfloat16", **sizes["bert"])
    losses = _losses(lambda: step(tokens, labels), sizes["train_steps"])
    _check_training(losses, "train_bert_base")
    stats = dict(fa.DISPATCH_STATS.items())
    rec = {"model": sizes["bert"], "losses": losses, "pallas_flash": stats,
           "softmax_ce": {path: telemetry.value(name)
                          for path, name in ce.items()}}
    # the loss read the logits once: no log-softmax array of their size
    _check(rec["softmax_ce"] == {"one_pass": 1, "materialized": 0},
           "the traced step's loss calls by path: %s" % rec["softmax_ce"])
    if on_tpu:
        # the flash kernels ran compiled, forward and backward: not
        # interpreted (the flag is an error on the chip), not replaced by
        # the XLA softmax or the blockwise XLA backward
        _check(stats["pallas"] > 0 and not stats["fallback_reasons"],
               "flash attention fell back: %s" % stats)
        _check(stats["bwd_pallas"] > 0 and stats["bwd_xla"] == 0,
               "the flash backward took the XLA path: %s" % stats)
        text = step.compiled().as_text()
        _check("tpu_custom_call" in text and "flash_attention_bwd" in text,
               "no flash kernels in the compiled BERT step")
        rec["tpu_custom_call_in_step"] = True
    # bf16 against the float32 oracle: tier-1 measures 0.0084 at most
    # through the interpreter; a wrong mask or lse term reads 0.1 or more
    kernel_backwards = fa.DISPATCH_STATS["bwd_pallas"]
    rec["flash_small_gaps"] = _flash_small_gaps(fa, seed)
    _check(max(rec["flash_small_gaps"].values()) <= 2e-2,
           "flash attention (causal, Tq != Tk, 3 x 4 blocks, g_lse) is %s "
           "from its float32 oracles" % rec["flash_small_gaps"])
    if on_tpu:
        after = dict(fa.DISPATCH_STATS.items())
        _check(after["bwd_pallas"] == kernel_backwards + 1
               and after["bwd_xla"] == 0,
               "the small flash backward took the XLA path: %s" % after)
    return rec


def phase_flash_kernels(n, seed, on_tpu):
    """Both flash kernels, compiled, causal, over many blocks, at the shape
    ``n``: keys and queries of one width and values of another (latent
    attention), or ``heads`` query heads over ``kv_heads`` key/value heads
    (grouped heads; K, V and their gradients stay at ``kv_heads``).
    ``jax.vjp`` through the public function, out and lse and a cotangent on
    each, against the plain float32 attention and its ``jax.vjp`` on the
    first ``check_heads`` query heads with the key/value heads they read
    (whole groups, so that dk and dv are sums over every reader; the scores
    of all heads in float32 would not fit). Fails on the chip if either
    kernel was left for XLA or K, V were repeated to the query heads. The
    record's ``pallas_flash.block_pairs`` are the call's (q block, k
    block) pairs a head: skipped / visible / crossed."""
    import importlib

    import jax
    import jax.numpy as jnp
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    kv_heads = n.get("kv_heads", n["heads"])
    group = n["heads"] // kv_heads
    rng = np.random.RandomState(seed % (2 ** 31))
    shape = lambda heads, d: (n.get("batch", 1), heads, n["seq"], n[d])
    q, k, v, g = (jnp.asarray(rng.randn(*shape(h, d)), jnp.bfloat16)
                  for h, d in ((n["heads"], "qk"), (kv_heads, "qk"),
                               (kv_heads, "v"), (n["heads"], "v")))
    g_lse = jnp.asarray(rng.randn(*shape(n["heads"], "v")[:3]), jnp.float32)
    fa.reset_dispatch_stats()
    out_lse, vjp = jax.vjp(
        lambda *a: fa.flash_attention_with_lse(*a, True), q, k, v)
    got = out_lse + vjp((g, g_lse))
    stats = dict(fa.DISPATCH_STATS.items())
    if on_tpu:
        _check(stats["pallas"] == 1 and stats["xla"] == 0,
               "the flash forward fell back: %s" % stats)
        _check(stats["bwd_pallas"] == 1 and stats["bwd_xla"] == 0,
               "the flash backward took the XLA path: %s" % stats)
        _check(not stats["kv_repeated"],
               "K and V were repeated to the query heads: %s" % stats)
    _check(stats["grouped"] == (group > 1),
           "grouped heads were not counted as such: %s" % stats)
    checked = n["check_heads"]
    f32 = lambda x: x[:, :checked if x.shape[1] == n["heads"]
                      else checked // group].astype(jnp.float32)
    want, ref_vjp = jax.vjp(
        lambda *a: fa._xla_attention_lse(*a, fa.Mask(True), n["qk"] ** -0.5),
        f32(q), f32(k), f32(v))
    want = want + ref_vjp((f32(g), f32(g_lse)))
    gaps = {name: float(jnp.max(jnp.abs(f32(a) - b)) / jnp.max(jnp.abs(b)))
            for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got,
                                  want)}
    # bf16 against float32: tier-1 measures 0.0084 at most through the
    # interpreter; a wrong mask, width, scale or head reads 0.1 or more
    _check(max(gaps.values()) <= 2e-2,
           "flash attention at %s is %s from the float32 oracle" % (n, gaps))
    return {"shape": n, "pallas_flash": stats, "gaps": gaps}


def _attention_by_rows(q, k, v, window, rows=1024, mask_t=None):
    """Plain float32 causal attention (out, lse), K and V repeated to the
    query heads, masked position by position (key j visible to query i iff
    ``i - window < j <= i``; or, with ``mask_t`` [B, T, T] int8, keys first,
    iff it is in the query's set), ``rows`` queries at a time against all
    keys: the whole score matrix of 16,384 positions would not fit."""
    import jax
    import jax.numpy as jnp
    group, t = q.shape[1] // k.shape[1], q.shape[2]
    rows = rows if t % rows == 0 else t
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(t)[None, :]

    def block(at):
        i = at + jnp.arange(rows)[:, None]
        if mask_t is not None:
            seen = jnp.swapaxes(jax.lax.dynamic_slice_in_dim(
                mask_t, at, rows, 2), 1, 2)[:, None] != 0
        else:
            seen = (j <= i) & (j > i - window) if window else j <= i
        s = jnp.einsum("bhqd,bhkd->bhqk",
                       jax.lax.dynamic_slice_in_dim(q, at, rows, 2), k,
                       precision="highest") * q.shape[-1] ** -0.5
        s = jnp.where(seen, s, -jnp.inf)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v,
                          precision="highest"), lse

    out, lse = jax.lax.map(block, jnp.arange(0, t, rows))
    merge = lambda x: jnp.moveaxis(x, 0, 2).reshape(
        x.shape[1:3] + (t,) + x.shape[4:])
    return merge(out), merge(lse)


def phase_window_attention(n, seed, on_tpu):
    """Both flash kernels, compiled, at a window / global stack's shape
    ``n`` (``heads`` query heads over ``kv_heads`` key/value heads, the
    whole context): once with the sliding window (``flash_window_fwd`` /
    ``_bwd``: the grid's sequential axis holds only the steps a block can
    need) and once without (the global layer's call, which in such a model
    also gets q and k unturned: rotary is the operator's, not the
    kernels'). ``jax.vjp`` through the public function; out against plain
    float32 attention masked position by position, dq, dk, dv against the
    float32 blockwise oracle on that reference's out and lse, on the first
    ``check_heads`` query heads with the key/value heads they read. Fails
    on the chip if a call was left for XLA, repeated K or V, or visited
    the pairs left of its window. Each record's ``block_pairs`` are the
    call's (q block, k block) pairs a head: skipped / visible / crossed."""
    import importlib

    import jax
    import jax.numpy as jnp
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    group = n["heads"] // n["kv_heads"]
    rng = np.random.RandomState(seed % (2 ** 31))
    q, k, v, g = (jnp.asarray(rng.randn(1, h, n["seq"], n["width"]),
                              jnp.bfloat16)
                  for h in (n["heads"], n["kv_heads"], n["kv_heads"],
                            n["heads"]))
    checked = n["check_heads"]
    f32 = lambda x: x[:, :checked if x.shape[1] == n["heads"]
                      else checked // group].astype(jnp.float32)
    rec = {"shape": n}
    for name, window in (("windowed", n["window"]), ("global", 0)):
        fa.reset_dispatch_stats()
        out, vjp = jax.vjp(
            lambda *a: fa.flash_attention(*a, True, window=window), q, k, v)
        got = (out,) + vjp(g)
        stats = dict(fa.DISPATCH_STATS.items())
        if on_tpu:
            _check(stats["pallas"] == 1 and stats["xla"] == 0
                   and stats["bwd_pallas"] == 1 and stats["bwd_xla"] == 0,
                   "a flash kernel (%s) was left for XLA: %s" % (name, stats))
            _check(not stats["kv_repeated"] and not stats["window_unskipped"],
                   "the %s call repeated K, V or visited the pairs left of "
                   "its window: %s" % (name, stats))
        _check(stats["windowed"] == (1 if window else 0)
               and stats["grouped"] == 1,
               "the %s call was not counted as such: %s" % (name, stats))
        want_out, lse = jax.jit(
            lambda *a: _attention_by_rows(*a, window))(f32(q), f32(k), f32(v))
        want = (want_out,) + jax.jit(
            lambda *a: fa._fa_backward_blockwise(
                *a, fa.Mask(True, window), n["width"] ** -0.5,
                min(1024, n["seq"])))(
            f32(q), f32(k), f32(v), want_out, lse, f32(g))
        gaps = {what: float(jnp.max(jnp.abs(f32(a) - b)) / jnp.max(jnp.abs(b)))
                for what, a, b in zip(("out", "dq", "dk", "dv"), got, want)}
        # bf16 against float32: a wrong edge of the mask, a block skipped
        # that a query sees, or rows of dq never opened read 0.1 or more
        _check(max(gaps.values()) <= 2e-2,
               "flash attention (%s) at %s is %s from the float32 oracles"
               % (name, n, gaps))
        rec[name] = {"pallas_flash": stats, "gaps": gaps}
    return rec


def phase_sparse_attention(n, seed, on_tpu):
    """One layer of sparse attention, compiled, at shape ``n``: the
    indexer's selection (op ``_contrib_index_select``: ``topk`` keys a
    query of ``seq``, exactly ``min(t + 1, topk)`` each and none ahead,
    counted here on the device's own result), then both sparse kernels
    (``sparse_attention_fwd`` / ``_bwd``) over those sets through
    ``jax.vjp`` of the public function: out against plain float32
    attention over the sets, dq, dk, dv against the float32 blockwise
    oracle on that reference's out and lse, on the first ``check_heads``
    query heads with the key/value heads they read. Fails on the chip if
    a call took a plain path, which holds [H, T, T]. ``pairs`` are the
    (query, key) pairs a head the sets hold and the masked form visits."""
    import importlib

    import jax
    import jax.numpy as jnp
    from mxtpu import telemetry
    from mxtpu.ops.registry import get_op
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    group, t, topk = n["heads"] // n["kv_heads"], n["seq"], n["topk"]
    rng = np.random.RandomState(seed % (2 ** 31))
    x = jnp.asarray(rng.randn(1, t, n["dim"]), jnp.bfloat16)
    wq, wk, ww = (jnp.asarray(0.02 * rng.randn(rows, n["dim"]), jnp.bfloat16)
                  for rows in (n["index_heads"] * n["index_width"],
                               n["index_width"], n["index_heads"]))
    q, k, v, g = (jnp.asarray(rng.randn(1, h, t, n["width"]), jnp.bfloat16)
                  for h in (n["heads"], n["kv_heads"], n["kv_heads"],
                            n["heads"]))
    select = jax.jit(lambda *a: get_op("_contrib_index_select").fn(
        *a, num_heads=n["index_heads"], topk=topk))
    mask_t = select(x, wq, wk, ww)
    kept = np.asarray(jnp.sum(mask_t.astype(jnp.int32), axis=1))[0]
    ahead = int(jnp.sum(jnp.tril(mask_t[0].astype(jnp.int32), -1)))
    _check(np.array_equal(kept, np.minimum(np.arange(t) + 1, topk))
           and ahead == 0,
           "the selection kept %s keys a query (first rows), %d ahead"
           % (kept[:4].tolist(), ahead))
    names = ("calls", "fallbacks", "bwd_pallas", "pairs_selected",
             "pairs_visited")
    for name in names:
        telemetry.reset_metric("sparse_attention." + name)
    out, vjp = jax.vjp(
        lambda *a: fa.sparse_attention(*a, mask_t, topk=topk), q, k, v)
    got = (out,) + vjp(g)[:3]
    stats = {name: telemetry.value("sparse_attention." + name)
             for name in names}
    if on_tpu:
        _check(stats["calls"] == 1 and stats["fallbacks"] == 0
               and stats["bwd_pallas"] == 1,
               "a sparse kernel was left for a plain path: %s %s"
               % (stats, telemetry.tagged("sparse_attention.fallbacks")))
    _check(stats["pairs_selected"] == topk * t - topk * (topk - 1) // 2,
           "the call counted %s" % stats)
    checked = n["check_heads"]
    f32 = lambda a: a[:, :checked if a.shape[1] == n["heads"]
                      else checked // group].astype(jnp.float32)
    want_out, lse = jax.jit(lambda *a: _attention_by_rows(
        *a, 0, mask_t=mask_t))(f32(q), f32(k), f32(v))
    want = (want_out,) + jax.jit(
        lambda *a: fa._fa_backward_blockwise(
            *a, fa.Mask(True, selected=True), n["width"] ** -0.5,
            min(1024, t), selection=mask_t))(
        f32(q), f32(k), f32(v), want_out, lse, f32(g))
    gaps = {what: float(jnp.max(jnp.abs(f32(a) - b)) / jnp.max(jnp.abs(b)))
            for what, a, b in zip(("out", "dq", "dk", "dv"), got, want)}
    # bf16 against float32: a set misread, a block skipped that holds a
    # selected key, or a key/value head's sum left open reads 0.1 or more
    _check(max(gaps.values()) <= 2e-2,
           "sparse attention at %s is %s from the float32 oracles"
           % (n, gaps))
    return {"shape": n, "sparse_attention": stats, "gaps": gaps,
            "pairs": {"selected": stats["pairs_selected"],
                      "visited": stats["pairs_visited"]}}


def phase_kda_conv(n, seed, on_tpu):
    """The short filter of a Kimi-Delta-Attention layer at shape ``n``,
    through the operator (``_contrib_kda_conv``) and ``jax.vjp`` of it,
    with a head's norm and without: on the chip both Pallas kernels
    (``kda_conv_fwd`` / ``kda_conv_bwd``) against the plain function under
    XLA, the value, ``d data`` and ``d weight``; on float32 data (no
    rounding at the end: the arithmetic itself, to float32's rounding) and
    on bf16 data (one rounding: an entry in some thousands falls the other
    way). Fails on the chip if a pass took the plain path."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    from mxtpu import telemetry
    from mxtpu.ops.registry import get_op
    plain = importlib.import_module("mxtpu.ops.nn")._kda_conv_plain
    op = get_op("_contrib_kda_conv").fn
    shape = (n["batch"], n["seq"], n["dim"])
    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    w = 0.5 * jax.random.normal(ks[1], (n["dim"], n["taps"]))
    names = ("calls", "pallas", "xla")
    for name in names:
        telemetry.reset_metric("kda_conv." + name)
    gaps = {}
    for dtype, limit in (("float32", 2e-5), ("bfloat16", 8e-3)):
        x = jax.random.normal(ks[0], shape).astype(dtype)
        g = jax.random.normal(ks[2], shape).astype(dtype)
        for head_dim in (n["head_dim"], 0):
            got, vjp = jax.vjp(functools.partial(op, head_dim=head_dim),
                               x, w)
            want, ref_vjp = jax.vjp(jax.jit(functools.partial(
                plain, head_dim)), x, w)
            for what, a, b in zip(("out", "ddata", "dweight"),
                                  (got,) + vjp(g), (want,) + ref_vjp(g)):
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                gap = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                gaps["%s.%s.%s" % (dtype, "normed" if head_dim else "bare",
                                   what)] = gap
                _check(gap <= limit, "the short filter at %s (%s, heads of "
                       "%d) has %s %g from the plain function's"
                       % (n, dtype, head_dim, what, gap))
    stats = {name: telemetry.value("kda_conv." + name) for name in names}
    if on_tpu:
        _check(stats == {"calls": 8, "pallas": 8, "xla": 0},
               "a pass of the short filter was left for the plain path: "
               "%s %s" % (stats, telemetry.tagged("kda_conv.xla")))
    return {"shape": n, "kda_conv": stats,
            "reasons": telemetry.tagged("kda_conv.xla"), "gaps": gaps}


def phase_kda_attention(n, seed, on_tpu):
    """One layer of Kimi Delta Attention, compiled, at shape ``n``: both
    kernels (``kda_fwd`` / ``kda_bwd``) through ``jax.vjp`` of the public
    function on bf16 operands with the log-decay over its whole range
    (-5 to 0), against the float32 recurrence taken token by token on the
    first ``check_heads`` heads: the output and the cotangents of q, k, v,
    the log-decay and beta over the first ``check_seq`` positions (a
    cotangent there depends on every later position, so the recurrence's
    is taken with the output's cotangent zero past them, and the kernels'
    too). Fails on the chip if a call took the plain path."""
    import importlib

    import jax
    import jax.numpy as jnp
    from mxtpu import telemetry
    kda = importlib.import_module("mxtpu.ops.pallas.kda")
    h, t, w = n["heads"], n["seq"], n["width"]
    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 6)

    def unit(key):
        x = jax.random.normal(key, (1, t, h, w))
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            1, t, h * w).astype(jnp.bfloat16)

    q, k = unit(ks[0]), unit(ks[1])
    v = jax.random.normal(ks[2], (1, t, h * w)).astype(jnp.bfloat16)
    g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (1, t, h * w)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h))).astype(
        jnp.bfloat16)
    ch, ct = n["check_heads"], n["check_seq"]
    do = jax.random.normal(ks[5], (1, t, h * w)).astype(jnp.bfloat16)
    do = do * (jnp.arange(t) < ct)[None, :, None].astype(jnp.bfloat16)
    names = ("calls", "fallbacks", "chunks")
    for name in names:
        telemetry.reset_metric("kda_attention." + name)
    out, vjp = jax.vjp(lambda *a: kda.kda_attention(*a, n["chunk"]),
                       q, k, v, g, beta)
    got = (out,) + vjp(do)
    stats = {name: telemetry.value("kda_attention." + name)
             for name in names}
    if on_tpu:
        _check(stats["calls"] == 1 and stats["fallbacks"] == 0,
               "a KDA kernel was left for the plain path: %s %s"
               % (stats, telemetry.tagged("kda_attention.fallbacks")))
    _check(stats["chunks"] == -(-t // n["chunk"]),
           "the call counted %s" % stats)

    def head(q, k, v, g, b):               # [T, .] of one head
        def token(s, x):
            q_t, k_t, v_t, g_t, b_t = x
            s = jnp.exp(g_t)[:, None] * s
            s = s + b_t * jnp.outer(k_t, v_t - s.T @ k_t)
            return s, s.T @ q_t / jnp.sqrt(float(w))
        return jax.lax.scan(token, jnp.zeros((w, w), jnp.float32),
                            (q, k, v, g, b))[1]

    def first(x, width):                   # the checked heads and positions
        return x[0, :ct].reshape(ct, h, width)[:, :ch].astype(jnp.float32)

    ops = (first(q, w), first(k, w), first(v, w), first(g, w),
           first(beta, 1)[..., 0])
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(
            jax.jit(jax.vmap(head, in_axes=1, out_axes=1)), *ops)
        want = (want,) + ref_vjp(first(do, w))
    mine = [first(a, w) for a in got[:5]] + [first(got[5], 1)[..., 0]]
    gaps = {what: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for what, a, b in zip(("out", "dq", "dk", "dv", "dg", "dbeta"),
                                  mine, want)}
    # bf16 operands against float32: a chunk's state dropped, the decay
    # misread or a sub-chunk's exponent out of range reads 0.1 or more
    _check(max(gaps.values()) <= 3e-2,
           "Kimi Delta Attention at %s is %s from the recurrence"
           % (n, gaps))
    return {"shape": n, "kda_attention": stats, "gaps": gaps}


def phase_routed_layer(sizes, seed, on_tpu):
    """The routed expert layer alone at one chip's share of the experts:
    its output and the gradients of x, the router and the three expert
    leaves through the grouped path (the rows routed here, at the rung
    ``moe.piece_plan`` picks) against the masked form, which multiplies
    every token by every expert held. On the chip the device's memory is
    filled with NaN and freed first: the grouped kernel leaves the rows
    past the last group unwritten, and whatever they hold must not reach a
    result. On the chip every grouped product, nine a rung, has to take the
    Pallas grouped-matmul kernel (``moe.grouped_mm.pallas``; one left to
    XLA's ``ragged_dot`` fails the phase with its reason), so the
    comparison with the masked form is the kernel's on the chip; off the
    chip the same nine are counted on either path. Prints the plan (rows
    live / run / laid out at most), the form
    each rung's sum by token takes in each direction (``gather`` over every
    pair or ``scatter``-add of the rung's rows: ``moe._sums_by_gather``, the
    predicate the layer asks, and what the trace counted of each), and what
    the traced gradient counted: the bytes the forward keeps for the
    backward (its two up products at all T*k rows) and the grouped products
    of one backward branch (six: none computed again)."""
    import jax
    import jax.numpy as jnp
    from mxtpu import telemetry
    from mxtpu.parallel import moe
    n = sizes["routed"]
    if on_tpu:      # NaN over half of what is free, a GiB at a time
        stats = jax.devices()[0].memory_stats()
        free = stats["bytes_limit"] - stats["bytes_in_use"] \
            - stats.get("bytes_reserved", 0)
        junk = [jnp.full((1 << 28,), jnp.nan, jnp.float32)
                for _ in range(free // 2 >> 30)]
        jax.block_until_ready(junk)
        del junk
    ks = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 5)
    leaf = lambda k, *shape: (0.02 * jax.random.normal(
        k, shape, jnp.float32)).astype(jnp.bfloat16)
    x = jax.random.normal(ks[0], (n["tokens"], n["dim"]),
                          jnp.float32).astype(jnp.bfloat16)
    router = leaf(ks[1], n["total"], n["dim"])
    bias = jnp.zeros((n["total"],), jnp.bfloat16)
    experts = (leaf(ks[2], n["held"], n["dim"], n["width"]),
               leaf(ks[3], n["held"], n["dim"], n["width"]),
               leaf(ks[4], n["held"], n["width"], n["dim"]))

    def grads(grouped):
        def loss(x, router, *experts):
            out = moe.routed_ffn(x, router, bias, *experts,
                                 top_k=n["top_k"], scale=2.448,
                                 grouped=grouped)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
        (_, out), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, router, *experts)
        return (out,) + g

    f32 = lambda a: a.astype(jnp.float32)
    counters = ("moe.kept_bytes", "moe.bwd_products",
                "moe.sum_by_token.gather", "moe.sum_by_token.scatter",
                "moe.grouped_mm.pallas", "moe.grouped_mm.xla")
    for name in counters:
        telemetry.reset_metric(name)
    got = grads(True)
    kept = {name: telemetry.value(name) for name in counters}
    _check(kept["moe.bwd_products"] == 6 and kept["moe.kept_bytes"]
           == 2 * n["tokens"] * n["top_k"] * n["width"]
           * experts[0].dtype.itemsize,
           "the routed layer's backward traced %s" % kept)
    want = grads(False)
    _check(all(bool(jnp.all(jnp.isfinite(f32(a)))) for a in got),
           "the routed layer's grouped path gave a value that is not finite")
    gaps = {name: float(jnp.linalg.norm(f32(a) - f32(b))
                        / jnp.linalg.norm(f32(b)))
            for name, a, b in zip(("out", "dx", "drouter", "dgate", "dup",
                                   "ddown"), got, want)}
    # bf16 both: tier-1 reads 0.01 between the forms; a row dropped, a row
    # of another token or a wrong weight reads 0.1 or more
    _check(max(gaps.values()) <= 3e-2,
           "the routed layer is %s from its masked form" % gaps)
    idx, _ = moe.route_top_k(x, router, bias, n["top_k"], 2.448)
    plan = moe.piece_plan(idx, 0, n["held"], n["total"])
    rows = {"live": int(plan.n_live), "run": int(plan.rows),
            "total": n["tokens"] * n["top_k"]}
    _check(rows["live"] <= rows["run"] == min(
        r for r in plan.rungs if r >= rows["live"]),
        "the plan runs %s of the rungs %s" % (rows, plan.rungs))
    products = {"pallas": kept["moe.grouped_mm.pallas"],
                "xla": telemetry.tagged("moe.grouped_mm.xla")}
    _check(products["pallas"] + kept["moe.grouped_mm.xla"]
           == 9 * len(plan.rungs)
           and not (on_tpu and kept["moe.grouped_mm.xla"]),
           "the routed layer's %d rungs traced the grouped products %s"
           % (len(plan.rungs), products))
    # the forward sums rows of the experts' dtype, the backward float32
    sums = {way: {str(r): "gather" if moe._sums_by_gather(
        r, rows["total"], size) else "scatter" for r in plan.rungs}
        for way, size in (("fwd", x.dtype.itemsize), ("bwd", 4))}
    built = [form for way in sums.values() for form in way.values()]
    _check(all(kept["moe.sum_by_token." + form] == built.count(form)
               for form in ("gather", "scatter"))
           and sums["fwd"][str(rows["total"])] == "gather"
           and sums["bwd"][str(rows["total"])] == "gather",
           "the routed layer's sums by token traced %s for %s" % (kept, sums))
    return {"shape": n, "rows": rows, "rungs": list(plan.rungs),
            "sums_by_token": sums, "kept_bytes": kept["moe.kept_bytes"],
            "bwd_products": kept["moe.bwd_products"],
            "grouped_products": products, "gaps": gaps}


def _gluon_loop(sizes, seed, mesh=None):
    """The user-facing loop — hybridize / record / backward / Trainer.step
    (CachedOp + FusedUpdater; mesh-native with ZeRO-1 when ``mesh`` is
    given). Returns ``(losses, net)``."""
    import bench
    from mxtpu import autograd, gluon
    _seed(seed)
    net, x, y = bench.build_resnet50(sizes["gluon_batch"], "bfloat16",
                                     "NHWC", **sizes["resnet"])
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9},
                            mesh=mesh, zero1=True)
    xs, ys = trainer.shard_batch(x, y)      # the identity without a mesh
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def one():
        with autograd.record():
            loss = loss_fn(net(xs), ys)
        loss.backward()
        trainer.step(sizes["gluon_batch"])
        return loss

    return _losses(one, sizes["gluon_steps"]), net


def phase_gluon_trainer(sizes, seed):
    """Returns the trained net too, for ``serve`` and ``warm_start``."""
    losses, net = _gluon_loop(sizes, seed)
    _check_training(losses, "gluon_trainer")
    return {"batch": sizes["gluon_batch"], "losses": losses}, net


def _http_predict(address, x):
    req = urllib.request.Request(
        "http://%s:%d/predict" % address,
        data=json.dumps({"data": x.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        _check(r.status == 200, "/predict answered %d" % r.status)
        return json.loads(r.read())


def _example(sizes):
    """One bf16 request row: the shape template a Predictor settles on."""
    import mxtpu as mx
    img = sizes["resnet"]["image"]
    return mx.nd.array(np.zeros((1, img, img, 3), np.float32),
                       dtype="bfloat16")


def _requests(sizes, seed):
    """The mixed-batch request payloads: bf16-representable float32."""
    import jax.numpy as jnp
    img = sizes["resnet"]["image"]
    rng = np.random.RandomState(seed)
    return [np.asarray(jnp.asarray(
        rng.uniform(-1, 1, (n, img, img, 3)), jnp.bfloat16).astype(
            jnp.float32)) for n in sizes["serve_requests"]]


def phase_serve(sizes, seed, net):
    """Predictor -> MicroBatcher -> ModelServer over HTTP; answers checked
    against a direct ``net(x)`` on the same device."""
    import mxtpu as mx
    from mxtpu import telemetry
    from mxtpu.serving import (BucketSpec, MicroBatcher, ModelServer,
                               Predictor)
    top = max(sizes["serve_batches"])
    pred = Predictor(net, BucketSpec(batch_sizes=list(sizes["serve_batches"])),
                     example=_example(sizes), warmup=True)
    warm_compiles = telemetry.value("retrace.serving.predict")
    _check(warm_compiles == len(sizes["serve_batches"]),
           "warmup compiled %d executables for %d buckets"
           % (warm_compiles, len(sizes["serve_batches"])))
    server = ModelServer(MicroBatcher(pred, max_batch_size=top,
                                      max_wait_ms=1)).start()
    worst = 0.0
    try:
        for x in _requests(sizes, seed):
            out = _http_predict(server.address, x)
            n = x.shape[0]
            got = np.asarray(out["outputs"][0], np.float32)
            _check(out["n"] == n and got.shape[0] == n,
                   "request of %d rows answered %s" % (n, got.shape))
            # direct reference at ONE shape (rows are independent in
            # inference, so padding to the top bucket changes no row)
            padded = np.zeros((top,) + x.shape[1:], np.float32)
            padded[:n] = x
            ref = net(mx.nd.array(padded, dtype="bfloat16")).asnumpy()
            ref = np.asarray(ref, np.float32)[:n]
            _check(np.isfinite(got).all(), "non-finite served output")
            err = float(np.max(np.abs(got - ref)))
            scale = max(1.0, float(np.max(np.abs(ref))))
            worst = max(worst, err / scale)
            _check(err <= 0.05 * scale,
                   "served output off the direct net(x) by %g (scale %g)"
                   % (err, scale))
    finally:
        server.close()
    after = telemetry.value("retrace.serving.predict")
    _check(after == warm_compiles,
           "%d compiles at serving.predict after warmup"
           % (after - warm_compiles))
    return {"buckets": list(sizes["serve_batches"]),
            "requests": list(sizes["serve_requests"]),
            "compiles_after_warmup": int(after - warm_compiles),
            "worst_rel_err_vs_direct": worst}


def _store_dir():
    """The program's own executable store for this run: a subdirectory of
    the in-checkout cache directory, emptied when a phase starts."""
    from mxtpu import compile_service
    d = os.path.join(compile_service.CHECKOUT_XLA_CACHE, "smoke_store")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def phase_warm_start(sizes, seed, net):
    """Build one Predictor against an empty executable store, drop it,
    rebuild it: the second is served from disk — zero compiles, bit-equal
    outputs (the ``execution_devices`` reload path on a real backend)."""
    import mxtpu as mx
    from mxtpu import compile_service, telemetry
    from mxtpu.serving import BucketSpec, Predictor
    spec = BucketSpec(batch_sizes=list(sizes["serve_batches"]))
    example = _example(sizes)
    x = mx.nd.array(_requests(sizes, seed)[1], dtype="bfloat16")
    os.environ["MXTPU_COMPILE_CACHE_DIR"] = _store_dir()
    try:
        cold = Predictor(net, spec, example=example, warmup=True,
                         site="serving.predict.cold")
        ref = cold.predict(x).asnumpy()
        writes = _tagged_total("compile.disk.writes")
        _check(writes >= len(spec), "cold build spilled %d blobs" % writes)
        del cold
        compile_service.reset()          # a fresh process, as far as the
        gc.collect()                     # service's memory goes
        c0, h0 = _retraces(), _tagged_total("compile.disk.hits")
        warm = Predictor(net, spec, example=example, warmup=True,
                         site="serving.predict.warm")
        out = warm.predict(x).asnumpy()
        compiles = _retraces() - c0
        hits = _tagged_total("compile.disk.hits") - h0
    finally:
        del os.environ["MXTPU_COMPILE_CACHE_DIR"]
    _check(hits > 0, "warm rebuild had no compile.disk.hits")
    _check(compiles == 0, "warm rebuild compiled %d times" % compiles)
    _check(np.array_equal(np.asarray(out), np.asarray(ref)),
           "disk-served executable is not bit-equal to the built one")
    return {"disk_writes": writes, "disk_hits": hits,
            "warm_compiles": compiles, "bit_equal": True,
            "disk_drops": dict(telemetry.tagged("compile.disk.drops"))}


# ----------------------------------------------------- four-chip phases
def _four(devices):
    _check(len(devices) >= 4, "--chips 4 needs 4 devices, found %d"
           % len(devices))
    return devices[:4]


def _device_spans(arrays):
    return {len(a.sharding.device_set) for a in arrays}


def _check_parity(one, four, rtol, what):
    _check(all(np.isfinite(one + four)), "%s: non-finite loss" % what)
    _check(np.allclose(one, four, rtol=rtol, atol=rtol),
           "%s: 1-device losses %s vs 4-device %s (rtol %g)"
           % (what, one, four, rtol))


def phase_dp_resnet50(sizes, seed, on_tpu):
    """Data-parallel ShardedTrainStep on a 4-device mesh against the same
    batch and seed on one device."""
    import jax

    import bench
    devs = _four(jax.devices())
    runs = {}
    for name, sub in (("one", devs[:1]), ("four", devs)):
        _seed(seed)
        step, (x, y) = bench.build_resnet50_step(
            sizes["resnet_batch"], "bfloat16", "NHWC", devices=sub,
            **sizes["resnet"])
        runs[name] = _losses(lambda: step(x, y), sizes["train_steps"])
    _check_parity(runs["one"], runs["four"], sizes["parity_rtol"],
                  "dp_resnet50")
    _check_training(runs["four"], "dp_resnet50")
    spans = _device_spans(step._param_datas)
    _check(spans == {4}, "parameters span %s devices, not 4" % spans)
    text = step.compiled().as_text()
    collectives = [c for c in ("all-reduce", "reduce-scatter", "all-gather")
                   if c in text]
    _check(collectives, "no collective in the compiled 4-device step")
    rec = {"losses_one": runs["one"], "losses_four": runs["four"],
           "param_device_span": 4, "collectives": collectives}
    if on_tpu:
        used = [int(d.memory_stats()["bytes_in_use"]) for d in devs]
        _check(all(u > 1 << 20 for u in used[1:]),
               "devices 1-3 hold %s bytes" % used[1:])
        rec["bytes_in_use"] = used
    return rec


def phase_dp_gluon_trainer(sizes, seed):
    """``gluon.Trainer(mesh=...)`` with ZeRO-1 on a 4-device mesh against
    the plain one-device Trainer, same batch and seed."""
    import jax

    from mxtpu.parallel import data_parallel_mesh
    one, _ = _gluon_loop(sizes, seed)
    four, net = _gluon_loop(sizes, seed,
                            data_parallel_mesh(_four(jax.devices())))
    _check_parity(one, four, sizes["parity_rtol"], "dp_gluon_trainer")
    spans = _device_spans(p.data()._data
                          for p in net.collect_params().values())
    _check(spans == {4}, "parameters span %s devices, not 4" % spans)
    return {"losses_one": one, "losses_four": four, "zero1": True,
            "param_device_span": 4}


def phase_replicas(sizes, seed, on_tpu):
    """``ReplicaSet(n=4)``: one replica per device, each answers, and a
    replaced replica comes back from the disk store with zero compiles."""
    import jax

    import bench
    import mxtpu as mx
    from mxtpu import telemetry
    from mxtpu.serving import BucketSpec, ReplicaSet
    devs = _four(jax.devices())
    _seed(seed)
    top = max(sizes["serve_batches"])
    net, _, _ = bench.build_resnet50(top, "bfloat16", "NHWC",
                                     **sizes["resnet"])
    x = mx.nd.array(_requests(sizes, seed)[2][:top], dtype="bfloat16")
    os.environ["MXTPU_COMPILE_CACHE_DIR"] = _store_dir()
    try:
        rs = ReplicaSet(net, BucketSpec(batch_sizes=[top]), devices=devs,
                        example=_example(sizes), warmup=True)
        outs, homes = [], []
        for rep in rs.replicas:
            where = set()
            for d in rep.predictor.param_args()[0]:
                where |= set(d.devices())
            _check(where == {rep.device},
                   "replica %d parameters live on %s" % (rep.index, where))
            homes.append(rep.device.id)
            outs.append(np.asarray(rep.predictor.predict(x).asnumpy(),
                                   np.float32))
        _check(len(set(homes)) == 4, "replicas share devices: %s" % homes)
        for o in outs[1:]:
            _check(np.allclose(o, outs[0], rtol=1e-2, atol=1e-2),
                   "replicas disagree on one request")
        # replace replica 2: retire it, bring a new one up on its device
        dev = rs.remove_replica(2).device
        rs.finalize_retiring()
        c0, h0 = _retraces(), _tagged_total("compile.disk.hits")
        rep = rs.add_replica(device=dev)
        compiles = _retraces() - c0
        hits = _tagged_total("compile.disk.hits") - h0
        again = np.asarray(rep.predictor.predict(x).asnumpy(), np.float32)
    finally:
        del os.environ["MXTPU_COMPILE_CACHE_DIR"]
    _check(hits > 0 and compiles == 0,
           "replacement replica: %d disk hits, %d compiles"
           % (hits, compiles))
    _check(np.array_equal(again, outs[2]),
           "disk-served replica is not bit-equal to the one it replaced")
    return {"replica_devices": homes, "replacement_disk_hits": hits,
            "replacement_compiles": compiles, "bit_equal": True,
            "disk_drops": dict(telemetry.tagged("compile.disk.drops"))}


# ----------------------------------------------------------------- run
def run(sizes, chips=1, seed=0, out=sys.stdout):
    """Every phase of the one-chip run (``chips=1``) or only the
    cross-chip paths (``chips=4``), one JSON line each on ``out``.
    Returns the device record of the last line. Raises on any failed
    check. The platform is whatever ``jax.devices()`` reports —
    ``main()`` is what refuses anything but a TPU."""
    import jax

    import bench
    from mxtpu import compile_service
    cache_dir = compile_service.use_checkout_xla_cache()
    from mxtpu import telemetry
    telemetry.watch_compiles()
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    t_run = time.perf_counter()
    compile_s0, hits0 = _compile_clock()

    def phase(name, fn, *args):
        (c0, h0), t0 = _compile_clock(), time.perf_counter()
        rec = fn(*args)
        extra = None
        if isinstance(rec, tuple):
            rec, extra = rec
        c1, h1 = _compile_clock()
        line = {"phase": name,
                "seconds": round(time.perf_counter() - t0, 3),
                "compile_seconds": round(c1 - c0, 3),
                "xla_cache_hits": h1 - h0}
        line.update(rec)
        print(json.dumps(line), file=out, flush=True)
        gc.collect()
        return extra

    # the bench's resnet stem variant (MLPerf s2d, default on for NHWC)
    with bench.s2d_stem_env("1"):
        phase("device", phase_device, on_tpu)
        if chips == 1:
            phase("sync", phase_sync, sizes, on_tpu)
            phase("train_resnet50", phase_train_resnet50, sizes, seed)
            phase("train_bert_base", phase_train_bert_base, sizes, seed,
                  on_tpu)
            phase("flash_two_widths", phase_flash_kernels, sizes["latent"],
                  seed, on_tpu)
            phase("flash_grouped", phase_flash_kernels, sizes["grouped"],
                  seed, on_tpu)
            phase("window_attention", phase_window_attention,
                  sizes["window"], seed, on_tpu)
            phase("sparse_attention", phase_sparse_attention,
                  sizes["sparse"], seed, on_tpu)
            phase("kda_attention", phase_kda_attention, sizes["kda"], seed,
                  on_tpu)
            phase("kda_conv", phase_kda_conv, sizes["kda_conv"], seed,
                  on_tpu)
            phase("routed_layer", phase_routed_layer, sizes, seed, on_tpu)
            net = phase("gluon_trainer", phase_gluon_trainer, sizes, seed)
            phase("serve", phase_serve, sizes, seed, net)
            phase("warm_start", phase_warm_start, sizes, seed, net)
        else:
            phase("dp_resnet50", phase_dp_resnet50, sizes, seed, on_tpu)
            phase("dp_gluon_trainer", phase_dp_gluon_trainer, sizes, seed)
            phase("replicas", phase_replicas, sizes, seed, on_tpu)
    compile_s, hits = _compile_clock()
    print(json.dumps({"phase": "total",
                      "seconds": round(time.perf_counter() - t_run, 3),
                      "compile_seconds": round(compile_s - compile_s0, 3),
                      "xla_cache_hits": hits - hits0,
                      "xla_cache_dir": cache_dir}), file=out, flush=True)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip paths, on one 4-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("chip_smoke.py: jax.devices()[0].platform is %r, not 'tpu' "
                 "— this script proves the chip path and has no CPU "
                 "fallback" % devices[0].platform)
    if len(devices) != args.chips:
        sys.exit("chip_smoke.py: --chips %d but JAX reports %d device(s)"
                 % (args.chips, len(devices)))
    device = run(FULL, chips=args.chips, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
