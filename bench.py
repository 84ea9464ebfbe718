"""Benchmarks for the BASELINE.json scoring configs.

Select with ``BENCH_CONFIG`` (default ``all`` — every scoring config, one
JSON line each, so the driver artifact captures all three):

* ``resnet50``  — ResNet-50 training, b128 bf16 NHWC (BENCH_LAYOUT=NCHW to
  compare layouts). Reference baseline 363.69 img/s: batch 128 fp32 on 1x
  V100 (docs/faq/perf.md:219; BASELINE.md "Training, single GPU").
* ``lstm_ptb``  — Gluon 2x650-unit LSTM PTB language model (reference
  example/gluon/word_language_model), tokens/sec.
* ``bert_base`` — BERT-base-shaped bidirectional encoder pretraining step
  (12L/768d/12H, seq 512) driving the Pallas flash-attention kernel,
  tokens/sec.

Every config prints ONE JSON line {"metric", "value", "unit", "vs_baseline",
"mfu", "hfu"} (resnet50 adds "pct_of_achievable" — per-chip fraction of the
measured 140 TFLOP/s achievable rate, the PERF.md gap statement). EVERY
printed line is stamped with the resolved ``platform`` and active
``policy_key``; ``main()`` refuses to run at all off the TPU, and any
config that errors makes the run exit non-zero:

* ``mfu`` — *model*-flops utilization in THE one convention used across
  BASELINE.md / PERF.md / this file (reconciled round 4): an analytic
  per-item train-step FLOP count with a multiply-add = 2 FLOPs (the
  standard MFU convention, and how XLA counts), divided by datasheet chip
  peak. ResNet-50 fwd = 4.089 GMAC/img = 8.18 GFLOP/img; train = 3x fwd =
  24.5 GFLOP/img; the >=50% north star is therefore 4,015 img/s/chip on a
  197 TFLOP/s v5e. (Rounds 1-3 reported mfu with MAC=1 against the MAC=2
  peak — a mixed convention that understated utilization 2x.)
* ``hfu`` — *hardware*-flops utilization: XLA's own executed-flop count for
  the exact compiled step (``ShardedTrainStep.compiled_step_flops``)
  against the same peak. Same FLOP convention as mfu, so hfu/mfu - 1 is
  exactly the recompute + non-model work XLA schedules.

Peak is v5e bf16 ~197 TFLOP/s; override with BENCH_PEAK_TFLOPS. The whole
train step (fwd+loss+bwd+update) runs as one compiled XLA program via
mxtpu.parallel.ShardedTrainStep; bf16 is the TPU design point (MXU-native),
matching how the reference leans on cuDNN fp32.
"""
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

STEPS = int(os.environ.get("BENCH_STEPS", "20"))


def _stamp(rec):
    """Stamp the resolved platform and the active lever set into a JSON
    record, in place. Every line bench.py prints carries these, so the
    device and the lever configuration each number was taken under are
    self-describing. A backend that cannot answer raises: a line without
    a device is not a measurement."""
    if "platform" not in rec:
        import jax
        rec["platform"] = jax.devices()[0].platform
    if "policy_key" not in rec:
        from mxtpu.ops.registry import policy_key
        rec["policy_key"] = list(policy_key())
    if "ledger" not in rec:
        # ISSUE 12: every bench line carries the run's memory trajectory
        # — executable-ledger compile totals + process-peak HBM — so a
        # BENCH round is attributable to its compile/memory cost after
        # the fact, exactly like platform/policy_key
        from mxtpu import xprof
        rec["ledger"] = xprof.summary() if xprof.enabled() else None
    return rec


def _emit(rec):
    print(json.dumps(_stamp(rec)), flush=True)


def _peak_flops():
    """Chip peak FLOP/s for the MFU denominator — ``BENCH_PEAK_TFLOPS``
    override first, else the ONE shared datasheet table
    (mxtpu/perf_model.py, which bench, tools/perf_peak.py, and the
    runtime ``perf.mfu`` gauge all read — the convention can no longer
    fork). None on the CPU fallback: MFU is meaningless there."""
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    from mxtpu import perf_model
    return perf_model.peak_flops()


def _run(step, batch, n_items, model_flops_per_item=None):
    """Warm up, time STEPS steps, return (items/sec, mfu, hfu).

    mfu uses the analytic per-item train FLOP count; hfu uses XLA's executed
    flops for the compiled step (see module docstring).
    """
    for _ in range(3):  # warmup + compile
        step(*batch).asnumpy()
    profile = os.environ.get("BENCH_PROFILE")
    if profile:
        # chrome-trace + jax device trace of the timed region, through the
        # framework's own profiler (mxtpu/profiler.py ~ src/profiler/
        # profiler.h) — profile_xla owns the jax start/stop_trace pair
        from mxtpu import profiler as _prof
        # capture bound: the whole timed region, not the 120 s default —
        # a truncated trace would silently misattribute the step time
        trace_max = float(os.environ.get("BENCH_TRACE_MAX_S", "900"))
        _prof.set_config(filename=profile, profile_xla=True,
                         xla_trace_dir=os.path.dirname(profile) or ".",
                         xla_trace_max_s=trace_max)
        _prof.start()
    try:
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = step(*batch)
        out.asnumpy()  # sync
        dt = time.perf_counter() - t0
    finally:
        if profile:
            _prof.stop()
            _prof.dump()
    rate = n_items * STEPS / dt
    peak = _peak_flops()
    mfu = hfu = None
    if peak:
        # rate is GLOBAL throughput across the mesh; peak must be the whole
        # mesh's peak, not one chip's (on the driver's single real chip this
        # is a no-op). compiled_step_flops is the per-device GSPMD module,
        # so hfu stays against the single-chip peak.
        mesh = getattr(step, "_mesh", None)
        n_dev = int(mesh.devices.size) if mesh is not None else 1
        if model_flops_per_item:
            mfu = rate * model_flops_per_item / (peak * n_dev)
        try:
            hfu = step.compiled_step_flops() / (dt / STEPS) / peak
        except Exception:
            pass
    return rate, mfu, hfu


def _default_s2d(layout):
    """s2d stem DEFAULT ON for NHWC as of round 5 (exactly-equivalent
    transform; measured positive in two on-chip sessions and part of the
    best-known config, resnet_best 2580.3 img/s). BENCH_S2D_STEM=0
    disables for A/Bs; the transform requires NHWC, so other layouts
    default off."""
    return os.environ.get("BENCH_S2D_STEM",
                          "1" if layout == "NHWC" else "0")


def build_resnet50(batch, dtype="bfloat16", layout="NHWC",
                   model="resnet50_v1", image=224, classes=1000):
    """The bench ResNet: zoo ``model`` under ``layout``, initialized,
    shapes settled on one random batch, the policy-mode s2d stem wrapped
    in (NHWC), cast to ``dtype``. Returns ``(net, x, y)``. Shared with
    ``chip_smoke.py`` so the smoke run drives THIS construction, not a
    copy of it. The stem variant is picked at trace time from
    ``MXTPU_S2D_STEM`` (see :func:`s2d_stem_env`)."""
    import mxtpu as mx
    from mxtpu.gluon.model_zoo import vision

    with mx.layout(layout):
        net = getattr(vision, model)(classes=classes)
    net.initialize()
    shape = ((batch, image, image, 3) if layout == "NHWC"
             else (batch, 3, image, image))
    x = mx.nd.array(np.random.uniform(-1, 1, size=shape), dtype="float32")
    net(x)  # settle deferred shapes
    if layout == "NHWC":
        # MLPerf space-to-depth stem, exactly equivalent, as a POLICY
        # lever (round 7): the wrap is unconditional and mode None defers
        # the variant choice to MXTPU_S2D_STEM at trace time (0 = the
        # plain stem, so the wrap is free). The env rides
        # registry.policy_key, so it recompiles per run. mode 1 = 4x4 conv
        # on 12 channels; mode 2 = double s2d -> MXU-shaped 3x3 conv on
        # 48->256 channels + depth-to-space (contrib/s2d_stem.py)
        from mxtpu.contrib import s2d_stem
        s2d_stem.apply_to_resnet(net)
    if dtype != "float32":
        net.cast(dtype)
        x = x.astype(dtype)
    y = mx.nd.array(np.random.randint(0, classes, size=(batch,)),
                    dtype="float32")
    return net, x, y


def build_resnet50_step(batch, dtype="bfloat16", layout="NHWC",
                        devices=None, **model_kw):
    """``(step, (x, y))``: :func:`build_resnet50` under the whole-step
    ``ShardedTrainStep`` (SGD + momentum) on a data mesh over ``devices``
    (default: every visible device)."""
    from mxtpu import gluon
    from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh

    net, x, y = build_resnet50(batch, dtype, layout, **model_kw)
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    step = ShardedTrainStep(net, loss, data_parallel_mesh(devices),
                            optimizer="sgd",
                            optimizer_params={"learning_rate": 0.01,
                                              "momentum": 0.9})
    return step, (x, y)


@contextlib.contextmanager
def s2d_stem_env(flag):
    """MXTPU_S2D_STEM pinned to ``flag`` for the build AND the run (it is
    read at trace time), restored on exit so the ambient policy is what
    later lines are stamped with."""
    saved = os.environ.get("MXTPU_S2D_STEM")
    os.environ["MXTPU_S2D_STEM"] = flag
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop("MXTPU_S2D_STEM", None)
        else:
            os.environ["MXTPU_S2D_STEM"] = saved


def bench_resnet50():
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")
    baseline = 363.69  # img/s, V100 fp32 batch 128 (docs/faq/perf.md:219)

    s2d_flag = _default_s2d(layout)
    if s2d_flag not in ("0", "1", "2"):
        # a typo must not silently measure the plain stem under an s2d
        # label
        raise RuntimeError("BENCH_S2D_STEM=%r: valid values are 0 (plain "
                           "stem), 1 (s2d), 2 (double-s2d)" % s2d_flag)
    if s2d_flag in ("1", "2") and layout != "NHWC":
        raise RuntimeError("BENCH_S2D_STEM requires BENCH_LAYOUT=NHWC "
                           "(refusing to report a plain-stem number as s2d)")
    with s2d_stem_env(s2d_flag if layout == "NHWC" else "0"):
        step, batch_xy = build_resnet50_step(batch, dtype, layout)
        # ResNet-50 @224: 4.089 GMAC/img forward = 8.18 GFLOP (MAC=2),
        # train = 3x fwd = 24.5 GFLOP/img (the module-docstring
        # north-star arithmetic)
        rate, mfu, hfu = _run(step, batch_xy, batch,
                              model_flops_per_item=3 * 2 * 4.089e9)
        # capture the lever set the measurement actually ran under — the
        # env restore on exit would otherwise let _stamp record the
        # ambient (s2d-less) policy onto this line
        from mxtpu.ops.registry import policy_key
        active_policy = list(policy_key())
    rec = {
        "metric": "resnet50_train_throughput_b%d_%s_%s"
                  % (batch, dtype, layout.lower()),
        "value": round(rate, 2),
        "unit": "images/sec",
        "vs_baseline": round(rate / baseline, 3),
        "mfu": round(mfu, 4) if mfu else None,
        "hfu": round(hfu, 4) if hfu else None,
        "policy_key": active_policy,
    }
    if mfu:
        # the gap statement PERF.md tracks: fraction of the chip's MEASURED
        # achievable rate (140 TFLOP/s ideal matmul, tools/perf_peak.py).
        # Derived from mfu, which already divides by peak * n_dev, so this
        # stays a PER-CHIP fraction on a multi-chip mesh.
        rec["pct_of_achievable"] = round(mfu * _peak_flops() / 140e12, 4)
    return rec


def bench_lstm_ptb():
    """Reference example/gluon/word_language_model defaults: 2-layer
    650-unit LSTM, bptt 35, PTB vocab 33278."""
    import mxtpu as mx
    from mxtpu import gluon
    from mxtpu.gluon import nn, rnn
    from mxtpu.gluon.block import HybridBlock
    from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    bptt, vocab, nhid, nlayers = 35, 33278, 650, 2

    class RNNModel(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = nn.Embedding(vocab, nhid)
                self.lstm = rnn.LSTM(nhid, num_layers=nlayers, layout="NTC")
                self.decoder = nn.Dense(vocab, flatten=False)

        def hybrid_forward(self, F, tokens):
            return self.decoder(self.lstm(self.embed(tokens)))

    net = RNNModel()
    net.initialize()
    tokens = mx.nd.array(np.random.randint(0, vocab, (batch, bptt)),
                         dtype="int32")
    labels = mx.nd.array(np.random.randint(0, vocab, (batch, bptt)),
                         dtype="float32")
    net(tokens)
    if dtype != "float32":
        net.cast(dtype)

    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(block, tokens, labels):
        logits = block(tokens)
        return loss_blk(logits.reshape((-1, vocab)),
                        labels.reshape((-1,)))

    step = ShardedTrainStep(net, None, data_parallel_mesh(), optimizer="sgd",
                            optimizer_params={"learning_rate": 1.0},
                            forward=forward)
    # per-token forward MACs: 4 gates x (in+hid) x hid per LSTM layer, plus
    # the vocab-sized decoder projection; x2 FLOPs/MAC, train = 3x forward
    fwd = 2 * (4 * (nhid + nhid) * nhid * nlayers + nhid * vocab)
    rate, mfu, hfu = _run(step, (tokens, labels), batch * bptt,
                          model_flops_per_item=3 * fwd)
    # the reference never published a PTB throughput (BASELINE.md: the
    # config is named but unmeasured) — vs_baseline reports progress toward
    # the BASELINE.json >=50%-MFU north star instead
    return {
        "metric": "lstm_ptb_train_throughput_b%d_%s" % (batch, dtype),
        "value": round(rate, 2),
        "unit": "tokens/sec",
        "vs_baseline": round((mfu or 0) / 0.5, 3),
        "mfu": round(mfu, 4) if mfu else None,
        "hfu": round(hfu, 4) if hfu else None,
    }


def build_bert_base_step(batch=16, seq=512, dtype="bfloat16", vocab=30522,
                         dim=768, heads=12, layers=12, devices=None):
    """``(step, (tokens, labels))``: BERT-base-shaped bidirectional
    encoder (defaults = bert-base-uncased widths) under the whole-step
    ``ShardedTrainStep`` with Adam. Shared with ``chip_smoke.py``."""
    import mxtpu as mx
    from mxtpu import gluon
    from mxtpu.gluon.model_zoo.transformer import TransformerLM
    from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh

    net = TransformerLM(vocab_size=vocab, dim=dim, num_heads=heads,
                        num_layers=layers, max_len=seq, causal=False)
    net.initialize()
    tokens = mx.nd.array(np.random.randint(0, vocab, (batch, seq)),
                         dtype="int32")
    labels = mx.nd.array(np.random.randint(0, vocab, (batch, seq)),
                         dtype="float32")
    net(tokens)
    if dtype != "float32":
        net.cast(dtype)

    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def forward(block, tokens, labels):
        logits = block(tokens)
        return loss_blk(logits.reshape((-1, vocab)),
                        labels.reshape((-1,)))

    step = ShardedTrainStep(net, None, data_parallel_mesh(devices),
                            optimizer="adam",
                            optimizer_params={"learning_rate": 1e-4},
                            forward=forward)
    return step, (tokens, labels)


def bench_bert_base():
    """BERT-base-shaped masked-LM pretraining: bidirectional 12L/768d/12H
    encoder, seq 512, flash-attention Pallas kernel on TPU."""
    batch = int(os.environ.get("BENCH_BATCH", "16"))
    seq = int(os.environ.get("BENCH_SEQ", "512"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    vocab = 30522  # bert-base-uncased
    step, (tokens, labels) = build_bert_base_step(batch, seq, dtype, vocab)
    # per-token forward MACs: 12 d^2 per layer (QKVO 4d^2 + MLP 8d^2) +
    # 2 s d attention (QK^T + AV) per layer + vocab head; x2 FLOPs/MAC,
    # train = 3x forward
    dim, layers = 768, 12
    fwd = 2 * (layers * (12 * dim * dim + 2 * seq * dim) + dim * vocab)
    rate, mfu, hfu = _run(step, (tokens, labels), batch * seq,
                          model_flops_per_item=3 * fwd)
    return {
        "metric": "bert_base_pretrain_throughput_b%d_s%d_%s"
                  % (batch, seq, dtype),
        "value": round(rate, 2),
        "unit": "tokens/sec",
        "vs_baseline": round((mfu or 0) / 0.5, 3),
        "mfu": round(mfu, 4) if mfu else None,
        "hfu": round(hfu, 4) if hfu else None,
    }


def bench_eager():
    """Eager-dispatch overhead guard (VERDICT r2 weak #5): ops/sec through
    the full imperative path (mx.nd wrapper -> _apply -> jax eager) on a
    small tensor, the mode every reference BASELINE table was measured in.
    Each iteration is 3 chained elementwise ops; sync only at the end
    (SURVEY §1 async-dispatch semantics)."""
    import mxtpu as mx

    n_iter = int(os.environ.get("BENCH_EAGER_ITERS", "200"))
    x = mx.nd.ones((128, 128))
    y = (x * 1.01 + 0.5).tanh()
    y.asnumpy()  # warm every kernel
    t0 = time.perf_counter()
    for _ in range(n_iter):
        y = (y * 1.01 + 0.5).tanh()
    y.asnumpy()
    dt = time.perf_counter() - t0
    rate = 3 * n_iter / dt
    # floor: the reference's eager NDArray path sustains O(10k) small ops/s
    # on CPU hosts (engine dispatch ~100us/op); below 3k ops/s eager mode
    # has regressed into per-call retracing
    return {
        "metric": "eager_dispatch_small_ops",
        "value": round(rate, 1),
        "unit": "ops/sec",
        "vs_baseline": round(rate / 3000.0, 3),
        "mfu": None,
        "hfu": None,
    }


def bench_optimizer_step():
    """Weight-update hot path: params-updated/s through Trainer.step, eager
    per-param loop vs the fused whole-model donated jit
    (mxtpu/optimizer_fused.py, MXTPU_FUSED_OPTIMIZER). The fused number is
    the headline value; ``vs_baseline`` is the fused/eager speedup — the
    dispatch-amortization win this metric exists to track."""
    import mxtpu as mx
    from mxtpu.gluon.parameter import Parameter
    from mxtpu.gluon.trainer import Trainer
    from mxtpu import optimizer_fused as of

    n_params = int(os.environ.get("BENCH_OPT_PARAMS", "80"))
    size = int(os.environ.get("BENCH_OPT_PARAM_SIZE", "16384"))
    steps = int(os.environ.get("BENCH_OPT_STEPS", "30"))
    optimizer = os.environ.get("BENCH_OPT_OPTIMIZER", "adam")
    rng = np.random.RandomState(0)

    def measure(fused):
        os.environ["MXTPU_FUSED_OPTIMIZER"] = "1" if fused else "0"
        params = []
        for j in range(n_params):
            p = Parameter("bench_p%d" % j, shape=(size,), dtype="float32")
            p.initialize()
            p.grad()[:] = mx.nd.array(
                rng.randn(size).astype(np.float32))
            params.append(p)
        tr = Trainer(params, optimizer, {"learning_rate": 1e-3},
                     kvstore=None)
        import jax

        def sync():  # EVERY param: the eager path is n_params independent
            jax.block_until_ready([p.data()._data for p in params])

        tr.step(1)  # warmup + compile
        sync()
        t0 = time.perf_counter()
        for _ in range(steps):
            tr.step(1)
        sync()  # async dispatches; syncing one would overstate its rate
        return n_params * steps / (time.perf_counter() - t0)

    prev = os.environ.get("MXTPU_FUSED_OPTIMIZER")
    try:
        eager_rate = measure(fused=False)
        of.reset()
        fused_rate = measure(fused=True)
        fused_calls = of.FUSED_STATS["fused_steps"]
    finally:
        if prev is None:
            os.environ.pop("MXTPU_FUSED_OPTIMIZER", None)
        else:
            os.environ["MXTPU_FUSED_OPTIMIZER"] = prev
    return {
        "metric": "optimizer_step_%s_p%d_n%d" % (optimizer, n_params, size),
        "value": round(fused_rate, 1),
        "unit": "params_updated/sec",
        "vs_baseline": round(fused_rate / eager_rate, 3),  # fused speedup
        "mfu": None,
        "hfu": None,
        "eager_params_per_s": round(eager_rate, 1),
        "fused_params_per_s": round(fused_rate, 1),
        "fused_jit_calls": fused_calls,  # == 1 + steps when fully fused
    }


def _overhead_workloads():
    """ONE copy of the workload builders the overhead benches
    (``guard_overhead``, ``telemetry_overhead``, ``integrity_overhead``)
    measure — the same optimizer-step and small-resnet shapes, read from
    the shared ``BENCH_GUARD_*`` env knobs. Returns ``{name: make}``
    where ``make(scaler=None) -> (step_fn, sync, trainer)``; attaching a
    DynamicLossScaler builds the guarded variant, and the trainer rides
    along so integrity_overhead can bracket it with the step-wedge
    watchdog + health monitor."""
    import jax

    import mxtpu as mx
    from mxtpu import autograd, gluon
    from mxtpu.gluon.parameter import Parameter
    from mxtpu.gluon.trainer import Trainer

    n_params = int(os.environ.get("BENCH_GUARD_PARAMS", "80"))
    size = int(os.environ.get("BENCH_GUARD_PARAM_SIZE", "16384"))
    batch = int(os.environ.get("BENCH_GUARD_BATCH", "8"))
    img = int(os.environ.get("BENCH_GUARD_IMG", "64"))
    rng = np.random.RandomState(0)

    def make_opt_step(scaler=None):
        params = []
        for j in range(n_params):
            p = Parameter("ovh_p%d" % j, shape=(size,), dtype="float32")
            p.initialize()
            p.grad()[:] = mx.nd.array(rng.randn(size).astype(np.float32))
            params.append(p)
        tr = Trainer(params, "adam", {"learning_rate": 1e-3}, kvstore=None,
                     loss_scaler=scaler)

        def sync():
            jax.block_until_ready([p.data()._data for p in params])

        return (lambda: tr.step(1)), sync, tr

    def make_resnet(scaler=None):
        from mxtpu.gluon.model_zoo import vision
        net = vision.resnet18_v1()
        net.initialize()
        x = mx.nd.array(rng.uniform(-1, 1, (batch, 3, img, img))
                        .astype(np.float32))
        y = mx.nd.array(rng.randint(0, 10, (batch,)).astype(np.float32))
        net(x)  # settle deferred shapes
        net.hybridize()
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.01, "momentum": 0.9}, kvstore=None,
                     loss_scaler=scaler)
        params = list(net.collect_params().values())

        def one():
            with autograd.record():
                l = loss(net(x), y)
                if scaler is not None:
                    l = scaler.scale(l)
            l.backward()
            tr.step(batch)

        def sync():
            jax.block_until_ready([p.data()._data for p in params])

        return one, sync, tr

    return {"optimizer_step": make_opt_step, "resnet": make_resnet}


def _time_steps(step_fn, sync, n):
    """The overhead benches' shared timing loop: warmup+compile, then n
    async dispatches closed by one host-fetch sync."""
    step_fn()
    sync()
    t0 = time.perf_counter()
    for _ in range(n):
        step_fn()
    sync()
    return n / (time.perf_counter() - t0)


def bench_guard_overhead(emit=None):
    """Numerics-sentinel + dynamic-loss-scaler cost (mxtpu/resilience.py):
    steps/s with the guard ON (DynamicLossScaler attached — in-jit finite
    flag, grad norm, skip-select, scaler update) vs OFF, for the
    ``optimizer_step`` hot path and a small-resnet Trainer step. One JSON
    line per (config, guard) plus a summary whose value is the worst
    overhead fraction — the <2% acceptance bound (ISSUE 3) is read off
    this artifact on the TPU tier. BENCH_GUARD_CONFIGS selects subsets."""
    from mxtpu import resilience

    if emit is None:
        emit = _emit
    which = [c.strip() for c in os.environ.get(
        "BENCH_GUARD_CONFIGS", "optimizer_step,resnet").split(",") if c]
    steps = int(os.environ.get("BENCH_GUARD_STEPS", "30"))
    makers = _overhead_workloads()
    bad = [c for c in which if c not in makers]
    if bad or not which:
        # fail BEFORE burning measurement time, naming the offending value
        raise RuntimeError(
            "BENCH_GUARD_CONFIGS=%r: expected a non-empty comma list from %s"
            % (os.environ.get("BENCH_GUARD_CONFIGS"), sorted(makers)))
    overheads = {}
    for cname in which:
        off_rate = _time_steps(*makers[cname](None)[:2], steps)
        on_rate = _time_steps(
            *makers[cname](resilience.DynamicLossScaler())[:2], steps)
        overheads[cname] = off_rate / on_rate - 1.0
        emit({"metric": "guard_overhead_%s" % cname, "guard": "off",
              "value": round(off_rate, 2), "unit": "steps/sec"})
        emit({"metric": "guard_overhead_%s" % cname, "guard": "on",
              "value": round(on_rate, 2), "unit": "steps/sec",
              "overhead_frac": round(overheads[cname], 4)})
    worst = max(overheads.values())
    return {
        "metric": "guard_overhead",
        "value": round(worst, 4),
        "unit": "overhead_frac",
        # >=1.0 means the sentinel fits the 2% budget on this platform
        "vs_baseline": round(0.02 / max(worst, 1e-9), 3),
        "mfu": None,
        "hfu": None,
        "per_config": {k: round(v, 4) for k, v in overheads.items()},
    }


def bench_telemetry_overhead(emit=None):
    """Telemetry layer cost (mxtpu/telemetry.py): steps/s with
    MXTPU_TELEMETRY=1 (step-phase spans + event ring + watchdog counter
    reads) vs 0, for the ``optimizer_step`` hot path and a small-resnet
    Trainer loop — the same shapes guard_overhead measures. ISSUE 10
    adds a third mode, ``trace`` (MXTPU_TELEMETRY=1 + MXTPU_TRACE=1):
    per-step trace contexts, span-id allocation, and the flight-recorder
    ring append, held to the SAME <1% budget. ISSUE 12 adds a fourth,
    ``xprof`` (all three levers on): the executable-observatory layer's
    lever-gated per-step work — the wrapped-jit per-dispatch lever check
    + call count and the Trainer's perf.mfu meter tick — same <1% budget
    again. (The wrapper FRAME is construction-time and rides every mode;
    what alternates is everything behind the per-call lever.) ISSUE 19
    adds a fifth, ``fleet_obs`` (all levers on + a HostObsPublisher's
    per-step ``maybe_publish`` cadence check against a throwaway board
    dir): the plane's HOT-PATH cost is one clock read per step; the blob
    write itself runs at cadence (seconds), so it is timed separately
    (``publish_ms``) and folded in amortized at a 1 s reference cadence
    — hot-path + publish_s/1s held to the SAME <1% budget. (Folding the
    raw write into a µs-scale alternating loop would measure one file
    write against a handful of microsecond steps — cadence amortization
    IS the design.) One JSON
    line per (config, mode) plus a summary whose value is the worst
    overhead fraction across modes (``vs_baseline`` = 0.01 / worst, so
    >=1.0 means the layer fits). BENCH_TELEMETRY_CONFIGS selects
    subsets.

    Methodology: ONE workload per config, then off/on/trace timings
    ALTERNATE over BENCH_TELEMETRY_ROUNDS rounds and each mode takes its
    MEDIAN rate — a single off-then-on pair measures host frequency/cache
    warmup drift instead of the ~8 us/step the three spans actually cost
    (measured: the span path is ~2.7 us each, the trace layer adds
    ~1 us/span on a CPU host; per-rep spread on a shared CPU host is
    +-10%, so the summary also carries ``noise_frac`` and the <1% budget
    is judged on the low-variance TPU tier)."""
    if emit is None:
        emit = _emit
    which = [c.strip() for c in os.environ.get(
        "BENCH_TELEMETRY_CONFIGS", "optimizer_step,resnet").split(",") if c]
    steps = int(os.environ.get("BENCH_GUARD_STEPS", "30"))
    rounds = int(os.environ.get("BENCH_TELEMETRY_ROUNDS", "3"))
    makers = _overhead_workloads()
    bad = [c for c in which if c not in makers]
    if bad or not which:
        raise RuntimeError(
            "BENCH_TELEMETRY_CONFIGS=%r: expected a non-empty comma list "
            "from %s"
            % (os.environ.get("BENCH_TELEMETRY_CONFIGS"), sorted(makers)))
    # mode -> (MXTPU_TELEMETRY, MXTPU_TRACE, MXTPU_XPROF, publisher?);
    # each lever pins the previous ones so the costs stay separately
    # attributable; fleet_obs rides all levers + the cadenced blob writer
    modes = {"0": ("0", "0", "0", False), "1": ("1", "0", "0", False),
             "trace": ("1", "1", "0", False),
             "xprof": ("1", "1", "1", False),
             "fleet_obs": ("1", "1", "1", True)}
    prev = os.environ.get("MXTPU_TELEMETRY")
    prev_trace = os.environ.get("MXTPU_TRACE")
    prev_xprof = os.environ.get("MXTPU_XPROF")
    import shutil
    import tempfile

    from mxtpu import fleet_obs as _fleet_obs
    obs_dir = tempfile.mkdtemp(prefix="mxtpu-bench-obs-")
    # cadence pinned beyond the measured window: the alternating loop
    # times the per-step cadence CHECK; the write is timed separately
    publisher = _fleet_obs.HostObsPublisher(obs_dir, 0, interval_s=1e9)
    obs_ref_cadence_s = 1.0
    overheads = {}
    trace_overheads = {}
    xprof_overheads = {}
    fleet_obs_overheads = {}
    noise = {}
    try:
        for cname in which:
            step_fn, sync = makers[cname](None)[:2]
            step_fn()  # warmup + compile (shared: one workload, all modes)
            sync()
            rates = {m: [] for m in modes}
            for _ in range(rounds):
                for mode, (tel, trace, xpr, pub) in modes.items():
                    os.environ["MXTPU_TELEMETRY"] = tel
                    os.environ["MXTPU_TRACE"] = trace
                    os.environ["MXTPU_XPROF"] = xpr
                    pub_local = publisher if pub else None
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        step_fn()
                        if pub_local is not None:
                            pub_local.maybe_publish()
                    sync()
                    rates[mode].append(steps / (time.perf_counter() - t0))
            med = {m: float(np.median(rs)) for m, rs in rates.items()}
            for mode in modes:
                emit({"metric": "telemetry_overhead_%s" % cname,
                      "telemetry": {"0": "off", "1": "on",
                                    "trace": "trace",
                                    "xprof": "xprof",
                                    "fleet_obs": "fleet_obs"}[mode],
                      "value": round(med[mode], 2), "unit": "steps/sec",
                      "rounds": [round(r, 2) for r in rates[mode]]})
            overheads[cname] = med["0"] / med["1"] - 1.0
            trace_overheads[cname] = med["0"] / med["trace"] - 1.0
            xprof_overheads[cname] = med["0"] / med["xprof"] - 1.0
            # the blob write, timed on the registry this config just
            # loaded, amortized at the reference cadence
            t0 = time.perf_counter()
            publisher.publish()
            publish_s = time.perf_counter() - t0
            fleet_obs_overheads[cname] = (
                med["0"] / med["fleet_obs"] - 1.0
                + publish_s / obs_ref_cadence_s)
            all_r = [r for rs in rates.values() for r in rs]
            noise[cname] = (max(all_r) - min(all_r)) / med["0"]
            emit({"metric": "telemetry_overhead_%s" % cname,
                  "overhead_frac": round(overheads[cname], 4),
                  "trace_overhead_frac": round(trace_overheads[cname], 4),
                  "xprof_overhead_frac": round(xprof_overheads[cname], 4),
                  "fleet_obs_overhead_frac":
                  round(fleet_obs_overheads[cname], 4),
                  "publish_ms": round(publish_s * 1e3, 3),
                  "noise_frac": round(noise[cname], 4)})
    finally:
        shutil.rmtree(obs_dir, ignore_errors=True)
        for var, old in (("MXTPU_TELEMETRY", prev),
                         ("MXTPU_TRACE", prev_trace),
                         ("MXTPU_XPROF", prev_xprof)):
            if old is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = old
    worst = max(list(overheads.values()) + list(trace_overheads.values())
                + list(xprof_overheads.values())
                + list(fleet_obs_overheads.values()))
    return {
        "metric": "telemetry_overhead",
        "value": round(worst, 4),
        "unit": "overhead_frac",
        # >=1.0 means the layer fits the 1% budget on this platform
        # (floor at 1e-4 caps the ratio when overhead is below the
        # measurement noise floor, incl. the "on measured faster" case)
        "vs_baseline": round(0.01 / max(worst, 1e-4), 3),
        "mfu": None,
        "hfu": None,
        "per_config": {k: round(v, 4) for k, v in overheads.items()},
        "per_config_trace": {k: round(v, 4)
                             for k, v in trace_overheads.items()},
        "per_config_xprof": {k: round(v, 4)
                             for k, v in xprof_overheads.items()},
        "per_config_fleet_obs": {k: round(v, 4)
                                 for k, v in fleet_obs_overheads.items()},
        "noise_frac": {k: round(v, 4) for k, v in noise.items()},
    }


def bench_integrity_overhead(emit=None):
    """Training-survivability stack cost (ISSUE 14): steps/s with the
    FULL integrity stack ON — numerics sentinel + loss scaler, the
    divergence fingerprint compiled into the donated update jit with
    host compares at cadence, the step-wedge watchdog bracket (arm /
    disarm + its off-thread monitor), and the TrainingHealthMonitor
    ``after_step`` — vs the bare loop, on the same optimizer-step and
    small-resnet shapes the other overhead benches use
    (``BENCH_INTEGRITY_CONFIGS``). OFF and ON timing rounds ALTERNATE
    (the telemetry_overhead methodology: a single off-then-on pair
    measures host drift, not the stack) over ``BENCH_INTEGRITY_ROUNDS``
    with the median per mode; each mode's workload is built AND
    dispatched under its own ``MXTPU_DIVERGENCE_EVERY``, so both sets of
    executables stay cached and steady-state compiles are flat — gated.

    serve_bench-style gate summary: ``overhead_budget`` (worst
    overhead_frac < 2%, the guard_overhead budget — judged on-chip; on a
    noisy CPU host it is reported but does not fail ``ok``),
    ``retrace_flat`` (zero compiles during the timed rounds),
    ``divergence_checks`` (the sentinel really compared), ``no_wedges``
    (the watchdog never tripped). ``vs_baseline`` >= 1.0 means the stack
    fits the budget on this platform."""
    import jax

    from mxtpu import optimizer_fused as of
    from mxtpu import resilience, telemetry
    from mxtpu.monitor import TrainingHealthMonitor

    if emit is None:
        emit = _emit
    which = [c.strip() for c in os.environ.get(
        "BENCH_INTEGRITY_CONFIGS", "optimizer_step,resnet").split(",")
        if c]
    steps = int(os.environ.get("BENCH_GUARD_STEPS", "30"))
    rounds = int(os.environ.get("BENCH_INTEGRITY_ROUNDS", "3"))
    every = 8  # divergence-compare cadence inside the ON mode
    makers = _overhead_workloads()
    bad = [c for c in which if c not in makers]
    if bad or not which:
        raise RuntimeError(
            "BENCH_INTEGRITY_CONFIGS=%r: expected a non-empty comma list "
            "from %s"
            % (os.environ.get("BENCH_INTEGRITY_CONFIGS"), sorted(makers)))
    prev_div = os.environ.get("MXTPU_DIVERGENCE_EVERY")

    def _set_div(on):
        if on:
            os.environ["MXTPU_DIVERGENCE_EVERY"] = str(every)
        else:
            os.environ.pop("MXTPU_DIVERGENCE_EVERY", None)

    overheads, noise = {}, {}
    wedges_before = telemetry.snapshot()["counters"].get("train.wedges", 0)
    checks_ran = 0
    compiles_moved = False
    watchdogs = []
    try:
        for cname in which:
            # one workload per mode, each traced under ITS policy env
            _set_div(False)
            off_fn, off_sync = makers[cname](None)[:2]
            _set_div(True)
            on_fn, on_sync, tr = makers[cname](
                resilience.DynamicLossScaler())
            wd = resilience.TrainStepWatchdog(
                timeout_x=50.0, min_timeout_s=5.0).start_monitor(0.05)
            watchdogs.append(wd)
            tr.attach_step_watchdog(wd)
            mon = TrainingHealthMonitor(
                interval=every, divergence_every=every,
                poison_streak=0).install(tr)

            def on_step(fn=on_fn, m=mon):
                fn()
                m.after_step()

            # warm both (compile under their own env), then pin compiles
            on_step()
            on_sync()
            _set_div(False)
            off_fn()
            off_sync()
            c0 = of.FUSED_STATS["compiles"]
            rates = {"off": [], "on": []}
            for _ in range(rounds):
                for mode in ("off", "on"):
                    _set_div(mode == "on")
                    fn = off_fn if mode == "off" else on_step
                    sync = off_sync if mode == "off" else on_sync
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        fn()
                    sync()
                    rates[mode].append(
                        steps / (time.perf_counter() - t0))
            compiles_moved |= of.FUSED_STATS["compiles"] != c0
            checks_ran += mon._sentinel.checks
            med = {m: float(np.median(rs)) for m, rs in rates.items()}
            for mode in ("off", "on"):
                emit({"metric": "integrity_overhead_%s" % cname,
                      "integrity": mode,
                      "value": round(med[mode], 2), "unit": "steps/sec",
                      "rounds": [round(r, 2) for r in rates[mode]]})
            overheads[cname] = med["off"] / med["on"] - 1.0
            all_r = [r for rs in rates.values() for r in rs]
            noise[cname] = (max(all_r) - min(all_r)) / med["off"]
            emit({"metric": "integrity_overhead_%s" % cname,
                  "overhead_frac": round(overheads[cname], 4),
                  "noise_frac": round(noise[cname], 4)})
    finally:
        for wd in watchdogs:
            wd.stop_monitor()
        if prev_div is None:
            os.environ.pop("MXTPU_DIVERGENCE_EVERY", None)
        else:
            os.environ["MXTPU_DIVERGENCE_EVERY"] = prev_div
    worst = max(overheads.values())
    wedges = telemetry.snapshot()["counters"].get("train.wedges", 0) \
        - wedges_before
    on_tpu = jax.default_backend() == "tpu"
    fits = worst < 0.02
    gates = {
        "overhead_budget": bool(fits),
        "retrace_flat": not compiles_moved,
        "divergence_checks": checks_ran > 0,
        "no_wedges": wedges == 0,
    }
    # the <2% budget is judged where it matters (the low-variance TPU
    # tier, the guard_overhead precedent); host-tier noise reports the
    # number without failing the gate verdict
    ok = gates["retrace_flat"] and gates["divergence_checks"] \
        and gates["no_wedges"] and (fits or not on_tpu)
    return {
        "metric": "integrity_overhead",
        "value": round(worst, 4),
        "unit": "overhead_frac",
        # >=1.0 means the full survivability stack fits the 2% budget
        "vs_baseline": round(0.02 / max(worst, 1e-4), 3)
        if ok else 0.0,
        "mfu": None,
        "hfu": None,
        "per_config": {k: round(v, 4) for k, v in overheads.items()},
        "noise_frac": {k: round(v, 4) for k, v in noise.items()},
        "divergence_checks": checks_ran,
        "train_wedges": int(wedges),
        "gates": gates,
        "ok": bool(ok),
    }


def _perf_common():
    """The shared scan-fused timing harness (tools/perf_common.py —
    ONE copy of the PERF.md methodology: K steps per dispatch,
    host-fetch sync). Imported lazily so bench stays runnable from any
    cwd."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import perf_common
    return perf_common


def _platform_name():
    try:
        import jax
        return jax.devices()[0].platform
    except Exception:  # noqa: BLE001 — a dead PJRT client still answers
        return "unknown"


def bench_flash_class(emit=None):
    """Per-attention-class TFLOP/s, XLA softmax path vs the Pallas flash
    kernel (mxtpu/ops/pallas/flash_attention.py) for the transformer hot
    path. One JSON line per (class, impl);
    classes cover the decoder/encoder shapes plus an odd length the
    block picker must still tile (768 → 384-blocks). Off-TPU the kernel
    runs through the Pallas interpreter (MXTPU_FLASH_INTERPRET) on
    host-scaled shapes — slower absolute numbers, but the dispatch
    routing exercises the real kernel. Scan-fused K-step timing with
    host-fetch sync."""
    import importlib

    import jax
    import jax.numpy as jnp
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")

    pcommon = _perf_common()
    if emit is None:
        emit = _emit
    k_steps = int(os.environ.get("BENCH_FLASH_STEPS", "8"))
    dtype = (jnp.float32 if os.environ.get("BENCH_DTYPE") == "float32"
             else jnp.bfloat16)
    host_tier = _platform_name() != "tpu"
    # (label, batch, heads, T, D, host_T) — host_T keeps interpret-mode
    # lines inside the battery budget while preserving each class's
    # tiling character (odd 768 scales to odd 384, not a power of two)
    classes = [
        ("dec_t512_d64", 4, 8, 512, 64, 256),
        ("enc_t1024_d128", 2, 8, 1024, 128, 512),
        ("odd_t768_d64", 2, 8, 768, 64, 384),
    ]
    causal = os.environ.get("BENCH_FLASH_CAUSAL", "0") == "1"
    lines = []
    saved = os.environ.get("MXTPU_FLASH_INTERPRET")
    try:
        if host_tier:
            # off-TPU the pallas impl needs the interpreter; the xla impl
            # path below bypasses the kernel either way
            os.environ["MXTPU_FLASH_INTERPRET"] = "1"
        for label, b, h, t, d, host_t in classes:
            if host_tier:
                b, h, t = 1, 2, host_t
            q = jax.random.normal(jax.random.PRNGKey(0), (b, h, t, d),
                                  dtype)
            kk = jax.random.normal(jax.random.PRNGKey(1), (b, h, t, d),
                                   dtype)
            vv = jax.random.normal(jax.random.PRNGKey(2), (b, h, t, d),
                                   dtype)
            # 2 matmuls (scores + values), 2 FLOPs each: 4*b*h*t*tk*d
            fl = 4 * b * h * t * t * d
            by_impl = {}
            for impl in ("xla", "pallas"):
                fa.reset_dispatch_stats()
                if impl == "xla":
                    scale = 1.0 / (d ** 0.5)
                    f = pcommon.reinject(
                        lambda qd, kk=kk, vv=vv, scale=scale:
                        fa._xla_attention(qd, kk, vv, causal, scale))
                else:
                    f = pcommon.reinject(
                        lambda qd, kk=kk, vv=vv:
                        fa.flash_attention(qd, kk, vv, causal))
                try:
                    dt = pcommon.timed_scan(f, q, K=k_steps)
                except Exception as e:  # noqa: BLE001 — keep the sweep
                    emit({"metric": "flash_class_%s" % label,
                          "impl": impl, "error": str(e)})
                    continue
                from mxtpu import telemetry
                if impl == "xla":
                    used = "xla"
                elif telemetry.value("pallas_flash.pallas"):
                    used = "pallas"
                else:
                    reasons = telemetry.tagged("pallas_flash.fallback")
                    used = ("xla_fallback(%s)"
                            % "; ".join(sorted(reasons)) if reasons
                            else "xla_fallback")
                rec = {"metric": "flash_class_%s" % label, "impl": impl,
                       "impl_used": used, "ms": round(dt * 1e3, 3),
                       "value": round(fl / dt / 1e12, 4),
                       "unit": "TFLOP/s"}
                by_impl[impl] = dt
                if impl == "pallas" and "xla" in by_impl:
                    rec["speedup_vs_xla"] = round(by_impl["xla"] / dt, 3)
                emit(rec)
                lines.append(rec)
    finally:
        if saved is None:
            os.environ.pop("MXTPU_FLASH_INTERPRET", None)
        else:
            os.environ["MXTPU_FLASH_INTERPRET"] = saved
    pallas_lines = [r for r in lines if r.get("impl") == "pallas"
                    and r.get("impl_used") == "pallas"]
    return {
        "metric": "flash_class",
        "value": len(lines),
        "unit": "json_lines",
        "vs_baseline": None,
        "mfu": None,
        "hfu": None,
        "pallas_kernel_lines": len(pallas_lines),
        "classes": [r["metric"] for r in lines],
    }


def bench_serving(emit=None):
    """Inference serving throughput (mxtpu/serving, ISSUE 5): the
    ``tools/serve_bench.py`` phases driven in-process — direct Predictor
    batch-bucket sweep (one line per bucket; items/s must be
    monotonically non-decreasing from batch 1 to the max bucket) and a
    closed-loop mixed-shape run through the MicroBatcher (one line:
    items/s, client p50/p99, compile count at retrace site
    ``serving.predict`` vs #buckets, watchdog trips, shed count). The
    summary's ``vs_baseline`` is 1.0 only when BOTH acceptance gates hold
    (monotonic sweep AND compiles <= buckets with zero trips)."""
    if emit is None:
        emit = _emit
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench as sb

    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", "500"))
    max_b = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "8"))
    wait_ms = float(os.environ.get("BENCH_SERVE_WAIT_MS", "2"))
    pred, spec = sb.build_predictor(max_batch=max_b)
    rates, monotonic = sb.run_sweep(pred, spec, emit=emit)
    closed = sb.run_closed(pred, spec, n_requests=n_req,
                           max_wait_ms=wait_ms, emit=emit)
    gates_ok = monotonic and closed["compiles"] <= closed["buckets"] \
        and closed["watchdog_trips"] == 0
    return {
        "metric": "serving",
        "value": closed["value"],
        "unit": "items/sec",
        "vs_baseline": 1.0 if gates_ok else 0.0,
        "mfu": None,
        "hfu": None,
        "p50_ms": closed["p50_ms"],
        "p99_ms": closed["p99_ms"],
        "compiles": closed["compiles"],
        "buckets": closed["buckets"],
        "watchdog_trips": closed["watchdog_trips"],
        "sweep_monotonic": monotonic,
        "sweep_items_per_s": [round(r, 1) for r in rates],
    }


def bench_serving_decode(emit=None):
    """Continuous-batching autoregressive decode (mxtpu/serving/decode,
    ISSUE 11 + the ISSUE 16 paged-KV phases): ``tools/serve_bench.py
    --mode decode`` driven in-process. The A/Bs the ROADMAP item names:
    continuous batching vs restart-per-batch at equal cohort capacity on
    identical executables, paged vs rowed KV at equal HBM budget
    (admitted-residency multiplier ≥ 2×), prefix reuse under a
    templated-prompt cohort (hit rate > 0, shared pages visible), and
    speculative decoding (tokens/step and tokens/s win at bit-identical
    greedy output), plus the int8 logits-parity and KV-bytes gates.
    ``vs_baseline`` is the continuous-vs-restart tokens/s speedup when
    EVERY gate holds (strictly > 1 continuous win, zero post-warmup
    compiles at ``serving.decode`` AND ``serving.draft``, zero in-loop
    d2h, token parity across every layout, int8 parity + <= ~half KV
    bytes), else 0.0."""
    if emit is None:
        emit = _emit
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench as sb

    rec = sb.run_decode(
        n_requests=int(os.environ.get("BENCH_DECODE_REQUESTS", "80")),
        slots=int(os.environ.get("BENCH_DECODE_SLOTS", "8")),
        max_new=int(os.environ.get("BENCH_DECODE_MAX_NEW", "32")),
        emit=emit)
    return {
        "metric": "serving_decode",
        "value": round(rec["continuous"]["tok_per_s"], 1),
        "unit": "tokens/sec",
        "vs_baseline": round(rec["speedup"], 3) if rec["ok"] else 0.0,
        "mfu": None,
        "hfu": None,
        "restart_tok_per_s": round(rec["restart"]["tok_per_s"], 1),
        "continuous_steps": rec["continuous"]["steps"],
        "restart_steps": rec["restart"]["steps"],
        "compiles_post_warmup": rec["continuous"]["compiles_post_warmup"],
        "int8_tok_per_s": round(rec["int8"]["tok_per_s"], 1),
        "prefill_logits_rel_err": round(rec["prefill_logits_rel_err"], 5),
        "step_logits_rel_err": round(rec["step_logits_rel_err"], 5),
        "kv_bytes_ratio": round(rec["kv_bytes_ratio"], 4),
        "paged_residency_x": round(rec["residency_x"], 2),
        "paged_ab_ok": rec["ab_ok"],
        "prefix_hit_rate": round(rec["prefix_hit_rate"], 3),
        "prefix_ok": rec["prefix_ok"],
        "spec_accept_rate": round(rec["accept_rate"], 3),
        "spec_tokens_per_step": round(rec["spec_tokens_per_step"], 3),
        "spec_ok": rec["spec_ok"],
        "gates_ok": rec["ok"],
    }


def bench_serving_slo(emit=None):
    """SLO-aware serving control plane (mxtpu/serving/controller,
    ISSUE 13): ``tools/serve_bench.py --mode slo`` driven in-process.
    Phase 1 is the overload curve — goodput-at-SLO (completions within
    deadline / offered) for the predictive-admission controller vs the
    static depth-shed router at EQUAL replicas, paced open-loop at
    multiples of calibrated capacity. Phase 2 (>= 2 devices) is the
    kill/restore sweep: a replica is quarantined as a dead chip and the
    controller must REPLACE it with windowed p99 recovering inside the
    gated window, zero hung futures. ``vs_baseline`` is the goodput
    gain at the best overload point when EVERY gate holds, else 0.0."""
    if emit is None:
        emit = _emit
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench as sb

    rec = sb.run_slo(
        n_requests=int(os.environ.get("BENCH_SLO_REQUESTS", "200")),
        emit=emit)
    kill = rec["killrestore"]
    return {
        "metric": "serving_slo",
        "value": round(max(rec["gains"]), 4),
        "unit": "goodput_gain_at_best_point",
        "vs_baseline": round(max(rec["gains"]), 4) if rec["ok"] else 0.0,
        "mfu": None,
        "hfu": None,
        "slo_ms": round(rec["slo_ms"], 2),
        "curve_ok": rec["curve_ok"],
        "hangs": rec["hangs"],
        "killrestore_ok": kill["ok"] if kill else None,
        "p99_recovery_s": kill["value"] if kill else None,
        "gates_ok": rec["ok"],
    }


def bench_serving_zoo(emit=None):
    """Multi-tenant model zoo (mxtpu/serving/zoo, ISSUE 20):
    ``tools/serve_bench.py --mode zoo`` driven in-process. K models
    multiplexed over a smaller device pool under skewed mixed-tenant
    open-loop load, with a mid-run canary deploy+promote AND
    deploy+rollback cycle. Gates: per-tenant goodput-at-SLO with
    priority isolation, page-in compiles == 0 (evicted models return
    disk/memory-warm), zero hung futures across the rollout, bounded
    eviction/page-in churn. ``vs_baseline`` is the achieved goodput
    fraction of offered load when EVERY gate holds, else 0.0."""
    if emit is None:
        emit = _emit
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench as sb

    rec = sb.run_zoo(emit=emit)
    frac = min(1.0, rec["value"] / max(rec["offered_qps"], 1e-9))
    return {
        "metric": "serving_zoo",
        "value": rec["value"],
        "unit": "goodput_rps",
        "vs_baseline": round(frac, 4) if rec["ok"] else 0.0,
        "mfu": None,
        "hfu": None,
        "models": rec["models"],
        "pageins": rec["pageins"],
        "evictions": rec["evictions"],
        "pagein_compiles": rec["pagein_compiles"],
        "hangs": rec["hung"],
        "attainment_gold": rec["attainment_gold"],
        "attainment_free": rec["attainment_free"],
        "gates_ok": rec["ok"],
    }


def bench_startup_time(emit=None):
    """Persistent compile cache (mxtpu/compile_service.py, ISSUE 15):
    cold-start vs warm-disk-cache wall time, each scenario in a FRESH
    python process (the thing measured is process restart): (a) gluon
    Trainer first completed step, (b) Predictor replica warmup + one
    served request. Gates: warm compiles == 0 across every retrace site
    (watchdog-pinned — a disk load is not a compile), warm disk_hits >
    0, warm wall < cold wall. ``vs_baseline`` is the WORST scenario's
    cold/warm speedup iff every gate holds, else 0.0."""
    if emit is None:
        emit = _emit
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import startup_bench

    rec = startup_bench.run_startup(emit=emit)
    tr = rec["scenarios"].get("trainer", {})
    pr = rec["scenarios"].get("predictor", {})
    return {
        "metric": "startup_time",
        "value": round(rec["speedup"], 3),
        "unit": "warm_vs_cold_speedup",
        "vs_baseline": round(rec["speedup"], 3) if rec["ok"] else 0.0,
        "mfu": None,
        "hfu": None,
        "trainer_cold_s": tr.get("cold_s"),
        "trainer_warm_s": tr.get("warm_s"),
        "trainer_warm_compiles": tr.get("warm_compiles"),
        "predictor_cold_s": pr.get("cold_s"),
        "predictor_warm_s": pr.get("warm_s"),
        "predictor_warm_compiles": pr.get("warm_compiles"),
        "gates_ok": rec["ok"],
    }


def bench_fleet_resume(emit=None):
    """Elastic fleet matrix (mxtpu/fleet.py, ISSUE 18): kill-one-host
    tiered restore + warm elastic rejoin, every host a real subprocess
    on the forced-CPU tier (chip-safe). Four phases — 2-host fleet with
    ``host_loss@K`` injected, 1-host restore onto a RESHAPED mesh,
    uninterrupted oracle, 2-host warm rejoin against the same compile
    cache. Gates: kill detected loud (exit 41/42, nothing hung), the
    restore resumes at K with the divergence sentinel green, post-restore
    losses match the oracle within reduce-order tolerance, and every
    rejoined host reaches step 1 with ZERO compiles (watchdog-pinned),
    all executables disk-served. ``vs_baseline`` = killed-fleet wall /
    warm-rejoin wall iff every gate holds, else 0.0."""
    if emit is None:
        emit = _emit
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import fleet_bench

    rec = fleet_bench.run_fleet_resume(emit=emit)
    gates = rec.get("gates", {})
    return {
        "metric": "fleet_resume",
        "value": round(rec.get("rejoin_wall_s") or 0.0, 3),
        "unit": "rejoin_wall_s",
        "vs_baseline": rec.get("vs_baseline", 0.0) if rec.get("ok")
        else 0.0,
        "mfu": None,
        "hfu": None,
        "kill_step": rec.get("kill_step"),
        "gates": gates,
        "gates_ok": rec.get("ok", False),
    }


def bench_multichip_resnet(emit=None):
    """Mesh-native Trainer scaling (ISSUE 7): resnet18 data-parallel over
    1..N devices through ``gluon.Trainer(mesh=...)`` with ZeRO-1 on, at a
    FIXED global batch (strong scaling — every device count computes the
    same mathematical step, which is what makes the parity gate below
    meaningful). One JSON line per device count (items/s, ``vs_baseline``
    = speedup over the 1-device plain-Trainer run) plus a summary line.

    Tiered gating: on a real multi-chip platform the
    summary's ``vs_baseline`` is the max-count scaling efficiency
    (speedup / devices — the ROADMAP item 1 acceptance number). On the
    forced-host-device tier the N "devices" share one socket, so scaling
    numbers are meaningless; there the summary gates on parity (every
    count's final loss tracks the 1-device run to reduce-order tolerance)
    + compile budget (ZERO post-warmup compiles at the fused_optimizer
    retrace site for every count) and reports 1.0/0.0."""
    import jax

    import mxtpu as mx
    from mxtpu import autograd, gluon, telemetry
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.parallel import make_mesh

    if emit is None:
        emit = _emit
    ndev = len(jax.devices())
    if ndev < 2:
        return {"metric": "multichip_resnet_scaling",
                "error": "skipped: needs >1 device (have %d) — run the "
                         "host tier with XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=8" % ndev}
    batch = int(os.environ.get("BENCH_MC_BATCH", "32"))
    img = int(os.environ.get("BENCH_MC_IMG", "64"))
    steps = int(os.environ.get("BENCH_MC_STEPS", "10"))
    counts = [n for n in (1, 2, 4, 8, 16, 32, 64)
              if n <= ndev and batch % n == 0]
    rng = np.random.RandomState(0)
    x_np = rng.uniform(-1, 1, (batch, 3, img, img)).astype(np.float32)
    y_np = rng.randint(0, 10, (batch,)).astype(np.float32)
    platform = jax.devices()[0].platform
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def measure(n):
        mx.random.seed(0)  # identical init per count — parity is exact
        net = vision.resnet18_v1()
        net.initialize()
        x, y = mx.nd.array(x_np), mx.nd.array(y_np)
        net(x)  # settle deferred shapes
        net.hybridize()
        mesh = make_mesh({"data": n}, jax.devices()[:n]) if n > 1 else None
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.01, "momentum": 0.9},
                           mesh=mesh, zero1=True)
        xs, ys = (tr.shard_batch(x, y)) if mesh is not None else (x, y)
        params = list(net.collect_params().values())

        def one():
            with autograd.record():
                l = loss_fn(net(xs), ys).mean()
            l.backward()
            tr.step(1)
            return l

        warm = None
        for _ in range(2):  # warmup: every compile lands here
            warm = one()
        jax.block_until_ready([p.data()._data for p in params])
        # parity gates on the POST-WARMUP loss: two steps in, the value is
        # O(log n_classes) and cross-device reduce-order ULPs have not yet
        # been amplified by training dynamics (a fully-trained-down loss
        # near zero diverges relatively even between correct runs)
        warm_loss = float(warm.asnumpy())
        # retrace_stats is None until the site's first recorded compile
        # (e.g. MXTPU_FUSED_OPTIMIZER=0 takes the eager loop)
        c0 = (telemetry.retrace_stats("fused_optimizer")
              or {}).get("compiles", 0)
        t0 = time.perf_counter()
        last = None
        for _ in range(steps):
            last = one()
        jax.block_until_ready([p.data()._data for p in params])
        dt = time.perf_counter() - t0
        compiles = (telemetry.retrace_stats("fused_optimizer")
                    or {}).get("compiles", 0) - c0
        return steps * batch / dt, warm_loss, float(last.asnumpy()), compiles

    rate1 = None
    lines = []
    for n in counts:
        rate, warm_loss, final_loss, compiles = measure(n)
        if rate1 is None:
            rate1 = rate
        line = {"metric": "multichip_resnet_n%d" % n, "devices": n,
                "value": round(rate, 2), "unit": "images/sec",
                "vs_baseline": round(rate / rate1, 3),
                "warm_loss": warm_loss, "final_loss": final_loss,
                "post_warmup_compiles": compiles}
        lines.append(line)
        emit(line)
    parity_ok = all(abs(l["warm_loss"] - lines[0]["warm_loss"]) < 1e-3
                    for l in lines)
    compile_ok = all(l["post_warmup_compiles"] == 0 for l in lines)
    top = lines[-1]
    if platform == "cpu":
        # host tier: the gate is parity + compile budget, not throughput
        vs = 1.0 if (parity_ok and compile_ok) else 0.0
    else:
        vs = round(top["vs_baseline"] / top["devices"], 3)  # efficiency
    return {
        "metric": "multichip_resnet_scaling_b%d" % batch,
        "value": top["value"], "unit": "images/sec",
        "devices": top["devices"],
        "speedup_vs_1dev": top["vs_baseline"],
        "parity_ok": parity_ok, "compile_budget_ok": compile_ok,
        "vs_baseline": vs,
        "mfu": None, "hfu": None,
    }


def bench_input_pipeline(emit=None):
    """Device-resident input pipeline (ISSUE 9): the double-buffered
    prefetch-to-device stream (mxtpu/io/stream.py) vs the synchronous
    pull-then-compute loop, over a synthetic JPEG RecordIO shard.

    Three measurements, JSON line each (ISSUE 9 satellite):

    * ``loader_only`` — ShardedRecordReader drain rate (pread + threaded
      jpeg-decode + batchify, no device work): the input-side ceiling.
    * ``sync`` — pull a batch, THEN upload + compute + block, per step:
      the pre-ISSUE-9 shape of the loop. Its ``wait_frac`` is decode
      time the devices sit idle (the ``data.wait`` pathology).
    * ``overlap`` — the same batches through DevicePrefetcher: decode +
      H2D of batch N+1 overlap compute on batch N; ``wait_frac`` is now
      only true starvation, measured by the prefetcher's own
      ``data.wait`` span.

    ``vs_baseline`` = overlapped speedup over the synchronous path.
    Tiered gating like multichip_resnet: the gate — parity (both paths
    consume the identical batch stream: same seed, compute checksums
    match) + the ``data.wait`` fraction dropping under overlap — applies
    everywhere, but on a SINGLE-CORE host the wall-clock speedup is
    meaningless (decode threads have no core to overlap onto — hiding
    latency needs parallel hardware somewhere), so there ``vs_baseline``
    reports the gate verdict 1.0/0.0; with >1 core (or a real chip doing
    the compute) it reports the measured speedup, zeroed if the gate
    fails so the battery artifact flags it."""
    import tempfile

    import cv2
    import jax
    import jax.numpy as jnp

    from mxtpu import recordio, telemetry
    from mxtpu.io.stream import DevicePrefetcher, ShardedRecordReader

    if emit is None:
        emit = _emit
    n_rec = int(os.environ.get("BENCH_PIPE_RECORDS", "192"))
    batch = int(os.environ.get("BENCH_PIPE_BATCH", "16"))
    img = int(os.environ.get("BENCH_PIPE_IMG", "96"))
    epochs = int(os.environ.get("BENCH_PIPE_EPOCHS", "3"))
    threads = int(os.environ.get("BENCH_PIPE_THREADS", "2"))
    chain = int(os.environ.get("BENCH_PIPE_COMPUTE", "6"))

    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as td:
        rec = os.path.join(td, "pipe.rec")
        idx = os.path.join(td, "pipe.idx")
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        for i in range(n_rec):
            # natural-ish images so jpeg decode work is realistic
            yy, xx = np.mgrid[0:img, 0:img].astype(np.float32) / img
            im = np.stack([
                128 + 100 * np.sin(3 * yy + i) + rng.normal(0, 12, (img, img)),
                128 + 100 * np.cos(2 * xx + i) + rng.normal(0, 12, (img, img)),
                128 + 80 * np.sin(4 * (xx + yy)) + rng.normal(0, 12,
                                                              (img, img)),
            ], axis=2).clip(0, 255).astype(np.uint8)
            hdr = recordio.IRHeader(0, float(i % 10), i, 0)
            w.write_idx(i, recordio.pack_img(hdr, im, quality=90,
                                             img_fmt=".jpg"))
        w.close()

        def decode(raw):
            hdr, im = recordio.unpack_img(raw, cv2.IMREAD_COLOR)
            out = im.astype(np.float32) * (1.0 / 255.0) - 0.5
            return np.ascontiguousarray(out.transpose(2, 0, 1)), \
                np.float32(hdr.label)

        def reader(n_threads=None):
            # n_threads=0: inline decode on the consumer thread — the
            # true synchronous baseline (the pool reader already overlaps
            # decode with the consumer, which would flatter "sync")
            return ShardedRecordReader(
                rec, batch_size=batch, decode_fn=decode, seed=7,
                num_threads=threads if n_threads is None else n_threads,
                last_batch="discard")

        hid = 512
        k = jax.random.PRNGKey(0)
        w0 = jax.random.normal(k, (3 * img * img, hid),
                               jnp.float32) * 0.02
        ws = [jax.random.normal(jax.random.PRNGKey(i + 1), (hid, hid),
                                jnp.float32) * 0.05 for i in range(chain)]

        @jax.jit
        def step(x):
            h = x.reshape(x.shape[0], -1) @ w0
            for wi in ws:
                h = jnp.tanh(h @ wi)
            return h.sum()

        # warmup: the one compile, off both timed phases
        float(step(jnp.zeros((batch, 3, img, img), jnp.float32)))

        # ---- loader only: the decode-side ceiling
        rd = reader()
        n_batches = len(rd) * epochs
        t0 = time.perf_counter()
        for _ in range(epochs):
            for _ in rd:
                pass
        t_loader = time.perf_counter() - t0
        emit({"metric": "input_pipeline_loader_only",
              "value": round(n_batches * batch / t_loader, 1),
              "unit": "images/sec", "batches_per_s":
              round(n_batches / t_loader, 2), "vs_baseline": None})

        # ---- synchronous: inline decode, then upload+compute+block
        rd = reader(n_threads=0)
        acc_sync = 0.0
        t_pull = 0.0
        t0 = time.perf_counter()
        for _ in range(epochs):
            it = iter(rd)
            while True:
                tp = time.perf_counter()
                try:
                    data, _label = next(it)
                except StopIteration:
                    break
                t_pull += time.perf_counter() - tp
                acc_sync += float(step(jnp.asarray(data)))
        t_sync = time.perf_counter() - t0
        wait_sync = t_pull / t_sync
        emit({"metric": "input_pipeline_sync",
              "value": round(n_batches * batch / t_sync, 1),
              "unit": "images/sec", "wait_frac": round(wait_sync, 4),
              "vs_baseline": 1.0})

        # ---- overlapped: DevicePrefetcher hides decode+H2D under compute
        for m in ("data.wait", "data.h2d", "data.starved"):
            telemetry.reset_metric(m)
        rd = reader()
        acc_over = 0.0
        t0 = time.perf_counter()
        for _ in range(epochs):
            pf = DevicePrefetcher(iter(rd))
            try:
                for data, _label in pf:
                    acc_over += float(step(data._data))
            finally:
                # a mid-epoch step failure must not leak the producer
                # thread into the tempdir teardown
                pf.close()
        t_over = time.perf_counter() - t0
        hist = telemetry.snapshot()["histograms"].get("data.wait")
        wait_over = (hist["sum"] if hist else 0.0) / t_over
        emit({"metric": "input_pipeline_overlap",
              "value": round(n_batches * batch / t_over, 1),
              "unit": "images/sec", "wait_frac": round(wait_over, 4),
              "starved": telemetry.value("data.starved"),
              "vs_baseline": round(t_sync / t_over, 3)})

    # parity: identical seed => identical batch stream => identical sums
    parity_ok = abs(acc_sync - acc_over) <= 1e-5 * max(1.0, abs(acc_sync))
    gate_ok = parity_ok and wait_over < wait_sync
    cores = os.cpu_count() or 1
    if cores < 2:
        vs = 1.0 if gate_ok else 0.0  # single-core tier: gate verdict
    else:
        vs = round(t_sync / t_over, 3) if gate_ok else 0.0
    return {
        "metric": "input_pipeline_overlap_b%d" % batch,
        "value": round(n_batches * batch / t_over, 1),
        "unit": "images/sec",
        "speedup": round(t_sync / t_over, 3), "host_cores": cores,
        "wait_frac_sync": round(wait_sync, 4),
        "wait_frac_overlap": round(wait_over, 4),
        "parity_ok": parity_ok, "gate_ok": gate_ok,
        "vs_baseline": vs,
        "mfu": None, "hfu": None,
    }


def bench_sparse_linear():
    """BASELINE config 5: sparse linear classification samples/sec
    (examples/sparse/linear_classification.py — LibSVM CSR batches through
    the gather/segment-sum csr x dense dot, row-sparse grads, lazy Adam).
    The reference never published a number for this config; vs_baseline
    reports throughput against a 100k samples/sec floor."""
    import importlib.util
    import tempfile

    spec = importlib.util.spec_from_file_location(
        "sparse_lc", os.path.join(os.path.dirname(
            os.path.abspath(__file__)),
            "examples", "sparse", "linear_classification.py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)

    num_features = int(os.environ.get("BENCH_SPARSE_FEATURES", "100000"))
    batch = int(os.environ.get("BENCH_SPARSE_BATCH", "1024"))
    rows = 16 * batch
    path = os.path.join(tempfile.gettempdir(), "bench_sparse.libsvm")
    m.make_synthetic_libsvm(path, num_rows=rows, num_features=num_features,
                            nnz_per_row=40)
    # steady-state: parsing + compile-heavy first epoch excluded
    acc, _, rate = m.train(path, num_features, batch_size=batch, epochs=3,
                           measure=True)
    return {
        "metric": "sparse_linear_train_b%d_f%d" % (batch, num_features),
        "value": round(rate, 1),
        "unit": "samples/sec",
        "vs_baseline": round(rate / 100000.0, 3),
        "mfu": None,
        "hfu": None,
    }


# headline config LAST: a driver that keeps one line keeps the final one
CONFIGS = {
    "eager": bench_eager,
    "optimizer_step": bench_optimizer_step,
    "guard_overhead": bench_guard_overhead,
    "telemetry_overhead": bench_telemetry_overhead,
    "integrity_overhead": bench_integrity_overhead,
    "flash_class": bench_flash_class,
    "serving": bench_serving,
    "serving_decode": bench_serving_decode,
    "serving_slo": bench_serving_slo,
    "serving_zoo": bench_serving_zoo,
    "startup_time": bench_startup_time,
    "fleet_resume": bench_fleet_resume,
    "multichip_resnet": bench_multichip_resnet,
    "input_pipeline": bench_input_pipeline,
    "sparse_linear": bench_sparse_linear,
    "lstm_ptb": bench_lstm_ptb,
    "bert_base": bench_bert_base,
    "resnet50": bench_resnet50,
}


# configs whose work happens in child processes that open the backend
# themselves. The chip belongs to ONE process at a time: a parent that
# has touched JAX holds it, and such a child then fails or hangs — so
# these never run under ``all`` (whose parent has by then run a dozen
# configs on the chip), and run alone the parent stays off JAX until
# the children are done. (``fleet_resume`` children are forced to the
# CPU by tools/fleet_bench.py and need no chip.)
CHILD_PROCESS_CONFIGS = ("startup_time",)


def _require_tpu():
    """bench.py measures the chip. Asserted ONCE, up front: a run that
    finds no TPU exits non-zero instead of timing XLA:CPU under device
    metric names (``Context.jax_device`` resolves ``mx.tpu()`` to a CPU
    device off the chip — that is for the tests, not for this file)."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit("bench.py: jax.devices()[0].platform is %r, not 'tpu' — "
                 "refusing to measure (the tests run on the CPU; the "
                 "benchmark does not)" % platform)


def _run_config(cname, fn):
    """Run one config; an exception becomes an error record (with its
    traceback on stderr) so the remaining configs still print their
    lines — and the run still exits non-zero (:func:`main`)."""
    try:
        return fn() or {"metric": cname, "error": "config returned nothing"}
    except Exception as e:  # noqa: BLE001 — boundary: record, go on, fail
        traceback.print_exc()
        return {"metric": cname, "error": "%s: %s" % (type(e).__name__, e)}


def main():
    name = os.environ.get("BENCH_CONFIG", "all")
    names = ([c for c in CONFIGS if c not in CHILD_PROCESS_CONFIGS]
             if name == "all" else [name])
    if name not in CHILD_PROCESS_CONFIGS:
        # (a child-process config keeps this parent off JAX until its
        # children are done; they place their own caches)
        _require_tpu()
        from mxtpu import compile_service
        compile_service.use_checkout_xla_cache()
    base_profile = os.environ.get("BENCH_PROFILE")
    failed = []
    for cname in names:
        if base_profile and len(names) > 1:
            # one trace file per config — a shared file would be
            # clobbered and merged across configs
            root, ext = os.path.splitext(base_profile)
            os.environ["BENCH_PROFILE"] = "%s.%s%s" % (root, cname,
                                                       ext or ".json")
        rec = _run_config(cname, CONFIGS[cname])
        _emit(rec)
        if "error" in rec:
            failed.append(cname)
    if failed:
        sys.exit("bench.py: failed configs: %s" % ", ".join(failed))


if __name__ == "__main__":
    main()
