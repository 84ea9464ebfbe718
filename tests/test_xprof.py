"""Executable observatory (mxtpu/xprof.py + mxtpu/perf_model.py) —
ISSUE 12:

* per-jit-site ledger: every compile recorded with cost-model
  FLOPs/bytes, HBM footprint, donated-bytes savings, and compile
  wall-time; the runtime ledger covers EVERY jit cache graftlint's
  static ``--inventory`` lists (the runtime/static cross-check);
* wrapped jits stay cache-stable: steady-state calls add zero compiles
  (fused-retrace-flat with ``MXTPU_XPROF=1``) and the per-call counting
  feeds ``executed_flops``;
* live HBM accounting: ``device_memory`` is the ONE normalizer
  (``util.get_gpu_memory`` / C-ABI parity), ``poll_memory`` gauges,
  the ``MXTPU_MEMWATCH_S`` monitor thread, and the warmup will-it-fit
  pre-flight (``memory.overcommit``);
* the OOM flight path: fault kind ``oom`` through Trainer.step, the
  Predictor dispatch, and the decode loop produces a
  ``flight_record("oom")`` artifact carrying the ledger + per-device
  memory stats (+ the KVCacheAccountant view in decode), and every
  loop fails LOUD, never hangs;
* runtime MFU: the ``perf.mfu`` gauge from ledger FLOPs x step rate
  over the shared datasheet-peak table;
* perf_model accessors: list-of-dicts vs dict vs None cost_analysis
  normalization, the roofline verdict, and the
  ``telemetry_report --ledger`` table.
"""
import json
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import perf_model, resilience, telemetry, xprof
from mxtpu.base import MXNetError
from mxtpu.gluon import nn
from mxtpu.gluon.parameter import Parameter
from mxtpu.gluon.trainer import Trainer

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # pytest rootdir variants
    sys.path.insert(0, str(REPO))
if str(REPO / "tools") not in sys.path:  # serve_bench's DecodeModel
    sys.path.insert(0, str(REPO / "tools"))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("MXTPU_TELEMETRY", "MXTPU_TRACE", "MXTPU_XPROF",
                "MXTPU_FAULT_INJECT", "MXTPU_FLIGHT_DIR",
                "MXTPU_MEMWATCH_S", "MXTPU_PEAK_TFLOPS",
                "MXTPU_PEAK_GBPS", "MXTPU_RETRACE_BUDGET"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    resilience.reset_faults()
    yield
    telemetry.reset()
    resilience.reset_faults()


def _make_trainer(n_params=2, shape=(6,), optimizer="sgd"):
    rng = np.random.RandomState(0)
    params = []
    for j in range(n_params):
        p = Parameter("xp%d" % j, shape=shape, dtype="float32")
        p.initialize()
        p.data()._set_data(mx.nd.array(
            rng.uniform(-1, 1, shape).astype(np.float32))._data)
        params.append(p)
    tr = Trainer(params, optimizer, {"learning_rate": 0.05},
                 kvstore=None)
    return tr, params, rng


def _set_grads(params, rng):
    for p in params:
        p.grad()[:] = mx.nd.array(rng.randn(*p.shape).astype(np.float32))


def _sites_of(entries):
    return {e["site"] for e in entries}


# ------------------------------------------------------------------ ledger
def test_record_retrace_compiled_returns_wrapped_and_ledgers():
    import jax
    import jax.numpy as jnp

    fn = telemetry.record_retrace(
        "demo.site", {"k": 1}, compiled=jax.jit(lambda a: (a @ a).sum()))
    a = jnp.ones((16, 16), jnp.float32)
    for _ in range(3):
        fn(a)
    led = xprof.ledger("demo.site")
    assert len(led) == 1
    e = led[0]
    assert e["calls"] == 3
    assert e["compile_s"] is not None and e["compile_s"] > 0
    assert e["error"] is None
    assert e["flops"] and e["flops"] > 0
    assert e["bytes_accessed"] and e["bytes_accessed"] > 0
    # memory_analysis footprint keys present on the CPU backend too
    assert e["argument_bytes"] > 0 and e["output_bytes"] >= 0
    assert "temp_bytes" in e and "donated_bytes" in e
    # executed FLOPs = flops x calls (the MFU numerator)
    assert xprof.executed_flops(("demo.site",)) == \
        pytest.approx(e["flops"] * 3)
    # compile wall-time reached the registry histogram
    assert telemetry.snapshot()["histograms"]["compile.wall_s"]["count"] == 1
    # the resolve-free view is exported in snapshot() (-> /metrics)
    assert _sites_of(telemetry.snapshot()["ledger"]) == {"demo.site"}


def test_xprof_off_returns_unwrapped(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("MXTPU_XPROF", "0")
    jfn = jax.jit(lambda a: a + 1)
    out = telemetry.record_retrace("demo.site", None, compiled=jfn)
    assert out is jfn  # zero added dispatch layers
    out(jnp.ones((2,)))
    assert xprof.ledger() == []
    assert "ledger" not in telemetry.snapshot()
    # the retrace count itself is unchanged by the lever
    assert telemetry.value("retrace.demo.site") == 1


def test_wrapped_jit_forwards_attributes():
    import jax
    import jax.numpy as jnp

    fn = telemetry.record_retrace(
        "demo.site", None, compiled=jax.jit(lambda a: a * 2))
    a = jnp.ones((4,), jnp.float32)
    fn(a)
    # .lower() keeps working through the wrapper (an AOT caller's path)
    c = fn.lower(jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
    assert perf_model.flops_of(c) is not None or True  # no raise is the pin


def test_ledger_bounded_per_site():
    import jax
    import jax.numpy as jnp

    a = jnp.ones((2,))
    for i in range(20):
        fn = telemetry.record_retrace(
            "demo.bounded", {"i": i}, compiled=jax.jit(lambda x: x + i))
        fn(a)
    led = xprof.ledger("demo.bounded", resolve=False)
    assert len(led) == 16  # newest kept, oldest evicted
    assert led[-1]["provenance"] == {"i": 19}


def test_fused_retrace_flat_and_mfu_with_xprof_on(monkeypatch):
    """Steady-state Trainer.steps through the WRAPPED fused jit add zero
    compiles (the fused-retrace-flat pin with MXTPU_XPROF=1), and the
    MFU meter turns ledger FLOPs x step rate into the perf.mfu gauge
    under an MXTPU_PEAK_TFLOPS override (CPU tier has no datasheet
    peak)."""
    monkeypatch.setenv("MXTPU_XPROF", "1")
    monkeypatch.setenv("MXTPU_PEAK_TFLOPS", "0.001")
    tr, params, rng = _make_trainer()
    tr._mfu = xprof.MFUMeter(every=2)  # test-tempo window
    for _ in range(6):
        _set_grads(params, rng)
        tr.step(1)
    assert telemetry.value("retrace.fused_optimizer") == 1  # flat
    led = xprof.ledger("fused_optimizer")
    assert len(led) == 1 and led[0]["calls"] == 6
    mfu = telemetry.snapshot()["gauges"].get("perf.mfu")
    assert mfu is not None and mfu > 0
    assert tr._mfu.last == pytest.approx(mfu)


# ---------------------------------------------- runtime/static cross-check
def test_ledger_covers_graftlint_inventory():
    """THE acceptance cross-check: after exercising every jit-cache
    owner, xprof.ledger() has an entry for every cache in graftlint's
    static ``--inventory`` — the runtime inventory matches the static
    scouting report site for site (per-instance families like
    ``serving.predict.r<i>`` match by dotted prefix)."""
    from tools.graftlint import LintConfig, run

    import jax.numpy as jnp

    static_sites = {e["retrace_site"]
                    for e in run(LintConfig(root=REPO),
                                 ["mxtpu"]).jit_inventory}
    assert None not in static_sites and "<dynamic>" not in static_sites

    # the ledger records COMPILES: the two process-global caches must be
    # cold or an earlier test's warm executable would skip record_retrace
    from mxtpu import optimizer_fused
    from mxtpu.ops import subgraph_ops
    optimizer_fused._JIT_CACHE.clear()
    subgraph_ops._SUBGRAPH_CACHE.clear()

    rng = np.random.RandomState(0)

    # fused_optimizer: one guarded-free Trainer step
    tr, params, trng = _make_trainer()
    _set_grads(params, trng)
    tr.step(1)

    # cached_op: hybridized gluon forward (first call settles deferred
    # shapes eagerly; the second compiles)
    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    net.hybridize()
    x = mx.nd.array(rng.randn(2, 3).astype(np.float32))
    net(x)
    net(x)

    # executor + executor.backward: a plain symbol bound and run fwd/bwd
    import mxtpu.symbol as sym_mod
    from mxtpu.symbol import partition

    data = sym_mod.Variable("data")
    out = sym_mod.FullyConnected(data, num_hidden=4, name="xfc")
    exe = out.simple_bind(grad_req="write", data=(2, 3))
    for arr in exe.arg_dict.values():
        arr._set_data(mx.nd.array(
            rng.normal(size=arr.shape).astype(np.float32))._data)
    exe.forward(is_train=True, data=mx.nd.ones((2, 3)))
    exe.backward(out_grads=mx.nd.ones((2, 4)))

    # subgraph_exec: the partitioned twin, inference mode (the region
    # executes as its own compiled executable there)
    part = partition(out, "default")
    args = {n: mx.nd.array(rng.normal(size=tuple(s)).astype(np.float32))
            for n, s in zip(out.list_arguments(),
                            out.infer_shape(data=(2, 3))[0])}
    part.bind(args=args, grad_req="null").forward(is_train=False)

    # parallel.train_step: the mesh step
    from mxtpu import gluon
    from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh

    pnet = nn.Dense(2)
    pnet.initialize()
    pnet(mx.nd.ones((8, 3)))  # settle deferred shapes before the step
    step = ShardedTrainStep(pnet, gluon.loss.L2Loss(),
                            data_parallel_mesh(), optimizer="sgd",
                            optimizer_params={"learning_rate": 0.01})
    step(mx.nd.ones((8, 3)), mx.nd.ones((8, 2)))

    # rtc: a runtime-compiled Pallas kernel launch
    from mxtpu.rtc import PallasModule
    mod = PallasModule(
        "def scale(x_ref, out_ref):\n"
        "    out_ref[...] = 2.0 * x_ref[...]\n")
    mod.get_kernel("scale").launch([mx.nd.ones((2, 4))],
                                   out_shapes=(2, 4))

    # serving.predict: a warmed single-bucket Predictor
    from mxtpu.serving import BucketSpec, DecodeEngine, Predictor
    snet = nn.Dense(3)
    snet.initialize()
    Predictor(snet, BucketSpec([2]),
              example=np.zeros((1, 5), np.float32), warmup=True)

    # serving.decode + serving.draft: a warmed tiny SPECULATIVE paged
    # engine compiles the whole six-caches inventory's serving tail —
    # the draft site only exists when a draft model is attached
    import serve_bench as sb
    model = sb.build_decode_model(vocab=16, dim=8, max_len=16, seed=3)
    DecodeEngine(model, BucketSpec([1], seq_lens=[4]),
                 BucketSpec(decode_slots=[2]), max_len=8,
                 page_tokens=4, draft_model=model, spec_k=2,
                 warmup=True, start=False)

    runtime_sites = _sites_of(xprof.ledger(resolve=False))
    missing = {s for s in static_sites
               if not any(r == s or r.startswith(s + ".")
                          for r in runtime_sites)}
    assert not missing, \
        "jit caches with no runtime ledger entry: %s (runtime saw %s)" \
        % (sorted(missing), sorted(runtime_sites))
    # and the executor entries resolve to real cost/memory analyses
    exe_entries = xprof.ledger("executor")
    assert exe_entries and all(e["error"] is None and e["flops"]
                               for e in exe_entries)


# --------------------------------------------------------- HBM accounting
class _FakeDev:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_device_memory_normalizes_and_unifies():
    d = _FakeDev({"bytes_in_use": 30, "bytes_limit": 100,
                  "peak_bytes_in_use": 60})
    m = xprof.device_memory(d)
    assert m == {"bytes_in_use": 30, "bytes_limit": 100,
                 "peak_bytes_in_use": 60, "bytes_free": 70}
    # key fallbacks: a backend with only the reservable spelling
    m2 = xprof.device_memory(_FakeDev({"bytes_reservable_limit": 50,
                                       "bytes_in_use": 10}))
    assert m2["bytes_limit"] == 50 and m2["bytes_free"] == 40
    assert m2["peak_bytes_in_use"] == 10  # falls back to in-use
    # stats-less backend (CPU): all zeros, never a guess
    assert xprof.device_memory(_FakeDev(None))["bytes_limit"] == 0


def test_util_and_c_api_agree_with_device_memory(monkeypatch):
    import jax

    from mxtpu import c_api_impl, util

    d = _FakeDev({"bytes_in_use": 25, "bytes_limit": 100})
    monkeypatch.setattr(jax, "devices", lambda *a: [d])
    assert util.get_gpu_memory(0) == (75, 100)
    assert c_api_impl.get_memory_information(0) == (75, 100)
    # CPU tier: util degrades to (0, 0), the C ABI refuses loudly
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDev(None)])
    assert util.get_gpu_memory(0) == (0, 0)
    with pytest.raises(MXNetError, match="no memory stats"):
        c_api_impl.get_memory_information(0)


def test_poll_memory_gauges_and_prometheus():
    xprof.poll_memory({"d0": {"bytes_in_use": 30, "bytes_limit": 100,
                              "peak_bytes_in_use": 60},
                       "d1": {"bytes_in_use": 10, "bytes_limit": 100,
                              "peak_bytes_in_use": 20}})
    g = telemetry.snapshot()["gauges"]
    assert g["memory.hbm_used_bytes"] == {"d0": 30.0, "d1": 10.0}
    assert g["memory.hbm_headroom_bytes"]["d0"] == 70.0
    assert g["memory.hbm_limit_bytes"]["d1"] == 100.0
    assert g["memory.hbm_peak_bytes"]["d0"] == 60.0
    text = telemetry.prometheus()
    assert 'mxtpu_memory_hbm_used_bytes{tag="d0"} 30' in text


def test_memwatch_thread_lifecycle(monkeypatch):
    monkeypatch.setenv("MXTPU_MEMWATCH_S", "0.01")
    polled = []
    monkeypatch.setattr(xprof, "poll_memory",
                        lambda stats=None: polled.append(1))
    assert xprof.ensure_memwatch() is True
    assert xprof.ensure_memwatch() is True  # idempotent
    deadline = time.time() + 2.0
    while not polled and time.time() < deadline:
        time.sleep(0.01)
    xprof.stop_memwatch()
    assert polled, "monitor thread never polled"
    # off by default: no interval, no thread
    monkeypatch.setenv("MXTPU_MEMWATCH_S", "0")
    assert xprof.ensure_memwatch() is False


def test_preflight_overcommit_warning():
    import jax
    import jax.numpy as jnp

    fn = telemetry.record_retrace(
        "demo.preflight", None,
        compiled=jax.jit(lambda a: (a @ a).sum()))
    fn(jnp.ones((32, 32), jnp.float32))
    # no limit known and none supplied -> skipped entirely (CPU tier)
    assert xprof.preflight("demo.preflight") is None
    # a generous budget: no overcommit
    need, limit = xprof.preflight("demo.preflight", limit=1 << 40)
    assert need > 0 and limit == 1 << 40
    assert telemetry.value("memory.overcommit") == 0
    # a tiny budget: overcommit counted + preflight gauge set
    xprof.preflight("demo.preflight", limit=16)
    assert telemetry.tagged("memory.overcommit") == {"demo.preflight": 1}
    g = telemetry.snapshot()["gauges"]["memory.preflight_bytes"]
    assert g["demo.preflight"] == need


# ------------------------------------------------------------- OOM flight
def _flight_files(d):
    return sorted(Path(d).glob("flight_oom_*.json"))


def test_trainer_oom_flight_artifact(monkeypatch, tmp_path):
    """Fault kind ``oom`` in Trainer.step: the step raises LOUD
    (ResourceExhausted reaches the caller) and the flight artifact
    carries the ledger snapshot + per-device memory stats."""
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "oom@0")
    tr, params, rng = _make_trainer()
    _set_grads(params, rng)
    with pytest.raises(resilience.ResourceExhausted,
                       match="RESOURCE_EXHAUSTED"):
        tr.step(1)
    files = _flight_files(tmp_path)
    assert len(files) == 1
    art = json.loads(files[0].read_text())
    assert art["reason"] == "oom"
    assert art["extra"]["where"] == "trainer.step"
    assert "RESOURCE_EXHAUSTED" in art["extra"]["error"]
    assert "ledger" in art["extra"] and "memory" in art["extra"]
    assert telemetry.tagged("memory.oom") == {"trainer.step": 1}
    # inject() itself dumps a "fault" artifact; the OOM path adds ITS own
    assert telemetry.tagged("flight.dumps")["oom"] == 1
    # the NEXT step (fault consumed) trains normally — fail loud, not dead
    _set_grads(params, rng)
    tr.step(1)


def test_predictor_oom_fails_cohort_loud(monkeypatch, tmp_path):
    """Fault kind ``oom`` on the Predictor dispatch: the batcher's
    error path completes the request future with the error (no hang)
    and the artifact is written."""
    from mxtpu.serving import BucketSpec, MicroBatcher, Predictor

    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    net = nn.Dense(3)
    net.initialize()
    pred = Predictor(net, BucketSpec([2]),
                     example=np.zeros((1, 5), np.float32), warmup=True)
    mb = MicroBatcher(pred, max_batch_size=1, start=False)
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "oom@0")
    fut = mb.submit(np.zeros((1, 5), np.float32))
    mb.poll()
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        fut.result(timeout=2.0)
    art = json.loads(_flight_files(tmp_path)[0].read_text())
    assert art["extra"]["where"] == "serving.predict"
    # the predict-site ledger entries ride the artifact's registry view
    assert any(e["site"] == "serving.predict"
               for e in art["extra"]["ledger"])


def test_decode_oom_flight_with_accountant_view(monkeypatch, tmp_path):
    """Fault kind ``oom`` in the decode loop (poll drive): the artifact
    carries the KVCacheAccountant residency view and the engine's
    failure is LOUD."""
    import serve_bench as sb

    from mxtpu.serving import BucketSpec, DecodeEngine, KVCacheAccountant

    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    model = sb.build_decode_model(vocab=16, dim=8, max_len=16, seed=3)
    acct = KVCacheAccountant()
    eng = DecodeEngine(model, BucketSpec([1], seq_lens=[4]),
                       BucketSpec(decode_slots=[2]), max_len=8,
                       accountant=acct, warmup=True, start=False)
    fut = eng.submit([1, 2, 3], max_new=4)
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "oom@0")
    with pytest.raises(resilience.ResourceExhausted):
        eng.poll()
    art = json.loads(_flight_files(tmp_path)[0].read_text())
    assert art["extra"]["where"] == "serving.decode"
    assert art["extra"]["kv"]  # the accountant snapshot rode along
    assert any(e["site"] == "serving.decode"
               for e in art["extra"]["ledger"])
    assert not fut.done()  # poll drive: the raise went to the caller
    eng.close()


def test_decode_oom_threaded_crash_barrier(monkeypatch, tmp_path):
    """Threaded decode loop + injected OOM: the crash barrier fails the
    pending future LOUD (never hangs) after the artifact is dumped."""
    import serve_bench as sb

    from mxtpu.serving import BucketSpec, DecodeEngine

    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    model = sb.build_decode_model(vocab=16, dim=8, max_len=16, seed=3)
    eng = DecodeEngine(model, BucketSpec([1], seq_lens=[4]),
                       BucketSpec(decode_slots=[2]), max_len=8,
                       warmup=True, start=False)
    fut = eng.submit([1, 2, 3], max_new=4)
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "oom@0")
    eng.start()
    # the injected RESOURCE_EXHAUSTED surfaces on the loop thread's
    # prefill dispatch; the future completes LOUD with it either way
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        fut.result(timeout=10.0)
    assert _flight_files(tmp_path)
    # ...and the re-raise reaches the crash barrier (poll: the future is
    # failed loud BEFORE the barrier runs, so wait for the counter)
    deadline = time.time() + 5.0
    while telemetry.value("serving.worker_crashes") < 1 \
            and time.time() < deadline:
        time.sleep(0.01)
    assert telemetry.value("serving.worker_crashes") == 1
    assert telemetry.tagged("memory.oom")  # at least one OOM site tagged
    eng.close()


# -------------------------------------------------------------- perf_model
def test_cost_dict_normalizes_every_shape():
    assert perf_model.cost_dict(None) == {}
    assert perf_model.cost_dict({}) == {}
    assert perf_model.cost_dict({"flops": 5.0}) == {"flops": 5.0}

    class _C:
        def cost_analysis(self):
            return {"flops": -1.0}  # XLA's "unknown" spelling

    assert perf_model.flops_of(_C()) is None


def test_peak_tables_and_roofline():
    assert perf_model.nominal_tflops("TPU v5 lite") == 197.0
    assert perf_model.nominal_tflops("TPU v4") == 275.0
    # a TPU kind in no table is an error, never another chip's peak
    with pytest.raises(LookupError, match="v9 mystery"):
        perf_model.nominal_tflops("TPU v9 mystery")
    with pytest.raises(LookupError):
        perf_model.peak_bandwidth("TPU v9 mystery")
    os.environ["MXTPU_PEAK_TFLOPS"] = "2"
    os.environ["MXTPU_PEAK_GBPS"] = "1"
    try:
        assert perf_model.peak_flops() == 2e12
        ridge = perf_model.critical_intensity()
        assert ridge == pytest.approx(2000.0)  # 2 TFLOP/s over 1 GB/s
        assert perf_model.roofline_verdict(1e7, 1.0, ridge) == "compute"
        assert perf_model.roofline_verdict(100.0, 1.0, 0.01) == "compute"
        assert perf_model.roofline_verdict(100.0, 1.0, ridge) == "memory"
        assert perf_model.roofline_verdict(None, 1.0, ridge) is None
    finally:
        os.environ.pop("MXTPU_PEAK_TFLOPS")
        os.environ.pop("MXTPU_PEAK_GBPS")
    # off-TPU with no override: no peak, no MFU
    assert perf_model.peak_flops() is None
    assert perf_model.mfu(1e12) is None


def test_bench_peak_delegates_to_perf_model(monkeypatch):
    import bench
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "3")
    assert bench._peak_flops() == 3e12
    monkeypatch.delenv("BENCH_PEAK_TFLOPS")
    assert bench._peak_flops() is None  # CPU tier -> table says no peak


# --------------------------------------------------- report + sink plumbing
def test_ledger_jsonl_roundtrip_and_report(monkeypatch, tmp_path):
    """Resolved ledger entries reach the JSONL sink at flush and
    ``telemetry_report --ledger`` folds them into the roofline table
    (last line per (site, seq) wins), including the ranked memory-bound
    Pallas-candidate shortlist."""
    import subprocess

    import jax
    import jax.numpy as jnp

    sink = tmp_path / "t.jsonl"
    monkeypatch.setenv("MXTPU_TELEMETRY", str(sink))
    monkeypatch.setenv("MXTPU_PEAK_TFLOPS", "1")
    monkeypatch.setenv("MXTPU_PEAK_GBPS", "1000")  # ridge = 1.0 FLOP/B
    fn = telemetry.record_retrace(
        "demo.sink", None, compiled=jax.jit(lambda a: a + 1.0))
    fn(jnp.ones((64,), jnp.float32))  # intensity << 1 -> memory-bound
    xprof.resolve()
    telemetry.flush()
    out = subprocess.run(
        [sys.executable, "tools/telemetry_report.py", str(sink),
         "--ledger", "--json"],
        cwd=str(REPO), capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = json.loads(out.stdout)["_ledger"]["rows"]
    row = [r for r in rows if r["site"] == "demo.sink"][0]
    assert row["verdict"] == "memory"
    assert "demo.sink#%s" % row["seq"] in \
        json.loads(out.stdout)["_ledger"]["candidates"]
    # the human table renders without error too
    from tools.telemetry_report import (format_ledger_table, ledger_summary,
                                        load)
    rows2, cands = ledger_summary(load(str(sink)))
    table = format_ledger_table(rows2, cands)
    assert "demo.sink" in table and "Pallas candidates" in table


def test_bench_stamp_carries_ledger_summary():
    import bench

    import jax
    import jax.numpy as jnp

    fn = telemetry.record_retrace(
        "demo.stamp", None, compiled=jax.jit(lambda a: a * 3))
    fn(jnp.ones((4,)))
    rec = bench._stamp({"metric": "x"})
    assert rec["ledger"]["compiles"] >= 1
    assert rec["ledger"]["compile_s_total"] > 0
    assert "peak_hbm_bytes" in rec["ledger"]
    json.dumps(rec)  # the stamp stays JSON-serializable
