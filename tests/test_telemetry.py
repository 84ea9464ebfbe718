"""Unified runtime telemetry (mxtpu/telemetry.py) — ISSUE 4:

* registry semantics: counters (tagged), gauges, histograms with
  quantiles, per-metric reset, MXTPU_TELEMETRY=0 span gating;
* step-phase timeline: spans present after a Trainer step, merged into
  profiler.dump()'s chrome trace with the op events;
* retrace watchdog: fires on an induced policy-flip recompile of the
  fused-update jit, stays silent across a schedule-only lr change;
* transfer watchdog: counts a forced d2h, reads ZERO for the guarded
  hot loop, warns once on a steady-state hot-span sync;
* adoption: pallas DISPATCH_STATS is a view over the registry, health
  monitor verdicts / retries / checkpoint latencies report through it;
* JSONL sink round-trips through tools/telemetry_report.py.
"""
import importlib.util
import json
import logging
import os
import sys

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import optimizer_fused as of
from mxtpu import profiler, resilience, telemetry
from mxtpu.gluon.parameter import Parameter
from mxtpu.gluon.trainer import Trainer


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("MXTPU_TELEMETRY", "MXTPU_TELEMETRY_FLUSH_S",
                "MXTPU_RETRACE_BUDGET", "MXTPU_NUMERICS_GUARD",
                "MXTPU_FAULT_INJECT", "MXTPU_FUSED_OPTIMIZER"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    resilience.reset_faults()
    of.reset()
    yield
    telemetry.reset()
    resilience.reset_faults()
    of.reset()


def _make_trainer(n_params=3, shape=(5,), optimizer="sgd", opt_params=None,
                  scaler=None, seed=0):
    rng = np.random.RandomState(seed)
    params = []
    for j in range(n_params):
        p = Parameter("tp%d" % j, shape=shape, dtype="float32")
        p.initialize()
        p.data()._set_data(mx.nd.array(
            rng.uniform(-1, 1, shape).astype(np.float32))._data)
        params.append(p)
    opt_params = opt_params or {"learning_rate": 0.05, "momentum": 0.9}
    tr = Trainer(params, optimizer, opt_params, kvstore=None,
                 loss_scaler=scaler)
    return tr, params, rng


def _set_grads(params, rng, scale=1.0):
    for p in params:
        p.grad()[:] = mx.nd.array(
            (rng.randn(*p.shape) * scale).astype(np.float32))


# ------------------------------------------------------- registry semantics
def test_counters_gauges_histograms():
    telemetry.inc("c.plain")
    telemetry.inc("c.plain", 4)
    telemetry.inc("c.tagged", tag="a")
    telemetry.inc("c.tagged", 2, tag="b")
    telemetry.gauge("g.one", 3.5)
    for v in range(1, 101):
        telemetry.observe("h.vals", float(v))
    assert telemetry.value("c.plain") == 5
    assert telemetry.value("c.tagged", tag="a") == 1
    assert telemetry.value("c.tagged") == 3  # sums tags when untagged absent
    assert telemetry.tagged("c.tagged") == {"a": 1, "b": 2}
    snap = telemetry.snapshot()
    assert snap["gauges"]["g.one"] == 3.5
    h = snap["histograms"]["h.vals"]
    assert h["count"] == 100 and h["min"] == 1.0 and h["max"] == 100.0
    assert abs(h["mean"] - 50.5) < 1e-9
    assert 49 <= h["p50"] <= 52
    assert 97 <= h["p99"] <= 100
    rep = telemetry.report()
    assert "c.tagged{a}" in rep and "h.vals" in rep
    telemetry.reset_metric("c.tagged")
    assert telemetry.tagged("c.tagged") == {}
    assert telemetry.value("c.plain") == 5  # untouched by per-metric reset


def test_span_disabled_by_env(monkeypatch):
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    with telemetry.span("off.region"):
        pass
    assert "off.region" not in telemetry.snapshot()["histograms"]
    assert telemetry.events() == []
    # bare counters stay always-on (the DISPATCH_STATS-style views
    # must keep working under the lever)
    telemetry.inc("always.on")
    assert telemetry.value("always.on") == 1


# ----------------------------------------------------- step-phase timeline
def test_trainer_step_phases_recorded():
    tr, params, rng = _make_trainer()
    for _ in range(2):
        _set_grads(params, rng)
        tr.step(1)
    hists = telemetry.snapshot()["histograms"]
    for name in ("trainer.step", "trainer.step.allreduce",
                 "trainer.step.update"):
        assert hists[name]["count"] == 2, name
    names = {e[0] for e in telemetry.events()}
    assert "trainer.step" in names and "trainer.step.update" in names


def test_profiler_dump_merges_phase_events(tmp_path):
    fname = str(tmp_path / "trace.json")
    profiler.set_config(filename=fname)
    profiler.start()
    tr, params, rng = _make_trainer()
    _set_grads(params, rng)
    tr.step(1)
    profiler.stop()
    profiler.dump()
    with open(fname) as f:
        trace = json.load(f)
    phase = [e for e in trace["traceEvents"] if e["cat"] == "phase"]
    names = {e["name"] for e in phase}
    assert "trainer.step" in names and "trainer.step.update" in names
    for e in phase:  # same shape/conventions as the op events
        assert e["ph"] == "X" and e["pid"] == 0 and "tid" in e
    # the telemetry ring is always-on; the merge is scoped to the
    # profiled window — spans from before start() must not stretch the
    # trace's time axis across the whole process lifetime
    with telemetry.span("outside.window"):
        pass
    profiler.dump()
    with open(fname) as f:
        names2 = {e["name"] for e in json.load(f)["traceEvents"]}
    assert "outside.window" not in names2 and "trainer.step" in names2


def test_data_wait_span_recorded():
    from mxtpu.gluon import data as gdata
    ds = gdata.ArrayDataset(mx.nd.array(
        np.arange(20, dtype=np.float32).reshape(10, 2)))
    loader = gdata.DataLoader(ds, batch_size=5)
    n = sum(1 for _ in loader)
    assert n == 2
    hists = telemetry.snapshot()["histograms"]
    assert hists["data.wait"]["count"] >= 2


# ------------------------------------------------------- retrace watchdog
def test_retrace_watchdog_fires_on_policy_flip(monkeypatch, caplog):
    """A guard-policy flip recompiles the fused-update jit exactly once —
    with MXTPU_RETRACE_BUDGET below that second compile, the watchdog
    must fire and carry the cache-key provenance."""
    monkeypatch.setenv("MXTPU_RETRACE_BUDGET", "1")
    tr, params, rng = _make_trainer(optimizer="adam",
                                    opt_params={"learning_rate": 0.01})
    _set_grads(params, rng)
    tr.step(1)
    assert telemetry.value("retrace.watchdog_trips") == 0  # warmup compile
    monkeypatch.setenv("MXTPU_NUMERICS_GUARD", "1")  # induced policy flip
    with caplog.at_level(logging.WARNING, logger="mxtpu.telemetry"):
        _set_grads(params, rng)
        tr.step(1)
    assert of.FUSED_STATS["compiles"] == 2
    assert telemetry.value("retrace.watchdog_trips") == 1
    st = telemetry.retrace_stats("fused_optimizer")
    assert st["compiles"] == 2 and st["trips"] == 1
    assert st["last"]["optimizer"] == "Adam" and st["last"]["guard"] is True
    assert "policy_key" in st["last"]
    assert any("retrace watchdog" in r.message for r in caplog.records)


def test_retrace_watchdog_silent_on_lr_schedule(monkeypatch, caplog):
    """Schedule-only hyper movement is traced, never recompiles, never
    trips the watchdog — even with the tightest budget."""
    monkeypatch.setenv("MXTPU_RETRACE_BUDGET", "1")
    tr, params, rng = _make_trainer(optimizer="adam",
                                    opt_params={"learning_rate": 0.01})
    with caplog.at_level(logging.WARNING, logger="mxtpu.telemetry"):
        for i in range(4):
            tr.set_learning_rate(0.01 / (i + 1))  # schedule-only change
            _set_grads(params, rng)
            tr.step(1)
    assert of.FUSED_STATS["compiles"] == 1
    assert telemetry.value("retrace.watchdog_trips") == 0
    assert not any("retrace watchdog" in r.message for r in caplog.records)


def test_cached_op_retrace_provenance(monkeypatch):
    """CachedOp compiles report through the same watchdog with policy
    provenance; a steady-state re-call adds nothing."""
    from mxtpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0).randn(2, 6).astype(np.float32))
    net(x)
    net.hybridize()
    net(x)
    compiles = telemetry.value("retrace.cached_op")
    assert compiles >= 1
    net(x)  # steady state: cache hit
    assert telemetry.value("retrace.cached_op") == compiles
    st = telemetry.retrace_stats("cached_op")
    assert "policy_key" in st["last"]


# ------------------------------------------------------ transfer watchdog
def test_transfer_watchdog_counts_forced_d2h():
    arr = mx.nd.ones((4,))
    c0 = telemetry.d2h_count()
    arr.asnumpy()
    assert telemetry.d2h_count() == c0 + 1
    float(arr.sum())  # asscalar routes through asnumpy too
    assert telemetry.d2h_count() == c0 + 2


def test_guarded_hot_loop_step_d2h_is_zero():
    """The acceptance contract read off the registry instead of a
    transfer guard: steady-state guarded Trainer.steps attribute ZERO
    d2h syncs to the step span."""
    scaler = resilience.DynamicLossScaler(init_scale=4.0)
    tr, params, rng = _make_trainer(optimizer="adam",
                                    opt_params={"learning_rate": 0.01},
                                    scaler=scaler)
    for _ in range(4):
        _set_grads(params, rng)
        ok = tr.step(1)
        assert ok is not None  # verdict handed back, NOT fetched
    assert telemetry.snapshot()["histograms"]["trainer.step"]["count"] == 4
    assert telemetry.value("trainer.step.d2h") == 0


def test_transfer_watchdog_warns_on_steady_state_sync(caplog):
    arr = mx.nd.ones((4,))
    with caplog.at_level(logging.WARNING, logger="mxtpu.telemetry"):
        for _ in range(4):
            with telemetry.span("hot.region", d2h=True):
                arr.asnumpy()
    assert telemetry.value("hot.region.d2h") == 4
    warns = [r for r in caplog.records
             if "transfer watchdog" in r.message]
    assert len(warns) == 1  # warns ONCE, past the warmup occurrences


# ------------------------------------------------------- adopted stats
def test_dispatch_stats_is_view_over_registry():
    import importlib
    import jax.numpy as jnp
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    fa.reset_dispatch_stats()
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    out = fa.flash_attention(q, q, q)
    assert out.shape == q.shape
    # off-TPU without the interpreter: counted XLA fallback
    assert telemetry.value("pallas_flash.xla") == 1
    assert any("platform" in r
               for r in telemetry.tagged("pallas_flash.fallback"))
    # the module-level dict is a THIN VIEW over the same registry entries
    assert fa.DISPATCH_STATS["xla"] == 1
    assert fa.DISPATCH_STATS["pallas"] == 0
    assert fa.DISPATCH_STATS["fallback_reasons"] == \
        telemetry.tagged("pallas_flash.fallback")
    assert set(fa.DISPATCH_STATS.keys()) == \
        {"pallas", "xla", "fallback_reasons", "grouped", "kv_repeated",
         "bwd_pallas", "bwd_xla", "bwd_fallback_reasons", "block_pairs",
         "windowed", "window_unskipped", "window_pairs_seen",
         "window_pairs_visited"}
    # equal heads: not a grouped call, and nothing was repeated
    assert fa.DISPATCH_STATS["grouped"] == 0
    assert fa.DISPATCH_STATS["kv_repeated"] == 0
    # no window: not a windowed call
    assert fa.DISPATCH_STATS["windowed"] == 0
    assert fa.DISPATCH_STATS["window_unskipped"] == 0
    # a forward that fell back counted no block pairs
    assert fa.DISPATCH_STATS["block_pairs"] == {}
    fa.reset_dispatch_stats()
    assert fa.DISPATCH_STATS["xla"] == 0
    assert fa.DISPATCH_STATS["fallback_reasons"] == {}


def test_health_monitor_emits_through_telemetry(monkeypatch):
    from mxtpu.monitor import TrainingHealthMonitor
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "nan_grad@1")
    scaler = resilience.DynamicLossScaler(init_scale=8.0)
    tr, params, rng = _make_trainer(scaler=scaler)
    mon = TrainingHealthMonitor(interval=3).install(tr)
    for _ in range(3):
        _set_grads(params, rng)
        tr.step(1)
        mon.after_step()
    assert telemetry.value("resilience.steps_ok") == 2
    assert telemetry.value("resilience.steps_skipped") == 1
    gauges = telemetry.snapshot()["gauges"]
    assert "resilience.grad_norm" in gauges
    assert gauges["resilience.loss_scale"] == 4.0  # backed off once
    # the report shows guard activity without a log scrape
    assert "resilience.steps_skipped" in telemetry.report()


def test_retry_counters():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("transient")
        return "ok"

    out = resilience.with_retries(flaky, "test op", retries=2,
                                  backoff=0.001, metric="retry.test_site")
    assert out == "ok"
    assert telemetry.value("retry.total") == 1
    assert telemetry.value("retry.test_site") == 1


def test_checkpoint_save_latency_recorded(tmp_path):
    from mxtpu.contrib import async_checkpoint as ackpt
    tr, params, rng = _make_trainer()
    _set_grads(params, rng)
    tr.step(1)
    ackpt.save_trainer(tr, str(tmp_path), step=0)
    snap = telemetry.snapshot()
    assert snap["histograms"]["checkpoint.save_s"]["count"] == 1
    assert telemetry.value("checkpoint.saves") == 1


def test_fault_injection_counted(monkeypatch):
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "nan_grad@0")
    tr, params, rng = _make_trainer(
        optimizer="adam", opt_params={"learning_rate": 0.01})
    monkeypatch.setenv("MXTPU_NUMERICS_GUARD", "1")
    _set_grads(params, rng)
    tr.step(1)
    assert telemetry.tagged("faults.injected") == {"nan_grad": 1}


# ------------------------------------------------------------ JSONL sink
def _report_mod():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "telemetry_report.py")
    spec = importlib.util.spec_from_file_location("telemetry_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_jsonl_sink_roundtrips_through_report(tmp_path, monkeypatch):
    sink = str(tmp_path / "tel.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY", sink)
    for v in range(1, 101):
        telemetry.observe("span.x", float(v))
    telemetry.inc("count.y", 7)
    telemetry.gauge("gauge.z", 2.25)
    telemetry.flush()
    telemetry.flush()  # counters repeat per flush; report must not double
    rep = _report_mod()
    summary = rep.aggregate(rep.load(sink))
    assert summary["span.x"]["count"] == 100
    assert abs(summary["span.x"]["mean"] - 50.5) < 1e-9
    assert 49 <= summary["span.x"]["p50"] <= 52
    assert 97 <= summary["span.x"]["p99"] <= 100
    assert summary["count.y"]["value"] == 7
    assert summary["gauge.z"]["value"] == 2.25
    table = rep.format_table(summary)
    assert "span.x" in table and "count.y" in table
    assert rep.main([sink]) == 0  # the CLI path runs clean too


def test_report_counters_fold_across_process_restarts(tmp_path):
    """Several sessions may share ONE sink file, each
    restarting its cumulative counters at 0 — the report must bank each
    session (Prometheus reset semantics), not take the max."""
    sink = str(tmp_path / "multi.jsonl")
    with open(sink, "w") as f:
        for v in (2, 5):      # session A flushes twice, ends at 5
            f.write(json.dumps({"t": 1, "kind": "counter",
                                "metric": "retry.total", "value": v}) + "\n")
        for v in (1, 3):      # session B restarts at 0, ends at 3
            f.write(json.dumps({"t": 2, "kind": "counter",
                                "metric": "retry.total", "value": v}) + "\n")
    rep = _report_mod()
    summary = rep.aggregate(rep.load(sink))
    assert summary["retry.total"]["value"] == 8  # 5 + 3, not max(5, 3)
    assert rep.main(["--json"]) == 1  # flags-only invocation: usage, rc 1


def test_mixed_tag_and_untagged_counter_survives_snapshot():
    telemetry.inc("mix.c", 2)
    telemetry.inc("mix.c", 3, tag="a")
    snap = telemetry.snapshot()["counters"]["mix.c"]
    assert snap == {"_untagged": 2, "a": 3}  # neither form dropped


def test_jsonl_sink_tolerates_torn_line(tmp_path, monkeypatch):
    sink = str(tmp_path / "torn.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY", sink)
    telemetry.observe("m.a", 1.0)
    telemetry.flush()
    with open(sink, "a") as f:
        f.write('{"t": 1, "kind": "obs", "metric": "m.a", "va')  # torn
    rep = _report_mod()
    summary = rep.aggregate(rep.load(sink))
    assert summary["m.a"]["count"] == 1
