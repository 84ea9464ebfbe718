"""startup_time bench: cold-start vs warm-disk-cache wall time (ISSUE 15).

Measures what the persistent compile cache actually buys: the wall time
from PROCESS START to (a) a gluon Trainer's first completed step and
(b) a Predictor replica finishing warmup — each run in a FRESH python
process (``--child``), because the thing being measured is process
restart. The orchestrator runs each scenario once against an empty
``MXTPU_COMPILE_CACHE_DIR`` (cold: every executable compiles + spills)
and again against the now-warm dir (warm: every executable
deserializes), and gates:

* warm ``compiles == 0`` — the retrace counters across every jit site
  stay at zero (watchdog-pinned: a disk load is not a compile),
* warm ``disk_hits > 0`` — the zero is because the disk served, not
  because nothing ran,
* warm wall < cold wall — ``vs_baseline`` is the cold/warm speedup.

JSON lines ride ``BENCH_CONFIG=startup_time python bench.py``. The
children open the backend themselves and a chip belongs to one process
at a time, so this orchestrator imports no JAX (and the config is not
part of ``BENCH_CONFIG=all``, whose parent holds the chip). Knobs: ``BENCH_STARTUP_HIDDEN`` / ``BENCH_STARTUP_LAYERS`` size the
model, ``BENCH_STARTUP_ROUNDS`` extra warm rounds (min taken),
``BENCH_STARTUP_CACHE_DIR`` pins the dir (default: fresh tempdir).
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hidden():
    return int(os.environ.get("BENCH_STARTUP_HIDDEN", "256"))


def _layers():
    return int(os.environ.get("BENCH_STARTUP_LAYERS", "4"))


# --------------------------------------------------------------- child side
def _build_net(nn):
    net = nn.HybridSequential()
    for _ in range(_layers()):
        net.add(nn.Dense(_hidden(), activation="relu"))
    net.add(nn.Dense(10))
    net.initialize()
    net.hybridize()
    return net


def _snapshot_counts():
    from mxtpu import telemetry
    snap = telemetry.snapshot()["counters"]
    compiles = sum(v for k, v in snap.items()
                   if isinstance(v, (int, float)) and k.startswith("retrace.")
                   and k != "retrace.watchdog_trips")
    def total(name):
        v = snap.get(name, 0)
        return sum(v.values()) if isinstance(v, dict) else v
    return {"compiles": int(compiles),
            "disk_hits": int(total("compile.disk.hits")),
            "disk_writes": int(total("compile.disk.writes")),
            "disk_drops": int(total("compile.disk.drops"))}


def child_trainer(t0):
    import numpy as np

    import mxtpu as mx
    from mxtpu import autograd, gluon
    from mxtpu.gluon import nn

    net = _build_net(nn)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(32, 64).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 10, size=(32,)))
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(32)
    first = float(loss.asnumpy().mean())  # sync: the step truly completed
    rec = {"scenario": "trainer", "wall_s": time.time() - t0,
           "loss": first}
    rec.update(_snapshot_counts())
    return rec


def child_predictor(t0):
    import numpy as np

    import mxtpu as mx
    from mxtpu.gluon import nn
    from mxtpu.serving import BucketSpec, Predictor

    net = _build_net(nn)
    example = mx.nd.array(np.zeros((1, 64), np.float32))
    pred = Predictor(net, BucketSpec.pow2(max_batch=8), example=example,
                     warmup=True)
    out = pred.predict(mx.nd.array(
        np.random.RandomState(0).randn(3, 64).astype(np.float32)))
    np.asarray(out.asnumpy())  # a served request really ran
    rec = {"scenario": "predictor", "wall_s": time.time() - t0,
           "buckets": len(pred.spec)}
    rec.update(_snapshot_counts())
    return rec


def run_child(scenario, t0):
    rec = child_trainer(t0) if scenario == "trainer" \
        else child_predictor(t0)
    print("STARTUP_BENCH " + json.dumps(rec), flush=True)


# ---------------------------------------------------------- orchestrator side
def _spawn(scenario, cache_dir, timeout_s=600):
    env = dict(os.environ)
    env["MXTPU_COMPILE_CACHE_DIR"] = cache_dir
    # cold against warm is the measurement, so this tool keeps a fresh
    # directory of its own (never the checkout's .jax_cache): jax's
    # cache beside the store catches what the service cannot key, with
    # the thresholds zeroed — a process start is all sub-second compiles
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache_dir, "xla")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", scenario,
         "--t0", repr(t0)],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout_s)
    for line in proc.stdout.splitlines():
        if line.startswith("STARTUP_BENCH "):
            return json.loads(line[len("STARTUP_BENCH "):])
    raise RuntimeError(
        "startup child (%s) produced no record: rc=%d\nstdout:\n%s\n"
        "stderr:\n%s" % (scenario, proc.returncode,
                         proc.stdout[-2000:], proc.stderr[-2000:]))


def run_startup(emit=None):
    """Cold vs warm process starts for both scenarios; returns the gate
    summary (and emits one JSON line per child run)."""
    if emit is None:
        def emit(rec):
            print(json.dumps(rec), flush=True)
    pinned = os.environ.get("BENCH_STARTUP_CACHE_DIR")
    # a FRESH directory of this tool's own (see _spawn)
    root = pinned or tempfile.mkdtemp(prefix="mxtpu-startup-bench-")
    rounds = max(1, int(os.environ.get("BENCH_STARTUP_ROUNDS", "1")))
    out = {"scenarios": {}, "ok": True}
    try:
        for scenario in ("trainer", "predictor"):
            cdir = os.path.join(root, scenario)
            shutil.rmtree(cdir, ignore_errors=True)
            os.makedirs(cdir, exist_ok=True)
            cold = _spawn(scenario, cdir)
            cold["mode"] = "cold"
            emit(dict(cold, metric="startup_time"))
            warms = [_spawn(scenario, cdir) for _ in range(rounds)]
            warm = min(warms, key=lambda r: r["wall_s"])
            warm["mode"] = "warm"
            emit(dict(warm, metric="startup_time"))
            gates = {
                # the acceptance pin: a warm start reaches the first
                # step / finished warmup with ZERO compiles...
                "zero_compiles": warm["compiles"] == 0,
                # ...BECAUSE the disk served (not because nothing ran)
                "disk_served": warm["disk_hits"] > 0,
                "faster": warm["wall_s"] < cold["wall_s"],
            }
            speedup = cold["wall_s"] / max(warm["wall_s"], 1e-9)
            out["scenarios"][scenario] = {
                "cold_s": round(cold["wall_s"], 3),
                "warm_s": round(warm["wall_s"], 3),
                "speedup": round(speedup, 3),
                "cold_compiles": cold["compiles"],
                "warm_compiles": warm["compiles"],
                "warm_disk_hits": warm["disk_hits"],
                "gates": gates,
            }
            out["ok"] = out["ok"] and all(gates.values())
    finally:
        if not pinned:
            shutil.rmtree(root, ignore_errors=True)
    out["speedup"] = min(s["speedup"] for s in out["scenarios"].values())
    return out


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", choices=("trainer", "predictor"))
    ap.add_argument("--t0", type=float, default=None)
    args = ap.parse_args(argv)
    if args.child:
        run_child(args.child, args.t0 if args.t0 else time.time())
        return 0
    summary = run_startup()
    print(json.dumps({"metric": "startup_time_summary", **summary}))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
