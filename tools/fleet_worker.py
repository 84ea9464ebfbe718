"""Fleet training child: one host of the elastic multi-host matrix.

The subprocess entrypoint ``FleetSupervisor`` / ``bench.py fleet_resume``
/ ``tests/test_fleet.py`` launch per host: joins the fleet
(``mxtpu.fleet.init`` — deadline bring-up off the env bootstrap the
supervisor exports), trains a small deterministic MLP with
``gluon.Trainer(mesh=..., zero1=True)`` so optimizer state is ZeRO-1
sharded over the mesh, checkpoints every step through ``ResilientLoop``
(rank 0 is the single writer), and reports a ``RESULT`` JSON line
(per-step losses, resume step, compile/disk-cache counters, divergence
checks).

Everything is a pure function of ``--seed`` — dataset, init, batch
order — and on this forced-CPU tier every host trains the FULL global
batch on its own local mesh (``--devices`` fake devices), so a run
killed at step K and restored onto a RESHAPED mesh (different
``--devices``) must reproduce the uninterrupted run's losses within
reduce-order tolerance. Cross-host coupling that a TPU fleet gets from
device collectives rides ``Fleet.step_barrier`` instead: a dead peer
fails the survivors LOUD (exit 42 with the membership diagnosis), and
the divergence fingerprints riding the barrier payloads are the
cross-host consistency gate. The ``shard_keys`` disjoint-union
invariant is asserted every step — the slice each host WOULD take on a
global-compute backend reassembles the exact global batch at any world
size.

Faults arrive via ``MXTPU_FAULT_INJECT`` in the child env
(``host_loss@K`` → ``os._exit(41)`` at step K; ``rejoin_stall@rank``
stalls the bring-up). The fleet collective watchdog
(``MXTPU_FLEET_COLLECTIVE_TIMEOUT_S``) is the backstop that turns a
wedge the barrier cannot see into a loud exit 42; the launcher's hard
child timeout is the outer backstop.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _snapshot_counts():
    # the startup_bench recipe: compiles = every retrace counter except
    # the watchdog's own trip count; disk_hits proves the cache served
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
            # a found-but-refused blob (key_mismatch, load_error,
            # corrupt...) is the difference between "cache cold" and
            # "cache rejected us" when a zero-compile gate fails
            "disk_drops": int(total("compile.disk.drops"))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--devices", type=int, default=1,
                    help="fake local devices (the mesh-reshape lever: "
                    "save on N, restore on M)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=8)
    ap.add_argument("--features", type=int, default=4)
    args = ap.parse_args()
    t0 = time.time()

    # forced CPU host tier: the fleet matrix is a control-plane /
    # correctness test, never a chip benchmark. The device count must be
    # pinned BEFORE jax imports.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=%d" % args.devices)
    # the divergence sentinel is part of the acceptance matrix: the
    # fused update emits its fingerprint every step
    os.environ.setdefault("MXTPU_DIVERGENCE_EVERY", "1")

    import numpy as np

    import mxtpu as mx
    from mxtpu import autograd, fleet, fleet_obs, gluon, resilience
    from mxtpu import telemetry
    from mxtpu.gluon import nn
    from mxtpu.io.stream import shard_keys
    from mxtpu.parallel import host_value

    f = fleet.init()
    rank, world = f.rank, f.num_hosts
    mesh = f.mesh()

    # fleet observability plane (ISSUE 19, mxtpu/fleet_obs.py): cadenced
    # obs_<rank>.json publication riding the telemetry flush hook, plus
    # the straggler/regression sentinels off the step-barrier payloads.
    # All opt-in: MXTPU_FLEET_OBS_S / MXTPU_STRAGGLER_X default off.
    pub = None
    if f.fleet_dir and fleet_obs.obs_interval_s() > 0:
        pub = fleet_obs.HostObsPublisher(f.fleet_dir, rank).install()
    straggler = fleet_obs.StragglerSentinel() if rank == 0 else None
    regression = fleet_obs.RegressionSentinel()

    # dataset: pure function of the seed (identical on every host and
    # across restarts/reshapes)
    n_rows = 64
    rs = np.random.RandomState(args.seed)
    x_all = rs.randn(n_rows, args.features).astype("float32")
    w_true = rs.randn(args.features, 1).astype("float32")
    y_all = (x_all @ w_true + 0.1 * rs.randn(n_rows, 1)).astype("float32")

    mx.random.seed(args.seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(args.hidden, activation="relu",
                     in_units=args.features))
    net.add(nn.Dense(1, in_units=args.hidden))
    net.initialize()
    loss_fn = gluon.loss.L2Loss()
    # momentum so there IS per-param optimizer state for ZeRO-1 to shard
    # (and re-shard onto the reshaped mesh after a loss)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            mesh=mesh, zero1=True)
    loop = resilience.ResilientLoop(trainer, resilience.CheckpointPolicy(
        args.ckpt_dir, every_steps=1, async_save=False))
    start = loop.resume()
    f.barrier("fleet_worker_resumed")

    wd = f.watchdog(exit_on_trip=True).start_monitor()
    sentinel = resilience.DivergenceSentinel()

    losses = []
    try:
        for step in range(start, args.steps):
            fleet.maybe_host_loss(step)
            f.check(step)
            # fixed global batch for this step. Every host trains the
            # WHOLE batch (replicated trajectories — the CPU tier's
            # stand-in for device collectives), but the per-host
            # shard_keys slices must still reassemble it exactly: the
            # invariant the global-compute sharding path rides.
            idx = [(step * args.batch + i) % n_rows
                   for i in range(args.batch)]
            parts = [shard_keys(idx, num_shards=world, shard_index=r,
                                shuffle=False) for r in range(world)]
            assert [k for p in parts for k in p] == idx, \
                "shard_keys shards no longer reassemble the global batch"
            xb, yb = trainer.shard_batch(x_all[idx], y_all[idx])
            # straggler_slow fault: a fixed host-side stall before this
            # step, billed to data.wait — the deterministic slow host
            # the straggler sentinel must name
            slow_s = 0.0
            if resilience.inject("straggler_slow", step):
                slow_s = 0.35
                time.sleep(slow_s)
            entry = wd.arm(step, what="train step")
            try:
                with autograd.record():
                    loss = loss_fn(net(xb), yb)
                loss.backward()
                trainer.step(args.batch)
                fp = getattr(trainer._updaters[0], "last_fingerprint", None)
                sentinel.check(fp, step=step)
                lval = float(np.mean(host_value(loss._data)))
                # cross-host consistency gate: the step barrier carries
                # each host's fingerprint; a dead peer or a divergent
                # one fails this loud. The obs payload stitches this
                # host's trace id + stage breakdown + arrival timestamp
                # into the board for the fleet critical-path view.
                stages = dict(getattr(trainer, "last_step_stages", {}) or {})
                if slow_s:
                    stages["data.wait"] = stages.get("data.wait", 0.0) + slow_s
                obs = {"trace": getattr(trainer, "last_step_trace", None),
                       "stages": stages}
                fps = f.step_barrier(step, fingerprint=None if fp is None
                                     else [float(x) for x in fp], obs=obs)
                if straggler is not None and fps:
                    straggler.observe(step, fps)
                regression.observe(step, sum(stages.values()) or None)
            finally:
                wd.disarm(entry)
            losses.append(lval)
            if pub is not None:
                pub.maybe_publish(step)
            if rank == 0:
                # single checkpoint writer: replicated state is
                # identical on every host, and two processes writing
                # one step dir would race
                loop.after_step(step)
    except fleet.FleetWedgeError as e:
        print("FLEET WEDGE rank %d: %s" % (rank, e), flush=True)
        os._exit(fleet.EXIT_FLEET_WEDGE)

    loop.wait_for_pending()
    if pub is not None:
        pub.publish()  # final blob: the completed run's full registry
    rec = {"rank": rank, "world": world, "start": start,
           "steps": args.steps, "devices": args.devices, "losses": losses,
           "divergence_checks": sentinel.checks,
           "wall_s": time.time() - t0}
    rec.update(_snapshot_counts())
    rec["obs_publishes"] = int(telemetry.value("fleet.obs.publishes"))
    rec["straggler_trips"] = sum(
        (telemetry.tagged("fleet.straggler_trips") or {}).values())
    if straggler is not None and straggler.trips:
        rec["straggler"] = straggler.trips[-1]["rank"]
    print("RESULT " + json.dumps(rec), flush=True)
    wd.stop_monitor()
    f.leave()


if __name__ == "__main__":
    main()
