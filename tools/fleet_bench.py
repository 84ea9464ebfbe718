"""fleet_resume bench: kill-one-host restore parity + warm rejoin (ISSUE 18).

The acceptance scenario for elastic fleet training, run end to end with
real subprocesses on the forced-CPU host tier (chip-safe — every child
pins ``JAX_PLATFORMS=cpu``; ``--devices`` sets its fake local device
count, the mesh-RESHAPE lever):

1. **fleet** — a 2-host fleet (2 devices each) trains with per-step
   checkpoints; ``host_loss@K`` is injected into host 1, which dies with
   ``os._exit(41)`` at step K. The survivor's step barrier diagnoses the
   dead peer off its stale heartbeat and exits 42 loud — the fleet
   collective watchdog is the backstop (gate: *kill_detected* — both
   exit codes surfaced, nothing hung).
2. **restore** — ONE host resumes from the same checkpoint dir onto a
   RESHAPED 1-device mesh: the last intact checkpoint (step K−1, saved
   from the 2-device ZeRO-1 layout) restores into the live 1-device
   shardings (orbax re-reads; the MeshPlan re-places optimizer state)
   and trains to completion (gates: resumed at K, clean exit,
   divergence sentinel green).
3. **oracle** — the same seed runs uninterrupted on 1 host × 1 device in
   a separate dir; gate *resume_parity*: the restore run's post-restore
   losses match the oracle's within reduce-order tolerance (the killed
   run's first K steps reduced over 2 devices, the oracle's over 1 —
   ULP-level divergence compounds, bitwise equality is not the right
   pin).
4. **rejoin** — the fleet grows back to 2 hosts against the SAME compile
   cache dir and trains 2 more steps (gates: every rejoined host records
   ZERO compiles across all registered jit sites, watchdog-pinned, and
   the disk cache served — warm elastic rejoin). XLA:CPU cannot
   round-trip multi-device executables (compile_service refuses them),
   so the rejoin generation runs 1 device per host, warm off the blobs
   the restore/oracle phases spilled; on TPU the same gate rides the
   full-mesh blobs.
5. **obs** — ISSUE 19's observability gate: a fresh 2-host fleet runs
   with the fleet obs plane ON (``MXTPU_FLEET_OBS_S``,
   ``MXTPU_STRAGGLER_X``) and rank 1 injected ``straggler_slow`` on
   every post-warmup step. Gates: *fleet_snapshot_merged* — the
   ``FleetObservatory`` merge over the board's ``obs_*.json`` blobs
   covers both hosts with step-time quantiles — and
   *straggler_tripped* — the ``flight_record("straggler")`` artifact
   names rank 1 with ``data.wait`` dominant within 16 steps.

JSON lines ride ``BENCH_CONFIG=fleet_resume python bench.py``.
Knobs: ``BENCH_FLEET_STEPS`` (default 6), ``BENCH_FLEET_KILL_STEP``
(default 3), ``BENCH_FLEET_CHILD_TIMEOUT_S``, ``BENCH_FLEET_DIR`` (pin
the work dir; default fresh tempdir).
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tools", "fleet_worker.py")
sys.path.insert(0, REPO)


def _steps():
    return int(os.environ.get("BENCH_FLEET_STEPS", "6"))


def _kill_step():
    return int(os.environ.get("BENCH_FLEET_KILL_STEP", "3"))


def _child_timeout_s():
    return float(os.environ.get("BENCH_FLEET_CHILD_TIMEOUT_S", "240"))


def _parse_result(tail):
    for line in reversed((tail or "").splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    return None


def _phase(name, world, ckpt_dir, steps, workdir, cache_dir, devices=1,
           env_extra=None, env_for=None):
    """One fleet generation through FleetSupervisor.launch_round: fresh
    fleet board dir, shared compile cache, hard child timeouts. Returns
    {rank: {"rc": ..., "result": parsed RESULT or None, "tail": ...}}."""
    from mxtpu.fleet import FleetSupervisor
    fleet_dir = os.path.join(workdir, "board_%s" % name)
    shutil.rmtree(fleet_dir, ignore_errors=True)

    def command_for(rank, w, generation):
        return [sys.executable, WORKER, "--ckpt-dir", ckpt_dir,
                "--steps", str(steps), "--devices", str(devices)]

    base_env = {
        "MXTPU_COMPILE_CACHE_DIR": cache_dir,
        # jax's own cache beside the store, for what the service cannot
        # key (the one cache rule: children are handed JAX's variable)
        "JAX_COMPILATION_CACHE_DIR": os.path.join(cache_dir, "xla"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
        "MXTPU_FLEET_BRINGUP_TIMEOUT_S": "90",
        "MXTPU_FLEET_HEARTBEAT_S": "0.5",
        # the post-kill wedge bound: the survivor's step-K collective
        # must fail loud well inside the child hard timeout
        "MXTPU_FLEET_COLLECTIVE_TIMEOUT_S": "30",
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    base_env.update(env_extra or {})

    def merged_env(rank, w, generation):
        env = dict(base_env)
        if env_for is not None:
            env.update(env_for(rank, w, generation) or {})
        return env

    sup = FleetSupervisor(
        command_for=command_for, num_hosts=world, fleet_dir=fleet_dir,
        timeout_s=_child_timeout_s(), env_for=merged_env)
    t0 = time.time()
    raw = sup.launch_round(world, 0)
    wall = time.time() - t0
    out = {}
    for rank, (rc, tail) in raw.items():
        out[rank] = {"rc": rc, "result": _parse_result(tail), "tail": tail}
    out["wall_s"] = wall
    return out


def run_fleet_resume(emit=None):
    """Run the 4-phase matrix; returns the gate summary (and emits one
    stamped JSON line per phase)."""
    if emit is None:
        def emit(rec):
            print(json.dumps(rec), flush=True)
    steps, kill = _steps(), _kill_step()
    pinned = os.environ.get("BENCH_FLEET_DIR")
    # cold against warm rejoin is the measurement: a FRESH directory of
    # this tool's own, never the checkout's .jax_cache
    root = pinned or tempfile.mkdtemp(prefix="mxtpu-fleet-bench-")
    cache_dir = os.path.join(root, "compile_cache")
    ckpt = os.path.join(root, "ckpt")
    ckpt_oracle = os.path.join(root, "ckpt_oracle")
    ckpt_obs = os.path.join(root, "ckpt_obs")
    flight_obs = os.path.join(root, "flight_obs")
    for d in (cache_dir, ckpt, ckpt_oracle, ckpt_obs, flight_obs):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d, exist_ok=True)
    summary = {"steps": steps, "kill_step": kill, "phases": {}}
    try:
        # 1. 2-host fleet, 2 devices each, host 1 killed at step K
        p1 = _phase(
            "fleet", 2, ckpt, steps, root, cache_dir, devices=2,
            env_for=lambda r, w, g:
                {"MXTPU_FAULT_INJECT": "host_loss@%d" % kill} if r == 1
                else {})
        rc_killed = p1[1]["rc"]
        rc_survivor = p1[0]["rc"]
        kill_detected = rc_killed == 41 and rc_survivor == 42
        summary["phases"]["fleet"] = {
            "wall_s": round(p1["wall_s"], 2),
            "rc": {"0": rc_survivor, "1": rc_killed}}
        emit({"metric": "fleet_resume", "phase": "fleet",
              "wall_s": round(p1["wall_s"], 3), "rc_survivor": rc_survivor,
              "rc_killed": rc_killed, "kill_detected": kill_detected})

        # 2. restore onto the reshaped 1-host x 1-device mesh
        p2 = _phase("restore", 1, ckpt, steps, root, cache_dir, devices=1)
        r2 = p2[0]["result"] or {}
        restored_at = r2.get("start")
        divergence_green = p2[0]["rc"] == 0 and \
            r2.get("divergence_checks", 0) > 0
        summary["phases"]["restore"] = {
            "wall_s": round(p2["wall_s"], 2), "rc": p2[0]["rc"],
            "resumed_at": restored_at}
        emit({"metric": "fleet_resume", "phase": "restore",
              "wall_s": round(p2["wall_s"], 3), "rc": p2[0]["rc"],
              "resumed_at": restored_at,
              "losses": r2.get("losses")})

        # 3. uninterrupted 1-host oracle, separate checkpoint dir
        p3 = _phase("oracle", 1, ckpt_oracle, steps, root, cache_dir,
                    devices=1)
        r3 = p3[0]["result"] or {}
        oracle_losses = r3.get("losses") or []
        restore_losses = r2.get("losses") or []
        parity = bool(
            p3[0]["rc"] == 0 and restored_at == kill and
            len(restore_losses) == steps - kill and
            len(oracle_losses) == steps and
            np.allclose(restore_losses, oracle_losses[kill:],
                        rtol=5e-4, atol=1e-6))
        max_rel = None
        if parity:
            a = np.asarray(restore_losses)
            b = np.asarray(oracle_losses[kill:])
            max_rel = float(np.max(np.abs(a - b) /
                                   np.maximum(np.abs(b), 1e-9)))
        summary["phases"]["oracle"] = {
            "wall_s": round(p3["wall_s"], 2), "rc": p3[0]["rc"],
            "max_rel_diff": max_rel}
        emit({"metric": "fleet_resume", "phase": "oracle",
              "wall_s": round(p3["wall_s"], 3), "rc": p3[0]["rc"],
              "losses": oracle_losses, "resume_parity": parity,
              "max_rel_diff": max_rel})

        # 4. warm rejoin: back to 2 hosts, +2 steps, same compile cache
        # (1 device per host — XLA:CPU disk blobs are single-device only)
        p4 = _phase("rejoin", 2, ckpt, steps + 2, root, cache_dir,
                    devices=1)
        r4 = [p4[r]["result"] or {} for r in (0, 1)]
        rejoin_ok = all(p4[r]["rc"] == 0 for r in (0, 1))
        zero_compiles = rejoin_ok and \
            all(r.get("compiles", 1) == 0 for r in r4)
        disk_served = all(r.get("disk_hits", 0) > 0 for r in r4)
        summary["phases"]["rejoin"] = {
            "wall_s": round(p4["wall_s"], 2),
            "rc": {"0": p4[0]["rc"], "1": p4[1]["rc"]},
            "compiles": [r.get("compiles") for r in r4],
            "disk_hits": [r.get("disk_hits") for r in r4]}
        emit({"metric": "fleet_resume", "phase": "rejoin",
              "wall_s": round(p4["wall_s"], 3),
              "compiles": [r.get("compiles") for r in r4],
              "disk_hits": [r.get("disk_hits") for r in r4],
              "rejoin_zero_compiles": zero_compiles})

        # 5. observability (ISSUE 19): fresh 2-host fleet with the obs
        # plane ON and rank 1 injected slow on every post-warmup step —
        # the merged fleet snapshot must cover both hosts and the
        # straggler sentinel must NAME rank 1 with its dominant stage.
        import glob as _glob

        from mxtpu import fleet_obs
        p5 = _phase(
            "obs", 2, ckpt_obs, steps, root, cache_dir, devices=1,
            env_extra={"MXTPU_FLEET_OBS_S": "0.05",
                       "MXTPU_STRAGGLER_X": "1.5",
                       "MXTPU_FLIGHT_DIR": flight_obs},
            env_for=lambda r, w, g:
                {"MXTPU_FAULT_INJECT": "straggler_slow@" + ",".join(
                    str(s) for s in range(1, steps))} if r == 1
                else {})
        obs_rc_ok = all(p5[r]["rc"] == 0 for r in (0, 1))
        board = os.path.join(root, "board_obs", "gen_0")
        merged = fleet_obs.FleetObservatory(board, 2).merged()
        hosts = merged.get("hosts", {})
        snapshot_merged = obs_rc_ok and all(
            r in hosts and hosts[r]["step_s"].get("p50") is not None
            for r in (0, 1))
        trip = None
        for art in sorted(_glob.glob(os.path.join(
                flight_obs, "flight_straggler_*.json"))):
            try:
                with open(art) as fh:
                    trip = (json.load(fh).get("extra") or {})
                break
            except ValueError:
                continue
        straggler_named = bool(
            trip and trip.get("rank") == 1 and
            trip.get("step", 1 << 30) < 16 and
            trip.get("dominant_stage") == "data.wait")
        summary["phases"]["obs"] = {
            "wall_s": round(p5["wall_s"], 2),
            "rc": {"0": p5[0]["rc"], "1": p5[1]["rc"]},
            "hosts_merged": sorted(hosts),
            "straggler": None if not trip else
            {k: trip.get(k) for k in
             ("rank", "step", "ratio", "dominant_stage")}}
        emit({"metric": "fleet_resume", "phase": "obs",
              "wall_s": round(p5["wall_s"], 3),
              "fleet_snapshot_merged": snapshot_merged,
              "straggler_tripped": straggler_named,
              "straggler": summary["phases"]["obs"]["straggler"]})

        gates = {
            "kill_detected": kill_detected,
            "restore_clean": p2[0]["rc"] == 0 and restored_at == kill,
            "divergence_green": divergence_green,
            "resume_parity": parity,
            "rejoin_zero_compiles": zero_compiles,
            "rejoin_disk_served": disk_served,
            "fleet_snapshot_merged": snapshot_merged,
            "straggler_tripped": straggler_named,
        }
        summary["gates"] = gates
        summary["ok"] = all(gates.values())
        # the headline numbers: how fast a grown-back fleet reaches
        # useful work vs the killed run's cost, all compiles disk-served
        summary["rejoin_wall_s"] = round(p4["wall_s"], 3)
        summary["vs_baseline"] = round(
            p1["wall_s"] / max(p4["wall_s"], 1e-9), 3)
        if not summary["ok"]:
            # surface the failing child's tail — a gate that fails in CI
            # must carry its evidence
            for name, p in (("fleet", p1), ("restore", p2),
                            ("oracle", p3), ("rejoin", p4), ("obs", p5)):
                for rank in (0, 1):
                    info = p.get(rank)
                    if info and info["rc"] != 0:
                        summary.setdefault("failures", []).append(
                            {"phase": name, "rank": rank, "rc": info["rc"],
                             "tail": info["tail"][-1500:]})
    finally:
        if not pinned:
            shutil.rmtree(root, ignore_errors=True)
    return summary


def main(argv=None):
    summary = run_fleet_resume()
    print(json.dumps({"metric": "fleet_resume_summary", **summary}))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
