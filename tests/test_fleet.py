"""Elastic multi-host fleet (ISSUE 18): the control-plane failure matrix —

* membership board: per-host heartbeat files, staleness diagnosis
  (never-seen vs stale vs clean ``left``), dead-coordinator check
  raising LOUD with a ``coordinator_loss`` flight artifact;
* board barrier: payload return, deadline miss naming the missing
  hosts, a stale peer failing the wait EARLY with the board diagnosis;
* deadline bring-up: ``_run_with_deadline`` timeout/success/error
  paths, ``fleet.init`` rendezvous deadline (monkeypatched
  ``_rendezvous_required`` drives it on CPU), connect retries counted
  into ``retry.fleet_connect``, board-only bring-up on the forced-CPU
  tier, the ``rejoin_stall`` fault exiting ``EXIT_REJOIN_STALL``;
* fleet collective watchdog: fixed-deadline trip with the membership
  diagnosis in the ``fleet_collective_wedge`` artifact, poisoning,
  ``exit_on_trip`` code;
* step barrier: fingerprint exchange green path, cross-host divergence
  raising with a ``fleet_divergence`` artifact, dead-peer wedge;
* FleetSupervisor: scripted elastic run (host loss -> N-1 -> warm
  rejoin -> clean), victim-vs-lost classification, poison-crash and
  crash-loop refusals dumping ``supervisor_refusal`` with history,
  launch_round exit-code surfacing + hard child timeout;
* ONE bounded multi-process acceptance run: kill a host mid-step,
  survivors exit loud, the reshaped generation resumes from the last
  intact checkpoint and finishes clean.

Everything above the acceptance run is sleep- and subprocess-free on
fake clocks.
"""
import glob
import json
import os
import random
import sys
import threading
import time

import pytest

from mxtpu import fleet, resilience, telemetry
from mxtpu.fleet import (EXIT_FLEET_WEDGE, EXIT_HOST_LOSS,
                         EXIT_REJOIN_STALL, Fleet, FleetBringupError,
                         FleetCollectiveWatchdog, FleetMembership,
                         FleetSupervisor, FleetWedgeError)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("MXTPU_FLEET_DIR", "MXTPU_FLEET_CONNECT_RETRIES",
                "MXTPU_FLEET_CONNECT_BACKOFF_S",
                "MXTPU_FLEET_BRINGUP_TIMEOUT_S", "MXTPU_FLEET_HEARTBEAT_S",
                "MXTPU_FLEET_HEARTBEAT_MISS",
                "MXTPU_FLEET_COLLECTIVE_TIMEOUT_S",
                "MXTPU_FLEET_CHILD_TIMEOUT_S", "MXTPU_FAULT_INJECT",
                "MXTPU_FLIGHT_DIR", "MXTPU_FLIGHT_MAX",
                "MXTPU_COORDINATOR", "MXTPU_NUM_PROCESSES",
                "MXTPU_PROCESS_ID", "MXTPU_SUPERVISOR_RESTARTS",
                "MXTPU_SUPERVISOR_BACKOFF_S", "MXTPU_FLEET_OBS_S",
                "MXTPU_STRAGGLER_X", "MXTPU_PROFILE_ON_TRIP"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    resilience.reset_faults()
    yield
    telemetry.reset()
    resilience.reset_faults()


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt

    def sleeper(self, s):
        # fake sleep + a real micro-yield: deadline loops that poll a
        # WORKER THREAD must let it get scheduled, or a busy fake-clock
        # loop can burn the whole fake deadline before the thread runs
        self.t += s
        time.sleep(0.0005)


class _Exit(Exception):
    def __init__(self, code):
        self.code = code


def _counter(name):
    v = telemetry.snapshot()["counters"].get(name, 0)
    return sum(v.values()) if isinstance(v, dict) else v


def _artifacts(tmp_path, reason):
    return sorted(glob.glob(os.path.join(str(tmp_path),
                                         "flight_%s_*" % reason)))


# ------------------------------------------------------- membership board
def test_membership_staleness_matrix(tmp_path):
    """never-seen and stale hosts are dead; fresh and clean-left are not."""
    clk = FakeClock()
    m0 = FleetMembership(tmp_path, 0, 4, clock=clk)
    m1 = FleetMembership(tmp_path, 1, 4, clock=clk)
    m2 = FleetMembership(tmp_path, 2, 4, clock=clk)
    m0.write("up")
    m1.write("up")
    m2.write("up")
    assert m0.dead_hosts() == [3]  # host 3: never seen
    # past the heartbeat bound (2.0s x 3 misses default) host 1 and 2 go
    # stale; host 0 keeps heartbeating; host 2 left CLEANLY first
    clk.advance(4.0)
    m2.write("left")
    clk.advance(100.0)
    m0.write("up")
    assert m0.dead_hosts() == [1, 3]
    assert m0.coordinator_alive()  # host 0 just heartbeat: alive
    desc = m0.describe()
    assert "host 3: NEVER SEEN" in desc and "host 2: left" in desc
    view = m0.view()
    assert sorted(view) == [0, 1, 2] and view[1]["status"] == "up"


def test_dead_coordinator_check_raises_loud(tmp_path, monkeypatch):
    """A survivor (rank != 0) diagnoses the dead coordinator instead of
    hanging: FleetWedgeError with the board, coordinator_loss artifact."""
    art = tmp_path / "flight"
    art.mkdir()
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(art))
    clk = FakeClock()
    board = tmp_path / "board"
    m0 = FleetMembership(board, 0, 2, clock=clk)
    m1 = FleetMembership(board, 1, 2, clock=clk)
    m0.write("up")
    assert m1.check(step=3) == []  # everyone fresh
    clk.advance(50.0)  # coordinator stops heartbeating
    with pytest.raises(FleetWedgeError, match="coordinator"):
        m1.check(step=4)
    arts = _artifacts(art, "coordinator_loss")
    assert len(arts) == 1
    snap = json.load(open(arts[0]))
    assert snap["extra"]["rank"] == 1 and 0 in snap["extra"]["dead"]
    # the coordinator ITSELF reports dead peers but never raises (check
    # above refreshed host 1's heartbeat; let it go stale again)
    clk.advance(50.0)
    assert m0.check(step=4) == [1]


def test_coordinator_loss_fault_injection(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "coordinator_loss@0")
    clk = FakeClock()
    m0 = FleetMembership(tmp_path, 0, 2, clock=clk)
    m1 = FleetMembership(tmp_path, 1, 2, clock=clk)
    m0.write("up")
    with pytest.raises(FleetWedgeError, match="coordinator"):
        m1.check(step=0)


def test_board_barrier_payload_exchange(tmp_path):
    clk = FakeClock()
    m0 = FleetMembership(tmp_path, 0, 2, clock=clk)
    m1 = FleetMembership(tmp_path, 1, 2, clock=clk)
    m1.write("up")
    # peer arrives first (its barrier file is already down)
    os.makedirs(os.path.join(str(tmp_path), "barrier_x"), exist_ok=True)
    fleet._atomic_write(
        os.path.join(str(tmp_path), "barrier_x", "host_1"),
        json.dumps({"rank": 1, "payload": [1.0, 2.0]}))
    got = m0.barrier("x", 10.0, payload=[3.0], clock=clk,
                     sleeper=clk.advance)
    assert got == {0: [3.0], 1: [1.0, 2.0]}


def test_board_barrier_deadline_names_missing_hosts(tmp_path):
    clk = FakeClock()
    m0 = FleetMembership(tmp_path, 0, 3, clock=clk)
    m0.write("up")
    with pytest.raises(FleetWedgeError, match=r"missing \[1, 2\]"):
        m0.barrier("b", 10.0, clock=clk, sleeper=clk.advance,
                   fail_on_dead=False)
    assert clk.t > 10.0  # it really waited out the (fake) deadline


def test_board_barrier_stale_peer_fails_early(tmp_path):
    """A peer whose heartbeat went stale mid-wait fails the barrier as
    soon as it is DIAGNOSED dead — not at the full deadline."""
    clk = FakeClock()
    m0 = FleetMembership(tmp_path, 0, 2, clock=clk)
    m1 = FleetMembership(tmp_path, 1, 2, clock=clk)
    m1.write("up")   # seen once...
    clk.advance(50.0)  # ...then silent far past the heartbeat bound
    m0.write("up")
    with pytest.raises(FleetWedgeError, match="died while the fleet"):
        m0.barrier("b", 1000.0, clock=clk, sleeper=clk.advance)
    assert clk.t < 60.0  # early: nowhere near the 1000s deadline


# ---------------------------------------------------- deadline bring-up
def test_run_with_deadline_paths():
    clk = FakeClock()
    # success
    assert fleet._run_with_deadline(
        lambda: 42, 1000.0, AssertionError,
        clock=clk, sleeper=clk.sleeper) == 42

    # the fn's own error is re-raised, not swallowed into a timeout
    def boom():
        raise ValueError("boom")
    with pytest.raises(ValueError, match="boom"):
        fleet._run_with_deadline(boom, 1000.0, AssertionError,
                                 clock=clk, sleeper=clk.sleeper)
    # a hang trips on_timeout at the (fake) deadline
    gate = threading.Event()
    t0 = clk.t
    try:
        with pytest.raises(FleetBringupError, match="stuck"):
            fleet._run_with_deadline(
                gate.wait, 5.0, lambda: FleetBringupError("stuck"),
                clock=clk, sleeper=clk.sleeper)
    finally:
        gate.set()
    assert clk.t - t0 > 5.0


def test_bringup_deadline_fails_loud_with_board(tmp_path, monkeypatch):
    """ISSUE-18 bring-up acceptance: a missing host fails the deadline
    LOUD with per-host status, instead of hanging the healthy host inside
    the rendezvous. Driven on CPU by forcing the rendezvous path."""
    art = tmp_path / "flight"
    art.mkdir()
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(art))
    monkeypatch.setattr(fleet, "_rendezvous_required", lambda: True)
    gate = threading.Event()
    from mxtpu import distributed
    monkeypatch.setattr(distributed, "init",
                        lambda **kw: (gate.wait(), (0, 2))[1])
    clk = FakeClock()
    board = tmp_path / "board"
    try:
        with pytest.raises(FleetBringupError, match="never joined"):
            fleet.init(fleet_dir=str(board), num_processes=2, process_id=0,
                       timeout_s=5.0, clock=clk, sleeper=clk.sleeper,
                       heartbeat=False)
    finally:
        gate.set()
    err = _artifacts(art, "fleet_bringup_timeout")
    assert len(err) == 1
    snap = json.load(open(err[0]))
    assert snap["extra"]["rank"] == 0 and snap["extra"]["world"] == 2
    # this host published "connecting" before blocking — the board shows
    # who to blame
    view = FleetMembership(board, 0, 2, clock=clk).view()
    assert view[0]["status"] == "connecting" and 1 not in view


def test_bringup_connect_retries_counted(tmp_path, monkeypatch):
    """Transient rendezvous failures retry with backoff under the ONE
    bring-up deadline, counted into retry.fleet_connect."""
    monkeypatch.setattr(fleet, "_rendezvous_required", lambda: True)
    calls = {"n": 0}

    def flaky_init(**kw):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("coordinator not up yet")
        return (0, 1)
    from mxtpu import distributed
    monkeypatch.setattr(distributed, "init", flaky_init)
    clk = FakeClock()
    f = fleet.init(fleet_dir=str(tmp_path / "b"), num_processes=1,
                   process_id=0, timeout_s=300.0, clock=clk,
                   sleeper=clk.sleeper, rng=random.Random(0),
                   heartbeat=False)
    assert (f.rank, f.num_hosts) == (0, 1) and calls["n"] == 3
    assert _counter("retry.fleet_connect") == 2
    assert f.membership.view()[0]["status"] == "up"


def test_board_only_bringup_two_hosts_in_process(tmp_path, monkeypatch):
    """Forced-CPU tier: bring-up never touches jax.distributed (the
    board IS the rendezvous — global device ids would poison the warm
    compile cache), and both hosts meet at the bring-up barrier."""
    from mxtpu import distributed

    def banned(**kw):
        raise AssertionError("rendezvous must not run on the CPU tier")
    monkeypatch.setattr(distributed, "init", banned)
    board = str(tmp_path / "b")
    out = {}

    def bring(rankid):
        out[rankid] = fleet.init(fleet_dir=board, num_processes=2,
                                 process_id=rankid, timeout_s=60.0,
                                 heartbeat=False)
    ts = [threading.Thread(target=bring, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30.0)
    assert sorted(out) == [0, 1]
    f0, f1 = out[0], out[1]
    assert (f0.rank, f0.num_hosts) == (0, 2)
    # the per-host mesh covers this process's own devices only
    assert f0.mesh().devices.size >= 1
    # PR 9 sharding: per-host shards are a disjoint union of the keys
    keys = list(range(10))
    s0 = f0.data_shard(keys, shuffle=False)
    s1 = f1.data_shard(keys, shuffle=False)
    assert sorted(s0 + s1) == keys and not set(s0) & set(s1)
    f1.leave()
    assert f0.membership.view()[1]["status"] == "left"
    f0.leave()


def test_a_host_waiting_at_bringup_keeps_its_heartbeat(tmp_path, monkeypatch):
    """A host that waits at the bring-up barrier for a late peer is alive
    and says so: its heartbeat runs from the moment it is ``up``, not from
    the barrier's end. With the heartbeat started after the barrier, a peer
    that arrived later than the staleness bound (six xdist workers beside
    two children: a start skew of seconds) found the waiting host's one
    write stale at its first barrier past bring-up and took it for dead;
    the acceptance test below then lost the wrong host and resumed from
    scratch (PR 44)."""
    monkeypatch.setenv("MXTPU_FLEET_HEARTBEAT_S", "0.05")
    monkeypatch.setenv("MXTPU_FLEET_HEARTBEAT_MISS", "3")
    board = str(tmp_path / "b")
    out = {}

    def bring(rankid):
        out[rankid] = fleet.init(fleet_dir=board, num_processes=2,
                                 process_id=rankid, timeout_s=60.0)
    early = threading.Thread(target=bring, args=(0,))
    early.start()
    late = FleetMembership(board, 1, 2)
    deadline = time.time() + 30.0
    while 0 not in late.view() and time.time() < deadline:
        time.sleep(0.01)
    first = late.view()[0]["t"]
    time.sleep(0.6)             # four times the staleness bound of 0.15 s
    assert late.view()[0]["t"] > first      # it went on heartbeating
    assert 0 not in late.dead_hosts()
    bring(1)
    early.join(timeout=30.0)
    assert sorted(out) == [0, 1]
    # past bring-up the late host does not take the early one for dead
    assert out[1].check(step=0) == []
    for f in out.values():
        f.leave()


def test_rejoin_stall_fault_exits_dedicated_code(tmp_path, monkeypatch):
    """Fault kind rejoin_stall@rank: the host publishes "stalled" on the
    board (its peers' deadline names it) and dies EXIT_REJOIN_STALL."""
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "rejoin_stall@1")

    def fake_exit(code):
        raise _Exit(code)
    monkeypatch.setattr(os, "_exit", fake_exit)
    with pytest.raises(_Exit) as ei:
        fleet.init(fleet_dir=str(tmp_path), num_processes=2, process_id=1,
                   timeout_s=1.0, _stall=lambda: None, heartbeat=False)
    assert ei.value.code == EXIT_REJOIN_STALL
    view = FleetMembership(tmp_path, 1, 2).view()
    assert view[1]["status"] == "stalled"


def test_maybe_host_loss_exits_41(monkeypatch):
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "host_loss@2")

    def fake_exit(code):
        raise _Exit(code)
    monkeypatch.setattr(os, "_exit", fake_exit)
    fleet.maybe_host_loss(0)
    fleet.maybe_host_loss(1)
    with pytest.raises(_Exit) as ei:
        fleet.maybe_host_loss(2)
    assert ei.value.code == EXIT_HOST_LOSS


# ------------------------------------------------- collective watchdog
def test_fleet_watchdog_trip_diagnoses_and_poisons(tmp_path, monkeypatch):
    art = tmp_path / "flight"
    art.mkdir()
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(art))
    clk = FakeClock()
    m0 = FleetMembership(tmp_path / "b", 0, 2, clock=clk)
    m1 = FleetMembership(tmp_path / "b", 1, 2, clock=clk)
    m1.write("up")
    exits = []
    wd = FleetCollectiveWatchdog(membership=m0, timeout_s=10.0, clock=clk,
                                 exit_on_trip=True, exit_fn=exits.append)
    e = wd.arm(7, what="step barrier")
    clk.advance(5.0)
    wd.disarm(e)  # in-bound: no trip
    wd.arm(8, what="step barrier")
    clk.advance(60.0)  # past the fixed deadline; peer 1 is stale too
    m0.write("up")
    with pytest.raises(FleetWedgeError, match="step 8 wedged"):
        wd.poll()
    assert exits == [EXIT_FLEET_WEDGE]
    assert _counter("fleet.wedges") == 1
    arts = _artifacts(art, "fleet_collective_wedge")
    assert len(arts) == 1
    snap = json.load(open(arts[0]))
    assert snap["extra"]["step"] == 8
    assert snap["extra"]["diagnosis"]["dead"] == [1]  # the diagnosis rode
    # the watchdog is poisoned: the next arm on this (dead) fleet refuses
    with pytest.raises(FleetWedgeError):
        wd.arm(9)


def test_fleet_watchdog_disabled_at_zero_timeout():
    wd = FleetCollectiveWatchdog(timeout_s=0)
    assert wd.arm(0) is None
    wd.disarm(None)
    wd.poll()  # never trips
    assert wd.start_monitor() is wd  # no thread either
    assert wd._monitor is None


def test_fleet_watchdog_monitor_lifecycle():
    wd = FleetCollectiveWatchdog(timeout_s=100.0)
    assert wd.start_monitor(0.01) is wd
    assert wd.start_monitor(0.01) is wd  # idempotent
    assert wd._monitor is not None and wd._monitor.is_alive()
    wd.stop_monitor()
    assert wd._monitor is None


# ----------------------------------------------------------- step barrier
def _peer_barrier_file(board, name, rank, payload):
    bdir = os.path.join(str(board), "barrier_%s" % name)
    os.makedirs(bdir, exist_ok=True)
    fleet._atomic_write(os.path.join(bdir, "host_%d" % rank),
                        json.dumps({"rank": rank, "payload": payload}))


def test_step_barrier_fingerprint_green_and_divergent(tmp_path,
                                                      monkeypatch):
    art = tmp_path / "flight"
    art.mkdir()
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(art))
    clk = FakeClock()
    board = tmp_path / "b"
    m0 = FleetMembership(board, 0, 2, clock=clk)
    FleetMembership(board, 1, 2, clock=clk).write("up")
    f = Fleet(0, 2, membership=m0, fleet_dir=str(board))
    # green: identical fingerprints on both hosts
    _peer_barrier_file(board, "step_3", 1, [1.5, 2.0])
    fps = f.step_barrier(3, fingerprint=[1.5, 2.0])
    assert fps == {0: [1.5, 2.0], 1: [1.5, 2.0]}
    assert _counter("resilience.divergence_checks") == 1
    # divergent: a forked replica fails the consistency gate LOUD
    _peer_barrier_file(board, "step_4", 1, [1.5, 999.0])
    with pytest.raises(resilience.DivergenceError, match="step 4"):
        f.step_barrier(4, fingerprint=[1.5, 2.0])
    arts = _artifacts(art, "fleet_divergence")
    assert len(arts) == 1
    snap = json.load(open(arts[0]))
    assert snap["extra"]["fingerprints"]["1"] == [1.5, 999.0]


def test_step_barrier_dead_peer_wedges_loud(tmp_path, monkeypatch):
    art = tmp_path / "flight"
    art.mkdir()
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(art))
    clk = FakeClock()
    board = tmp_path / "b"
    m0 = FleetMembership(board, 0, 2, clock=clk)
    FleetMembership(board, 1, 2, clock=clk).write("up")
    clk.advance(50.0)  # peer dies before reaching the step barrier
    m0.write("up")
    f = Fleet(0, 2, membership=m0, fleet_dir=str(board))
    with pytest.raises(FleetWedgeError, match="died while the fleet"):
        f.step_barrier(5, fingerprint=[1.0])
    assert _counter("fleet.wedges") == 1
    assert len(_artifacts(art, "fleet_collective_wedge")) == 1


# ------------------------------------------------------- fleet supervisor
def _supervisor(script, worlds, latest, **kw):
    """A FleetSupervisor wired subprocess- and sleep-free: ``script`` maps
    generation -> {rank: (rc, tail)}, ``worlds`` pins the expected world
    size per generation, ``latest`` is the checkpoint-step sequence."""
    seen = []
    latest_it = iter(latest)

    def launch(world, generation, extra_env):
        assert world == worlds[generation], (world, generation)
        seen.append(generation)
        return dict(script[generation])
    sup = FleetSupervisor(
        command_for=lambda r, w, g: ["unused"], launch=launch,
        clock=FakeClock(), sleeper=lambda s: None, rng=random.Random(0),
        latest_fn=lambda: next(latest_it), **kw)
    sup._seen = seen
    return sup


def test_supervisor_elastic_loss_then_warm_rejoin():
    """The scripted ISSUE-18 arc: gen0 loses host 1 (exit 41) and host 0
    wedges as its victim (exit 42) -> relaunch on world 1 -> gen1 crashes
    WITH progress -> grow back to full size -> gen2 exits clean."""
    sup = _supervisor(
        {0: {0: (EXIT_FLEET_WEDGE, ""), 1: (EXIT_HOST_LOSS, "")},
         1: {0: (EXIT_HOST_LOSS, "")},
         2: {0: (0, "ok"), 1: (0, "ok")}},
        worlds={0: 2, 1: 1, 2: 2},
        # _latest() is read at each launch AND after each crash:
        # gen0 launch None, gen0 crash 5, gen1 launch 5, gen1 crash 7
        # (progress!), gen2 launch 7
        latest=[None, 5, 5, 7, 7],
        num_hosts=2, min_hosts=1)
    results = sup.run()
    assert results == {0: (0, "ok"), 1: (0, "ok")}
    events = [h["event"] for h in sup.history]
    assert events == ["launch", "crash", "host_loss", "launch", "crash",
                      "rejoin_attempt", "launch", "clean_exit"]
    loss = next(h for h in sup.history if h["event"] == "host_loss")
    assert loss["ranks"] == [1] and loss["world"] == 1
    rejoin = next(h for h in sup.history if h["event"] == "rejoin_attempt")
    assert rejoin["world"] == 2 and rejoin["ckpt_step"] == 7
    assert sup.restarts == 2
    assert _counter("supervisor.restarts") == 2


def test_supervisor_all_victims_still_shrinks():
    """Every failure a wedge with nobody owning the death: the highest
    victim is treated as lost so the fleet cannot flap at a size that
    can never work."""
    sup = _supervisor(
        {0: {0: (EXIT_FLEET_WEDGE, ""), 1: ("timeout", "")},
         1: {0: (0, "")}},
        worlds={0: 2, 1: 1}, latest=[None, 3, 3],
        num_hosts=2, min_hosts=1)
    sup.run()
    loss = next(h for h in sup.history if h["event"] == "host_loss")
    assert loss["ranks"] == [1]  # the highest-ranked victim
    crash = next(h for h in sup.history if h["event"] == "crash")
    assert crash["victims"] == [0] and crash["lost"] == [1]


def test_supervisor_poison_crash_refuses_with_artifact(tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    sup = _supervisor(
        {0: {0: (1, "")}, 1: {0: (1, "")}},
        worlds={0: 1, 1: 1}, latest=[3, 3, 3, 3],
        num_hosts=1)
    with pytest.raises(resilience.SupervisorRefusal, match="poison-crash"):
        sup.run()
    arts = _artifacts(tmp_path, "supervisor_refusal")
    assert len(arts) == 1
    snap = json.load(open(arts[0]))
    # the artifact carries the full membership-event history
    events = [h["event"] for h in snap["extra"]["history"]]
    assert events == ["launch", "crash", "launch", "crash"]
    assert "poison-crash" in snap["extra"]["diagnosis"]


def test_supervisor_crash_loop_budget_refuses(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    sup = _supervisor(
        {g: {0: (1, "")} for g in range(4)},
        worlds={g: 1 for g in range(4)},
        latest=[1, 2, 2, 3, 3, 4, 4],  # progress every time: never poison
        num_hosts=1, max_restarts=2)
    with pytest.raises(resilience.SupervisorRefusal, match="crash-loop"):
        sup.run()
    assert sup.restarts == 2
    assert len(_artifacts(tmp_path, "supervisor_refusal")) == 1


def test_launch_round_surfaces_exit_codes_and_timeouts():
    """Real children, hard-bounded: a quick exit surfaces its code and
    tail; a hang is killed and surfaced as "timeout" — never waited on
    unboundedly (the tier-1 budget depends on this)."""
    sup = FleetSupervisor(
        command_for=lambda r, w, g: [
            sys.executable, "-c",
            "import sys; print('tail-marker'); sys.exit(7)"],
        num_hosts=1, timeout_s=30.0)
    out = sup.launch_round(1, 0)
    assert out[0][0] == 7 and "tail-marker" in out[0][1]
    sup2 = FleetSupervisor(
        command_for=lambda r, w, g: [
            sys.executable, "-c", "import time; time.sleep(60)"],
        num_hosts=1, timeout_s=1.5)
    out2 = sup2.launch_round(1, 0)
    assert out2[0][0] == "timeout"


def test_launch_round_exports_env_bootstrap(tmp_path):
    """Children get the standard bootstrap: rank/world/coordinator plus a
    FRESH per-generation fleet board dir."""
    prog = ("import json, os; print('ENV ' + json.dumps("
            "{k: os.environ.get(k) for k in ('MXTPU_PROCESS_ID',"
            "'MXTPU_NUM_PROCESSES', 'MXTPU_COORDINATOR',"
            "'MXTPU_FLEET_DIR', 'EXTRA_MARK')}))")
    sup = FleetSupervisor(
        command_for=lambda r, w, g: [sys.executable, "-c", prog],
        num_hosts=2, fleet_dir=str(tmp_path / "board"), timeout_s=30.0,
        env_for=lambda r, w, g: {"EXTRA_MARK": "r%d" % r})
    out = sup.launch_round(2, 3)
    envs = {}
    for rank, (rc, tail) in out.items():
        assert rc == 0, tail
        envs[rank] = json.loads(
            [ln for ln in tail.splitlines()
             if ln.startswith("ENV ")][0][4:])
    assert envs[0]["MXTPU_PROCESS_ID"] == "0"
    assert envs[1]["MXTPU_PROCESS_ID"] == "1"
    assert envs[0]["MXTPU_NUM_PROCESSES"] == "2"
    assert envs[0]["MXTPU_COORDINATOR"] == envs[1]["MXTPU_COORDINATOR"]
    assert envs[0]["MXTPU_FLEET_DIR"].endswith("gen_3")
    assert envs[1]["EXTRA_MARK"] == "r1"


# --------------------------------------- bounded multi-process acceptance
@pytest.mark.multidevice
def test_fleet_kill_one_host_restore_acceptance(tmp_path):
    """ISSUE-18 acceptance, the bounded tier-1 spelling: a 2-host fleet
    loses host 1 mid-run (injected host_loss@1, exit 41), the survivor
    exits LOUD (42, diagnosed off the board), and the reshaped 1-host
    generation restores the last intact checkpoint and finishes clean —
    resuming at the kill step, never from scratch. Children carry hard
    timeouts; the full run is bounded by them."""
    worker = os.path.join(REPO, "tools", "fleet_worker.py")
    ckpt = str(tmp_path / "ckpt")
    steps = 3

    def command_for(rank, world, generation):
        return [sys.executable, worker, "--ckpt-dir", ckpt,
                "--steps", str(steps), "--devices", "1"]

    def env_for(rank, world, generation):
        env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=1",
               "MXTPU_FLEET_COLLECTIVE_TIMEOUT_S": "30"}
        if generation == 0 and rank == 1:
            env["MXTPU_FAULT_INJECT"] = "host_loss@1"
        return env

    sup = FleetSupervisor(
        command_for=command_for, num_hosts=2, min_hosts=1,
        ckpt_dir=ckpt, fleet_dir=str(tmp_path / "board"),
        timeout_s=240.0, env_for=env_for,
        sleeper=lambda s: None, rng=random.Random(0))
    results = sup.run()
    events = [h["event"] for h in sup.history]
    assert events[:3] == ["launch", "crash", "host_loss"], sup.history
    assert events[-1] == "clean_exit"
    crash = next(h for h in sup.history if h["event"] == "crash")
    assert crash["lost"] == [1], crash  # the injected death, exit 41
    assert crash["exit_codes"]["0"] in (EXIT_FLEET_WEDGE, "timeout"), crash
    # the surviving generation ran on the reshaped world and RESUMED
    assert sorted(results) == [0]
    rc, tail = results[0]
    assert rc == 0, tail
    rec = json.loads([ln for ln in tail.splitlines()
                      if ln.startswith("RESULT ")][0][len("RESULT "):])
    assert rec["world"] == 1
    assert rec["start"] >= 1, rec  # restored, not from scratch
    assert len(rec["losses"]) == steps - rec["start"]
    assert rec["divergence_checks"] >= 1  # the sentinel stayed armed
