"""Elastic multi-host fleet training: bring-up, failure detection, rejoin.

The reference's production layer is the dist kvstore over ps-lite
(src/kvstore/kvstore_dist.h): a scheduler process rendezvouses workers
and servers, and a lost worker simply hangs the Van's TCP connections
until an operator notices. This module is the TPU-native rebuild of that
layer (ROADMAP item 3), made *elastic* — runs survive hardware churn:

* **Coordinated bring-up** — :func:`init` wraps
  ``mxtpu.distributed.init`` (→ ``jax.distributed.initialize``) with
  bounded-retry/backoff connection handling and a DEADLINE on the whole
  join (connect + barrier): a host that never shows up fails the
  bring-up LOUD with per-host status read off the fleet's filesystem
  status board, instead of every healthy host hanging forever inside a
  collective. Per-host data sharding rides the PR 9
  ``shard_keys``/``ShardedRecordReader`` determinism
  (:meth:`Fleet.data_shard`), and :meth:`Fleet.mesh` spans the global
  device set for ``gluon.Trainer(mesh=)``.
* **Failure detection** — :class:`FleetMembership` keeps a per-host
  heartbeat board on the shared fleet directory (the same shared-disk
  assumption checkpoints already make). A host whose heartbeat goes
  stale is diagnosed dead; a dead COORDINATOR (host 0 — the
  jax.distributed rendezvous service lives in that process) raises
  :class:`FleetWedgeError` with the membership view instead of an
  infinite collective hang. :class:`FleetCollectiveWatchdog` generalizes
  the PR 14 step-wedge watchdog to fleet collectives: a step blocked in
  a dead collective trips off-thread, dumps
  ``flight_record("fleet_collective_wedge")`` with the membership
  diagnosis, and (``exit_on_trip``) exits the process loud — the monitor
  cannot raise into a thread wedged inside a device call, so the
  artifact + exit code IS the loud failure.
* **Tiered restore + warm rejoin** — :class:`FleetSupervisor` is
  ``TrainSupervisor``'s fleet mode: per-host child processes with HARD
  timeouts and exit-code surfacing, membership-change events in
  ``history``, and the same poison-crash refusal discipline fleet-wide
  (refusals dump ``flight_record("supervisor_refusal")``). On a lost
  host the next generation launches on the surviving N−1 hosts; the
  child's ``ResilientLoop.resume`` restores the last intact checkpoint
  onto the RESHAPED mesh (orbax re-reads with live shardings and the
  ``MeshPlan`` re-places ZeRO-1 optimizer state), with the divergence
  sentinel as the cross-host consistency gate after restore. Once a
  reshaped generation shows checkpoint progress, the supervisor grows
  the fleet back to full size — the replacement host's rejoin is a
  zero-compile event via the compile-service disk cache
  (``MXTPU_COMPILE_CACHE_DIR``; gated in ``bench.py fleet_resume``).

Fault kinds ``host_loss@step`` (sudden host death — ``os._exit`` before
the step's collective), ``coordinator_loss`` (the membership probe sees
host 0 stale) and ``rejoin_stall`` (a joining host stalls inside
bring-up so its peers' deadline trips) ride ``resilience.inject``, so
the whole matrix runs deterministically in tier-1 via 2-process
fixtures and fake clocks. See docs/resilience.md (degradation matrix)
and docs/parallelism.md (multi-host section).
"""
from __future__ import annotations

import json
import os
import threading
import time

from .base import MXNetError

__all__ = ["Fleet", "FleetBringupError", "FleetWedgeError",
           "FleetMembership", "FleetCollectiveWatchdog", "FleetSupervisor",
           "init", "maybe_host_loss", "EXIT_HOST_LOSS", "EXIT_FLEET_WEDGE",
           "EXIT_REJOIN_STALL"]

import logging

_log = logging.getLogger("mxtpu.fleet")

# Exit codes the supervisor tier pattern-matches on: a sudden host death
# (injected or real SIGKILL-analog), a collective-wedge loud exit, and a
# bring-up stall — all distinct from ordinary crashes so membership events
# in FleetSupervisor.history carry the right diagnosis.
EXIT_HOST_LOSS = 41
EXIT_FLEET_WEDGE = 42
EXIT_REJOIN_STALL = 43


# ------------------------------------------------------------------ policies
def connect_retries():
    """Bring-up connection retry budget (MXTPU_FLEET_CONNECT_RETRIES,
    default 4): how many times :func:`init` re-attempts the
    jax.distributed join before the bring-up fails. Host-side control
    flow — nothing traced."""
    return int(os.environ.get("MXTPU_FLEET_CONNECT_RETRIES", "4"))  # graftlint: disable=policy-key-coverage


def connect_backoff_s():
    """Initial connect-retry backoff (MXTPU_FLEET_CONNECT_BACKOFF_S,
    default 1.0); later waits use decorrelated jitter
    (``resilience._next_backoff``) so a fleet re-joining a restarted
    coordinator cannot stampede it. Host-side — nothing traced."""
    return float(os.environ.get("MXTPU_FLEET_CONNECT_BACKOFF_S", "1.0"))  # graftlint: disable=policy-key-coverage


def bringup_timeout_s():
    """Deadline on the WHOLE bring-up — connect retries plus the
    rendezvous barrier (MXTPU_FLEET_BRINGUP_TIMEOUT_S, default 300 s).
    Past it :func:`init` raises :class:`FleetBringupError` carrying the
    per-host status board instead of hanging in the collective forever.
    Host-side deadline policy — nothing traced."""
    return float(os.environ.get("MXTPU_FLEET_BRINGUP_TIMEOUT_S", "300"))  # graftlint: disable=policy-key-coverage


def heartbeat_s():
    """Heartbeat write cadence on the fleet status board
    (MXTPU_FLEET_HEARTBEAT_S, default 2.0 s). Host-side — nothing
    traced."""
    return float(os.environ.get("MXTPU_FLEET_HEARTBEAT_S", "2.0"))  # graftlint: disable=policy-key-coverage


def heartbeat_miss():
    """Missed-heartbeat threshold (MXTPU_FLEET_HEARTBEAT_MISS, default
    3): a host whose newest heartbeat is older than ``miss × cadence``
    is diagnosed dead by :meth:`FleetMembership.dead_hosts`. Host-side —
    nothing traced."""
    return int(os.environ.get("MXTPU_FLEET_HEARTBEAT_MISS", "3"))  # graftlint: disable=policy-key-coverage


def collective_timeout_s():
    """Fleet collective-wedge bound (MXTPU_FLEET_COLLECTIVE_TIMEOUT_S,
    default 0 = off): a fleet step still armed past this many seconds
    trips :class:`FleetCollectiveWatchdog` — flight artifact with the
    membership diagnosis, then a loud failure. A FIXED bound (not the
    step watchdog's rolling baseline): a dead peer wedges the FIRST
    post-loss collective, long before any baseline exists on the new
    membership. Host-side deadline policy — nothing traced."""
    return float(os.environ.get("MXTPU_FLEET_COLLECTIVE_TIMEOUT_S", "0") or "0")  # graftlint: disable=policy-key-coverage


def child_timeout_s():
    """Per-child hard timeout in :meth:`FleetSupervisor.launch_round`
    (MXTPU_FLEET_CHILD_TIMEOUT_S, default 600 s): a hung child (dead
    collective, stalled rejoin) is killed and surfaced as ``"timeout"``
    instead of wedging the supervisor — and, in tier-1, the test suite.
    Host-side — nothing traced."""
    return float(os.environ.get("MXTPU_FLEET_CHILD_TIMEOUT_S", "600"))  # graftlint: disable=policy-key-coverage


class FleetBringupError(MXNetError):
    """The coordinated bring-up missed its deadline (or spent its connect
    retries): at least one host never joined. The message carries the
    per-host status board — who checked in, who is still connecting, who
    was never heard from — so the operator fixes the right host instead
    of staring at a hung collective."""


class FleetWedgeError(MXNetError):
    """A fleet collective wedged (a step blocked past the fleet bound) or
    the coordinator stopped heartbeating. By the time this raises, the
    flight artifact (``fleet_collective_wedge`` / ``coordinator_loss``)
    with the membership diagnosis is already on disk."""


# ------------------------------------------------------------ status board
def _atomic_write(path, payload):
    # a name of the writer's own: a host's heartbeat thread and its main
    # thread (``check``) write the same file, and with one name a process
    # the second ``os.replace`` found its file already moved
    tmp = "%s.%d.%d.tmp" % (path, os.getpid(), threading.get_ident())
    with open(tmp, "w") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class FleetMembership:
    """Per-host heartbeat/status board on the shared fleet directory.

    Each host owns ONE file (``host_<rank>.json``) it rewrites
    atomically: status (``connecting`` → ``up`` → ``left``), a heartbeat
    timestamp, pid and the newest training step. Readers never block on
    a peer — liveness is file age, the same shared-disk trust model the
    checkpoint directory already relies on. ``clock`` is injectable so
    the whole staleness matrix tests sleep-free; the heartbeat timestamp
    uses the SAME clock, so fake-clock tests control both sides."""

    def __init__(self, fleet_dir, rank, num_hosts, clock=None):
        self.fleet_dir = str(fleet_dir)
        self.rank = int(rank)
        self.num_hosts = int(num_hosts)
        self._clock = time.time if clock is None else clock
        self._hb_thread = None
        self._hb_stop = None
        self.step = None
        os.makedirs(self.fleet_dir, exist_ok=True)

    def _path(self, rank):
        return os.path.join(self.fleet_dir, "host_%d.json" % rank)

    def write(self, status, step=None):
        """Publish this host's status (atomic rewrite of its board file)."""
        if step is not None:
            self.step = int(step)
        _atomic_write(self._path(self.rank), json.dumps(
            {"rank": self.rank, "status": status, "t": self._clock(),
             "pid": os.getpid(), "step": self.step}))

    def view(self):
        """{rank: record} for every host file present (a host never heard
        from simply has no entry — :meth:`dead_hosts` reports those too)."""
        out = {}
        for r in range(self.num_hosts):
            try:
                with open(self._path(r)) as f:
                    out[r] = json.load(f)
            except Exception:  # noqa: BLE001 — absent/torn file: not seen
                continue
        return out

    def describe(self, view=None):
        """One status line per host — the diagnosis text bring-up and
        wedge errors carry."""
        view = self.view() if view is None else view
        now = self._clock()
        lines = []
        for r in range(self.num_hosts):
            rec = view.get(r)
            if rec is None:
                lines.append("host %d: NEVER SEEN (no status file)" % r)
            else:
                lines.append(
                    "host %d: %s, heartbeat %.1fs ago (pid %s, step %s)"
                    % (r, rec.get("status"), now - rec.get("t", 0.0),
                       rec.get("pid"), rec.get("step")))
        return "; ".join(lines)

    def dead_hosts(self):
        """Ranks diagnosed dead: never seen, or heartbeat older than
        ``heartbeat_s() * heartbeat_miss()`` without a clean ``left``."""
        bound = heartbeat_s() * heartbeat_miss()
        now = self._clock()
        view = self.view()
        dead = []
        for r in range(self.num_hosts):
            rec = view.get(r)
            if rec is None:
                dead.append(r)
            elif rec.get("status") != "left" and \
                    now - rec.get("t", 0.0) > bound:
                dead.append(r)
        return dead

    def coordinator_alive(self):
        return 0 not in self.dead_hosts()

    def check(self, step=None):
        """Membership probe for the training loop / watchdog tier: writes
        this host's heartbeat, returns the dead-host list. A dead
        COORDINATOR is special-cased into a loud
        :class:`FleetWedgeError` — jax.distributed's rendezvous service
        lives in host 0, so once it is gone every later barrier or
        compile-cache coordination would hang, not error. Fault kind
        ``coordinator_loss`` forces that diagnosis deterministically."""
        from . import resilience, telemetry
        self.write("up", step=step)
        dead = self.dead_hosts()
        if resilience.inject("coordinator_loss") and 0 not in dead:
            dead.insert(0, 0)
        if 0 in dead and self.rank != 0:
            view = self.view()
            telemetry.flight_record(
                "coordinator_loss",
                extra={"rank": self.rank, "step": step, "dead": dead,
                       "view": view})
            raise FleetWedgeError(
                "fleet coordinator (host 0) stopped heartbeating — the "
                "jax.distributed rendezvous lives in that process, so "
                "collectives would hang forever, not error. Board: %s. "
                "Flight artifact dumped (reason=coordinator_loss); the "
                "supervisor tier restores onto a re-coordinated fleet."
                % self.describe(view))
        return dead

    # -------------------------------------------------------------- barrier
    def barrier(self, name, timeout_s, payload=None, clock=None,
                sleeper=None, poll_s=0.05, fail_on_dead=True):
        """Filesystem rendezvous on the status board: every host drops
        ``barrier_<name>/host_<rank>`` and polls for the full set under a
        deadline. This is the fleet's control-plane barrier — it works on
        every backend (XLA:CPU cannot run cross-process collectives at
        all, so a device-collective barrier is not portable) and it fails
        DIAGNOSABLY: a peer whose heartbeat went stale mid-wait fails the
        barrier as soon as it is diagnosed dead (``fail_on_dead``) rather
        than at the full deadline, and the raised
        :class:`FleetWedgeError` carries the board. A host that was never
        seen only fails at the deadline — during bring-up "not arrived
        yet" is not "dead". Returns ``{rank: payload}`` of every host's
        barrier payload (the cross-host divergence gate compares
        fingerprints through exactly this)."""
        clock = self._clock if clock is None else clock
        bdir = os.path.join(self.fleet_dir, "barrier_%s" % name)
        os.makedirs(bdir, exist_ok=True)
        mine = os.path.join(bdir, "host_%d" % self.rank)
        _atomic_write(mine, json.dumps({"rank": self.rank,
                                        "payload": payload}))
        deadline = clock() + float(timeout_s)
        while True:
            seen = {}
            for r in range(self.num_hosts):
                try:
                    with open(os.path.join(bdir, "host_%d" % r)) as f:
                        seen[r] = json.load(f).get("payload")
                except Exception:  # noqa: BLE001 — absent/torn: not there
                    continue
            if len(seen) == self.num_hosts:
                return seen
            if fail_on_dead:
                # only STALE hosts (file present, heartbeat old) fail the
                # wait early — dead_hosts() also lists never-seen ranks,
                # which here just have not arrived yet
                view = self.view()
                stale = [r for r in self.dead_hosts()
                         if r in view and r not in seen]
                if stale:
                    raise FleetWedgeError(
                        "fleet barrier %r: host(s) %s died while the "
                        "fleet waited (%d/%d arrived). Board: %s"
                        % (name, stale, len(seen), self.num_hosts,
                           self.describe(view)))
            if clock() > deadline:
                raise FleetWedgeError(
                    "fleet barrier %r missed its %.0fs deadline: %d/%d "
                    "hosts arrived (missing %s). Board: %s"
                    % (name, float(timeout_s), len(seen), self.num_hosts,
                       sorted(set(range(self.num_hosts)) - set(seen)),
                       self.describe()))
            if sleeper is None:
                time.sleep(poll_s)
            else:
                sleeper(poll_s)

    # ------------------------------------------------------------ heartbeat
    def start_heartbeat(self, interval_s=None):
        """Off-thread heartbeat writer (idempotent); fake-clock tests call
        :meth:`write` directly instead."""
        if self._hb_thread is not None and self._hb_thread.is_alive():
            return self
        interval_s = heartbeat_s() if interval_s is None else interval_s
        stop = threading.Event()

        def loop():
            while not stop.wait(interval_s):
                try:
                    self.write("up")
                except Exception:  # noqa: BLE001 — a flaky disk must not
                    pass           # kill the heartbeat thread
        t = threading.Thread(target=loop, daemon=True,
                             name="mxtpu-fleet-heartbeat")
        self._hb_thread, self._hb_stop = t, stop
        t.start()
        return self

    def stop_heartbeat(self):
        if self._hb_stop is not None:
            self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2.0)
        self._hb_thread = self._hb_stop = None


# ------------------------------------------------------- deadline bring-up
def _run_with_deadline(fn, timeout_s, on_timeout, clock=None, sleeper=None,
                       poll_s=0.05, thread_name="mxtpu-fleet-bringup"):
    """Run a possibly-hanging join step on a daemon thread under a
    deadline. On the deadline, ``on_timeout()`` builds the loud error —
    the stuck thread is abandoned (it is blocked inside a native
    rendezvous call nothing can interrupt; bring-up failure is fatal to
    the process anyway). ``clock``/``sleeper`` injectable → sleep-free
    tier-1."""
    clock = time.monotonic if clock is None else clock
    done = threading.Event()
    box = {}

    def run():
        try:
            box["out"] = fn()
        except BaseException as e:  # noqa: BLE001 — surfaced to the caller
            box["err"] = e
        finally:
            done.set()

    t = threading.Thread(target=run, daemon=True, name=thread_name)
    t.start()
    deadline = clock() + timeout_s
    while not done.is_set():
        if clock() > deadline:
            raise on_timeout()
        if sleeper is None:
            done.wait(poll_s)
        else:
            sleeper(poll_s)
    if "err" in box:
        raise box["err"]
    return box.get("out")


class Fleet:
    """Handle returned by :func:`init`: identity, membership, data
    sharding and the global mesh for one joined host."""

    def __init__(self, rank, num_hosts, membership=None, fleet_dir=None):
        self.rank = int(rank)
        self.num_hosts = int(num_hosts)
        self.membership = membership
        self.fleet_dir = fleet_dir

    def mesh(self, axes=None, devices=None):
        """The device mesh for ``gluon.Trainer(mesh=...)`` /
        ``ShardedTrainStep``. Default is pure data-parallel over all
        global devices — except where the backend cannot run
        process-spanning computations at all
        (``distributed.global_compute_supported()`` is False: XLA:CPU,
        the forced-CPU test tier), where each host gets a mesh over its
        OWN devices and cross-host coupling rides the fleet board
        (:meth:`step_barrier`) instead of device collectives."""
        import jax

        from . import distributed
        from .parallel import make_mesh
        if devices is None:
            if distributed.global_compute_supported():
                devices = jax.devices()
            else:
                devices = jax.local_devices()
                _log.info(
                    "fleet mesh: backend %r cannot span processes — "
                    "per-host local mesh over %d device(s), board-"
                    "coupled", jax.default_backend(), len(devices))
        return make_mesh({"data": -1} if axes is None else axes, devices)

    def data_shard(self, keys, epoch=0, seed=0, shuffle=True):
        """This host's deterministic slice of ``keys`` — PR 9
        ``shard_keys``: disjoint per-host shards whose union is exactly
        ``keys``, a pure function of ``(seed, epoch, rank, world)``, so
        a reshaped fleet re-derives balanced shards with no exchange."""
        from .io.stream import shard_keys
        return shard_keys(keys, num_shards=self.num_hosts,
                          shard_index=self.rank, epoch=epoch, seed=seed,
                          shuffle=shuffle)

    def reader(self, rec_path, **kwargs):
        """A ``ShardedRecordReader`` over this host's shard (the PR 9
        deterministic per-replica stream, fleet-wired)."""
        from .io.stream import ShardedRecordReader
        return ShardedRecordReader(rec_path, num_shards=self.num_hosts,
                                   shard_index=self.rank, **kwargs)

    def watchdog(self, timeout_s=None, clock=None, exit_on_trip=False,
                 exit_fn=None):
        """A :class:`FleetCollectiveWatchdog` wired to this fleet's
        membership view."""
        return FleetCollectiveWatchdog(
            membership=self.membership, timeout_s=timeout_s, clock=clock,
            exit_on_trip=exit_on_trip, exit_fn=exit_fn)

    def check(self, step=None):
        """Heartbeat + membership probe (see
        :meth:`FleetMembership.check`); no-op without a fleet dir."""
        if self.membership is None:
            return []
        return self.membership.check(step=step)

    def barrier(self, name="mxtpu_fleet", timeout_s=None, payload=None):
        """Fleet-wide rendezvous. With a membership board this is the
        filesystem barrier (portable, deadline-bounded, diagnosable —
        see :meth:`FleetMembership.barrier`); without one it degrades to
        the device-collective ``distributed.barrier`` (unbounded, but
        the only rendezvous there is). Returns ``{rank: payload}`` on
        the board path, None otherwise."""
        if self.membership is not None:
            if timeout_s is None:
                timeout_s = collective_timeout_s() or bringup_timeout_s()
            return self.membership.barrier(name, timeout_s,
                                           payload=payload)
        from . import distributed
        distributed.barrier(name)
        return None

    def step_barrier(self, step, fingerprint=None, obs=None):
        """Per-step cross-host coupling on the board: every host must
        finish step ``step`` within the fleet collective bound or the
        survivors fail LOUD (a dead peer is diagnosed off its stale
        heartbeat — the portable spelling of "the collective wedged").
        ``fingerprint`` (the divergence sentinel's update fingerprint)
        rides the barrier payload, and a cross-host mismatch — replicas
        whose states silently diverged — trips the same wedge path: the
        flight artifact carries every host's fingerprint. ``obs`` (a
        dict, e.g. ``{"trace": trace_id, "stages": {...}}``) upgrades
        the payload to the ISSUE-19 stitched form — fingerprint under
        ``"fp"``, plus this host's step trace id, stage breakdown, and
        barrier-arrival timestamp ``"t"`` — which fleet_obs' straggler
        sentinel and ``telemetry_report --fleet`` consume. Without
        ``obs`` the payload stays the bare fingerprint list (board
        compatibility with ISSUE-18 peers). No-op without a membership
        board."""
        if self.membership is None:
            return None
        from . import telemetry
        bound = collective_timeout_s() or bringup_timeout_s()
        payload = None if fingerprint is None else list(fingerprint)
        if obs is not None:
            payload = dict(obs)
            payload["fp"] = None if fingerprint is None else list(fingerprint)
            payload.setdefault("t", self.membership._clock())
        try:
            fps = self.membership.barrier(
                "step_%d" % int(step), bound, payload=payload)
        except FleetWedgeError:
            telemetry.inc("fleet.wedges")
            telemetry.flight_record(
                "fleet_collective_wedge",
                extra={"step": int(step), "what": "step barrier",
                       "diagnosis": {
                           "dead": self.membership.dead_hosts(),
                           "board": self.membership.describe()}})
            raise
        got = {}
        for r, p in fps.items():
            fp = p.get("fp") if isinstance(p, dict) else p
            if fp is not None:
                got[r] = fp
        if got:
            telemetry.inc("resilience.divergence_checks")
        if len(set(map(tuple, got.values()))) > 1:
            telemetry.flight_record(
                "fleet_divergence",
                extra={"step": int(step), "fingerprints": {
                    str(r): p for r, p in got.items()}})
            from .resilience import DivergenceError
            raise DivergenceError(
                "cross-host divergence at step %d: update fingerprints "
                "disagree across hosts (%s) — replicated state is no "
                "longer replicated. Flight artifact dumped "
                "(reason=fleet_divergence)." % (int(step), got))
        return fps

    def leave(self):
        """Clean departure: publish ``left`` (so peers diagnose a planned
        exit, not a death), stop the heartbeat, leave the runtime."""
        from . import distributed
        if self.membership is not None:
            self.membership.stop_heartbeat()
            try:
                self.membership.write("left")
            except Exception:  # noqa: BLE001 — board on a dying disk
                pass
        distributed.shutdown()


def _rendezvous_required():
    """Whether bring-up must join the global jax.distributed runtime.
    TPU/GPU fleets: yes — the rendezvous is what fuses every host's
    devices into one mesh. The forced-CPU tier: no — see the board-only
    branch in :func:`init`. Tests monkeypatch this to drive the
    rendezvous deadline/retry machinery on CPU."""
    import jax
    return jax.default_backend() != "cpu"


def init(fleet_dir=None, coordinator_address=None, num_processes=None,
         process_id=None, local_device_ids=None, timeout_s=None,
         clock=None, sleeper=None, rng=None, heartbeat=True, _stall=None):
    """Coordinated multi-host bring-up; returns a :class:`Fleet`.

    The join (``mxtpu.distributed.init`` under bounded
    retry-with-backoff — ``MXTPU_FLEET_CONNECT_RETRIES`` /
    ``MXTPU_FLEET_CONNECT_BACKOFF_S``, decorrelated jitter) plus the
    rendezvous barrier run under ONE deadline
    (``MXTPU_FLEET_BRINGUP_TIMEOUT_S``): a missing host fails the
    bring-up with :class:`FleetBringupError` carrying per-host status
    from the fleet directory's board, instead of hanging every healthy
    host inside the collective. With ``fleet_dir`` (or
    ``MXTPU_FLEET_DIR``) each host publishes ``connecting`` before the
    blocking join and ``up`` after it, then starts the off-thread
    heartbeat — the board is what bring-up timeouts and the supervisor
    tier diagnose from. ``clock``/``sleeper``/``rng`` are injectable for
    sleep-free tests.

    Fault kind ``rejoin_stall@rank`` makes THIS host stall inside
    bring-up (status ``stalled``, never reaches the barrier): its peers'
    deadline trips with the stalled host named, and the process exits
    ``EXIT_REJOIN_STALL`` once the hold expires — the deterministic
    tier-1 spelling of a replacement host that hangs while rejoining."""
    from . import distributed, resilience, telemetry
    fleet_dir = fleet_dir or os.environ.get("MXTPU_FLEET_DIR")  # graftlint: disable=policy-key-coverage
    timeout_s = bringup_timeout_s() if timeout_s is None else float(timeout_s)
    env_coord, env_n, env_id = distributed._env_config()
    world = num_processes if num_processes is not None else env_n
    rank_hint = process_id if process_id is not None else env_id

    mem = None
    if fleet_dir is not None and world is not None and rank_hint is not None:
        mem = FleetMembership(fleet_dir, rank_hint, world, clock=clock)
        mem.write("connecting")

    if resilience.inject("rejoin_stall", rank_hint):
        # the stalled-rejoin simulation: publish the diagnosis, hold past
        # every peer's deadline, then die with the dedicated exit code
        # (the supervisor's child hard-timeout is the outer backstop)
        if mem is not None:
            mem.write("stalled")
        hold = _stall if _stall is not None else (
            lambda: time.sleep(2.0 * timeout_s))
        hold()
        os._exit(EXIT_REJOIN_STALL)

    def on_timeout():
        board = mem.describe() if mem is not None else \
            "no fleet_dir: per-host status unavailable (pass fleet_dir= " \
            "or set MXTPU_FLEET_DIR for a shared status board)"
        telemetry.flight_record(
            "fleet_bringup_timeout",
            extra={"rank": rank_hint, "world": world,
                   "timeout_s": timeout_s,
                   "view": mem.view() if mem is not None else None})
        return FleetBringupError(
            "fleet bring-up missed its %.0fs deadline "
            "(MXTPU_FLEET_BRINGUP_TIMEOUT_S): at least one host never "
            "joined the rendezvous. Board: %s. Flight artifact dumped "
            "(reason=fleet_bringup_timeout)." % (timeout_s, board))

    def join():
        return resilience.with_retries(
            lambda: distributed.init(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id,
                local_device_ids=local_device_ids),
            "fleet join (rank %s)" % rank_hint,
            retries=connect_retries(), backoff=connect_backoff_s(),
            metric="retry.fleet_connect", sleeper=sleeper, rng=rng,
            logger=_log)

    if mem is not None and not _rendezvous_required():
        # board-only bring-up (forced-CPU tier): joining the global jax
        # runtime there buys nothing (XLA:CPU cannot run a
        # process-spanning computation) and actively poisons the
        # compile cache — global device ids bake the host rank into
        # every serialized executable (a blob host 0 spilled names
        # device 0, which host 1 cannot even address), killing the
        # warm-rejoin zero-compile path. Each host stays its own
        # single-process jax world; membership, barriers, and the
        # divergence gate all ride the board.
        rank, world = int(rank_hint), int(world)
    else:
        rank, world = _run_with_deadline(join, timeout_s, on_timeout,
                                         clock=clock, sleeper=sleeper)
    if mem is None and fleet_dir is not None:
        mem = FleetMembership(fleet_dir, rank, world, clock=clock)
    if mem is not None:
        mem.rank, mem.num_hosts = rank, world  # autodetected identity wins
        mem.write("up")
        if heartbeat:
            # BEFORE the bring-up barrier: a host that waits there for a
            # late peer is alive, and its "up" must not age meanwhile. A
            # peer that arrived more than heartbeat_s() * heartbeat_miss()
            # later found that write stale and, at its first barrier past
            # bring-up, took the waiting host for dead (a start skew of 7 s
            # between two children of one supervisor: PR 44)
            mem.start_heartbeat()
        # board barrier: portable (XLA:CPU cannot run the psum-rendezvous
        # across processes at all), deadline-bounded, and the timeout
        # diagnosis IS the board. fail_on_dead off — during bring-up a
        # host not yet arrived must get the full deadline, not a snap
        # "dead" diagnosis off its missing heartbeat
        try:
            mem.barrier("bringup", timeout_s, clock=clock, sleeper=sleeper,
                        fail_on_dead=False)
        except FleetWedgeError:
            mem.stop_heartbeat()
            raise on_timeout() from None
    else:
        _run_with_deadline(
            lambda: distributed.barrier("mxtpu_fleet_bringup"),
            timeout_s, on_timeout, clock=clock, sleeper=sleeper,
            thread_name="mxtpu-fleet-barrier")
    _log.info("fleet up: rank %d of %d hosts", rank, world)
    return Fleet(rank, world, membership=mem, fleet_dir=fleet_dir)


def maybe_host_loss(step):
    """Fault-injection point for sudden host death (kind
    ``host_loss@step``): the process exits ``EXIT_HOST_LOSS`` via
    ``os._exit`` — no cleanup, no ``left`` status, exactly the shape of
    a preempted/zapped host. Call at the top of the training step so the
    survivors wedge in THAT step's collective (the detection path under
    test). ``inject`` has already flight-recorded the fault when this
    fires."""
    from . import resilience
    if resilience.inject("host_loss", step):
        _log.error("injected host_loss at step %d: exiting %d",
                   step, EXIT_HOST_LOSS)
        os._exit(EXIT_HOST_LOSS)


# ------------------------------------------------- fleet collective watchdog
class FleetCollectiveWatchdog:
    """The PR 14 step-wedge watchdog generalized to fleet collectives.

    Same bracket discipline as ``resilience.TrainStepWatchdog`` — arm
    before the step's dispatch, disarm in its finally — but with a FIXED
    deadline (``MXTPU_FLEET_COLLECTIVE_TIMEOUT_S``): after a host loss
    the very FIRST collective wedges, before any rolling baseline could
    exist for the new membership. A trip consults the membership board
    for the diagnosis (which hosts are dead, is the coordinator among
    them), dumps ``flight_record("fleet_collective_wedge")``, bumps
    ``fleet.wedges`` — and then, because the training thread is blocked
    inside a dead collective no exception can reach, ``exit_on_trip``
    exits the process with ``EXIT_FLEET_WEDGE``: the artifact + exit
    code is the loud failure, and the supervisor tier reads the code as
    a host-level event. Fake-clock ``poll()`` drives the whole matrix
    sleep-free in tier-1."""

    def __init__(self, membership=None, timeout_s=None, clock=None,
                 exit_on_trip=False, exit_fn=None):
        self.membership = membership
        self.timeout_s = collective_timeout_s() if timeout_s is None \
            else float(timeout_s)
        self._clock = time.monotonic if clock is None else clock
        self._exit_on_trip = bool(exit_on_trip)
        self._exit_fn = exit_fn if exit_fn is not None else os._exit
        self._lock = threading.Lock()
        self._entries = []
        self._tripped = None
        self._monitor = None
        self._monitor_stop = None

    def arm(self, step, what="collective"):
        self._check_poisoned()
        if self.timeout_s <= 0:
            return None
        now = self._clock()
        entry = {"step": int(step), "what": what, "t0": now,
                 "deadline": now + self.timeout_s}
        with self._lock:
            self._entries.append(entry)
        return entry

    def disarm(self, entry):
        if entry is None:
            return
        with self._lock:
            if entry in self._entries:
                self._entries.remove(entry)
        self._check_poisoned()

    def poll(self):
        """Synchronous wedge scan (fake-clock test drive): raises
        :class:`FleetWedgeError` on a trip, artifact already written."""
        tripped = self._scan()
        if tripped:
            raise FleetWedgeError(self._describe(tripped[0]))

    def _check_poisoned(self):
        if self._tripped is not None:
            raise FleetWedgeError(self._describe(self._tripped))

    def _diagnosis(self):
        if self.membership is None:
            return {"dead": None, "board": "no membership view attached"}
        try:
            dead = self.membership.dead_hosts()
            return {"dead": dead, "coordinator_dead": 0 in dead,
                    "board": self.membership.describe()}
        except Exception as e:  # noqa: BLE001 — a dead disk still trips
            return {"dead": None, "board": "membership read failed: %s" % e}

    def _describe(self, e):
        diag = self._diagnosis()
        return ("fleet %s at step %d wedged: no completion within %.1fs "
                "(MXTPU_FLEET_COLLECTIVE_TIMEOUT_S); dead hosts: %s — %s. "
                "Flight artifact dumped (reason=fleet_collective_wedge)."
                % (e["what"], e["step"], self.timeout_s, diag.get("dead"),
                   diag.get("board")))

    def _scan(self):
        now = self._clock()
        with self._lock:
            tripped = [e for e in self._entries if now > e["deadline"]]
            for e in tripped:
                self._entries.remove(e)
        for e in tripped:
            self._trip(e, now)
        return tripped

    def _trip(self, e, now):
        from . import telemetry
        self._tripped = e
        telemetry.inc("fleet.wedges")
        diag = self._diagnosis()
        telemetry.flight_record(
            "fleet_collective_wedge",
            extra={"step": e["step"], "what": e["what"],
                   "elapsed_s": now - e["t0"], "bound_s": self.timeout_s,
                   "diagnosis": diag})
        _log.error("%s", self._describe(e))
        if self._exit_on_trip:
            self._exit_fn(EXIT_FLEET_WEDGE)

    def start_monitor(self, interval_s=0.25):
        """Off-thread scan (idempotent) — the production drive. The
        monitor holds the watchdog strongly only via the thread target;
        with ``exit_on_trip`` a trip exits the process from HERE, since
        the training thread is unreachable inside the dead collective."""
        if self.timeout_s <= 0:
            return self
        if self._monitor is not None and self._monitor.is_alive():
            return self
        stop = threading.Event()

        def loop():
            while not stop.wait(interval_s):
                try:
                    self._scan()
                except Exception:  # noqa: BLE001 — scan must never die
                    _log.exception("fleet wedge monitor scan failed")
        t = threading.Thread(target=loop, daemon=True,
                             name="mxtpu-fleet-wedge-monitor")
        self._monitor, self._monitor_stop = t, stop
        t.start()
        return self

    def stop_monitor(self):
        if self._monitor_stop is not None:
            self._monitor_stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=2.0)
        self._monitor = self._monitor_stop = None


# ------------------------------------------------------- fleet supervisor
class FleetSupervisor:
    """``TrainSupervisor``'s fleet mode: one supervisor, N per-host
    children per generation.

    ``command_for(rank, world, generation)`` builds each child's argv;
    :meth:`launch_round` gives every child the standard env bootstrap
    (``MXTPU_PROCESS_ID``/``MXTPU_NUM_PROCESSES``/``MXTPU_COORDINATOR``
    on a fresh port per generation, plus the fleet/checkpoint dirs), a
    HARD per-child timeout (``MXTPU_FLEET_CHILD_TIMEOUT_S`` — a hung
    collective is killed and surfaced as ``"timeout"``, it can never
    wedge the caller), and exit-code surfacing into ``history``.

    :meth:`run` is the elastic respawn loop with TrainSupervisor's
    refusal discipline fleet-wide:

    * a generation where some child died with a HOST-LEVEL signature
      (``EXIT_HOST_LOSS``, ``EXIT_FLEET_WEDGE``, a kill, or a timeout)
      relaunches on the surviving world size — membership event
      ``host_loss`` — and the children's tiered resume restores the last
      intact checkpoint onto the reshaped mesh;
    * a reshaped generation that crashes WITH checkpoint progress grows
      back to full size next launch — membership event
      ``rejoin_attempt`` (the replacement host starts warm off the
      compile-service disk cache);
    * two consecutive failed generations at the SAME checkpoint step are
      a poison-crash, and a spent ``MXTPU_SUPERVISOR_RESTARTS`` budget a
      crash-loop — both refuse via :class:`SupervisorRefusal` AFTER
      dumping ``flight_record("supervisor_refusal")`` with ``history``
      and the diagnosis.

    ``launch``/``clock``/``sleeper``/``rng``/``latest_fn`` are injectable
    so the loop tests sleep-free and subprocess-free in tier-1."""

    # codes meaning THIS child's host is gone (shrink the next world by
    # these) vs. codes meaning this child was a healthy VICTIM of someone
    # else's death (its collective wedged / it timed out blocked) — the
    # victims relaunch, so they must not count toward the shrink
    LOST_CODES = (EXIT_HOST_LOSS, EXIT_REJOIN_STALL, -9, -15)
    VICTIM_CODES = (EXIT_FLEET_WEDGE, "timeout")

    def __init__(self, command_for, num_hosts, ckpt_dir=None, fleet_dir=None,
                 max_restarts=None, backoff_s=None, max_backoff_s=60.0,
                 timeout_s=None, min_hosts=1, rejoin=True, env_for=None,
                 launch=None, clock=None, sleeper=None, rng=None,
                 latest_fn=None, logger=None):
        from .resilience import TrainSupervisor  # env defaults shared
        if num_hosts < 1:
            raise MXNetError("FleetSupervisor needs num_hosts >= 1")
        self.command_for = command_for
        self.num_hosts = int(num_hosts)
        self.min_hosts = int(min_hosts)
        self.rejoin = bool(rejoin)
        self.ckpt_dir = ckpt_dir
        self.fleet_dir = fleet_dir
        if max_restarts is None:
            max_restarts = os.environ.get("MXTPU_SUPERVISOR_RESTARTS", "8")  # graftlint: disable=policy-key-coverage
        if backoff_s is None:
            backoff_s = os.environ.get("MXTPU_SUPERVISOR_BACKOFF_S", "2.0")  # graftlint: disable=policy-key-coverage
        self.max_restarts = int(max_restarts)
        self.backoff_s = float(backoff_s)
        self.max_backoff_s = float(max_backoff_s)
        self.timeout_s = child_timeout_s() if timeout_s is None \
            else float(timeout_s)
        self.env_for = env_for
        self._launch = self.launch_round if launch is None else launch
        self._clock = time.monotonic if clock is None else clock
        self._sleeper = time.sleep if sleeper is None else sleeper
        self._rng = rng
        self._latest_fn = latest_fn
        self._log = logger or _log
        self.restarts = 0
        self.history = []  # [{"event": ..., ...}] membership-change log

    def _event(self, event, **detail):
        rec = {"event": event, **detail}
        self.history.append(rec)
        self._log.info("fleet supervisor: %s %s", event, detail)
        return rec

    def _latest(self):
        if self._latest_fn is not None:
            return self._latest_fn()
        if self.ckpt_dir is None:
            return None
        from .contrib import async_checkpoint as ackpt
        try:
            return ackpt.latest_step(self.ckpt_dir)
        except Exception:  # noqa: BLE001 — a broken dir reads as fresh
            return None

    # --------------------------------------------------------------- launch
    @staticmethod
    def _free_port():
        import socket
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def launch_round(self, world, generation, extra_env=None):
        """Launch one fleet generation and reap every child under a HARD
        deadline. Returns ``{rank: (rc, output_tail)}`` where ``rc`` is
        the exit code or the string ``"timeout"`` for a child that had
        to be killed — a hung collective is surfaced, never waited on
        unboundedly (the tier-1 1140 s budget depends on this)."""
        import subprocess
        port = self._free_port()
        procs = {}
        for rank in range(world):
            env = dict(os.environ)
            env.update({
                "MXTPU_COORDINATOR": "127.0.0.1:%d" % port,
                "MXTPU_NUM_PROCESSES": str(world),
                "MXTPU_PROCESS_ID": str(rank),
            })
            if self.fleet_dir is not None:
                # a FRESH board per generation: barrier dirs and host
                # status files from a dead generation must never satisfy
                # (or poison the divergence compare of) the next one
                env["MXTPU_FLEET_DIR"] = os.path.join(
                    str(self.fleet_dir), "gen_%d" % generation)
            if extra_env:
                env.update(extra_env)
            if self.env_for is not None:
                env.update(self.env_for(rank, world, generation) or {})
            procs[rank] = subprocess.Popen(
                self.command_for(rank, world, generation), env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        out = {}
        deadline = time.monotonic() + self.timeout_s
        for rank, p in procs.items():
            budget = max(0.1, deadline - time.monotonic())
            try:
                txt, _ = p.communicate(timeout=budget)
                out[rank] = (p.returncode, (txt or "")[-4000:])
            except subprocess.TimeoutExpired:
                p.kill()
                txt, _ = p.communicate()
                out[rank] = ("timeout", (txt or "")[-4000:])
                self._log.error(
                    "fleet child rank %d/%d (gen %d) hit the %.0fs hard "
                    "timeout and was killed", rank, world, generation,
                    self.timeout_s)
        return out

    # ------------------------------------------------------------------ run
    def run(self, extra_env=None):
        """Drive generations until one exits clean everywhere (returns
        the per-rank results of that generation) or a refusal raises."""
        from . import telemetry
        from .resilience import _next_backoff, _process_rng, _refuse
        delay = self.backoff_s
        prev_crash_step = ()  # sentinel: no failed generation yet
        generation = 0
        world = self.num_hosts
        while True:
            resume_step = self._latest()
            self._event("launch", generation=generation, world=world,
                        resume_step=resume_step)
            results = self._launch(world, generation, extra_env)
            rcs = {r: rc for r, (rc, _) in
                   ((r, v if isinstance(v, tuple) else (v, ""))
                    for r, v in results.items())}
            failed = {r: rc for r, rc in rcs.items() if rc != 0}
            if not failed:
                self._event("clean_exit", generation=generation, world=world)
                return results
            crash_step = self._latest()
            lost = sorted(r for r, rc in failed.items()
                          if rc in self.LOST_CODES)
            victims = sorted(r for r, rc in failed.items()
                             if rc in self.VICTIM_CODES)
            if not lost and victims:
                # every failure is a wedge/timeout with no identified
                # death: someone IS gone (a wedge means a peer stopped
                # answering) but no child owned up — treat the
                # highest-ranked victim as lost so the fleet still
                # shrinks instead of flapping at a size that cannot work
                lost = [victims[-1]]
                victims = victims[:-1]
            self._event("crash", generation=generation, world=world,
                        exit_codes={str(r): rc for r, rc in rcs.items()},
                        ckpt_step=crash_step, lost=lost, victims=victims)
            if crash_step is not None and crash_step == prev_crash_step:
                raise _refuse(
                    "the fleet crashed twice at checkpoint step %s with "
                    "ZERO progress in between (exit codes %s) — a "
                    "deterministic poison-crash; respawning replays it "
                    "forever. Inspect the flight artifacts before "
                    "restarting by hand." % (crash_step, failed),
                    self.history, self._log)
            if self.restarts >= self.max_restarts:
                raise _refuse(
                    "crash-loop budget spent: %d fleet restarts "
                    "(MXTPU_SUPERVISOR_RESTARTS) with children still dying "
                    "(last exit codes %s, last checkpoint step %s) — "
                    "refusing to flap further"
                    % (self.restarts, failed, crash_step),
                    self.history, self._log)
            progressed = crash_step is not None and (
                prev_crash_step == () or crash_step != prev_crash_step)
            prev_crash_step = crash_step
            self.restarts += 1
            generation += 1
            telemetry.inc("supervisor.restarts", tag="fleet")
            if lost and world - len(lost) >= self.min_hosts:
                world = world - len(lost)
                self._event("host_loss", ranks=lost, world=world,
                            ckpt_step=crash_step)
            elif self.rejoin and progressed and world < self.num_hosts:
                world = self.num_hosts
                self._event("rejoin_attempt", world=world,
                            ckpt_step=crash_step)
            self._sleeper(delay)
            delay = _next_backoff(self._rng or _process_rng(),
                                  self.backoff_s, delay, self.max_backoff_s)
