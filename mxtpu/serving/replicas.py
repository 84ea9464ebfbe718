"""Fault-tolerant replica serving: ReplicaSet router + wedge watchdog.

PR 5's server is one Predictor on one device behind one dispatch thread.
A wedged chip (a device call that never returns) therefore hangs the sole worker inside
``MicroBatcher._dispatch`` forever — every queued future strands, and the
box fails its SLO while still answering ``/healthz`` 200. This module is
the serving half of the resilience story (ROADMAP item 2(a)):

* :class:`ReplicaSet` — one AOT-warmed
  :class:`~mxtpu.serving.engine.Predictor` per device. Each replica's
  parameters are ``device_put`` to its chip and its compiles report at a
  per-replica retrace site ``serving.predict.r<i>`` — post-warmup
  compiles stay ≤ #buckets × #replicas by construction, attributable per
  replica. The executable is the unit of failover (PyGraph's
  capture-once/replay-forever economics, arXiv:2503.19779): losing a
  replica loses capacity, never the ability to serve.
* :class:`ReplicaDispatcher` — a :class:`~mxtpu.serving.batcher.
  MicroBatcher` whose single worker is replaced by one dispatch worker
  PER replica, all fed from the same per-bucket FIFO cohorts
  (shed-aware least-loaded routing: a busy or quarantined replica simply
  stops pulling work; the explicit router picks the least-loaded healthy
  replica when dispatch is driven via :meth:`poll`).
* **Wedge watchdog** — every dispatch is bracketed by a per-dispatch
  deadline (``MXTPU_SERVE_DISPATCH_TIMEOUT_MS``). On trip: the replica
  is marked wedged and quarantined, the batch re-dispatches on a healthy
  replica exactly ONCE (a twice-wedged batch fails its futures — bounded
  behavior, never a loop), and a late answer from the wedged call is
  discarded as stale.
* **Circuit breaker** — ``MXTPU_SERVE_BREAKER_THRESHOLD`` consecutive
  dispatch failures open a replica's breaker (quarantine). A half-open
  probe re-warms the replica with a synthetic min-bucket batch on an
  exponential backoff schedule (``MXTPU_SERVE_BREAKER_BACKOFF_MS`` …
  ``_MAX_MS``); success restores it, failure doubles the backoff. The
  clock is injectable, so the whole failure matrix runs sleep-free in
  tier-1 under a fake clock.
* **Graceful degradation** — losing k of N replicas raises the shed
  rate (`queue_full`, and `no_healthy_replica` once ALL are down)
  instead of hanging or 500-ing; ``/healthz`` reports per-replica state
  and queue depth; ``/metrics`` carries the replica-tagged counters
  ``serving.replica.{dispatches,failures,wedges,quarantines,restores,
  redispatches}``.

Deterministic fault kinds (``MXTPU_FAULT_INJECT``, docs/resilience.md):
``replica_fail@i`` — the replica executing serving dispatch *i* raises;
``replica_wedge@i`` — that dispatch never returns (simulated wedge: the
watchdog path runs without a blocked thread, so fake-clock tests cover
it end to end).
"""
from __future__ import annotations

import logging
import os
import threading
import time

import jax
import jax.numpy as jnp

from .. import telemetry
from ..base import MXNetError
from ..resilience import inject
from .batcher import DeadlineExceeded, MicroBatcher, QueueFull
from .engine import Predictor

__all__ = ["Replica", "ReplicaSet", "ReplicaDispatcher", "ReplicaFailure",
           "replica_count_default", "dispatch_timeout_ms_default",
           "breaker_threshold_default", "breaker_backoff_ms_default",
           "breaker_backoff_max_ms_default"]

_log = logging.getLogger("mxtpu.serving")

# the simulated-wedge sentinel: "the device call has not returned" — the
# dispatch path keeps its watchdog entry armed and delivers nothing
_WEDGED = object()


# ------------------------------------------------------------------ policies
def replica_count_default():
    """Replica count for :class:`ReplicaSet` when neither ``n`` nor
    ``devices`` is given (``MXTPU_SERVE_REPLICAS``, default 1 — the PR-5
    single-predictor behavior; ``auto``/``0`` = one per visible device)."""
    v = os.environ.get("MXTPU_SERVE_REPLICAS", "1").strip().lower()
    return 0 if v in ("auto", "all") else int(v)


def dispatch_timeout_ms_default():
    """Per-dispatch wedge deadline (``MXTPU_SERVE_DISPATCH_TIMEOUT_MS``,
    default 10000): a dispatched batch that has not answered within this
    bound trips the wedge watchdog — the replica is quarantined and the
    batch re-dispatches once on a healthy replica. Generous by default: a
    warm-bucket forward is milliseconds, so 10 s only ever fires on a
    genuinely dead device call, never on a slow one."""
    return float(os.environ.get("MXTPU_SERVE_DISPATCH_TIMEOUT_MS", "10000"))


def breaker_threshold_default():
    """Consecutive dispatch failures that open a replica's circuit
    breaker (``MXTPU_SERVE_BREAKER_THRESHOLD``, default 3)."""
    return int(os.environ.get("MXTPU_SERVE_BREAKER_THRESHOLD", "3"))


def breaker_backoff_ms_default():
    """Initial half-open probe backoff after a quarantine
    (``MXTPU_SERVE_BREAKER_BACKOFF_MS``, default 1000); doubles per
    failed probe."""
    return float(os.environ.get("MXTPU_SERVE_BREAKER_BACKOFF_MS", "1000"))


def breaker_backoff_max_ms_default():
    """Probe backoff ceiling (``MXTPU_SERVE_BREAKER_BACKOFF_MAX_MS``,
    default 30000)."""
    return float(os.environ.get("MXTPU_SERVE_BREAKER_BACKOFF_MAX_MS",
                                "30000"))


class ReplicaFailure(MXNetError):
    """A replica-level dispatch failure (device error / injected
    ``replica_fail``): counts toward that replica's circuit breaker."""


class Replica:
    """One serving replica: an AOT-warmed Predictor pinned to a device,
    plus its health state. State machine: ``healthy`` (routable) ->
    ``quarantined`` (breaker open / wedged; half-open probe scheduled at
    ``probe_at``) -> ``probing`` (one in-flight probe) -> back. The
    elastic states: ``warming`` (AOT bring-up off the serving path —
    never routed until every bucket compiled), ``retiring`` (drains its
    in-flight work, then ``removed`` — the scale-down / replacement
    exit)."""

    __slots__ = ("index", "device", "predictor", "state", "consecutive",
                 "inflight", "dispatches", "wedged", "backoff_s", "probe_at",
                 "down_since")

    def __init__(self, index, device, predictor, backoff_s,
                 state="healthy"):
        self.index = index
        self.device = device
        self.predictor = predictor
        self.state = state
        self.consecutive = 0      # consecutive dispatch failures (breaker)
        self.inflight = 0         # batches currently executing here
        self.dispatches = 0
        self.wedged = False       # a dispatch never returned
        self.backoff_s = backoff_s
        self.probe_at = None
        self.down_since = None    # clock of the breaker open (replacement)

    @property
    def tag(self):
        return "r%d" % self.index


class ReplicaSet:
    """One warmed Predictor per device + the health/routing state machine.

    ``block`` is shared (parameters are read-only in serving): each
    replica's Predictor snapshots the params ``device_put`` to ITS device
    and compiles its own per-bucket executables, reported at retrace site
    ``serving.predict.r<i>``. Pass ``n`` (or ``MXTPU_SERVE_REPLICAS``;
    0/"auto" = every visible device) or an explicit ``devices`` list.

    All state transitions are clock-value driven (``now`` is passed in by
    the dispatcher), so the set itself never sleeps and never reads a
    wall clock — the fake-clock contract of the tier-1 failure tests.
    """

    def __init__(self, block, spec, n=None, devices=None, example=None,
                 warmup=True, name="predictor", breaker_threshold=None,
                 breaker_backoff_ms=None, breaker_backoff_max_ms=None):
        if devices is None:
            count = replica_count_default() if n is None else int(n)
            avail = list(jax.devices())
            if count == 0:
                count = len(avail)
            if count < 1:
                raise MXNetError("ReplicaSet: need at least 1 replica")
            if count > len(avail):
                raise MXNetError(
                    "ReplicaSet: %d replicas requested but only %d device"
                    "(s) visible" % (count, len(avail)))
            devices = avail[:count]
        if not devices:
            raise MXNetError("ReplicaSet: empty device list")
        self.spec = spec
        self.threshold = int(breaker_threshold
                             if breaker_threshold is not None
                             else breaker_threshold_default())
        self.backoff0_s = float(breaker_backoff_ms
                                if breaker_backoff_ms is not None
                                else breaker_backoff_ms_default()) / 1e3
        self.backoff_max_s = float(breaker_backoff_max_ms
                                   if breaker_backoff_max_ms is not None
                                   else breaker_backoff_max_ms_default()) / 1e3
        self._lock = threading.Lock()
        self._accountant = None   # optional KVCacheAccountant (attach_...)
        self._block = block       # elastic growth rebuilds from these
        self._example = example
        self._name = name
        self.replicas = []
        for i, dev in enumerate(devices):
            pred = Predictor(block, spec, example=example, warmup=False,
                             name="%s.r%d" % (name, i), device=dev,
                             site="serving.predict.r%d" % i)
            self.replicas.append(Replica(i, dev, pred, self.backoff0_s))
        # replica indices are IDENTITIES, never positions: elastic
        # add/remove keeps retiring a replica from invalidating another's
        # retrace site (serving.predict.r<i>) or telemetry tag family
        self._next_index = len(self.replicas)
        telemetry.gauge("serving.replicas", len(self.replicas))
        if warmup:
            self.warmup()

    # --------------------------------------------------- batcher interface
    # (a ReplicaSet slots in wherever MicroBatcher expects a predictor)
    @property
    def input_templates(self):
        return self.replicas[0].predictor.input_templates

    @property
    def _jits(self):
        # the MicroBatcher cold-start check reads this: warm iff every
        # SERVING replica compiled its buckets (a replica still in its
        # elastic warming window is by definition not serving yet)
        reps = [r for r in self.replicas if r.state != "warming"]
        if reps and all(r.predictor._jits for r in reps):
            return reps[0].predictor._jits
        return {}

    def warmup(self):
        """AOT-compile every bucket on every replica through the compile
        service's shared-lowering path: identical lowerings carry one
        group token, so the shared block traces ONCE per bucket (not
        once per replica serialized behind the trace lock) and each
        replica's executables compile for its own device, concurrently
        on the service pool — and load from a warm
        ``MXTPU_COMPILE_CACHE_DIR`` with zero compiles. Returns self."""
        from .. import compile_service as csvc
        entries = []
        for r in self.replicas:
            entries.extend(r.predictor.warmup_entries())
        csvc.warmup(entries)
        for r in self.replicas:
            r.predictor.finish_warmup()
        return self

    def __len__(self):
        return len(self.replicas)

    # ------------------------------------------------------------ elasticity
    def _find_locked(self, index):
        for r in self.replicas:
            if r.index == index:
                return r
        raise MXNetError("ReplicaSet: no replica with index %d (live: %s)"
                         % (index, [r.index for r in self.replicas]))

    def _free_devices_locked(self):
        used = {id(r.device) for r in self.replicas}
        return [d for d in jax.devices() if id(d) not in used]

    def free_devices(self):
        """Visible devices no current replica (any state) is pinned to —
        where a replacement or scale-up replica goes first."""
        with self._lock:
            return self._free_devices_locked()

    def add_replica(self, device=None, warm=True):
        """Grow the set by one replica (the elastic half of ROADMAP item
        4). The new member starts in state ``warming`` — visible on
        ``/healthz``, NEVER routed — and joins the dispatch pool only
        after :meth:`warm_replica` AOT-compiles every bucket at its own
        fresh retrace site ``serving.predict.r<i>`` (indices are never
        reused, so per-replica compile budgets stay pinned at #buckets).
        ``warm=False`` leaves the bring-up to the caller — how the
        :class:`~mxtpu.serving.controller.ServingController` runs it on
        a side thread, off the serving path. Returns the new replica."""
        with self._lock:
            idx = self._next_index
            self._next_index += 1
            if device is None:
                free = self._free_devices_locked()
                if not free:
                    raise MXNetError(
                        "ReplicaSet.add_replica: every visible device "
                        "already hosts a replica — pass device= to "
                        "double up explicitly")
                device = free[0]
            pred = Predictor(self._block, self.spec, example=self._example,
                             warmup=False, name="%s.r%d" % (self._name, idx),
                             device=device,
                             site="serving.predict.r%d" % idx)
            rep = Replica(idx, device, pred, self.backoff0_s,
                          state="warming")
            self.replicas.append(rep)
            telemetry.gauge("serving.replicas", len(self.replicas))
        if warm:
            self.warm_replica(rep)
        return rep

    def warm_replica(self, rep):
        """AOT-compile the warming replica's buckets, then flip it to
        ``healthy`` (the moment it becomes routable). A failed warmup
        removes the replica and re-raises — a member that cannot compile
        must never join the pool. Returns the replica."""
        try:
            rep.predictor.warmup()
        except Exception:
            with self._lock:
                if rep in self.replicas:
                    self.replicas.remove(rep)
                telemetry.gauge("serving.replicas", len(self.replicas))
            raise
        with self._lock:
            if rep.state == "warming":
                rep.state = "healthy"
                telemetry.inc("serving.replica.joins", tag=rep.tag)
                _log.info("serving replica %d warmed and joined the "
                          "dispatch pool", rep.index)
        return rep

    def remove_replica(self, index):
        """Begin removing a replica (scale-down, or the dead half of a
        replacement): it flips to ``retiring`` — stops pulling work, is
        never picked, is no longer probed — and leaves the set once its
        in-flight work drains (:meth:`finalize_retiring`, the PR-8 drain
        discipline: in-flight futures always complete). Returns the
        replica."""
        with self._lock:
            rep = self._find_locked(index)
            if rep.state != "retiring":
                rep.state = "retiring"
                rep.probe_at = None
                telemetry.inc("serving.replica.retirements", tag=rep.tag)
                _log.info("serving replica %d retiring (inflight=%d)",
                          rep.index, rep.inflight)
            return rep

    def finalize_retiring(self):
        """Drop retiring replicas whose in-flight work drained. Returns
        the replicas removed this pass (dispatch workers exit on seeing
        state ``removed``)."""
        done = []
        with self._lock:
            for rep in [r for r in self.replicas
                        if r.state == "retiring" and r.inflight == 0]:
                rep.state = "removed"
                self.replicas.remove(rep)
                done.append(rep)
            if done:
                telemetry.gauge("serving.replicas", len(self.replicas))
        return done

    # ------------------------------------------------------------- routing
    def pick(self, exclude=()):
        """Least-loaded healthy replica (ties break to the lowest index);
        None when every replica is down — the caller sheds."""
        with self._lock:
            cands = [r for r in self.replicas
                     if r.state == "healthy" and r.index not in exclude]
            if not cands:
                return None
            return min(cands, key=lambda r: (r.inflight, r.index))

    def healthy_count(self):
        with self._lock:
            return sum(1 for r in self.replicas if r.state == "healthy")

    def acquire(self, rep):
        with self._lock:
            rep.inflight += 1
            rep.dispatches += 1

    def release(self, rep):
        with self._lock:
            rep.inflight -= 1

    # ------------------------------------------------------- health events
    def record_success(self, rep):
        with self._lock:
            rep.consecutive = 0

    def record_failure(self, rep, now):
        """One dispatch failure; opens the breaker at ``threshold``
        consecutive failures. Returns True when this call opened it."""
        telemetry.inc("serving.replica.failures", tag=rep.tag)
        with self._lock:
            rep.consecutive += 1
            if rep.state == "healthy" and rep.consecutive >= self.threshold:
                self._open_locked(rep, now)
                return True
        return False

    def mark_wedged(self, rep, now):
        """Wedge-watchdog trip: the replica's dispatch never returned."""
        telemetry.inc("serving.replica.wedges", tag=rep.tag)
        with self._lock:
            rep.wedged = True
            if rep.state == "healthy":
                self._open_locked(rep, now)

    def force_quarantine(self, index, now, backoff_s=None):
        """Operational kill switch (and the bench's mid-run chip-loss
        knob): quarantine a replica as if its breaker opened; it
        half-open-probes back after ``backoff_s``."""
        with self._lock:
            rep = self._find_locked(index)
            if backoff_s is not None:
                rep.backoff_s = float(backoff_s)
            if rep.state == "healthy":
                self._open_locked(rep, now)
            else:
                rep.probe_at = now + rep.backoff_s
            return rep

    def _open_locked(self, rep, now):
        rep.state = "quarantined"
        rep.probe_at = now + rep.backoff_s
        if rep.down_since is None:
            # the CONTINUOUS-outage clock the controller's replacement
            # bound reads: restarts only on a successful probe
            rep.down_since = now
        telemetry.inc("serving.replica.quarantines", tag=rep.tag)
        _log.warning("serving replica %d quarantined (wedged=%s, "
                     "consecutive_failures=%d); half-open probe in %.1f s",
                     rep.index, rep.wedged, rep.consecutive, rep.backoff_s)

    # --------------------------------------------------------------- probes
    def due_probes(self, now):
        """Quarantined replicas whose backoff elapsed; each is moved to
        ``probing`` (claimed) before being returned, so concurrent
        maintainers can't double-probe."""
        with self._lock:
            due = [r for r in self.replicas
                   if r.state == "quarantined" and r.probe_at is not None
                   and now >= r.probe_at]
            for r in due:
                r.state = "probing"
            return due

    def run_probe(self, rep):
        """The half-open probe: re-warm with a synthetic min-bucket batch
        (zero-filled templates, smallest batch × smallest seq bucket) and
        block until the device answers. Raises on failure; a wedge here
        is caught by the dispatcher's watchdog bracket."""
        pred = rep.predictor
        if pred._templates is None:
            raise MXNetError("probe before settle: ReplicaSet needs "
                             "example= at construction")
        b = self.spec.batch_sizes[0]
        s = self.spec.seq_lens[0] if self.spec.seq_lens else None
        datas = [jnp.zeros((b,) + pred._bucket_trailing(t, s), dt)
                 for t, dt in pred._templates]
        flat, _ = pred._run_padded(datas)
        jax.block_until_ready([o._data for o in flat])

    def probe_result(self, rep, ok, now):
        """Half-open verdict: success closes the breaker (restore),
        failure doubles the backoff and re-quarantines."""
        with self._lock:
            if rep.state in ("retiring", "removed"):
                return  # written off mid-probe: a verdict cannot resurrect
            if ok:
                rep.state = "healthy"
                rep.wedged = False
                rep.consecutive = 0
                rep.backoff_s = self.backoff0_s
                rep.probe_at = None
                rep.down_since = None
                telemetry.inc("serving.replica.restores", tag=rep.tag)
                _log.info("serving replica %d restored by half-open probe",
                          rep.index)
            else:
                rep.state = "quarantined"
                rep.backoff_s = min(rep.backoff_s * 2, self.backoff_max_s)
                rep.probe_at = now + rep.backoff_s
                _log.warning("serving replica %d probe failed; next probe "
                             "in %.1f s", rep.index, rep.backoff_s)

    # ----------------------------------------------------- KV accountability
    def attach_accountant(self, accountant):
        """Attach a :class:`~mxtpu.serving.decode.KVCacheAccountant`
        whose per-replica pools are tagged ``r<i>`` (the same family as
        the retrace sites): ``states()`` then reports each replica's
        resident KV bytes, and the :class:`ReplicaDispatcher` sheds
        ``kv_residency`` when NO healthy replica has admission headroom —
        overload is judged by cache memory, not queue depth. The seam is
        unit-agnostic: a rowed decode engine registers worst-case slots,
        a PAGED one registers its page pool (``slots`` = pages,
        ``page_tokens`` set), and ``would_admit``/``states()`` report
        real free-page headroom with no dispatcher change. Returns
        self."""
        self._accountant = accountant
        return self

    @property
    def accountant(self):
        return self._accountant

    def kv_admissible(self):
        """True while at least one HEALTHY replica's KV pool admits
        (vacuously true without an accountant)."""
        acct = self._accountant
        if acct is None:
            return True
        with self._lock:
            tags = [r.tag for r in self.replicas if r.state == "healthy"]
        return any(acct.would_admit(t) for t in tags)

    # ------------------------------------------------------------ reporting
    def states(self):
        """Per-replica health for ``/healthz`` (JSON-serializable)."""
        acct = self._accountant
        with self._lock:
            out = [{"replica": r.index,
                    "device": str(r.device),
                    "state": r.state,
                    "inflight": r.inflight,
                    "dispatches": r.dispatches,
                    "consecutive_failures": r.consecutive,
                    "wedged": r.wedged,
                    "probe_at": r.probe_at}
                   for r in self.replicas]
        if acct is not None:
            snap = acct.snapshot()
            for row in out:
                tag = "r%d" % row["replica"]
                row["kv_resident_bytes"] = acct.resident_bytes(tag)
                pool = snap.get(tag)
                if pool is not None and pool.get("page_tokens"):
                    # paged pools surface their page economics next to
                    # the byte view: a fleet dispatcher can route on
                    # free pages, not just bytes
                    row["kv_page_tokens"] = pool["page_tokens"]
                    row["kv_pages"] = pool["slots"]
                    row["kv_pages_live"] = pool["live"]
        return out


class ReplicaDispatcher(MicroBatcher):
    """A MicroBatcher routed over a :class:`ReplicaSet`.

    Admission, coalescing, deadlines, shedding, and fault hooks are the
    base class's unchanged; what changes is dispatch: ONE worker per
    replica (each pulls the next FIFO cohort only while its replica is
    healthy — shed-aware least-loaded routing by construction), every
    dispatch bracketed by the wedge watchdog, failures counted by the
    per-replica breaker, and a monitor thread that scans for wedges and
    schedules half-open probes. ``start=False`` + an injected clock keeps
    everything synchronous for tests: :meth:`poll` runs maintenance
    (watchdog scan + due probes) and then dispatches one batch on the
    least-loaded healthy replica.
    """

    def __init__(self, replica_set, dispatch_timeout_ms=None, **kwargs):
        if not isinstance(replica_set, ReplicaSet):
            raise MXNetError("ReplicaDispatcher routes a ReplicaSet (got "
                             "%s); plain Predictors take a MicroBatcher"
                             % type(replica_set).__name__)
        self._set = replica_set
        self._timeout_s = float(
            dispatch_timeout_ms if dispatch_timeout_ms is not None
            else dispatch_timeout_ms_default()) / 1e3
        self._watch = []          # armed dispatch/probe watchdog entries
        self._flight_pending = []  # dump payloads deferred out of the lock
        self._threads = []
        self._monitor = None
        self._stop = threading.Event()
        self._tls = threading.local()
        super().__init__(replica_set, **kwargs)

    # ------------------------------------------------------------- routing
    @property
    def replica_set(self):
        return self._set

    def replica_states(self):
        """Per-replica health — surfaced by ``ModelServer`` ``/healthz``."""
        return self._set.states()

    def quarantine_replica(self, index, backoff_s=None):
        """Operational kill switch: see :meth:`ReplicaSet.force_quarantine`."""
        self._set.force_quarantine(index, self._clock(), backoff_s)
        with self._cond:
            self._cond.notify_all()

    def submit(self, inputs, deadline_ms=None, priority="interactive",
               meta=None):
        if self._set.healthy_count() == 0:
            # give a due half-open probe the chance to restore a replica
            # before refusing (the all-down shed must not outlive the
            # backoff schedule by even one submit)
            self._maintain()
            if self._set.healthy_count() == 0:
                self._shed("no_healthy_replica")
        if not self._set.kv_admissible():
            # every healthy replica's KV pool is over budget: shedding by
            # RESIDENCY, not queue depth — an admitted sequence would only
            # grow time-to-first-token on a replica with no cache room
            self._shed("kv_residency")
        return super().submit(inputs, deadline_ms=deadline_ms,
                              priority=priority, meta=meta)

    # ---------------------------------------------------------- elasticity
    def add_replica(self, device=None):
        """Grow the pool by one replica. Bring-up (AOT warmup of every
        bucket at the new ``serving.predict.r<i>`` site) runs OFF the
        serving path — on a side thread in threaded mode, inline under a
        fake clock — and the replica joins dispatch only once warm; in
        threaded mode its dedicated worker starts at that moment.
        Returns the (possibly still warming) replica."""
        rep = self._set.add_replica(device=device, warm=False)

        def _bringup():
            try:
                self._set.warm_replica(rep)  # failure removes the replica
            except Exception as e:  # noqa: BLE001 — a failed bring-up
                # must be RECORDED, not lost on a daemon thread: the
                # controller's warmup_failed decision is the only signal
                # an operator gets that capacity never arrived
                _log.exception("serving: replica %d bring-up failed",
                               rep.index)
                ctrl = self._controller
                if ctrl is not None:
                    ctrl.note_warmup_failed(e, self._clock())
                return
            with self._cond:
                self._cond.notify_all()
            if self._threads:
                self._spawn_worker(rep)

        if self._threads:
            threading.Thread(target=_bringup, daemon=True,
                             name="mxtpu-serving-warmup-r%d"
                             % rep.index).start()
        else:
            _bringup()
        return rep

    def remove_replica(self, index):
        """Retire a replica through the drain machinery: it stops
        pulling work immediately, in-flight futures complete, and the
        next maintenance pass removes it once drained."""
        rep = self._set.remove_replica(index)
        with self._cond:
            self._cond.notify_all()
        return rep

    # --------------------------------------------------------- maintenance
    def _maintain(self):
        """Wedge-watchdog scan + due half-open probes — called from
        :meth:`poll` (fake-clock tests) and admission. Probes run inline
        (synchronously) here, but STILL under a watchdog entry: if the
        probe itself wedges, the monitor's next scan abandons it — the
        replica goes back to ``quarantined`` with a doubled backoff
        instead of sticking in ``probing`` forever, and the system keeps
        its shed-never-hang guarantee even though the probing caller
        (e.g. an HTTP handler thread) stays blocked until the device
        answers or its client gives up."""
        now = self._clock()
        due = []
        with self._cond:
            self._scan_wedges_locked(now)
            for rep in self._set.due_probes(now):
                entry = {"kind": "probe", "rep": rep, "live": None,
                         "idx": -1, "deadline": now + self._timeout_s,
                         "done": False, "abandoned": False,
                         "released": True}
                self._watch.append(entry)
                due.append((rep, entry))
        self._flush_flight()
        for rep, entry in due:
            self._probe(rep, entry)
        self._post_maintain()

    def _post_maintain(self):
        """The elastic tail of every maintenance pass: drop retiring
        replicas whose in-flight work drained, then give the attached
        ServingController its control-loop tick (scale/replace decisions
        run here — outside every lock, since a bring-up is device
        work). Under a fake clock this is what makes ``poll()`` drive
        the whole control plane sleep-free."""
        self._set.finalize_retiring()
        ctrl = self._controller
        if ctrl is not None:
            ctrl.tick(self._clock())

    def _flush_flight(self):
        """Write dumps the wedge scan deferred — NEVER under self._cond
        (callers invoke this right after releasing it). No-op when
        nothing is pending or MXTPU_FLIGHT_DIR is unset."""
        if not self._flight_pending:
            return
        with self._cond:
            pending, self._flight_pending = self._flight_pending, []
        for reason, tids, extra in pending:
            telemetry.flight_record(reason, trace_ids=tids, extra=extra)

    def poll(self):
        self._maintain()
        if self._set.healthy_count() == 0:
            return 0  # nothing routable: keep requests queued (they shed
            # at admission, expire via deadlines, or serve after restore)
        return super().poll()

    def _scan_wedges_locked(self, now):
        """The wedge watchdog. An armed entry past its deadline means a
        dispatch (or probe) never answered: quarantine the replica,
        re-dispatch the batch exactly once on a healthy replica (or shed
        when none is left), and discard the wedged call's eventual
        answer as stale."""
        for entry in list(self._watch):
            if entry["done"] or entry["abandoned"] \
                    or now < entry["deadline"]:
                continue
            entry["abandoned"] = True
            self._watch.remove(entry)
            rep = entry["rep"]
            if not entry["released"]:
                entry["released"] = True
                self._set.release(rep)
            if entry["kind"] == "probe":
                # the probe itself wedged: treat as a failed probe
                self._set.probe_result(rep, False, now)
                continue
            self._set.mark_wedged(rep, now)
            _log.warning(
                "serving: dispatch %d wedged on replica %d (no answer in "
                "%.0f ms) — replica quarantined, batch re-dispatching",
                entry["idx"], rep.index, self._timeout_s * 1e3)
            # the post-mortem artifact: the wedged dispatch's traces are
            # the owning ones — a p99 investigation (or this watchdog's
            # own trip) can match a request's trace_id to the exact
            # dispatch + per-thread stacks without a live repro. The
            # DUMP is deferred (self._flight_pending, flushed by the
            # caller after releasing self._cond): flight_record does
            # disk IO + an all-thread stack walk, and doing that under
            # the serving lock would stall every submit/dispatch for
            # the dump duration — during the exact incident being
            # recorded
            self._flight_pending.append(
                ("replica_wedge",
                 [r.trace.trace_id for r in entry["live"]
                  if r.trace is not None],
                 {"replica": rep.index, "dispatch": entry["idx"],
                  "timeout_ms": self._timeout_s * 1e3}))
            for r in entry["live"]:
                telemetry.trace_mark(r.trace, "serving.wedged")
            fresh = [r for r in entry["live"] if not r.redispatched]
            burnt = [r for r in entry["live"] if r.redispatched]
            for r in burnt:
                # exactly-once: the re-dispatch ALSO wedged — fail loud
                self._fail(r, DeadlineExceeded(
                    "re-dispatched batch wedged again (replica %d)"
                    % rep.index))
                telemetry.inc("serving.deadline_expired")
            if not fresh:
                continue
            if self._set.healthy_count() == 0:
                telemetry.inc("serving.shed", len(fresh),
                              tag="no_healthy_replica")
                err = QueueFull("request shed: no_healthy_replica (wedge "
                                "re-dispatch found no live replica)")
                for r in fresh:
                    self._fail(r, err)
                continue
            for r in reversed(fresh):
                r.redispatched = True
                # same _Request, same .trace: the re-dispatch's spans and
                # stages JOIN the original trace — the tree shows wedge ->
                # re-dispatch -> delivery as one causal story
                telemetry.trace_mark(r.trace, "serving.redispatch")
                self._q.appendleft(r)  # head: it already waited its turn
                self._items += r.n
            telemetry.inc("serving.replica.redispatches", tag=rep.tag)
            telemetry.gauge("serving.queue_depth", self._items)
            self._cond.notify_all()

    def _probe(self, rep, entry=None):
        """Run one half-open probe (device work — never under the lock)."""
        ok = True
        try:
            with telemetry.span("serving.probe"):
                self._set.run_probe(rep)
        except Exception as e:  # noqa: BLE001 — verdict, not control flow
            ok = False
            _log.warning("serving replica %d half-open probe failed: %s",
                         rep.index, e)
        with self._cond:
            if entry is not None:
                if entry["abandoned"]:
                    return  # the scan already ruled it a wedged probe
                entry["done"] = True
                if entry in self._watch:
                    self._watch.remove(entry)
            self._set.probe_result(rep, ok, self._clock())
            self._cond.notify_all()

    # -------------------------------------------------------------- dispatch
    def _run_batch(self, live, joined, idx):
        now = self._clock()
        t_route = time.perf_counter()
        rep = getattr(self._tls, "rep", None)  # a worker owns its replica
        if rep is not None and rep.state != "healthy":
            rep = None  # quarantined between gather and dispatch: re-route
        if rep is None:
            rep = self._set.pick()
        if rep is None:
            # admitted while healthy, orphaned by the time it dispatched:
            # shed late (bounded) rather than hang
            telemetry.inc("serving.shed", len(live),
                          tag="no_healthy_replica")
            err = QueueFull("request shed: no_healthy_replica")
            for r in live:
                self._fail(r, err)
            return
        self._set.acquire(rep)
        telemetry.inc("serving.replica.dispatches", tag=rep.tag)
        entry = {"kind": "dispatch", "rep": rep, "live": live, "idx": idx,
                 "deadline": now + self._timeout_s,
                 "done": False, "abandoned": False, "released": False}
        with self._cond:
            self._watch.append(entry)
        # routing + watchdog arm = the "replica dispatch" stage of the
        # per-request breakdown (runs under the cohort lead's trace)
        self._share_stage(live, "serving.dispatch",
                          time.perf_counter() - t_route)
        try:
            host = self._execute(rep, joined, idx, live)
        except Exception as e:  # noqa: BLE001 — breaker counts it
            with self._cond:
                abandoned = entry["abandoned"]
                entry["done"] = True
                if entry in self._watch:
                    self._watch.remove(entry)
                if not entry["released"]:
                    entry["released"] = True
                    self._set.release(rep)
                opened = self._set.record_failure(rep, self._clock())
                self._cond.notify_all()
            if opened:
                # the failure that OPENED the breaker: capture the moment
                # with the owning traces tagged (flight-recorder trigger)
                telemetry.flight_record(
                    "breaker_open",
                    trace_ids=[r.trace.trace_id for r in live
                               if r.trace is not None],
                    extra={"replica": rep.index, "dispatch": idx,
                           "error": "%s: %s" % (type(e).__name__, e)})
            if not abandoned:
                self._fail_batch(live, e, idx)
            return
        if host is _WEDGED:
            # simulated wedge (replica_wedge fault): the entry stays armed
            # — the watchdog trip quarantines + re-dispatches
            return
        with self._cond:
            stale = entry["abandoned"]
            entry["done"] = True
            if entry in self._watch:
                self._watch.remove(entry)
            if not entry["released"]:
                entry["released"] = True
                self._set.release(rep)
            self._set.record_success(rep)
            self._cond.notify_all()
        if stale:
            # the wedge watchdog already re-dispatched this batch; a late
            # answer must not double-deliver
            telemetry.inc("serving.replica.stale_results", tag=rep.tag)
            return
        self._deliver(live, host)

    def _execute(self, rep, joined, idx, live=()):
        if inject("replica_fail", idx):
            raise ReplicaFailure(
                "injected replica failure (dispatch %d, replica %d)"
                % (idx, rep.index))
        if inject("replica_wedge", idx):
            return _WEDGED
        t0 = time.perf_counter()
        flat, _fmt, _bucket = rep.predictor.predict_flat(tuple(joined))
        self._share_stage(live, "serving.predict", time.perf_counter() - t0)
        t0 = time.perf_counter()
        with telemetry.span("serving.fetch", cat="sync"):
            host = [o.asnumpy() for o in flat]
        self._share_stage(live, "serving.fetch", time.perf_counter() - t0)
        return host

    # ---------------------------------------------------------------- worker
    def _spawn_worker(self, rep):
        t = threading.Thread(target=self._replica_worker, args=(rep,),
                             daemon=True,
                             name="mxtpu-serving-replica-%d" % rep.index)
        self._threads.append(t)
        t.start()
        return t

    def start(self):
        if self._threads:
            return self
        if not getattr(self._set, "_jits", True):
            raise MXNetError(
                "ReplicaDispatcher.start on a cold ReplicaSet: warmup() "
                "every replica first")
        for rep in self._set.replicas:
            self._spawn_worker(rep)
        interval = max(0.005, min(0.25, self._timeout_s / 4))
        self._monitor = threading.Thread(
            target=self._monitor_loop, args=(interval,), daemon=True,
            name="mxtpu-serving-monitor")
        self._monitor.start()
        self._thread = self._threads[0]  # base-class compat only
        return self

    def _replica_worker(self, rep):
        self._tls.rep = rep
        try:
            self._worker_loop_for(rep)
        except Exception as e:  # noqa: BLE001 — same barrier as the base
            self._worker_crashed(e)

    def _worker_loop_for(self, rep):
        # mirrors MicroBatcher._worker_loop with two deltas: a wedge scan
        # + routability gate per iteration, and BOUNDED waits everywhere
        # (which also subsume the base loop's dedicated draining-park
        # branch — a parked worker here re-checks state every 250 ms)
        while True:
            with self._cond:
                batch = None
                while batch is None:
                    if self._closed and not self._q:
                        return
                    if rep.state == "removed":
                        return  # retired and drained: this worker is done
                    now = self._clock()
                    self._scan_wedges_locked(now)
                    if rep.state != "healthy":
                        # quarantined/probing/retiring: park (the monitor
                        # owns probes and retirement); bounded wait
                        # re-checks state
                        self._cond.wait(0.05)
                        continue
                    batch = self._gather_locked(now)
                    if batch is not None:
                        break
                    if self._q:
                        head_due = self._q[0].t_enq + self.max_wait_s - now
                        self._cond.wait(min(max(head_due, 1e-4), 0.25))
                    else:
                        self._cond.wait(0.25)
                self._inflight += len(batch)
            self._flush_flight()
            try:
                self._dispatch(batch)
            finally:
                with self._cond:
                    self._inflight -= len(batch)
                    self._cond.notify_all()

    def _monitor_loop(self, interval):
        """Wedge scans + probe scheduling with real-time pacing. This
        thread never does device work itself: probes run on fresh daemon
        threads (a wedged probe must not stop the scanning), each armed
        with its own watchdog entry."""
        while not self._stop.is_set():
            due = []
            with self._cond:
                if self._closed and not self._q and not self._watch:
                    return
                now = self._clock()
                self._scan_wedges_locked(now)
                for rep in self._set.due_probes(now):
                    entry = {"kind": "probe", "rep": rep, "live": None,
                             "idx": -1, "deadline": now + self._timeout_s,
                             "done": False, "abandoned": False,
                             "released": True}
                    self._watch.append(entry)
                    due.append((rep, entry))
            self._flush_flight()
            for rep, entry in due:
                threading.Thread(
                    target=self._probe, args=(rep, entry), daemon=True,
                    name="mxtpu-serving-probe-%d" % rep.index).start()
            self._post_maintain()
            self._stop.wait(interval)

    # ------------------------------------------------------- drain / close
    def _worker_alive(self):
        return any(t.is_alive() for t in self._threads)

    def _pending_extra(self):
        return any(e["kind"] == "dispatch" and not e["done"]
                   for e in self._watch)

    def _abort_extra_locked(self, err):
        dead = []
        for entry in self._watch:
            if entry["kind"] == "dispatch" and not entry["done"] \
                    and not entry["abandoned"]:
                entry["abandoned"] = True
                dead.extend(entry["live"])
        self._watch = [e for e in self._watch if e["kind"] != "dispatch"]
        return dead

    def close(self, timeout=5.0):
        self.drain(timeout=timeout)
        with self._cond:
            self._closed = True
            self._draining = True
            self._cond.notify_all()
        self._stop.set()
        for t in self._threads:
            t.join(timeout)
        if self._monitor is not None:
            self._monitor.join(timeout)
        return self
