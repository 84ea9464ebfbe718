"""Unified runtime telemetry: metrics registry, step-phase timeline, watchdogs.

The reference framework's ops-facing surface is its engine-level profiler
(src/profiler/profiler.h: per-op events, queue time, chrome-trace dump,
aggregate tables). On a jit-compiled TPU stack the signals that matter are
different — recompiles, host syncs, kernel-dispatch routing, skip-steps,
IO retries — and before this module they were scattered across five
modules (``optimizer_fused.FUSED_STATS``, ``ops.pallas.
flash_attention.DISPATCH_STATS``, ``resilience.FAULT_STATS``, monitor logs,
bench-only counters) with no common surface. This module is that surface:

* **Registry** — process-global counters / gauges / histograms with
  near-zero-overhead host-side updates (one short lock, no device work,
  no syncs — safe inside a ``jax.transfer_guard``), ``snapshot()`` for a
  structured view and ``report()`` for the aggregate table.
* **Spans** — ``with telemetry.span("trainer.step"): ...`` times a host
  region into a histogram AND a bounded event ring that
  :func:`mxtpu.profiler.dump` merges into the chrome-trace JSON, so one
  file shows the host step phases alongside the XLA trace. Every span is
  also a ``jax.profiler.TraceAnnotation``: under any running JAX trace it
  lies on the ``.xplane.pb`` host plane, on the trace's one timeline with
  the device's operations (the two agree to about half a millisecond).
* **Compile events** — :func:`watch_compiles`: JAX's own account of every
  Python trace, lowering and backend compile, as ring events
  (``jax.trace`` / ``jax.lower`` / ``jax.backend_compile``) and
  ``compile.*_s`` counters tagged with the span that was open. ``import
  mxtpu`` registers the listeners, so the ring holds every compile of the
  process, a restart's among them: ``mxtpu.import`` (:func:`record_interval`),
  ``gluon.param.*`` / ``gluon.cast`` and ``train_step.init`` with its three
  children are the restart's own spans (docs/observability.md).
* **Retrace watchdog** — jit-cache owners (``optimizer_fused.
  FusedUpdater``, gluon ``CachedOp``) report every compile with its
  cache-key / ``registry.policy_key`` provenance via
  :func:`record_retrace`; once a site exceeds ``MXTPU_RETRACE_BUDGET``
  compiles the watchdog warns with the provenance — steady-state
  recompiles are where jit-stack performance silently dies (PyGraph's
  core lesson: graph-capture systems fail without first-class re-capture
  accounting).
* **Transfer watchdog** — ``NDArray.asnumpy``-class device->host syncs
  bump a global counter; a ``span(..., d2h=True)`` attributes the delta
  to its region (``<name>.d2h``) and warns when a steady-state hot-loop
  region syncs at all. This generalizes the transfer-guard TEST machinery
  of the resilience PR into an always-available production counter.
* **JSON-lines sink** — ``MXTPU_TELEMETRY=<path>`` streams observations
  (and cumulative counters at flush) to a JSONL file; flushing is
  off-thread (``MXTPU_TELEMETRY_FLUSH_S``) and OFF by default — the hot
  path only ever appends to an in-memory deque.
  ``tools/telemetry_report.py`` turns the file into the aggregate table.
* **Causal tracing** — a :class:`TraceContext` (trace id + span id)
  carried in a ``contextvars.ContextVar`` so nested :class:`span` calls
  build per-request / per-step trees, with an EXPLICIT handoff API
  (:func:`trace_handoff`) for crossing threads: batcher dispatch workers,
  replica re-dispatches, and prefetch producers adopt the originating
  trace instead of losing it at the thread boundary. A bounded trace
  ring feeds the **flight recorder** (:func:`flight_record`): on
  watchdog trips, breaker opens, injected faults, and SIGTERM a JSON
  artifact with the recent trace events + per-thread stacks is written
  to ``MXTPU_FLIGHT_DIR``, so post-mortems need no live repro.
  ``MXTPU_TRACE=0`` turns the trace layer off (spans keep timing).
* **Prometheus exposition** — :func:`prometheus` renders the whole
  registry in the text exposition format; the model server
  content-negotiates it on ``/metrics`` next to the JSON snapshot.

Gating: ``MXTPU_TELEMETRY=0`` disables the span/event/sink machinery
(timers, ring appends). Plain counter/gauge increments stay always-on —
they are single dict updates, and the adopted stats views
(``DISPATCH_STATS`` etc.) must keep working regardless of the lever.
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import json
import logging
import os
import threading
import time

__all__ = ["enabled", "retrace_budget", "inc", "gauge", "observe", "value",
           "tagged", "gauge_value", "reset_metric", "span",
           "record_interval", "open_span",
           "record_d2h", "d2h_count",
           "record_retrace", "retrace_stats", "snapshot", "report",
           "events", "flush", "jsonl_path", "reset",
           "tracing_enabled", "TraceContext", "new_trace", "current_trace",
           "trace_handoff", "add_stage", "trace_mark", "link", "pend_link",
           "link_pending", "trace_breakdown", "trace_events", "trace_flows",
           "flight_record", "flight_snapshot", "prometheus",
           "on_flush", "register_prometheus_extra",
           "watch_compiles", "EVENT_RING_CAP"]

_log = logging.getLogger("mxtpu.telemetry")

# one short lock for every structural update; individual increments hold it
# for nanoseconds (the "lock-cheap host-side increment" contract)
_LOCK = threading.Lock()
_COUNTERS = {}            # (name, tag-or-None) -> float
_GAUGES = {}              # name -> float
_HISTS = {}               # name -> [count, sum, min, max, reservoir-deque]
# a reader that finds this many lost the head. JAX reports a trace for
# every jnp call under a trace, cached or not: one step with six pairs of
# KDA kernels under recomputation is 63,258 events before its first call
EVENT_RING_CAP = 262144
_EVENTS = collections.deque(maxlen=EVENT_RING_CAP)
#                         ^ (name, cat, ts_us, dur_us, tid)
_RESERVOIR = 2048         # per-histogram quantile sample bound

# retrace watchdog: site -> {"compiles", "trips", "last"}
_RETRACE = {}
# transfer watchdog: hot-loop span names already warned about
_D2H_WARNED = set()
_D2H_WARMUP = 2           # first occurrences of a span may legitimately sync


class _D2HLocal(threading.local):
    """Per-thread d2h sync count. Span attribution reads THIS, not the
    global counter: a span times a host region on its own thread, so a
    concurrent server thread's ``asnumpy`` (the serving fetch path) must
    not land in another thread's ``<name>.d2h`` delta. The global
    ``transfer.d2h`` counter still aggregates every thread."""

    def __init__(self):
        self.count = 0


_D2H_LOCAL = _D2HLocal()


class _OpenLocal(threading.local):
    """The innermost span open on this thread (None outside any): what a
    JAX compile event that fires on the thread is tagged with."""

    def __init__(self):
        self.span = None


_OPEN = _OpenLocal()

# jax.profiler's (TraceAnnotation, StepTraceAnnotation) once
# ``watch_compiles`` imported them (``import mxtpu`` calls it): this module
# itself imports nothing of JAX when it is imported (the flight recorder
# runs in processes that are dying)
_TRACE_ME = None
# jax.monitoring's compile events -> (ring event, counter compile.<x>_s)
_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jax.trace", "trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("jax.lower", "lower"),
    "/jax/core/compile/backend_compile_duration":
        ("jax.backend_compile", "backend"),
}
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# JSONL sink: hot path appends to the queue; a flush (explicit, atexit, or
# the off-thread timer) drains it to the file
_SINK = {"queue": collections.deque(maxlen=1 << 20), "thread": None,
         "atexit": False, "lock": threading.Lock()}

# extension points (mxtpu/fleet_obs.py rides both): flush hooks run after
# every sink flush — periodic, explicit, AND the atexit/SIGTERM final one;
# prometheus extras append provider output to the /metrics exposition
_FLUSH_HOOKS = []
_PROM_EXTRAS = []

# ---- causal tracing state ----
# current trace context (None outside any trace); contextvars are
# per-thread by construction, which is exactly the handoff discipline:
# a trace crosses a thread boundary ONLY through trace_handoff()
_TRACE_CV = contextvars.ContextVar("mxtpu_trace", default=None)
_SPAN_IDS = itertools.count(1)   # process-global span ids (GIL-atomic)
_TRACE_IDS = itertools.count(1)
_TRACE_PREFIX = "%04x" % (os.getpid() & 0xFFFF)


def _trace_ring_cap():
    try:
        return int(os.environ.get("MXTPU_TRACE_RING", "4096"))
    except ValueError:
        return 4096


# flight-recorder ring: (kind, trace_id, span_id, parent, name, ts_us,
# dur_us, tid) tuples; parent is a span id for kind=="span", a
# (trace_id, span_id) source pair for kind=="link"
_TRACE_EVENTS = collections.deque(maxlen=_trace_ring_cap())
# consumer -> next-trace link handoffs (data.wait / data.h2d): the step
# trace that CONSUMES a batch drains these into link events. THREAD-LOCAL:
# both pend (loader __next__) and drain (Trainer.step) happen on the
# consuming thread, and a process-global queue would let a background
# thread's loader events misattribute to the foreground thread's step
class _PendingLocal(threading.local):
    def __init__(self):
        self.q = collections.deque(maxlen=64)


_PENDING_LINKS = _PendingLocal()
_FLIGHT = {"count": 0, "lock": threading.Lock()}


# ------------------------------------------------------------------ policies
def enabled():
    """Span/event/sink machinery lever: ``MXTPU_TELEMETRY`` default ON
    (read per call, like every other A/B lever, so bench can flip it
    mid-process). ``0`` disables spans; bare counters stay always-on."""
    return os.environ.get("MXTPU_TELEMETRY", "1") != "0"


def jsonl_path():
    """``MXTPU_TELEMETRY`` doubles as the sink switch: any value other
    than ``0``/``1`` is a JSONL path observations stream to."""
    v = os.environ.get("MXTPU_TELEMETRY", "1")
    return v if v not in ("0", "1") else None


def tracing_enabled():
    """Causal-tracing lever: ``MXTPU_TRACE`` default ON (requires the
    span machinery, so ``MXTPU_TELEMETRY=0`` implies off). Tracing is
    pure host bookkeeping — an id allocation, a contextvar set, and a
    bounded ring append per span — so the zero-host-sync and
    ``trainer.step.d2h == 0`` contracts hold with it ON (pinned by the
    transfer-guard test parametrized over this var)."""
    return os.environ.get("MXTPU_TRACE", "1") != "0" and enabled()


def flight_dir():
    """Flight-recorder artifact directory (``MXTPU_FLIGHT_DIR``). Unset
    or empty = no files are written (the in-memory ring and
    :func:`flight_snapshot` still work); triggers call
    :func:`flight_record` unconditionally and it no-ops here."""
    return os.environ.get("MXTPU_FLIGHT_DIR") or None


def flight_max():
    """Dump cap per process (``MXTPU_FLIGHT_MAX``, default 16): a
    repeatedly-tripping watchdog must not fill the disk with thousands
    of near-identical artifacts."""
    try:
        return int(os.environ.get("MXTPU_FLIGHT_MAX", "16"))
    except ValueError:
        return 16


def retrace_budget():
    """Compiles a single jit-cache site may accumulate before the retrace
    watchdog warns (``MXTPU_RETRACE_BUDGET``, default 64 — far above any
    legitimate warmup, low enough to catch a per-step recompile within
    the first minute)."""
    return int(os.environ.get("MXTPU_RETRACE_BUDGET", "64"))


def _flush_interval():
    """Off-thread flush period in seconds (``MXTPU_TELEMETRY_FLUSH_S``);
    0 (default) = no background thread — flush happens on
    :func:`flush` and at interpreter exit."""
    try:
        return float(os.environ.get("MXTPU_TELEMETRY_FLUSH_S", "0"))
    except ValueError:
        return 0.0


# ----------------------------------------------------------------- registry
def inc(name, n=1, tag=None):
    """Add ``n`` to a counter. ``tag`` keys a labeled sub-counter (e.g.
    pallas fallback reasons). Always-on: a single locked dict update."""
    k = (name, tag)
    with _LOCK:
        _COUNTERS[k] = _COUNTERS.get(k, 0) + n


def gauge(name, v, tag=None):
    """Set a gauge to the latest value (last-write-wins). ``tag`` keys a
    labeled sub-gauge (e.g. the per-device ``memory.hbm_*_bytes{device}``
    family) exactly like counter tags."""
    with _LOCK:
        _GAUGES[(name, tag)] = float(v)


def observe(name, v):
    """Record one histogram observation (span durations land here)."""
    v = float(v)
    with _LOCK:
        h = _HISTS.get(name)
        if h is None:
            h = [0, 0.0, v, v, collections.deque(maxlen=_RESERVOIR)]
            _HISTS[name] = h
        h[0] += 1
        h[1] += v
        h[2] = min(h[2], v)
        h[3] = max(h[3], v)
        h[4].append(v)
    p = jsonl_path()
    if p is not None:
        _queue_line({"t": time.time(), "kind": "obs", "metric": name,
                     "value": v}, p)


def value(name, tag=None):
    """Current counter value (0 when never incremented); with no ``tag``
    and no untagged entry, the sum across tags."""
    with _LOCK:
        v = _COUNTERS.get((name, tag))
        if v is not None or tag is not None:
            return v or 0
        return sum(v for (n, t), v in _COUNTERS.items()
                   if n == name and t is not None) or 0


def tagged(name):
    """``{tag: value}`` over a labeled counter family."""
    with _LOCK:
        return {t: v for (n, t), v in _COUNTERS.items()
                if n == name and t is not None}


def gauge_value(name, tag=None):
    """Current gauge value, or None when never set (gauges are
    last-write-wins, so unlike :func:`value` there is no meaningful
    zero default or cross-tag sum)."""
    with _LOCK:
        return _GAUGES.get((name, tag))


def reset_metric(name):
    """Zero one metric (counters incl. tags, gauge, histogram) — the
    adopted stats views (``reset_dispatch_stats``) use this; it must NOT
    clear the rest of the registry."""
    with _LOCK:
        for k in [k for k in _COUNTERS if k[0] == name]:
            del _COUNTERS[k]
        for k in [k for k in _GAUGES if k[0] == name]:
            del _GAUGES[k]
        _HISTS.pop(name, None)


def _quantile(sorted_vals, q):
    n = len(sorted_vals)
    if n == 0:
        return None
    pos = q * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def snapshot():
    """Structured aggregate view of everything the registry holds."""
    with _LOCK:
        by_name = {}
        for (name, tag), v in _COUNTERS.items():
            by_name.setdefault(name, {})[tag] = v
        # pure-untagged collapses to a scalar; a name incremented BOTH
        # ways keeps every entry (untagged under "_untagged") — mixing
        # must not silently drop either form from the aggregate view
        counters = {}
        for name, tags in by_name.items():
            if set(tags) == {None}:
                counters[name] = tags[None]
            else:
                counters[name] = {
                    ("_untagged" if t is None else t): v
                    for t, v in tags.items()}
        g_by_name = {}
        for (name, tag), v in _GAUGES.items():
            g_by_name.setdefault(name, {})[tag] = v
        # same collapse rule as counters: pure-untagged gauges stay
        # scalars (every pre-existing consumer reads them that way),
        # tagged families become {tag: value} dicts
        gauges = {}
        for name, tags in g_by_name.items():
            if set(tags) == {None}:
                gauges[name] = tags[None]
            else:
                gauges[name] = {("_untagged" if t is None else t): v
                                for t, v in tags.items()}
        hists = {}
        for name, (cnt, total, mn, mx, res) in _HISTS.items():
            vals = sorted(res)
            hists[name] = {"count": cnt, "sum": total, "mean": total / cnt,
                           "min": mn, "max": mx,
                           "p50": _quantile(vals, 0.5),
                           "p99": _quantile(vals, 0.99)}
        retrace = {site: dict(st) for site, st in _RETRACE.items()}
    snap = {"counters": counters, "gauges": gauges, "histograms": hists,
            "retrace": retrace}
    # executable-ledger export (mxtpu/xprof.py): the resolve-free view —
    # a /metrics scrape must never invoke the compiler
    from . import xprof
    if xprof.enabled():
        led = xprof.ledger_snapshot()
        if led:
            snap["ledger"] = led
    return snap


def report():
    """The aggregate table, profiler-dumps style: one call shows guard
    activity, dispatch routing, retries, and the step-phase timing without
    a log scrape."""
    snap = snapshot()
    lines = []
    if snap["histograms"]:
        lines.append("%-38s %8s %10s %10s %10s %10s" %
                     ("Span/Histogram", "Count", "Mean(ms)", "P50(ms)",
                      "P99(ms)", "Max(ms)"))
        for name in sorted(snap["histograms"],
                           key=lambda n: -snap["histograms"][n]["sum"]):
            h = snap["histograms"][name]
            lines.append("%-38s %8d %10.3f %10.3f %10.3f %10.3f" %
                         (name, h["count"], h["mean"] * 1e3,
                          (h["p50"] or 0) * 1e3, (h["p99"] or 0) * 1e3,
                          h["max"] * 1e3))
    if snap["counters"]:
        lines.append("")
        lines.append("%-38s %12s" % ("Counter", "Value"))
        for name in sorted(snap["counters"]):
            v = snap["counters"][name]
            if isinstance(v, dict):
                for tag in sorted(v):
                    lines.append("%-38s %12g" %
                                 ("%s{%s}" % (name, tag), v[tag]))
            else:
                lines.append("%-38s %12g" % (name, v))
    if snap["gauges"]:
        lines.append("")
        lines.append("%-38s %12s" % ("Gauge", "Value"))
        for name in sorted(snap["gauges"]):
            v = snap["gauges"][name]
            if isinstance(v, dict):
                for tag in sorted(v):
                    lines.append("%-38s %12g" %
                                 ("%s{%s}" % (name, tag), v[tag]))
            else:
                lines.append("%-38s %12g" % (name, v))
    if snap["retrace"]:
        lines.append("")
        lines.append("%-20s %9s %6s  %s" %
                     ("Retrace site", "Compiles", "Trips", "Last provenance"))
        for site in sorted(snap["retrace"]):
            st = snap["retrace"][site]
            lines.append("%-20s %9d %6d  %s" %
                         (site, st["compiles"], st["trips"],
                          st["last"]))
    return "\n".join(lines) if lines else "(telemetry registry empty)"


def events():
    """The bounded span-event ring — (name, cat, ts_us, dur_us, tid)
    tuples on the ``time.perf_counter_ns`` clock, the SAME clock and
    shape :mod:`mxtpu.profiler` records op events with, so
    ``profiler.dump()`` merges them into one chrome trace."""
    with _LOCK:
        return list(_EVENTS)


def reset():
    """Test hook: clear the whole registry, event ring, trace ring, and
    watchdog state (the sink file, if any, is left alone). The trace
    ring is re-created so a changed ``MXTPU_TRACE_RING`` takes effect."""
    global _TRACE_EVENTS
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTS.clear()
        _EVENTS.clear()
        _RETRACE.clear()
        _D2H_WARNED.clear()
        _TRACE_EVENTS = collections.deque(maxlen=_trace_ring_cap())
        _PENDING_LINKS.q.clear()  # the calling thread's (tests drain
        _FLIGHT["count"] = 0      # their own; other threads' are bounded)
    del _FLUSH_HOOKS[:]
    del _PROM_EXTRAS[:]
    from . import xprof
    xprof.reset()  # the executable ledger rides the registry lifecycle


# -------------------------------------------------------------------- spans
class span:
    """Context manager timing a host-side region into the histogram
    ``name`` (seconds) and the chrome-trace event ring. ``d2h=True``
    additionally attributes device->host syncs observed inside the region
    to ``<name>.d2h`` and arms the transfer watchdog: a steady-state
    occurrence (past the first ``_D2H_WARMUP``) that syncs at all warns
    once — the guarded hot loop's contract is ZERO.

    Causal tracing: when a :class:`TraceContext` is active on this
    thread (see :func:`new_trace` / :func:`trace_handoff`) the span joins
    the trace tree — it allocates a span id, becomes the current context
    for its body (children nest under it), and records one trace-ring
    event with its parent linkage on exit. ``new_trace=True`` starts a
    fresh trace when none is active (the per-request / per-step roots);
    with one already active it simply nests, preserving causality.

    Profiler mirror: the span also enters a ``jax.profiler.
    TraceAnnotation(name, cat=cat)`` (``step=n`` makes it a
    ``StepTraceAnnotation`` with ``step_num=n``: the per-step roots; the
    ``cat`` stat marks the event as one of the program's among JAX's own
    host events), so whenever ANY JAX trace is
    running the span lies on the ``.xplane.pb`` host plane, on the
    timeline of the device's ``XLA Ops`` line (a v5e trace showed device
    events up to half a millisecond ahead of the host call that enqueued
    them: good for gaps of milliseconds, not of microseconds); with no
    trace running it is one inactive ``TraceMe``.

    Pure host bookkeeping: no device ops, no syncs — safe under a
    ``jax.transfer_guard`` and inside the zero-sync Trainer.step contract.
    The enter/exit pair is hand-tuned for sub-millisecond hot loops: ONE
    env read (lever + sink path resolved together), ONE lock acquisition
    on exit (histogram + event ring inline), lock-free d2h snapshot.
    """

    __slots__ = ("name", "cat", "_d2h", "_t0", "_d0", "_sink",
                 "_new_trace", "_parent", "_tok", "ctx", "_step", "_ann",
                 "_outer")

    def __init__(self, name, cat="phase", d2h=False, new_trace=False,
                 step=None):
        self.name = name
        self.cat = cat
        self._d2h = d2h
        self._new_trace = new_trace
        self._step = step
        self._ann = None
        self._outer = None
        self._t0 = None
        self._d0 = None
        self._sink = None
        self._parent = None
        self._tok = None
        self.ctx = None

    def __enter__(self):
        lever = os.environ.get("MXTPU_TELEMETRY", "1")
        if lever != "0":
            self._sink = lever if lever != "1" else None
            parent = _TRACE_CV.get()
            if parent is None and self._new_trace \
                    and os.environ.get("MXTPU_TRACE", "1") != "0":
                parent = new_trace()
            if parent is not None:
                self._parent = parent.span_id
                self.ctx = TraceContext(parent.trace_id, next(_SPAN_IDS),
                                        parent._stages)
                self._tok = _TRACE_CV.set(self.ctx)
            self._outer = _OPEN.span
            _OPEN.span = self
            tm = _TRACE_ME or watch_compiles()
            # the ``cat`` stat is what tells the program's spans from
            # JAX's own host events in a trace (tools/perf_trace.py)
            self._ann = ann = tm[0](self.name, cat=self.cat) \
                if self._step is None \
                else tm[1](self.name, step_num=self._step, cat=self.cat)
            ann.__enter__()
            self._t0 = time.perf_counter_ns()
            if self._d2h:
                # thread-local snapshot: only syncs issued by THIS thread
                # inside the region are attributed — concurrent server
                # threads cannot corrupt another span's delta
                self._d0 = _D2H_LOCAL.count
        return self

    def __exit__(self, *exc):
        t0 = self._t0
        if t0 is None:
            return False
        dur_ns = time.perf_counter_ns() - t0
        self._ann.__exit__(None, None, None)
        _OPEN.span = self._outer
        v = dur_ns * 1e-9
        name = self.name
        if self._tok is not None:
            _TRACE_CV.reset(self._tok)
            self._tok = None
            _TRACE_EVENTS.append(
                ("span", self.ctx.trace_id, self.ctx.span_id, self._parent,
                 name, t0 // 1000, dur_ns // 1000,
                 threading.get_ident() & 0xFFFF))
        with _LOCK:
            h = _HISTS.get(name)
            if h is None:
                h = [0, 0.0, v, v, collections.deque(maxlen=_RESERVOIR)]
                _HISTS[name] = h
            h[0] += 1
            h[1] += v
            if v < h[2]:
                h[2] = v
            if v > h[3]:
                h[3] = v
            h[4].append(v)
            occurrences = h[0]
            _EVENTS.append((name, self.cat, t0 // 1000, dur_ns // 1000,
                            threading.get_ident() & 0xFFFF))
        if self._sink is not None:
            rec = {"t": time.time(), "kind": "obs", "metric": name,
                   "value": v}
            if self.ctx is not None:
                # trace linkage rides the SAME obs line (old readers
                # ignore the extra keys): tools/telemetry_report.py
                # rebuilds per-trace critical paths from these
                rec["trace"] = self.ctx.trace_id
                rec["span"] = self.ctx.span_id
                rec["parent"] = self._parent
            _queue_line(rec, self._sink)
        if self._d0 is not None:
            delta = _D2H_LOCAL.count - self._d0
            if delta:
                inc(name + ".d2h", delta)
                self._watchdog(delta, occurrences)
        self._t0 = None
        return False

    def _watchdog(self, delta, occurrences):
        with _LOCK:
            if occurrences <= _D2H_WARMUP or self.name in _D2H_WARNED:
                return
            _D2H_WARNED.add(self.name)
        _log.warning(
            "transfer watchdog: %d device->host sync(s) inside '%s' after "
            "warmup (occurrence %d) — the hot loop should be transfer-free; "
            "fetch verdicts/metrics asynchronously off the step path "
            "(docs/observability.md)", delta, self.name, occurrences)


def record_interval(name, t0_ns, cat="phase"):
    """Record the interval from ``t0_ns`` (a ``time.perf_counter_ns()``
    read the caller took earlier) to now as if a span ``name`` had been
    open over it: one histogram observation, one ring event and, under an
    active trace, one trace-ring event below the current context. For a
    region no ``with`` can wrap: ``import mxtpu`` from its first line to
    its last (``mxtpu.import``). No ``TraceAnnotation``: a profiler cannot
    be told of an interval that has already begun."""
    dur_ns = time.perf_counter_ns() - t0_ns
    if not enabled():
        return
    tid = threading.get_ident() & 0xFFFF
    ctx = _TRACE_CV.get()
    if ctx is not None:
        _TRACE_EVENTS.append(("span", ctx.trace_id, next(_SPAN_IDS),
                              ctx.span_id, name, t0_ns // 1000,
                              dur_ns // 1000, tid))
    observe(name, dur_ns * 1e-9)
    with _LOCK:
        _EVENTS.append((name, cat, t0_ns // 1000, dur_ns // 1000, tid))


# ------------------------------------------------------- JAX compile events
def watch_compiles():
    """Import ``jax.profiler`` and register this module's ONE pair of
    ``jax.monitoring`` listeners; idempotent. ``import mxtpu`` calls it as
    soon as this module is there, so that the ring and the counters hold
    every compile of the process, those before the first span too (a
    restart's parameter load and optimizer state); a span or an entry
    point may call it again at no cost. Every Python trace, lowering and
    backend compile JAX reports then lands, where it happens:

    * in the event ring (and, with tracing on, the trace ring, under the
      context open on the compiling thread) as ``jax.trace`` /
      ``jax.lower`` / ``jax.backend_compile`` with ``ts = now - secs``.
      A jitted function traced inside another reports its own
      ``jax.trace``, nested in the outer one's interval: add intervals
      up by their union, not by their sum;
    * in the counters ``compile.trace_s`` / ``compile.lower_s`` /
      ``compile.backend_s`` (seconds; nested traces counted once each,
      so ``trace_s`` is an upper bound) and ``compile.xla_cache_hits``
      (backend compiles JAX's persistent cache served), tagged with the
      innermost span open on that thread, ``untraced`` when none.

    Returns ``(TraceAnnotation, StepTraceAnnotation)``."""
    global _TRACE_ME
    import jax.profiler as jp
    from jax import monitoring
    with _LOCK:
        if _TRACE_ME is None:
            monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            monitoring.register_event_listener(_on_jax_event)
            _TRACE_ME = (jp.TraceAnnotation, jp.StepTraceAnnotation)
    return _TRACE_ME


def open_span():
    """Name of the innermost span open on this thread, None outside any."""
    sp = _OPEN.span
    return None if sp is None else sp.name


def _on_jax_duration(event, secs, **_):
    names = _JAX_DURATIONS.get(event)
    if names is None:
        return
    inc("compile.%s_s" % names[1], secs, tag=open_span() or "untraced")
    if not enabled():
        return
    dur_us = int(secs * 1e6)
    ts_us = time.perf_counter_ns() // 1000 - dur_us
    tid = threading.get_ident() & 0xFFFF
    if tracing_enabled():
        ctx = _TRACE_CV.get()
        _TRACE_EVENTS.append(
            ("span", None if ctx is None else ctx.trace_id,
             next(_SPAN_IDS), None if ctx is None else ctx.span_id,
             names[0], ts_us, dur_us, tid))
    with _LOCK:
        _EVENTS.append((names[0], "compile", ts_us, dur_us, tid))


def _on_jax_event(event, **_):
    if event == _JAX_CACHE_HIT:
        inc("compile.xla_cache_hits", tag=open_span() or "untraced")


# ----------------------------------------------------------- causal tracing
class TraceContext:
    """One position in a trace tree: ``trace_id`` (process-prefixed hex
    string) + ``span_id`` (globally unique int; 0 = the trace root).
    Contexts are immutable hand-off tokens: :class:`span` derives a child
    for its body, :func:`trace_handoff` adopts one on another thread.
    ``_stages`` is the per-TRACE accumulator shared by every context of
    the trace — :func:`add_stage` appends (stage, seconds) pairs there
    and :func:`trace_breakdown` folds them into the latency breakdown a
    served request returns."""

    __slots__ = ("trace_id", "span_id", "_stages")

    def __init__(self, trace_id, span_id, stages):
        self.trace_id = trace_id
        self.span_id = span_id
        self._stages = stages

    def __repr__(self):
        return "TraceContext(%s, span=%d)" % (self.trace_id, self.span_id)


def new_trace():
    """Root context for a fresh trace (None when tracing is off). The
    per-request / per-step entry points call this; everything below them
    nests via :class:`span` or joins via :func:`trace_handoff`."""
    if not tracing_enabled():
        return None
    return TraceContext("%s-%x" % (_TRACE_PREFIX, next(_TRACE_IDS)), 0, [])


def current_trace():
    """This thread's active context (None outside any trace)."""
    return _TRACE_CV.get()


class trace_handoff:
    """Adopt ``ctx`` as the current trace for a ``with`` body — THE way a
    trace crosses a thread boundary (contextvars do not follow threads,
    by design: implicit inheritance would attribute a worker's whole
    lifetime to whichever request was live when it spawned). ``ctx`` may
    be None (tracing off / untraced caller): the handoff is a no-op, so
    call sites stay unconditional."""

    __slots__ = ("_ctx", "_tok")

    def __init__(self, ctx):
        self._ctx = ctx
        self._tok = None

    def __enter__(self):
        if self._ctx is not None:
            self._tok = _TRACE_CV.set(self._ctx)
        return self._ctx

    def __exit__(self, *exc):
        if self._tok is not None:
            _TRACE_CV.reset(self._tok)
            self._tok = None
        return False


def add_stage(ctx, name, dur_s, event=False):
    """Credit ``dur_s`` seconds of stage ``name`` to ``ctx``'s trace
    breakdown (None-safe). ``event=True`` additionally records a trace
    event under ``ctx`` — used for stages measured OUTSIDE a span body
    (queue-wait is an interval between threads, not a code region).
    Batch-level stages (pad/predict/fetch) are credited to every cohort
    member's breakdown but recorded as ONE event under the lead trace:
    each request's numbers stay per-request, the tree stays deduplicated."""
    if ctx is None:
        return
    ctx._stages.append((name, float(dur_s)))
    if event:
        now_us = time.perf_counter_ns() // 1000
        dur_us = int(dur_s * 1e6)
        sid = next(_SPAN_IDS)
        _TRACE_EVENTS.append(
            ("span", ctx.trace_id, sid, ctx.span_id, name,
             max(0, now_us - dur_us), dur_us,
             threading.get_ident() & 0xFFFF))
        p = jsonl_path()
        if p is not None:
            # interval stages reach the sink like span observations do,
            # so the per-trace critical path (telemetry_report --traces)
            # sees queue-wait next to the span stages
            _queue_line({"t": time.time(), "kind": "obs", "metric": name,
                         "value": float(dur_s), "trace": ctx.trace_id,
                         "span": sid, "parent": ctx.span_id}, p)


def trace_mark(ctx, name):
    """Zero-duration marker event in ``ctx``'s trace (None-safe) — e.g.
    ``serving.redispatch`` when a wedged batch re-enters the queue."""
    if ctx is None:
        return
    _TRACE_EVENTS.append(
        ("mark", ctx.trace_id, next(_SPAN_IDS), ctx.span_id, name,
         time.perf_counter_ns() // 1000, 0,
         threading.get_ident() & 0xFFFF))


def link(src, name="link"):
    """Causal edge from ``src`` (a TraceContext on ANOTHER trace/thread)
    to the CURRENT context — rendered as a chrome-trace flow arrow by
    ``profiler.dump()``. No-op when either side is absent."""
    dst = _TRACE_CV.get()
    if src is None or dst is None:
        return
    _TRACE_EVENTS.append(
        ("link", dst.trace_id, dst.span_id, (src.trace_id, src.span_id),
         name, time.perf_counter_ns() // 1000, 0,
         threading.get_ident() & 0xFFFF))


def pend_link(name, ctx):
    """Queue a causal edge whose DESTINATION does not exist yet: the
    loader's ``__next__`` (on the CONSUMING thread) records the batch's
    ``data.h2d``/``data.wait`` contexts here, and the next
    ``trainer.step`` trace ON THE SAME THREAD drains them via
    :func:`link_pending` — the step that consumes a batch links the
    transfer that produced it. The queue is thread-local, so a
    background thread's loader can never pollute another thread's step;
    within one thread, iteration that never reaches a step (e.g. an
    interleaved un-stepped validation pass) attributes to the NEXT step
    drained there — the bounded queue caps how far that can drift."""
    if ctx is not None:
        _PENDING_LINKS.q.append((name, ctx.trace_id, ctx.span_id))


def link_pending():
    """Drain this thread's pended edges into link events targeting the
    current context. Returns the number of links emitted (0 outside a
    trace — the queue is cleared either way so stale edges never attach
    to an unrelated later step)."""
    dst = _TRACE_CV.get()
    q = _PENDING_LINKS.q
    n = 0
    while True:
        try:
            name, src_trace, src_span = q.popleft()
        except IndexError:
            break
        if dst is None:
            continue
        _TRACE_EVENTS.append(
            ("link", dst.trace_id, dst.span_id, (src_trace, src_span),
             name, time.perf_counter_ns() // 1000, 0,
             threading.get_ident() & 0xFFFF))
        n += 1
    return n


def trace_breakdown(ctx):
    """Fold ``ctx``'s stage accumulator into ``{stage: seconds}`` (empty
    when untraced). The serving path returns this per request; its values
    sum to ~the request's end-to-end latency (serve_bench's 5% gate)."""
    if ctx is None:
        return {}
    out = {}
    for name, dur in list(ctx._stages):
        out[name] = out.get(name, 0.0) + dur
    return out


def trace_events(trace_id=None):
    """Snapshot of the trace ring as dicts (optionally one trace's);
    ``parent`` is a span id for tree edges, ``{"trace", "span"}`` for
    cross-trace links."""
    out = []
    for kind, tr, sp, parent, name, ts, dur, tid in list(_TRACE_EVENTS):
        if trace_id is not None and tr != trace_id:
            continue
        rec = {"kind": kind, "trace": tr, "span": sp, "name": name,
               "ts_us": ts, "dur_us": dur, "tid": tid}
        if kind == "link":
            rec["parent"] = {"trace": parent[0], "span": parent[1]}
        else:
            rec["parent"] = parent
        out.append(rec)
    return out


def trace_flows(lo=None, hi=None):
    """Chrome-trace flow events (``ph: s/f`` pairs) for the trace ring's
    causal edges — parent→child span edges (cat ``trace``, flow id = the
    globally-unique child span id) and explicit cross-thread links (cat
    ``trace.link``, a fresh id per link: several links may target the
    SAME destination span, e.g. every cohort member linking the lead) —
    scoped to a ``[lo, hi]`` ts window like the rest of
    ``profiler.dump()``'s merge. A link whose source is a trace ROOT
    (span 0 — roots have no ring event of their own) anchors to that
    trace's earliest recorded event instead of being dropped."""
    evs = list(_TRACE_EVENTS)
    index = {}
    first_of_trace = {}
    for kind, tr, sp, parent, name, ts, dur, tid in evs:
        if kind != "link":
            index[(tr, sp)] = (ts, dur, tid)
            best = first_of_trace.get(tr)
            if best is None or ts < best[0]:
                first_of_trace[tr] = (ts, dur, tid)
    flows = []

    def _in_window(ts):
        return (lo is None or ts >= lo) and (hi is None or ts <= hi)

    for i, (kind, tr, sp, parent, name, ts, dur, tid) in enumerate(evs):
        if kind == "link":
            src = index.get(parent)
            if src is None and parent[1] == 0:
                src = first_of_trace.get(parent[0])
            if src is None or not _in_window(ts):
                continue
            s_ts, s_dur, s_tid = src
            link_id = (1 << 32) + i  # disjoint from span-id flow ids
            flows.append({"ph": "s", "cat": "trace.link", "name": name,
                          "id": link_id, "ts": s_ts + s_dur, "pid": 0,
                          "tid": s_tid})
            flows.append({"ph": "f", "bp": "e", "cat": "trace.link",
                          "name": name, "id": link_id, "ts": ts, "pid": 0,
                          "tid": tid})
        elif kind == "span" and parent:
            src = index.get((tr, parent))
            if src is None or not _in_window(ts):
                continue
            s_ts, _s_dur, s_tid = src
            # the parent span's X event starts at s_ts; arrow from the
            # parent's start to the child's start shows the causal tree
            # even when the child ran on another thread
            flows.append({"ph": "s", "cat": "trace", "name": name,
                          "id": sp, "ts": s_ts, "pid": 0, "tid": s_tid})
            flows.append({"ph": "f", "bp": "e", "cat": "trace",
                          "name": name, "id": sp, "ts": ts, "pid": 0,
                          "tid": tid})
    return flows


# ---------------------------------------------------------- flight recorder
def flight_snapshot(reason, trace_ids=(), extra=None):
    """The post-mortem dict: recent trace events, per-thread stacks, the
    registry snapshot, and the owning trace ids the trigger tagged
    (wedge/breaker/fault sites pass the affected requests' traces)."""
    import sys
    import traceback
    names = {t.ident: t.name for t in threading.enumerate()}
    stacks = []
    for tid, frame in sys._current_frames().items():
        stacks.append({"thread_id": tid,
                       "thread_name": names.get(tid, "?"),
                       "stack": traceback.format_stack(frame)})
    snap = {"reason": reason, "t": time.time(), "pid": os.getpid(),
            "trace_ids": list(trace_ids),
            "events": trace_events(),
            "threads": stacks,
            "registry": snapshot()}
    if extra:
        snap["extra"] = dict(extra)
    return snap


def flight_record(reason, trace_ids=(), extra=None):
    """Dump a :func:`flight_snapshot` JSON artifact to
    ``MXTPU_FLIGHT_DIR`` (no-op returning None when unset). Triggers:
    wedge-watchdog trips, circuit-breaker opens, retrace-watchdog first
    trips, injected faults, serving worker crashes, and SIGTERM. Bounded
    by ``MXTPU_FLIGHT_MAX`` dumps per process; the write is tmp+rename so
    a dump interrupted by the dying process never leaves a torn artifact."""
    d = flight_dir()
    if d is None:
        return None
    with _FLIGHT["lock"]:
        if _FLIGHT["count"] >= flight_max():
            return None
        _FLIGHT["count"] += 1
        seq = _FLIGHT["count"]
    try:
        os.makedirs(d, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "-_" else "_"
                       for c in str(reason))
        path = os.path.join(d, "flight_%s_%d_%d.json"
                            % (safe, os.getpid(), seq))
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(flight_snapshot(reason, trace_ids, extra), f)
        os.replace(tmp, path)
    except OSError as e:  # pragma: no cover - dump IO failure
        _log.warning("flight recorder dump failed: %s", e)
        return None
    inc("flight.dumps", tag=str(reason))
    _log.warning("flight recorder: dumped %s (reason=%s, traces=%s)",
                 path, reason, list(trace_ids) or "-")
    return path


# ------------------------------------------------------ prometheus rendering
def _prom_name(name):
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch in "_:" else "_")
    return "mxtpu_" + "".join(out)


def _prom_label(v):
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def prometheus():
    """The whole registry in Prometheus text exposition format 0.0.4:
    counters (tag families as a ``tag`` label), gauges, and histograms as
    summaries (``quantile`` 0.5/0.99 + ``_sum``/``_count``). The model
    server serves this on ``/metrics`` under ``Accept: text/plain`` so a
    stock Prometheus scraper needs no sidecar. Registered extras (e.g. a
    FleetObservatory's host-labeled fleet view) run FIRST — a provider
    that refreshes registry gauges lands them in this same scrape — and
    their output is appended after the registry's own families."""
    extras = []
    for fn in list(_PROM_EXTRAS):
        try:
            out = fn()
        except Exception as e:  # noqa: BLE001 — a broken provider must
            _log.warning("prometheus extra %r failed: %s", fn, e)
            continue           # not take down the scrape
        if out:
            extras.append(out.rstrip("\n"))
    snap = snapshot()
    lines = []
    for name in sorted(snap["counters"]):
        v = snap["counters"][name]
        pn = _prom_name(name)
        lines.append("# TYPE %s counter" % pn)
        if isinstance(v, dict):
            for tag in sorted(v):
                if tag == "_untagged":
                    lines.append("%s %g" % (pn, v[tag]))
                else:
                    lines.append('%s{tag="%s"} %g'
                                 % (pn, _prom_label(tag), v[tag]))
        else:
            lines.append("%s %g" % (pn, v))
    for name in sorted(snap["gauges"]):
        v = snap["gauges"][name]
        pn = _prom_name(name)
        lines.append("# TYPE %s gauge" % pn)
        if isinstance(v, dict):
            for tag in sorted(v):
                if tag == "_untagged":
                    lines.append("%s %g" % (pn, v[tag]))
                else:
                    lines.append('%s{tag="%s"} %g'
                                 % (pn, _prom_label(tag), v[tag]))
        else:
            lines.append("%s %g" % (pn, v))
    for name in sorted(snap["histograms"]):
        h = snap["histograms"][name]
        pn = _prom_name(name)
        lines.append("# TYPE %s summary" % pn)
        if h["p50"] is not None:
            lines.append('%s{quantile="0.5"} %g' % (pn, h["p50"]))
        if h["p99"] is not None:
            lines.append('%s{quantile="0.99"} %g' % (pn, h["p99"]))
        lines.append("%s_sum %g" % (pn, h["sum"]))
        lines.append("%s_count %d" % (pn, h["count"]))
    lines.extend(extras)
    return "\n".join(lines) + "\n"


def register_prometheus_extra(fn):
    """Register a zero-arg provider whose text-exposition output is
    appended to every :func:`prometheus` render (idempotent; cleared by
    :func:`reset`). Returns ``fn``."""
    if fn not in _PROM_EXTRAS:
        _PROM_EXTRAS.append(fn)
    return fn


# -------------------------------------------------------- transfer watchdog
def record_d2h(n=1):
    """Called from the NDArray sync points (``asnumpy`` and friends): one
    global device->host sync counter, always on, plus a thread-local count
    — spans opened with ``d2h=True`` attribute the THREAD-LOCAL delta to
    their region, so concurrent server threads (``mxtpu.serving``) cannot
    pollute the hot loop's per-region attribution."""
    inc("transfer.d2h", n)
    _D2H_LOCAL.count += n


def d2h_count():
    return value("transfer.d2h")


# --------------------------------------------------------- retrace watchdog
def record_retrace(site, provenance=None, compiled=None, compile_s=None):
    """Report one jit-cache compile at ``site`` with its cache-key
    provenance (optimizer class, ``registry.policy_key`` tuple, ...).
    Counts into ``retrace.<site>``; past :func:`retrace_budget` compiles
    the watchdog warns with the provenance and bumps
    ``retrace.watchdog_trips`` — a steady-state recompile means a policy
    env flipped mid-run or a cache key is unstable (shapes/hyper leaking
    into the static config), both of which silently serialize training
    behind the compiler.

    ``compiled=`` (ISSUE 12) hands the freshly-built executable to the
    :mod:`mxtpu.xprof` ledger: pass the jitted callable and CACHE THE
    RETURN VALUE — with the observatory on it comes back wrapped for
    first-dispatch compile timing, call counting, and lazy
    cost/memory-analysis capture (``MXTPU_XPROF=0`` returns it
    unchanged). Without ``compiled`` the call behaves exactly as before
    and returns None.

    ``compile_s=`` (the compile service's AOT path) carries an
    explicitly-measured lower+compile wall time: the executable arrives
    already compiled, so the wrapper must not re-time the first
    dispatch."""
    inc("retrace." + site)
    wrapped = None
    if compiled is not None:
        from . import xprof
        wrapped = xprof.attach(site, provenance, compiled,
                               compile_s=compile_s)
    budget = retrace_budget()
    with _LOCK:
        st = _RETRACE.setdefault(site,
                                 {"compiles": 0, "trips": 0, "last": None})
        st["compiles"] += 1
        st["last"] = provenance
        over = st["compiles"] > budget
        if over:
            st["trips"] += 1
        compiles = st["compiles"]
        trips = st["trips"]
    if over:
        inc("retrace.watchdog_trips")
        if trips == 1:
            # first trip at this site: capture the moment (the provenance
            # of the compile that blew the budget + who is on-stack)
            flight_record("retrace_watchdog",
                          extra={"site": site, "compiles": compiles,
                                 "provenance": str(provenance)})
        # rate-limit the LOG (the trip counter stays exact): the target
        # pathology is a recompile every step — warning each time would
        # flood hours of logs with the message meant to make them readable
        if trips != 1 and trips % 100 != 0:
            return wrapped
        _log.warning(
            "retrace watchdog: '%s' compiled %d times, over "
            "MXTPU_RETRACE_BUDGET=%d. Last provenance: %s. Steady-state "
            "recompiles usually mean a policy env var flipped mid-run or "
            "an unstable cache key — each one stalls every step behind "
            "the compiler (docs/observability.md)",
            site, compiles, budget, provenance)
    return wrapped


def retrace_stats(site=None):
    """Watchdog state: ``{site: {compiles, trips, last}}`` (or one
    site's dict / None)."""
    with _LOCK:
        if site is not None:
            st = _RETRACE.get(site)
            return dict(st) if st else None
        return {s: dict(st) for s, st in _RETRACE.items()}


# --------------------------------------------------------------- JSONL sink
def _queue_line(rec, path):
    _SINK["queue"].append((path, rec))
    interval = _flush_interval()
    if interval > 0 and _SINK["thread"] is None:
        with _SINK["lock"]:
            if _SINK["thread"] is None:
                t = threading.Thread(target=_flush_loop, args=(interval,),
                                     daemon=True, name="mxtpu-telemetry")
                _SINK["thread"] = t
                t.start()


def _flush_loop(interval):
    while True:
        time.sleep(interval)
        try:
            flush()
        except Exception:  # noqa: BLE001 — a sink error must never kill
            pass           # the flusher (next interval retries)


def flush():
    """Drain queued observations to the JSONL sink and append one
    cumulative line per counter/gauge. Off the hot path by construction
    (explicit call, atexit, or the off-thread timer)."""
    path = jsonl_path()
    lines_by_path = {}
    while True:
        try:
            p, rec = _SINK["queue"].popleft()
        except IndexError:
            break
        lines_by_path.setdefault(p, []).append(rec)
    if path is not None:
        now = time.time()
        with _LOCK:
            for (name, tag), v in _COUNTERS.items():
                rec = {"t": now, "kind": "counter", "metric": name,
                       "value": v}
                if tag is not None:
                    rec["tag"] = tag
                lines_by_path.setdefault(path, []).append(rec)
            for (name, tag), v in _GAUGES.items():
                rec = {"t": now, "kind": "gauge", "metric": name,
                       "value": v}
                if tag is not None:
                    rec["tag"] = tag
                lines_by_path.setdefault(path, []).append(rec)
        # executable-ledger lines (kind="ledger", cumulative like the
        # counters — tools/telemetry_report.py --ledger folds the last
        # line per (site, seq) into the roofline table). Resolve-free:
        # flush may run at interpreter exit, no compiler invocations.
        from . import xprof
        if xprof.enabled():
            for e in xprof.ledger_snapshot():
                lines_by_path.setdefault(path, []).append(
                    dict(e, t=now, kind="ledger"))
    with _SINK["lock"]:
        for p, recs in lines_by_path.items():
            try:
                with open(p, "a") as f:
                    for rec in recs:
                        f.write(json.dumps(rec) + "\n")
            except OSError as e:  # pragma: no cover - sink IO failure
                _log.warning("telemetry sink write to %s failed: %s", p, e)
    for fn in list(_FLUSH_HOOKS):
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — a broken hook must not
            _log.warning("flush hook %r failed: %s", fn, e)  # kill a flush


def on_flush(fn):
    """Register a zero-arg hook to run after every :func:`flush` —
    including the atexit/SIGTERM final one, which is how the fleet obs
    blob (mxtpu/fleet_obs.py) captures a dying host's last window.
    Idempotent; cleared by :func:`reset`. Returns ``fn``."""
    if fn not in _FLUSH_HOOKS:
        _FLUSH_HOOKS.append(fn)
    return fn


# Final-flush guarantee (ISSUE 19 satellite): registration used to be
# lazy inside _queue_line, so a process that only bumped counters (never
# queued an obs line) lost its cumulative counter/gauge lines even on a
# CLEAN exit — and the off-thread timer is a daemon, so exit-between-
# flushes lost the last window too. Register unconditionally at import:
# flush() with no sink configured is a cheap no-op.
import atexit  # noqa: E402  (deliberate: after flush is defined)

atexit.register(flush)
_SINK["atexit"] = True
