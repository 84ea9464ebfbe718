"""mx.profiler: op-level profiling with chrome://tracing output.

Reference: ``python/mxnet/profiler.py:33-291`` over the C++ profiler
(src/profiler/profiler.h — per-op events incl. engine queue time, chrome-trace
JSON dump, aggregate stats tables).

TPU-native re-design: eager op events are timed at the dispatch boundary
(ndarray._apply); compiled regions are one event per executable call — the
inside of a jit step is XLA's domain, so ``profile_xla=True`` additionally
starts the JAX/XLA profiler (TensorBoard trace with per-HLO timing), replacing
the reference's engine-level instrumentation. Dump format is chrome://tracing
JSON, same as the reference, plus ``aggregate_stats`` tables.
"""
from __future__ import annotations

import json
import os
import signal
import threading
import time
from collections import defaultdict

__all__ = ["set_config", "start", "stop", "pause", "resume", "dump", "dumps",
           "ProfileTask", "ProfileFrame", "ProfileEvent", "ProfileScope",
           "scope"]

class _Profiler:
    def __init__(self):
        self.active = False
        self.events = []          # (name, cat, ts_us, dur_us, tid)
        self.clear_gen = 0        # bumped whenever events are cleared
        self.lock = threading.Lock()
        self.filename = "profile.json"
        self.aggregate = True
        self.profile_xla = False
        self._xla_dir = None
        self._xla_tracing = False       # a jax device trace is live
        self._xla_max_s = 120.0         # hard bound on any device capture
        self._xla_watchdog = None
        self._xla_guard_installed = False
        self._xla_last_error = None     # last swallowed stop_trace error
        # profiled-window bounds (us, perf_counter clock) — dump() scopes
        # the always-on telemetry event ring to these
        self.window_start_us = None
        self.window_stop_us = None


_PROF = _Profiler()


def set_config(filename="profile.json", profile_all=False,
               profile_symbolic=True, profile_imperative=True,
               profile_memory=False, profile_api=False, aggregate_stats=True,
               profile_xla=False, xla_trace_dir=None, xla_trace_max_s=None,
               **_kwargs):
    """(ref: profiler.py:set_config — continuous_dump etc accepted via kwargs)"""
    _PROF.filename = filename
    _PROF.aggregate = aggregate_stats
    _PROF.profile_xla = profile_xla
    _PROF._xla_dir = xla_trace_dir or (filename + ".xla")
    # reset like every other field — a sticky bound from a previous
    # set_config would silently truncate later captures
    _PROF._xla_max_s = (120.0 if xla_trace_max_s is None
                        else float(xla_trace_max_s))


def _stop_xla_trace():
    """Idempotent device-trace stop, safe from any thread/signal context.

    A capture must not outlive the work it traces: a trace left running
    keeps buffering and its dump is never written. The reference's
    profiler is always-stoppable
    (src/profiler/profiler.h:256-437); this is the analog for the
    XLA-capture path: every exit route — normal stop(), atexit, SIGTERM/
    SIGINT, or the bounded-duration watchdog — funnels here, and only the
    first caller actually stops.
    """
    if not _PROF._xla_tracing:
        return
    _PROF._xla_tracing = False
    try:
        import jax
        jax.profiler.stop_trace()
        _PROF._xla_last_error = None
    except Exception as e:  # noqa: BLE001 — a stop must never raise, but
        # the swallowed reason stays inspectable (a failed stop usually
        # means no xplane dump was written)
        _PROF._xla_last_error = e


def _install_xla_guards():
    """atexit + SIGTERM/SIGINT hooks so an interrupted capture still sends
    stop_trace. SIGKILL cannot be caught; the bounded-duration watchdog
    (``xla_trace_max_s``) is what ends a capture whose workload hangs."""
    if _PROF._xla_guard_installed:
        return
    _PROF._xla_guard_installed = True
    import atexit
    atexit.register(_stop_xla_trace)
    if threading.current_thread() is not threading.main_thread():
        return  # signal handlers only installable from the main thread
    for signum in (signal.SIGTERM, signal.SIGINT):
        prev = signal.getsignal(signum)

        def handler(sig, frame, _prev=prev):
            _stop_xla_trace()
            if callable(_prev):
                _prev(sig, frame)
            elif _prev is signal.SIG_IGN:
                return  # the signal was deliberately ignored; keep it so
            else:
                signal.signal(sig, signal.SIG_DFL)
                os.kill(os.getpid(), sig)

        signal.signal(signum, handler)


def start():
    """(ref: profiler.py:set_state('run'))"""
    _PROF.active = True
    _PROF.window_start_us = time.perf_counter_ns() // 1000
    _PROF.window_stop_us = None
    if _PROF.profile_xla:
        import jax
        _install_xla_guards()
        jax.profiler.start_trace(_PROF._xla_dir)
        _PROF._xla_tracing = True
        # bounded duration: even if the profiled workload hangs (so the
        # user's own stop() is never reached), the capture ends and the
        # chip is released before any external watchdog resorts to SIGKILL
        t = threading.Timer(_PROF._xla_max_s, _stop_xla_trace)
        t.daemon = True
        t.start()
        _PROF._xla_watchdog = t


def stop():
    _PROF.active = False
    _PROF.window_stop_us = time.perf_counter_ns() // 1000
    if _PROF.profile_xla:
        w = _PROF._xla_watchdog
        _PROF._xla_watchdog = None
        if w is not None:
            w.cancel()
        _stop_xla_trace()
        if w is not None and w.is_alive():
            # the watchdog may have fired and be mid-write inside
            # stop_trace (it clears _xla_tracing BEFORE the write so later
            # stoppers no-op); stop() is synchronous like the reference's
            # profiler (src/profiler/profiler.h), so wait for the dump
            w.join(30)


def pause():
    _PROF.active = False


def resume():
    _PROF.active = True


def record_event(name, cat, ts_us, dur_us):
    """Called from the op dispatch path when profiling is on."""
    tid = threading.get_ident() & 0xFFFF
    with _PROF.lock:
        _PROF.events.append((name, cat, ts_us, dur_us, tid))


def is_active():
    return _PROF.active


def dumps(reset=False):
    """Aggregate statistics table as a string (ref: profiler.py:dumps)."""
    stats = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
    with _PROF.lock:
        events = list(_PROF.events)
        if reset:
            _PROF.events.clear()
            _PROF.clear_gen += 1
    for name, _cat, _ts, dur, _tid in events:
        s = stats[name]
        s[0] += 1
        s[1] += dur
        s[2] = min(s[2], dur)
        s[3] = max(s[3], dur)
    lines = ["%-40s %10s %12s %12s %12s %12s" %
             ("Name", "Calls", "Total(us)", "Avg(us)", "Min(us)", "Max(us)")]
    for name in sorted(stats, key=lambda n: -stats[n][1]):
        cnt, total, mn, mx = stats[name]
        lines.append("%-40s %10d %12.1f %12.1f %12.1f %12.1f" %
                     (name, cnt, total, total / cnt, mn, mx))
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):
    """Write chrome://tracing JSON (ref: profiler.py:dump; C++ emitter
    src/profiler/profiler.h:256-437).

    Telemetry spans (mxtpu/telemetry.py — trainer step phases, module
    forward/backward/update, data-wait, blocking syncs) are merged in with
    the same event shape and clock (``perf_counter_ns``-derived ts/dur),
    so ONE file shows the host phase timeline alongside the op events —
    and, with ``profile_xla``, alongside the XLA device trace. Trace-tree
    causality (parent/child span edges and explicit cross-thread links,
    ``telemetry.trace_flows``) rides along as chrome flow events
    (``ph: s/f``), so the timeline shows which thread's work BELONGS to
    which request/step instead of mere temporal overlap."""
    with _PROF.lock:
        events = list(_PROF.events)
    flows = []
    try:
        from . import telemetry
        tel = telemetry.events()
        # telemetry's span ring is ALWAYS-ON (MXTPU_TELEMETRY default 1),
        # unlike the window-gated op events — scope the merge to the
        # profiled window, or a 5-step trace after a long run would carry
        # the whole process lifetime on its time axis
        lo = _PROF.window_start_us
        hi = _PROF.window_stop_us
        if lo is not None:
            tel = [e for e in tel
                   if e[2] >= lo and (hi is None or e[2] <= hi)]
        events = events + tel
        flows = telemetry.trace_flows(lo, hi)
    except Exception:  # noqa: BLE001 — the op trace must dump regardless
        pass
    trace = {"traceEvents": [
        {"ph": "X", "name": name, "cat": cat, "ts": ts, "dur": dur,
         "pid": 0, "tid": tid}
        for name, cat, ts, dur, tid in events] + flows}
    with open(_PROF.filename, "w") as f:
        json.dump(trace, f)


# ------------------------------------------------------------ user scopes
class ProfileScope:
    """Context manager timing a custom region (ref: ProfileTask/Frame/Event,
    profiler.py:287+)."""

    def __init__(self, name, cat="user"):
        self.name = name
        self.cat = cat
        self._t0 = None

    def start(self):
        # gate at START: a scope opened while profiling is OFF records
        # nothing (no unbounded event growth from always-on bracketing),
        # while a scope opened during an active window is recorded even
        # if the profiler stops before the bracket closes (teardown must
        # not silently drop an in-flight measurement)
        if is_active():
            self._t0 = time.perf_counter_ns()
            self._gen = _PROF.clear_gen
        else:
            self._t0 = None

    def stop(self):
        if self._t0 is None:
            return
        # in-flight events survive a profiler STOP, but not a window
        # CLEAR (dumps(reset=True)): an event from before the clear would
        # leak into the next, unrelated window's table
        if is_active() or self._gen == _PROF.clear_gen:
            dur = (time.perf_counter_ns() - self._t0) // 1000
            record_event(self.name, self.cat, self._t0 // 1000, dur)
        self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()


def _domain_name(domain, name):
    """Tasks/frames in different domains must stay distinct rows in the
    aggregate table (ref MXProfileCreateTask keeps them apart)."""
    dn = getattr(domain, "name", None)
    return "%s:%s" % (dn, name) if dn else name


class ProfileTask(ProfileScope):
    def __init__(self, name, domain=None):
        super().__init__(_domain_name(domain, name), cat="task")


class ProfileFrame(ProfileScope):
    def __init__(self, name, domain=None):
        super().__init__(_domain_name(domain, name), cat="frame")


class ProfileEvent(ProfileScope):
    def __init__(self, name):
        super().__init__(name, cat="event")


def scope(name, cat="user"):
    return ProfileScope(name, cat)
