"""What the readers of the program's own spans share. The program
(``mxtpu.telemetry``) keeps every span it closed and every compile event
JAX reported in one bounded ring, ``telemetry.events()``: ``(name, cat,
start_us, duration_us, thread)`` in the order they ended, on the host's
``perf_counter`` clock. ``ShardedTrainStep.__call__`` is one ``train_step``
span with the children ``train_step.place`` / ``.rng`` / ``.build`` (a
rebuild) or ``.launch`` / ``.commit``; a Python trace, a lowering and a
backend compile are ``jax.trace`` / ``jax.lower`` / ``jax.backend_compile``.

The window's calls are the LAST ``attempted`` ``train_step`` spans of the
ring: after the window only the reference runs, which never calls the
step. A program without these spans (the parent of the PR that brought
them) has none, and every reader then returns ``None``.
"""
import statistics

from benchmark.trace_reduce import union_ns

ROOT = "train_step"
# ``run.py`` starts the profiler no earlier than 20% into the window
# (``TRACE_FROM``): the first 15% of the window's calls ran with it off,
# as the calls ``dispatch_ms.train`` counts did
HEAD = 0.15


def ring(ctx):
    """-> (events, the window's calls as [(start_us, end_us)]), or None:
    no window, a ring that has wrapped (it lost its head, and a reader
    cannot tell how much), or fewer calls in it than the window made."""
    from mxtpu import telemetry
    calls = ctx["window"].get("attempted")
    if not calls:
        return None
    events = telemetry.events()
    if len(events) >= getattr(telemetry, "EVENT_RING_CAP", 65536):
        return None
    roots = [(ts, ts + dur) for name, _c, ts, dur, _t in events
             if name == ROOT]
    if len(roots) < calls:
        return None
    return events, roots[-calls:]


def call_ms(ctx, name):
    """Median duration of the spans ``name`` that began inside the first
    ``HEAD`` of the window's calls, in milliseconds."""
    found = ring(ctx)
    if found is None:
        return None
    events, roots = found
    head = roots[:max(1, int(HEAD * len(roots)))]
    lo, hi = head[0][0], head[-1][1]
    durs = [dur for n, _c, ts, dur, _t in events
            if n == name and lo <= ts <= hi]
    return statistics.median(durs) / 1e3 if durs else None


def window_compiles(ctx):
    """Python traces and backend compiles that began between the start of
    the window's first call and the end of its last."""
    found = ring(ctx)
    if found is None:
        return None
    events, roots = found
    lo, hi = roots[0][0], roots[-1][1]
    return sum(1 for n, _c, ts, _d, _t in events
               if n in ("jax.trace", "jax.backend_compile")
               and lo <= ts <= hi)


def first_build_s(ctx, name):
    """Seconds of the events ``name`` inside the first ``train_step.build``
    (the step's first call, in set-up): the union of their intervals,
    because a function jitted inside another reports its own trace,
    nested in the outer one's."""
    found = ring(ctx)
    if found is None:
        return None
    events = found[0]
    builds = [(ts, ts + dur) for n, _c, ts, dur, _t in events
              if n == ROOT + ".build"]
    if not builds:
        return None
    lo, hi = builds[0]
    return union_ns([(ts, ts + dur) for n, _c, ts, dur, _t in events
                     if n == name and lo <= ts and ts + dur <= hi]) / 1e6
