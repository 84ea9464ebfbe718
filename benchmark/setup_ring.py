"""What the readers of a restart share: set-up as the program's own ring
saw it. "Before the window" is every ring event (``span_ring``'s docstring
has their shape) that ended before the start of the window's first
``train_step`` root. Among them the program's spans (everything that is no
``jax.*`` compile event) are laid out as four phases and the step's first
build, disjoint by construction: an interval that two of them cover is
counted for the earlier one of

    ``mxtpu.import``                   the program's own import
    the first ``train_step.init``      leaves placed, optimizer state made
    the first ``train_step.build``     the three ``step_*_s`` lie in here
    ``train_step`` roots, ``ndarray.asnumpy``   the first steps, waited for
    ``gluon.param.set_data`` / ``.init``, ``gluon.cast``   weights loaded

``program`` is the union of EVERY program span before the window, whatever
its name: ``setup_s`` less its seconds is what ran under no span of the
program, so the five parts and that remainder add up to ``setup_s`` exactly
when no program code ran under a span that no phase counts.

A program without ``mxtpu.import`` in its ring (the parent of the PR that
brought these spans) has no restart to split, and every reader then returns
``None``; so does a ring ``span_ring.ring`` would not trust.
"""
import bisect

from benchmark import span_ring

IMPORT = "mxtpu.import"
INIT = span_ring.ROOT + ".init"
BUILD = span_ring.ROOT + ".build"
STEPS = (span_ring.ROOT, "ndarray.asnumpy")
LOAD = ("gluon.param.set_data", "gluon.param.init", "gluon.cast")
COMPILE = ("jax.trace", "jax.lower", "jax.backend_compile")


def merged(intervals):
    """The union of [start, end) intervals as a sorted list of disjoint
    ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def minus(a, b):
    """What of ``a`` lies outside ``b``; both as ``merged`` gives them."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def within(a, b):
    """What of ``a`` lies inside ``b``."""
    return minus(a, minus(a, b))


def seconds(intervals):
    return sum(e - s for s, e in intervals) / 1e6


def _holds(region, t):
    i = bisect.bisect_right(region, [t, float("inf")]) - 1
    return i >= 0 and region[i][0] <= t < region[i][1]


def split(ctx):
    """-> the restart's parts, or None. ``import``, ``step_init``,
    ``first_build``, ``first_steps`` and ``param_load`` are disjoint lists
    of [start_us, end_us); ``program`` is the union of every program span;
    ``eager`` is that union less every ``train_step.build``, the region in
    which a compile event is one of the program's small eager programs;
    ``compiles`` are the compile events ``(name, start_us, end_us)``."""
    found = span_ring.ring(ctx)
    if found is None:
        return None
    events, roots = found
    t0 = roots[0][0]
    head = [(n, ts, ts + dur) for n, _c, ts, dur, _t in events
            if ts + dur <= t0]
    spans = [ev for ev in head if ev[0] not in COMPILE]
    if not any(n == IMPORT for n, _s, _e in spans):
        return None

    def named(names, first=False):
        ivs = [[s, e] for n, s, e in spans if n in names]
        return ivs[:1] if first else ivs

    taken = []

    def claim(intervals):
        nonlocal taken
        part = minus(merged(intervals), taken)
        taken = merged(taken + part)
        return part

    parts = {"import": claim(named((IMPORT,), first=True)),
             "step_init": claim(named((INIT,), first=True)),
             "first_build": claim(named((BUILD,), first=True)),
             "first_steps": claim(named(STEPS)),
             "param_load": claim(named(LOAD))}
    parts["program"] = merged([[s, e] for _n, s, e in spans])
    parts["eager"] = minus(parts["program"], merged(named((BUILD,))))
    parts["compiles"] = [ev for ev in head if ev[0] in COMPILE]
    return parts


def phase_s(ctx, part):
    """Seconds of one part of ``split``; None where there is no restart to
    split, or where the program opened no such span."""
    parts = split(ctx)
    if parts is None or not parts[part]:
        return None
    return seconds(parts[part])


def eager_compiles(ctx):
    """-> (seconds, programs) of the compile events before the window that
    lie inside a program span other than a ``train_step.build``: the union
    of their intervals, cut to that region (a jitted function traced inside
    another reports its own trace, nested in the outer one's), and how
    many of them are backend compiles (or cache loads: one a program)."""
    parts = split(ctx)
    if parts is None:
        return None
    region = parts["eager"]
    every = merged([[s, e] for _n, s, e in parts["compiles"]])
    programs = sum(1 for n, s, e in parts["compiles"]
                   if n == "jax.backend_compile"
                   and _holds(region, (s + e) // 2))
    return seconds(within(every, region)), programs


def outside_program_s(ctx):
    """``setup_s`` less the seconds some span of the program was open."""
    parts = split(ctx)
    setup_s = ctx["window"]["end_to_end"].get("setup_s")
    if parts is None or setup_s is None:
        return None
    return setup_s - seconds(parts["program"])
