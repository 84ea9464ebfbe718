"""From a profiler trace to numbers: the union of the intervals in which an
operation ran on the device (busy), the idle share, how long each compiled
module ran, the operations that took most time and the longest idle gaps.

Two stages, so that the arithmetic can be checked on a small recorded
trace: :func:`read_xplane` turns the profiler's ``.xplane.pb`` into plain
lists (with ``jax.profiler.ProfileData`` alone, as ``tools/perf_trace.py``
reads it), :func:`reduce` does the arithmetic on those lists.
"""
import glob
import os
import shutil
import time


def read_xplane(path):
    """-> {plane name: {line name: [[event name, start_ns, duration_ns]]}}
    for the device planes (``/device:TPU:0`` ...)."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        planes[plane.name] = {
            line.name: [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
            for line in plane.lines}
    return planes


def union_ns(intervals):
    """Total length of the union of [start, end) intervals."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_ns(intervals):
    """The idle gaps between the merged intervals: [(start, length)]."""
    out, end = [], None
    for s, e in sorted(intervals):
        if end is not None and s > end:
            out.append((end, s - end))
        end = e if end is None else max(end, e)
    return out


def _line(lines, name):
    """The line of exactly this name ('Async XLA Ops' is another line: the
    copies that run beside the operations)."""
    return lines.get(name, [])


def short(name, width=96):
    """An operation as the trace prints it, cut to its name: the trace
    gives the whole HLO instruction, thousands of characters of it."""
    return name.split(" = ")[0].lstrip("%")[:width]


def reduce(planes, top=10):
    """``planes`` as :func:`read_xplane` gives them. A device plane carries
    an 'XLA Ops' line (one event per operation) under envelope lines ('XLA
    Modules' spans a whole executable, 'Steps' a step): busy time is the
    union over the ops line only. Planes without an ops line (a chip's
    second core view, say) are left out; what remains is averaged. A gap
    is named by the module it lies inside, or by the modules on either
    side of it (what the host was doing in it needs the program's spans on
    this clock), and gaps of one name are added up. ``ops`` is the whole
    operation table, seconds by name (mean over the planes, like
    ``top_ops``, which is its ``top`` longest); ``modules`` holds every
    plane's runs, so a module's runs a chip are its runs over ``planes``."""
    per_chip, modules, ops, gaps = [], {}, {}, {}
    for _name, lines in sorted(planes.items()):
        events = _line(lines, "XLA Ops")
        if not events:
            continue
        spans = [(s, s + d) for _n, s, d in events]
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
        per_chip.append((union_ns(spans), hi - lo))
        for name, _s, d in events:
            ops[short(name)] = ops.get(short(name), 0) + d
        mods = sorted((s, s + d, short(n))
                      for n, s, d in _line(lines, "XLA Modules"))
        for name, _s, d in _line(lines, "XLA Modules"):
            modules.setdefault(short(name), []).append(d / 1e9)
        for start, length in gaps_ns(spans):
            end = start + length
            # the module running (or last to have run) where the gap opens,
            # and the one running (or next to run) where it closes
            a = [m for m in mods if m[0] <= start]
            b = [m for m in mods if m[1] >= end and (not a or m >= a[-1])]
            if a and b and a[-1] is b[0]:
                name = "inside " + a[-1][2]
            else:
                name = "between %s and %s" % (a[-1][2] if a else "the start",
                                              b[0][2] if b else "the end")
            gaps[name] = gaps.get(name, 0) + length
    if not per_chip:
        return None
    n = len(per_chip)
    busy = sum(b for b, _ in per_chip) / n / 1e9
    window = sum(w for _, w in per_chip) / n / 1e9

    def ranked(table):
        return [[k, v / n / 1e9] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy, "window_s": window, "planes": n,
            "idle_share": 1.0 - busy / window, "modules": modules,
            "ops": {k: v / n / 1e9 for k, v in ops.items()},
            "top_ops": ranked(ops), "top_gaps": ranked(gaps)}


class Tracer:
    """Traces ``for_s`` seconds of the window, from ``after_s`` on. The
    runner calls :meth:`tick` with the window's elapsed seconds wherever it
    can afford the call; :meth:`stop` at the end of the window at the
    latest."""

    def __init__(self, directory, after_s, for_s):
        self.dir, self.after_s, self.for_s = directory, after_s, for_s
        self.started_at = None
        self.done = False
        self.overhead_s = 0.0     # spent starting and stopping the profiler

    @property
    def running(self):
        return self.started_at is not None and not self.done

    def tick(self, elapsed):
        import jax
        if self.done:
            return
        if self.started_at is None:
            if elapsed >= self.after_s:
                shutil.rmtree(self.dir, ignore_errors=True)
                os.makedirs(self.dir, exist_ok=True)
                t0 = time.perf_counter()
                jax.profiler.start_trace(self.dir)
                self.started_at = time.perf_counter()
                self.overhead_s += self.started_at - t0
        elif time.perf_counter() - self.started_at >= self.for_s:
            self.stop()

    def stop(self):
        import jax
        if self.started_at is not None and not self.done:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.overhead_s += time.perf_counter() - t0
        self.done = True

    def reduce(self, keep=False):
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        out = reduce(read_xplane(files[0]))
        if not keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return out
