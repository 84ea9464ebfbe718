"""Capture a jax.profiler device trace of the resnet50 train step, or read
one that is there, and print per-op time aggregates and, for the longest
idle gaps of the device, what the host was doing in them (PERF.md evidence).

    python tools/perf_trace.py                    # capture, then read
    python tools/perf_trace.py --read <dir|file>  # read a kept .xplane.pb

The capture goes through mxtpu.profiler's guarded path — bounded duration
(TRACE_MAX_S), atexit/SIGTERM stop. Only the process that holds the chip can
trace it, so run this script alone. Traces land under ``chiprun_out/`` (what
the chip tool brings back). Prefer the scan-fusion timing tools
(perf_peak/perf_stages/perf_bisect) when per-HLO data isn't needed.

The program's spans (``mxtpu.telemetry.span``) are ``TraceAnnotation``s, so
they lie on the trace's host plane, on one timeline with the device: a gap
of the ``XLA Ops`` line is attributed to the span open on the host when it
began (``ndarray.asnumpy``, ``train_step.rng``, ``serving.pad`` ...). The
two clocks agree to about half a millisecond (PERF.md, section 5): a gap of
milliseconds is the host's (a drained queue being refilled), one of
microseconds is the runtime's launch latency between queued programs,
whatever span a host that runs calls ahead happens to be in."""
import bisect
import glob
import os
import shutil
import sys
from collections import defaultdict

import jax
import jax.numpy as jnp

LOGDIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chiprun_out", "perf_trace")


def build_step():
    from mxtpu import gluon
    from mxtpu.parallel import pure_forward
    from mxtpu.ndarray import NDArray
    from perf_common import build_resnet

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    net, x, yl = build_resnet(batch)
    fn_t, params_t = pure_forward(net, train=True)
    loss_blk = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_of(p, xd, yd):
        out = fn_t(p, xd)
        return jnp.mean(loss_blk(NDArray(out), NDArray(yd))._data)

    @jax.jit
    def step(p, xd, yd):
        l, g = jax.value_and_grad(loss_of)(p, xd, yd)
        return [(w - 0.01 * gw.astype(w.dtype)) for w, gw in zip(p, g)], l

    return step, params_t, x._data, yl._data


def main():
    step, p, xd, yd = build_step()
    newp, l = step(p, xd, yd)
    float(l)  # ensure compiled + executed

    shutil.rmtree(LOGDIR, ignore_errors=True)
    from mxtpu import profiler
    profiler.set_config(filename=LOGDIR + "/host.json", profile_xla=True,
                        xla_trace_dir=LOGDIR,
                        xla_trace_max_s=float(os.environ.get("TRACE_MAX_S",
                                                             "120")))
    profiler.start()
    try:
        for _ in range(3):
            newp, l = step(p, xd, yd)
        float(l)
    finally:
        profiler.stop()

    files = glob.glob(LOGDIR + "/**/*.xplane.pb", recursive=True)
    print("xplane files:", files)
    if not files:
        return
    print_op_aggregates(files)
    print_gap_spans(files)


def _planes(path):
    from jax.profiler import ProfileData
    return list(ProfileData.from_file(path).planes)


def _line(plane, name):
    """The line of EXACTLY this name: 'Async XLA Ops' (the copies that run
    beside the operations) is another line than 'XLA Ops'."""
    for ln in plane.lines:
        if ln.name == name:
            return list(ln.events)
    return []


def print_op_aggregates(files, top=30):
    """Aggregate per-op device time from the xplane file with JAX alone
    (``jax.profiler.ProfileData``: planes > lines > timed events). A device
    plane carries envelope lines ('XLA Modules' spans all its ops, 'Steps'
    the step) on top of the per-op line: only 'XLA Ops' is summed. With no
    device plane (a CPU run) the host's XLA executor lines stand in."""
    agg = defaultdict(lambda: [0, 0.0])  # name -> [count, total_us]
    for path in files:
        planes = _planes(path)
        device = [p for p in planes if p.name.startswith("/device:")]
        events = [ev for p in device for ev in _line(p, "XLA Ops")] or \
                 [ev for p in planes for ln in p.lines if "XLA" in ln.name
                  for ev in ln.events]
        for ev in events:
            a = agg[ev.name]
            a[0] += 1
            a[1] += ev.duration_ns / 1e3
    rows = sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]
    total = sum(v[1] for _, v in agg.items())
    print("%-72s %8s %12s %6s" % ("op", "calls", "total_us", "%"))
    for name, (cnt, us) in rows:
        print("%-72s %8d %12.1f %6.2f"
              % (name[:72], cnt, us, 100 * us / max(total, 1e-9)))
    print("total device-time us:", round(total, 1))


def host_index(planes):
    """The host threads' events, ready for :func:`host_stacks`: per thread
    (name, events by start, and for each the latest end so far, which
    bounds how far back an open event can lie)."""
    index = []
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for ln in p.lines:
            # a span of the program carries the ``cat`` stat its
            # TraceAnnotation was given (mxtpu/telemetry.py); JAX's own
            # host events (PjitFunction(f), $file:1 f) do not
            evs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          ev.name, any(k == "cat" for k, _v in ev.stats))
                         for ev in ln.events)
            ends, latest = [], 0
            for _s, e, _n, _p in evs:
                latest = max(latest, e)
                ends.append(latest)
            if evs:
                index.append((ln.name, evs, ends))
    return index


def host_stacks(index, t_ns):
    """For each host thread with an event open at ``t_ns``: (thread, [the
    program's spans open then, outermost first], the innermost event of
    any kind)."""
    out = []
    for thread, evs, ends in index:
        i = bisect.bisect_right(evs, (t_ns, float("inf"))) - 1
        open_ = []
        while i >= 0 and ends[i] > t_ns:
            if evs[i][1] > t_ns:
                open_.append(evs[i])
            i -= 1
        if open_:
            open_.reverse()
            out.append((thread, [ev[2] for ev in open_ if ev[3]],
                        open_[-1][2]))
    return out


def print_gap_spans(files, top=12):
    """The longest idle gaps of each device's 'XLA Ops' line, with the
    modules on either side and what every host thread had open when the
    gap began; then the gaps added up by the program span they fell in."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.trace_reduce import gaps_ns, short
    for path in files:
        planes = _planes(path)
        index = host_index(planes)
        for p in planes:
            ops = _line(p, "XLA Ops") if p.name.startswith("/device:") else []
            if not ops:
                continue
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           short(ev.name, 40))
                          for ev in _line(p, "XLA Modules"))
            gaps = gaps_ns([(ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in ops])
            by_span = defaultdict(lambda: [0, 0.0])
            rows = []
            for start, length in gaps:
                before = [m for m in mods if m[0] <= start]
                after = [m for m in mods if m[1] >= start + length]
                stacks = host_stacks(index, start)
                spans = [s for _t, prog, _i in stacks for s in prog]
                inner = spans[-1] if spans else "(no program span open)"
                by_span[inner][0] += 1
                by_span[inner][1] += length / 1e3
                rows.append((length, before[-1][2] if before else "-",
                             after[0][2] if after else "-", inner, stacks))
            print("%s: %d gaps, %.1f us idle" % (
                p.name, len(gaps), sum(g[1] for g in gaps) / 1e3))
            print("%10s  %-40s %-40s %s" % ("gap_us", "after module",
                                            "before module",
                                            "host span open"))
            for length, a, b, inner, stacks in sorted(
                    rows, key=lambda r: -r[0])[:top]:
                print("%10.1f  %-40s %-40s %s" % (length / 1e3, a, b, inner))
                for thread, prog, last in stacks:
                    print("%12s%s: %s | innermost: %s" % (
                        "", thread, " > ".join(prog) or "-", last[:60]))
            print("%-40s %8s %12s" % ("host span open", "gaps", "idle_us"))
            for name, (n, us) in sorted(by_span.items(),
                                        key=lambda kv: -kv[1][1]):
                print("%-40s %8d %12.1f" % (name, n, us))


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--read":
        target = sys.argv[2]
        found = [target] if os.path.isfile(target) else glob.glob(
            target + "/**/*.xplane.pb", recursive=True)
        if not found:
            sys.exit("no .xplane.pb under %s" % target)
        print_op_aggregates(found)
        print_gap_spans(found)
    else:
        from perf_common import use_xla_cache
        use_xla_cache()
        main()
