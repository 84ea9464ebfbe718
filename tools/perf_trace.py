"""Capture a jax.profiler device trace of the resnet50 train step and print
per-op time aggregates (PERF.md evidence).

The capture goes through mxtpu.profiler's guarded path — bounded duration
(TRACE_MAX_S), atexit/SIGTERM stop. Only the process that holds the chip can
trace it, so run this script alone. Traces land under ``chiprun_out/`` (what
the chip tool brings back). Prefer the scan-fusion timing tools
(perf_peak/perf_stages/perf_bisect) when per-HLO data isn't needed."""
import glob
import os
import shutil
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


def print_op_aggregates(files, top=30):
    """Aggregate per-op device time from the xplane file with JAX alone
    (``jax.profiler.ProfileData``: planes > lines > timed events)."""
    from jax.profiler import ProfileData

    agg = defaultdict(lambda: [0, 0.0])  # name -> [count, total_us]
    for path in files:
        all_planes = list(ProfileData.from_file(path).planes)
        # prefer device planes (/device:TPU:0 ...); fall back to the host
        # XLA executor lines when there is no device plane (CPU runs)
        planes = [p for p in all_planes if "/device:" in p.name] or \
                 [p for p in all_planes if any("XLA" in ln.name
                                               for ln in p.lines)]
        for p in planes:
            is_dev = "/device:" in p.name
            lines = list(p.lines)
            # a device plane carries envelope lines ('XLA Modules' spans
            # all its ops, 'Steps' spans the step) on top of the per-op
            # line — summing every line would count each us ~3x
            dev_lines = [ln for ln in lines if "XLA Ops" in ln.name] or \
                        [ln for ln in lines
                         if "Modules" not in ln.name and
                         "Steps" not in ln.name and "Source" not in ln.name]
            for ln in (dev_lines if is_dev else lines):
                if not is_dev and "XLA" not in ln.name:
                    continue
                for ev in ln.events:
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


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    main()
