"""One process, one cell, once:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object and nothing else;
everything further goes on earlier lines. Nothing here names a cell, a
configuration or a metric: a cell is an entry of ``BENCHMARK.json``, and
what belongs to one configuration, traffic mix or per-layer metric is a
file of its own that is found by its name (benchmark/README.md).
"""
import time
T_START = time.time()          # before the heavy imports: they are set-up

import argparse                # noqa: E402
import gc                      # noqa: E402
import importlib               # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import sys                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# a traced run traces this stretch of its window, as shares of ``--seconds``
TRACE_FROM, TRACE_SHARE = 0.2, 0.3


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """A cell as ``BENCHMARK.json`` and the files it names describe it."""

    def __init__(self, name, rehearse=False, spec=None):
        self.spec = spec or _json(ROOT, "BENCHMARK.json")
        rows = [w for w in self.spec["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit("no workload %r in BENCHMARK.json" % name)
        self.row = rows[0]
        self.name, self.chips = name, self.row["chips"]
        # what is too long for the output: check vectors, the trace
        self.out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", name)
        conf = [c for c in self.spec["configs"]
                if c["name"] == self.row["config"]][0]
        self.cfg = _json(ROOT, conf["file"])
        self.traffic = _json(HERE, "traffic", self.row["traffic"] + ".json")
        limits = _json(HERE, "limits", name + ".json")
        self.limits = limits["limits"]
        self.rehearse = rehearse
        if rehearse:
            self.limits = dict(self.limits, **limits.get("rehearsal", {}))
            self.cfg.update(self.cfg.get("rehearsal", {}))
            self.traffic.update(self.traffic.get("rehearsal", {}))
        self.end_to_end = [m for m in self.spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        mine = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in self.spec["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in mine]

    def module(self, kind):
        """``benchmark/<kind>/<name>.py``, the name being what the
        configuration (model, reference, flops) or the traffic (runners)
        gives under that key."""
        key = {"models": "model", "runners": "runner"}.get(kind, kind)
        name = (self.traffic if kind == "runners" else self.cfg)[key]
        return importlib.import_module("benchmark.%s.%s" % (kind, name))


def reader(metric):
    """The per-layer metric's own reader, ``layer_metrics/<metric>.py``."""
    path = os.path.join(HERE, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak(device_kind):
    table = _json(HERE, "peaks.json")
    if device_kind not in table:
        raise SystemExit("device kind %r is not in benchmark/peaks.json"
                         % device_kind)
    return table[device_kind]


def device_record(out=print):
    """The device as JAX reports it, read at the close of the window while
    the program's state is still held. ``memory_peak_bytes`` is one reading,
    true of one moment: ``bytes_in_use + bytes_reserved`` of the fullest
    chip then (this runtime keeps a loaded program's scratch, a whole
    training step's activations among it, under "reserved", apart from the
    buffers "in use"), or ``peak_bytes_in_use`` where that is larger. The
    parts are printed, so that what fills the chip can be seen."""
    import jax
    devs = jax.devices()
    held = []
    for d in devs:
        s = d.memory_stats() or {}
        held.append((max(s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0),
                         s.get("peak_bytes_in_use", 0)), d.id, s))
    peak, chip, stats = max(held, key=lambda h: h[:2])
    for key in ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
                "peak_bytes_reserved", "largest_free_block_bytes",
                "bytes_limit"):
        out("note memory.chip%d.%s = %r" % (chip, key, stats.get(key)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run_cell(cell, seed, seconds, trace, out=print):
    """Set-up, the measured window, then the comparison with the plain
    reference. Returns the result object."""
    from benchmark import trace_reduce
    from benchmark.compile_clock import CompileClock

    clock = CompileClock()
    runner = cell.module("runners")
    state = runner.setup(cell, seed)
    setup_s = time.time() - T_START
    # what set-up compiled: the reference, after the window, compiles too,
    # and that is no part of ``setup_s``
    compiled = clock.snapshot()
    tracer = trace_reduce.Tracer(
        os.path.join(cell.out_dir, "trace"), TRACE_FROM * seconds,
        TRACE_SHARE * seconds) if trace else None
    win = runner.window(state, seconds, tracer)
    device = device_record(out)
    win["end_to_end"]["setup_s"] = setup_s
    for name, value in sorted(win["end_to_end"].items()):
        out("measured %s = %r" % (name, value))
    for name, value in sorted(win.get("notes", {}).items()):
        out("note %s = %r" % (name, value))
    out("note setup_compile_s = %r" % compiled.seconds)
    out("note setup_xla_cache_hits = %r" % compiled.cache_hits)

    ctx = {"cell": cell, "window": win, "compile_clock": compiled,
           "device": device, "trace": None, "peak": None}
    if not cell.rehearse:
        ctx["peak"] = peak(device["kind"])
    if tracer is not None:
        ctx["trace"] = tracer.reduce()
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            # the whole operation table, too long for the result's line
            with open(os.path.join(cell.out_dir, "trace_ops.json"), "w") as f:
                json.dump({k: ctx["trace"][k] for k in (
                    "busy_s", "window_s", "planes", "modules", "ops")}, f)

    # the reference runs with the program's state freed, after the device's
    # peak was read, and outside both set-up and the window
    t0 = time.time()
    checks = runner.check(state)
    del state
    gc.collect()
    correct = True
    for name, value, limit in checks:
        ok = value <= limit           # a NaN compares false: not correct
        correct = correct and bool(ok)
        out("check %s = %.6g (limit %.6g) %s"
            % (name, value, limit, "ok" if ok else "NOT CORRECT"))
    after = clock.snapshot()
    out("note reference_s = %r" % (time.time() - t0))
    out("note reference_compile_s = %r" % (after.seconds - compiled.seconds))
    out("note reference_xla_cache_hits = %r"
        % (after.cache_hits - compiled.cache_hits))

    units = {m["name"]: m["unit"]
             for m in cell.spec["end_to_end"] + cell.spec["per_layer"]}
    if trace:
        values = {}
        for m in cell.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = v
    else:
        values = {m["name"]: win["end_to_end"][m["name"]]
                  for m in cell.end_to_end}
    if cell.rehearse:
        values = {}                   # a rehearsal measures nothing
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"],
              "metrics": {k: {"value": v, "unit": units[k]}
                          for k, v in values.items()},
              "device": device}
    if ctx["trace"] is not None:
        result["breakdown"] = {"device_ops": ctx["trace"]["top_ops"],
                               "idle_gaps": ctx["trace"]["top_gaps"]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's rehearsal sizes on whatever "
                         "backend there is; prints no metric")
    args = ap.parse_args(argv)
    found = sorted(k for k in os.environ
                   if k.startswith(("MXTPU_", "BENCH_")) and k != "BENCH_RUN")
    print("environment: MXTPU_*/BENCH_* variables set: %s" % (found or "none"))

    cell = Cell(args.workload, rehearse=args.rehearse)
    import jax
    from mxtpu import compile_service
    print("compile cache: %s" % compile_service.use_checkout_xla_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) != cell.chips):
        print("refused: cell %s needs %d TPU chip(s); found %d x %s"
              % (cell.name, cell.chips, len(devs), devs[0].platform),
              file=sys.stderr)
        return 3
    result = run_cell(cell, args.seed, args.seconds, args.trace)
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
