"""How ``data/recorded_planes.json`` was made, on the chip:

    python3 -m benchmark.tests.record_planes <cell> <seed>

Runs the cell's traced run, keeps the profiler's file, reads it with
``trace_reduce.read_xplane`` and cuts it to the first few hundred operation
events of chip 0 (with the module and step events that overlap them),
beside what ``trace_reduce.reduce`` gives on the cut.
"""
import glob
import json
import os
import sys

from benchmark import run, trace_reduce

EVENTS = 400


def main(name, seed):
    cell = run.Cell(name)
    whole = trace_reduce.Tracer.reduce
    trace_reduce.Tracer.reduce = lambda self: whole(self, True)
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    run.run_cell(cell, seed, 6.0, 1)
    path = glob.glob(os.path.join(cell.out_dir, "trace", "**", "*.xplane.pb"),
                     recursive=True)[0]
    planes = trace_reduce.read_xplane(path)
    for plane, lines in planes.items():
        print("plane %s: %s" % (plane, {k: len(v) for k, v in lines.items()}))
    first = sorted(p for p in planes if planes[p].get("XLA Ops"))[0]
    ops = sorted(planes[first]["XLA Ops"], key=lambda e: e[1])
    # start at a module boundary some way in, so the cut holds whole steps'
    # ends and starts and the gap between them
    mods = sorted(planes[first]["XLA Modules"], key=lambda e: e[1])
    lo = mods[len(mods) // 2][1] + mods[len(mods) // 2][2] * 9 // 10
    kept = [e for e in ops if e[1] >= lo][:EVENTS]
    hi = kept[-1][1] + kept[-1][2]
    cut = {first: {
        line: [[trace_reduce.short(n, 200), s - lo, d] for n, s, d in events
               if s + d > lo and s < hi]
        for line, events in planes[first].items() if line != "XLA Ops"}}
    cut[first]["XLA Ops"] = [[trace_reduce.short(n, 200), s - lo, d]
                             for n, s, d in kept]
    out = trace_reduce.reduce(cut)
    rec = {"cell": name, "seed": seed, "planes": cut,
           "expected": {"busy_s": out["busy_s"], "window_s": out["window_s"],
                        "idle_share": out["idle_share"],
                        "top_op": out["top_ops"][0][0]}}
    with open(os.path.join(cell.out_dir, "recorded_planes.json"), "w") as f:
        json.dump(rec, f)
    print("cut: %d op events over %.3f ms, idle share %.4f"
          % (len(kept), (hi - lo) / 1e6, out["idle_share"]))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
