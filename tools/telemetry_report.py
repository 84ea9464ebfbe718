#!/usr/bin/env python
"""Summarize a telemetry JSONL file into the aggregate table.

The sink (``MXTPU_TELEMETRY=<path>``, mxtpu/telemetry.py) streams one line
per histogram observation plus cumulative counter/gauge lines at each
flush. This tool folds a file of those lines into the per-metric table —
count / mean / p50 / p99 / max for observations, the final cumulative
value for counters, the last write for gauges — the telemetry analog of
``profiler.dumps()``'s aggregate stats, runnable after the fact on a
sink file a bench session left behind.

With causal tracing on (``MXTPU_TRACE``, default 1), span observations
carry their trace linkage (``trace``/``span``/``parent`` keys) and
``--traces [K]`` adds the per-trace critical-path view: the top-K traces
by total latency, each with its span count and SLOWEST stage — the
"which stage made this request/step slow" question answered from the
artifact alone, no live repro.

With the executable observatory on (``MXTPU_XPROF``, default 1), each
flush also streams ``kind="ledger"`` lines — one per jit-site executable
with its cost-model FLOPs/bytes, HBM footprint, and compile wall-time —
and ``--ledger`` renders the per-site roofline table: arithmetic
intensity vs the chip's ridge point → compute- vs memory-bound verdict,
plus the ranked hand-kernel (Pallas) candidate list — the fusion-gap
methodology of arXiv:2301.13062 as a standing report. The "achieved"
column folds in the site's own span p50 where one exists (e.g.
``serving.predict``) — an approximation (host dispatch wall time, not
device occupancy), printed only where the span times the dispatch.

Multi-host runs produce one sink PER HOST: any path argument may be a
directory (every ``*.jsonl`` inside) or a glob, and several paths are
merged — counters fold per-file then sum, gauges take the freshest
write, and duplicated trace-linked observations collapse on their
``(trace, span)`` identity (trace ids carry the originating pid prefix,
so cross-host lines never collide and true copies dedup cleanly).

``--fleet <dir>`` points at a fleet board directory (``MXTPU_FLEET_DIR``
generation dir) and renders the ISSUE-19 merged fleet view on top: the
``FleetObservatory`` per-host/aggregate snapshot from the ``obs_*.json``
blobs plus the per-step critical path stitched from the step-barrier
payloads — which rank arrived last and which stage made it late.

Usage::

    python tools/telemetry_report.py <jsonl|dir|glob>... [--json]
        [--traces [K]] [--ledger] [--fleet <board-dir>]
"""
from __future__ import annotations

import glob as _glob
import json
import os
import sys


def _quantile(sorted_vals, q):
    n = len(sorted_vals)
    if n == 0:
        return None
    pos = q * (n - 1)
    lo = int(pos)
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def aggregate(lines):
    """Fold decoded JSONL records into {metric: summary-dict}.

    Counters are cumulative WITHIN one process and repeat per flush, but
    several sessions may append to one file (one MXTPU_TELEMETRY path
    shared by bench, benchmark_score and bandwidth runs, each restarting
    at 0) — so they fold Prometheus-style:
    a value that DROPS marks a process restart, banking the previous
    session's total. Multi-file merges (``load_many``) tag records with
    their source file index ``_src``: the restart fold then runs PER
    FILE and the per-file totals sum — two hosts' cumulative streams
    never alias each other's banking. Gauges take the freshest write
    (by record timestamp, stream order on ties); observation streams
    get count/mean/p50/p99/min/max."""
    obs = {}
    counters = {}   # (src, key) -> [banked_total, last_seen_in_session]
    gauges = {}     # key -> (t, value)
    for rec in lines:
        kind = rec.get("kind")
        name = rec.get("metric")
        if name is None:
            continue
        if kind == "obs":
            obs.setdefault(name, []).append(float(rec["value"]))
        elif kind == "counter":
            tag = rec.get("tag")
            key = "%s{%s}" % (name, tag) if tag else name
            ckey = (rec.get("_src"), key)
            banked, last = counters.get(ckey, (0, 0))
            if rec["value"] < last:  # process restart: bank the old run
                banked += last
            counters[ckey] = (banked, rec["value"])
        elif kind == "gauge":
            tag = rec.get("tag")
            key = "%s{%s}" % (name, tag) if tag else name
            t = rec.get("t")
            prev = gauges.get(key)
            if prev is None or t is None or prev[0] is None or t >= prev[0]:
                gauges[key] = (t, float(rec["value"]))
    totals = {}
    for (_src, key), (banked, last) in counters.items():
        totals[key] = totals.get(key, 0) + banked + last
    counters = totals
    gauges = {k: v for k, (_t, v) in gauges.items()}
    out = {}
    for name, vals in obs.items():
        vals.sort()
        out[name] = {"kind": "obs", "count": len(vals),
                     "mean": sum(vals) / len(vals),
                     "min": vals[0], "max": vals[-1],
                     "p50": _quantile(vals, 0.5),
                     "p99": _quantile(vals, 0.99)}
    for name, v in counters.items():
        out[name] = {"kind": "counter", "count": v, "value": v}
    for name, v in gauges.items():
        out[name] = {"kind": "gauge", "value": v}
    return out


def trace_summary(lines, top=10):
    """Fold trace-linked observations into the per-trace critical-path
    view: ``[{trace, total, spans, slowest, slowest_s, slowest_frac,
    stages}]`` sorted by total latency, truncated to ``top``.

    Total latency is the sum of ROOT-level stages (``parent == 0``) —
    for a served request those are exactly the breakdown stages
    (submit + queue-wait + pad + predict + fetch + deliver ≈ e2e), for a
    training step the ``trainer.step`` span itself; nested child spans
    must not double-count into the total but DO compete for slowest."""
    traces = {}
    for rec in lines:
        if rec.get("kind") != "obs" or rec.get("trace") is None:
            continue
        t = traces.setdefault(rec["trace"], {"stages": [], "root_s": 0.0})
        v = float(rec["value"])
        t["stages"].append((rec["metric"], v))
        if not rec.get("parent"):
            t["root_s"] += v
    rows = []
    for tid, t in traces.items():
        total = t["root_s"] or sum(v for _, v in t["stages"])
        agg = {}
        for name, v in t["stages"]:
            agg[name] = agg.get(name, 0.0) + v
        slowest = max(agg.items(), key=lambda kv: kv[1])
        rows.append({"trace": tid, "total": total,
                     "spans": len(t["stages"]),
                     "slowest": slowest[0], "slowest_s": slowest[1],
                     "slowest_frac": slowest[1] / total if total else 0.0,
                     "stages": agg})
    rows.sort(key=lambda r: -r["total"])
    return rows[:top]


def format_trace_table(rows):
    if not rows:
        return "(no trace-linked records — is MXTPU_TRACE on?)"
    lines = ["%-14s %10s %6s  %-28s %10s %6s" %
             ("Trace", "Total(ms)", "Spans", "Slowest stage", "ms", "%")]
    for r in rows:
        lines.append("%-14s %10.3f %6d  %-28s %10.3f %5.1f%%" %
                     (r["trace"], r["total"] * 1e3, r["spans"],
                      r["slowest"], r["slowest_s"] * 1e3,
                      r["slowest_frac"] * 100))
    return "\n".join(lines)


def ledger_summary(lines):
    """Fold ``kind=="ledger"`` records into per-executable roofline rows.

    Ledger lines are cumulative like the counters (one batch per flush):
    the LAST line per (site, seq) wins. Returns ``(rows, candidates)``
    where candidates is the memory-bound shortlist ranked by executed
    FLOPs (flops x calls) — the entries where a hand kernel buys the
    most."""
    entries = {}
    obs = {}
    for rec in lines:
        kind = rec.get("kind")
        if kind == "ledger" and rec.get("site") is not None:
            entries[(rec["site"], rec.get("seq"))] = rec
        elif kind == "obs" and rec.get("metric") is not None:
            obs.setdefault(rec["metric"], []).append(float(rec["value"]))
    rows = []
    for (site, seq), e in sorted(entries.items(),
                                 key=lambda kv: kv[0][1] or 0):
        fl = e.get("flops")
        row = {"site": site, "seq": seq, "calls": int(e.get("calls") or 0),
               "compile_s": e.get("compile_s"), "flops": fl,
               "bytes_accessed": e.get("bytes_accessed"),
               "intensity": e.get("intensity"),
               "critical_intensity": e.get("critical_intensity"),
               "verdict": e.get("verdict"), "error": e.get("error"),
               "shapes": e.get("shapes")}
        vals = obs.get(site)
        if vals and fl:
            vals = sorted(vals)
            p50 = _quantile(vals, 0.5)
            if p50:
                row["achieved_flops_per_s"] = fl / p50
        rows.append(row)
    cands = [r for r in rows if r.get("verdict") == "memory"
             and r.get("flops")]
    cands.sort(key=lambda r: -(r["flops"] * max(r["calls"], 1)))
    return rows, cands


def format_ledger_table(rows, cands):
    if not rows:
        return ("(no ledger records — is MXTPU_XPROF on, and did the "
                "process flush its telemetry sink?)")
    lines = ["%-30s %7s %9s %9s %9s %8s %8s  %s" %
             ("Site#seq", "Calls", "Compile(s)", "GFLOP", "MB-acc",
              "FLOP/B", "Achieved", "Verdict")]
    for r in rows:
        ach = r.get("achieved_flops_per_s")
        lines.append("%-30s %7d %9s %9s %9s %8s %8s  %s" % (
            "%s#%s" % (r["site"], r["seq"]), r["calls"],
            "%.3f" % r["compile_s"] if r.get("compile_s") else "-",
            "%.2f" % (r["flops"] / 1e9) if r.get("flops") else "-",
            "%.1f" % (r["bytes_accessed"] / 1e6)
            if r.get("bytes_accessed") else "-",
            "%.1f" % r["intensity"] if r.get("intensity") else "-",
            "%.1fT" % (ach / 1e12) if ach else "-",
            r.get("error") or r.get("verdict")
            or "unknown (no chip ridge)"))
    if cands:
        lines.append("")
        lines.append("Pallas candidates (memory-bound, by executed "
                     "FLOPs): " + ", ".join(
                         "%s#%s" % (r["site"], r["seq"])
                         for r in cands[:8]))
    return "\n".join(lines)


def load(path):
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                # a torn final line from a killed process must not void
                # the rest of the artifact
                continue
    return records


def expand_paths(paths):
    """Each argument may be a file, a directory (every ``*.jsonl``
    inside), or a glob pattern; returns the flat sorted file list."""
    out = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(_glob.glob(os.path.join(p, "*.jsonl"))))
        elif any(ch in p for ch in "*?["):
            out.extend(sorted(_glob.glob(p)))
        else:
            out.append(p)
    return out


def load_many(paths):
    """Merge several sink files: records gain a ``_src`` file index (the
    per-file counter-banking key), and trace-linked observation lines
    that appear in more than one file collapse on ``(trace, span,
    metric)`` — the trace id's process prefix makes that identity
    host-unique, so only true duplicates dedup."""
    records = []
    seen = set()
    for i, path in enumerate(expand_paths(paths)):
        for rec in load(path):
            if rec.get("kind") == "obs" and rec.get("trace") is not None:
                key = (rec["trace"], rec.get("span"), rec.get("metric"))
                if key in seen:
                    continue
                seen.add(key)
            rec["_src"] = i
            records.append(rec)
    return records


def format_table(summary):
    lines = []
    obs = {n: s for n, s in summary.items() if s["kind"] == "obs"}
    if obs:
        lines.append("%-38s %8s %12s %12s %12s %12s" %
                     ("Metric", "Count", "Mean", "P50", "P99", "Max"))
        for name in sorted(obs, key=lambda n: -obs[n]["mean"] * obs[n]["count"]):
            s = obs[name]
            lines.append("%-38s %8d %12.6g %12.6g %12.6g %12.6g" %
                         (name, s["count"], s["mean"], s["p50"], s["p99"],
                          s["max"]))
    rest = {n: s for n, s in summary.items() if s["kind"] != "obs"}
    if rest:
        if lines:
            lines.append("")
        lines.append("%-38s %8s %12s" % ("Counter/Gauge", "Kind", "Value"))
        for name in sorted(rest):
            s = rest[name]
            lines.append("%-38s %8s %12g" % (name, s["kind"], s["value"]))
    return "\n".join(lines) if lines else "(no telemetry records)"


def format_fleet(merged, steps):
    """The merged fleet view + per-step critical path as text tables."""
    fl = merged["fleet"]
    lines = ["Fleet: %d/%d host(s) up | mfu=%s | step p50=%s p99=%s" % (
        fl["hosts_up"], fl["hosts_seen"],
        "%.3f" % fl["mfu"] if fl.get("mfu") is not None else "-",
        "%.4gs" % fl["step_s"]["p50"]
        if fl["step_s"].get("p50") is not None else "-",
        "%.4gs" % fl["step_s"]["p99"]
        if fl["step_s"].get("p99") is not None else "-")]
    lines.append("")
    lines.append("%4s %-10s %6s %8s %12s %12s %10s" % (
        "Rank", "Status", "Step", "MFU", "Step p50", "Step p99", "HB age"))
    for rank in sorted(merged["hosts"]):
        h = merged["hosts"][rank]
        ss = h["step_s"]
        lines.append("%4d %-10s %6s %8s %12s %12s %10s" % (
            rank, h.get("status") or "-",
            "-" if h.get("step") is None else h["step"],
            "%.3f" % h["mfu"] if h.get("mfu") is not None else "-",
            "%.4gs" % ss["p50"] if ss.get("p50") is not None else "-",
            "%.4gs" % ss["p99"] if ss.get("p99") is not None else "-",
            "%.1fs" % h["heartbeat_age_s"]
            if h.get("heartbeat_age_s") is not None else "-"))
    if steps:
        lines.append("")
        lines.append("Per-step critical path (who arrived last, and why):")
        lines.append("%6s %6s %10s %10s  %-28s %-14s" % (
            "Step", "Last", "Skew(ms)", "Step(ms)", "Dominant stage",
            "Trace"))
        for r in steps:
            lines.append("%6d %6d %10s %10s  %-28s %-14s" % (
                r["step"], r["last_rank"],
                "%.2f" % (r["skew_s"] * 1e3)
                if r.get("skew_s") is not None else "-",
                "%.2f" % (r["step_s"] * 1e3)
                if r.get("step_s") is not None else "-",
                r.get("dominant_stage") or "-", r.get("trace") or "-"))
    else:
        lines.append("")
        lines.append("(no stitched step-barrier payloads on the board)")
    return "\n".join(lines)


def main(argv):
    argv = list(argv)
    as_json = "--json" in argv
    with_ledger = "--ledger" in argv
    fleet_dir = None
    if "--fleet" in argv:
        nxt = argv.index("--fleet") + 1
        if nxt >= len(argv):
            print("--fleet needs a board directory", file=sys.stderr)
            return 1
        fleet_dir = argv.pop(nxt)    # consume BY INDEX, like --traces
    top = None
    if "--traces" in argv:
        top = 10
        nxt = argv.index("--traces") + 1
        if nxt < len(argv) and argv[nxt].isdigit():
            # consume the count token BY INDEX: a data file that happens
            # to be named like the number must not be dropped from paths
            top = int(argv.pop(nxt))
    paths = [a for a in argv if not a.startswith("-")]
    if (not paths and fleet_dir is None) or "-h" in argv or "--help" in argv:
        print(__doc__)
        return 0 if "-h" in argv or "--help" in argv else 1
    records = load_many(paths)
    fleet_view = None
    if fleet_dir is not None:
        # lazy: the plain report stays stdlib-only; the fleet merge
        # reuses the observatory itself rather than re-implementing it
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from mxtpu import fleet_obs
        fleet_view = (
            fleet_obs.FleetObservatory(fleet_dir).merged(),
            fleet_obs.step_traces(fleet_dir))
    summary = aggregate(records)
    traces = trace_summary(records, top=top) if top is not None else None
    ledger = ledger_summary(records) if with_ledger else None
    if as_json:
        out = dict(summary)
        if traces is not None:
            out["_traces"] = traces
        if ledger is not None:
            out["_ledger"] = {"rows": ledger[0],
                              "candidates": ["%s#%s" % (r["site"], r["seq"])
                                             for r in ledger[1]]}
        if fleet_view is not None:
            out["_fleet"] = {"merged": fleet_view[0],
                             "steps": fleet_view[1]}
        print(json.dumps(out, sort_keys=True))
    else:
        if paths:
            print(format_table(summary))
        if traces is not None:
            print()
            print(format_trace_table(traces))
        if ledger is not None:
            print()
            print(format_ledger_table(*ledger))
        if fleet_view is not None:
            if paths:
                print()
            print(format_fleet(*fleet_view))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
