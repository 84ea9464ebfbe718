#!/usr/bin/env python
"""A traced run's device time by the program's own names, printed. The join
is the program's and the benchmark's: ``mxtpu.xprof`` keeps the operation
table of the executable the step runs (every instruction's ``op_name``: the
blocks' names, the operators' scopes, the transforms), and a traced run of
the benchmark joins it with the trace's seconds by instruction and writes
``chiprun_out/benchmark/<cell>/step_by_scope.json``
(``benchmark/step_scopes.py``: a switch or a loop by its self time, what
runs inside it under its own name). This prints that file:

    python tools/trace_by_scope.py step_by_scope.json [--by-transform]

or joins a kept trace with a kept compiled text first (an older run's
``trace_ops.json`` and the ``as_text()`` of the SAME executable: instruction
names are the compiler's and match no other):

    python tools/trace_by_scope.py trace_ops.json step_hlo.txt [--by-transform]

Milliseconds a step by scope (a layer's number starred), most first;
``--by-transform`` adds the forward / recomputed / backward / optimizer
columns a scope; then the split by layer kind and by layer, and the
longest operations (``fusion.1364``) under the names the program gave them.
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())         # the checkout's, not this file's


def matrix(paths):
    with open(paths[0]) as f:
        found = json.load(f)
    if len(paths) == 1:
        return found
    from benchmark import step_scopes
    from mxtpu import xprof
    with open(paths[1]) as f:
        table = xprof.operation_table(f.read())
    return step_scopes.join(found, table)


def rows(title, table, columns, by_transform, out):
    total = sum(sum(row.values()) for row in table.values())
    out("-- %s: %.3f" % (title, total))
    if by_transform:
        out("  %-52s %9s  %s" % ("", "ms", "  ".join(
            "%10s" % c for c in columns)))
    for key, row in sorted(table.items(), key=lambda kv: -sum(
            kv[1].values())):
        line = "  %-52s %9.3f" % (key, sum(row.values()))
        if by_transform:
            line += "  " + "  ".join("%10.3f" % row[c] for c in columns)
        out(line)


def main(argv, out=print):
    by_transform = "--by-transform" in argv
    paths = [a for a in argv if not a.startswith("--")]
    found = matrix(paths)
    if found is None:
        out("nothing to join: no module's run in the trace, or no "
            "operation of it in the text (another executable's?)")
        return 1
    from benchmark.step_scopes import KINDS, TRANSFORMS
    out("%s: %.3f ms a step on the device over %g runs a chip; names "
        "matched %.2f%%, unattributed %.2f%%, outside every block %.2f%%, "
        "inside switches and loops %.3f ms" % (
            found.get("cell", paths[0]), found["step_ms"],
            found["runs_a_chip"], found["matched_pct"],
            found["unattributed_pct"], found["outside_blocks_pct"],
            found["inside_switches_ms"]))
    out("-- by transform: " + ", ".join(
        "%s %.3f" % (t, found["by_transform"][t]) for t in TRANSFORMS)
        + ", unattributed %.3f" % found["unattributed_ms"])
    rows("by scope", found["by_scope"], TRANSFORMS, by_transform, out)
    rows("by layer kind", found["by_kind"], TRANSFORMS, by_transform, out)
    layers = {"layer %s" % k: v for k, v in found["by_layer"].items()}
    rows("by layer", layers, KINDS, True, out)
    out("-- the longest operations, by the names the program gave them")
    for op in found.get("top_operations", ()):
        out("  %-44s %9.3f  %-10s %s" % (op["operation"], op["ms"],
                                         op["transform"], op["scope"]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
