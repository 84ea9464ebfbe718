#!/usr/bin/env python
"""A traced run's device time by ``jax.named_scope``: the benchmark's
``chiprun_out/benchmark/<cell>/trace_ops.json`` (every operation's seconds
over the traced stretch) joined with the compiled step's text, whose
instructions carry their scope in ``metadata={op_name="..."}``. The text is
the running program's own (``ShardedTrainStep.compiled().as_text()``, which
JAX's cache serves after set-up): instruction names are the compiler's and
only match a trace of the same executable.

    python tools/trace_by_scope.py trace_ops.json step_hlo.txt [scope ...]

Prints ms a step: every operation of the entry computation under the
innermost of the named scopes its ``op_name`` holds (the model zoo's and
the operators' by default), else under ``(backward)``, ``(recomputed)`` or
``(forward)`` by the transform it was traced under; a Pallas kernel under
its own name as well. A ``conditional`` / ``while`` is one operation of the
entry computation and is counted whole, under ``(switches)``; what runs
inside its branches is listed apart, by scope, and is NOT added again.
"""
import collections
import json
import re
import sys

SCOPES = ("kda_conv", "kda_gate", "kda_attention", "gdn_gate",
          "gated_delta_rule", "gated_norm", "mla_attention",
          "gqa_attention", "window_attention", "sparse_attention",
          "rotary", "rotary_yarn", "head_gate", "element_gate",
          "index_select", "short_conv", "flash_attention_bwd",
          "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "moe.shared", "moe.shared_gate", "softmax_ce", "optimizer")
_HEAD = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def instructions(text):
    """-> ({instruction: (computation, opcode, op_name)}, the entry
    computation's name, {fusion instruction: its fused computation})."""
    found, entry, comp, fused = {}, None, None, {}
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            comp = head.group(2)
            entry = comp if head.group(1) else entry
            continue
        instr = _INSTR.match(line)
        if comp is None or not instr:
            continue
        name, opcode = instr.groups()
        op_name = _OP_NAME.search(line)
        found[name] = (comp, opcode, op_name.group(1) if op_name else "")
        callee = _CALLS.search(line)
        if opcode == "fusion" and callee:
            fused[name] = callee.group(1)
    return found, entry, fused


def scope_of(op_name, scopes):
    # a scope stands between slashes, or inside the transforms' brackets:
    # ``transpose(jvp(kda_conv))/jit(_backward)/...``
    for part in reversed(re.findall(r"[\w.\-]+", op_name)):
        if part in scopes:
            return part
    if "rematted_computation" in op_name:
        return "(recomputed)"
    if "transpose(" in op_name:
        return "(backward)"
    return "(forward)" if op_name else "(no op_name)"


def main(argv):
    with open(argv[0]) as f:
        trace = json.load(f)
    with open(argv[1]) as f:
        text = f.read()
    scopes = tuple(argv[2:]) or SCOPES
    step = max(trace["modules"], key=lambda m: sum(trace["modules"][m]))
    steps = len(trace["modules"][step])
    found, entry, fused = instructions(text)
    # a fusion without an op_name of its own takes its fused root's
    by_comp = collections.defaultdict(list)
    for name, (comp, _, op_name) in found.items():
        by_comp[comp].append(op_name)
    top, inside, kernels = (collections.Counter() for _ in range(3))
    unmatched = 0.0
    for name, seconds in trace["ops"].items():
        ms = 1e3 * seconds / steps
        if name not in found:
            unmatched += ms
            continue
        comp, opcode, op_name = found[name]
        if not op_name and name in fused:
            op_name = next((o for o in reversed(by_comp[fused[name]]) if o),
                           "")
        if opcode in ("conditional", "while"):
            scope = "(switches)"
        else:
            scope = scope_of(op_name, scopes)
        (top if comp == entry else inside)[scope] += ms
        if opcode == "custom-call":
            kernels[re.sub(r"\.\d+$", "", name)] += ms
    print("%s: %d steps, %.3f ms a step on the device; matched %.3f, "
          "unmatched names %.3f" % (step, steps, 1e3 * sum(
              trace["modules"][step]) / steps, sum(top.values()), unmatched))
    for title, table in (("entry computation", top),
                         ("inside the switches (counted above)", inside),
                         ("custom calls, by name (counted above)", kernels)):
        print("-- %s: %.3f" % (title, sum(table.values())))
        for scope, ms in table.most_common():
            print("  %-28s %9.3f" % (scope, ms))


if __name__ == "__main__":
    main(sys.argv[1:])
