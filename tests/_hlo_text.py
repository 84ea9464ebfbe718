"""Reading a compiled module's HLO text: which computations run under a
conditional's branch or a loop's body, and which arrays live outside them.
Shared by ``test_latent_moe.py`` (XLA:CPU's text) and
``test_tpu_compile.py`` (the text XLA:TPU emits for a described chip)."""
import re

_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_CALLEE = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_GUARDED = re.compile(
    r"(?:body|true_computation|false_computation)=%?([\w.\-]+)")


def computations(text):
    """-> {computation name: its instruction lines}."""
    out, name = {}, None
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            name = head.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def conditionals(text):
    """The ``conditional`` instruction lines of the module."""
    return [line for lines in computations(text).values() for line in lines
            if " conditional(" in line]


def grouped_kernels(text):
    """XLA:TPU's grouped matmul kernels (``ragged-dot`` custom calls, their
    metadata call apart) in each computation that holds any, sorted."""
    counts = (sum("custom-call(" in line and "ragged-dot" in line
                  and "ragged-dot-metadata" not in line for line in lines)
              for lines in computations(text).values())
    return sorted(n for n in counts if n)


def branch_computations(text):
    """For each ``conditional`` of the module, in the text's order: for each
    of its branches, in the switch's order, the instruction lines of the
    branch's computation and of everything it calls (its fusions' bodies
    among them)."""
    comps = computations(text)
    calls = {name: {c for line in lines for c in _CALLEE.findall(line)}
             for name, lines in comps.items()}
    return [[[line for name in sorted(_reachable(calls, [branch]))
              for line in comps.get(name, ())] for branch in _names(found)]
            for line in conditionals(text)
            for found in _BRANCHES.findall(line)]


def _reachable(calls, roots):
    """The computations ``roots`` and whatever they call."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo.extend(calls.get(name, ()))
    return seen


def _names(found):
    return [n.strip().lstrip("%") for n in found.split(",") if n.strip()]


def under_control_flow(comps):
    """The computations that run only inside a conditional's branch or a
    loop's body: those, and whatever they call."""
    calls = {name: set() for name in comps}
    roots = set()
    for name, lines in comps.items():
        for line in lines:
            calls[name].update(_CALLEE.findall(line))
            roots.update(_GUARDED.findall(line))
            for found in _BRANCHES.findall(line):
                calls[name].update(_names(found))
                roots.update(_names(found))
    return _reachable(calls, roots)


def arrays_outside_control_flow(text, rows, cols):
    """The instruction lines that name an array of ``rows`` x ``cols`` (any
    element type) in a computation that runs unconditionally."""
    comps = computations(text)
    guarded = under_control_flow(comps)
    shape = "[%d,%d]" % (rows, cols)
    return [line.strip()[:200] for name, lines in comps.items()
            if name not in guarded for line in lines if shape in line]
