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


_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")


def is_grouped_kernel(line):
    """Whether an instruction line is a call of the Pallas grouped-matmul
    kernel: a custom call named ``grouped_matmul_<form>.<n>`` after the
    kernel (``mxtpu/ops/pallas/grouped_matmul.py``)."""
    found = _RESULT.match(line)
    return bool(found) and found.group(3) == "custom-call" \
        and found.group(1).startswith("grouped_matmul_")


def grouped_kernels(text):
    """The grouped-matmul kernel's calls in each computation that holds
    any, sorted."""
    counts = (sum(map(is_grouped_kernel, lines))
              for lines in computations(text).values())
    return sorted(n for n in counts if n)


def producers(lines, *shapes):
    """(name, opcode) of the instructions among ``lines`` whose result is
    an array of one of ``shapes`` (tuples of ints), any element type."""
    dims = {",".join(map(str, shape)) for shape in shapes}
    found = (_RESULT.match(line) for line in lines)
    return [(f.group(1), f.group(3)) for f in found
            if f and f.group(2) in dims]


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
