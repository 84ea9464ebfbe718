#!/usr/bin/env python
"""sha256 of the lowered text of a cell's train step at the cell's
rehearsal sizes, on the CPU, for the checkout in the working directory:
two trees whose hashes agree give that cell the same program, so a change
that is meant to leave a cell alone can show it before any chip time.

    JAX_PLATFORMS=cpu python tools/step_text_hash.py [cell ...]

(every cell of ``BENCHMARK.json`` by default; a four-chip cell wants
``XLA_FLAGS=--xla_force_host_platform_device_count=4``). One line a cell:
its name, the text's length, the hash.

The private functions of one name are counted anew (:func:`renumbered`):
the numbers MLIR gives them are no part of the program.
"""
import hashlib
import json
import os
import re
import sys


def renumbered(text):
    """``text`` with the private functions of one name numbered 1, 2, ...
    in the order they first appear. MLIR tells them apart by a counter that
    every lowering of the module before them has moved (JAX lowers each new
    primitive, shape and parameters as a function of the primitive's name
    and inlines it), so ``@_where_138`` becomes ``@_where_140`` when two
    ``checkpoint_name`` equations, which lower to nothing, stand ahead of
    it: the same program under another sha256 (PERF.md section 6, PR 46 and
    PR 49)."""
    seen = {}

    def count(match):
        numbers = seen.setdefault(match.group(1), {})
        return "%s_%d" % (match.group(1), numbers.setdefault(
            match.group(2), len(numbers) + 1))
    return re.sub(r"(@[A-Za-z_]\w*?)_(\d+)\b", count, text)


def step_of(name):
    """Cell ``name``'s train step after its first steps, built as the
    cell's runner builds it at rehearsal sizes, and its network."""
    from benchmark import run
    cell = run.Cell(name, rehearse=True)
    state = cell.module("runners").setup(cell, 7)
    return state["step"], state["net"]


def step_text(name, step=None):
    """The lowered text (no locations, :func:`renumbered`) of cell
    ``name``'s step (``ShardedTrainStep.lowered``: the step traced and
    lowered anew at the signature it ran at)."""
    step = step or step_of(name)[0]
    return renumbered(step.lowered().as_text())


def main(argv):
    sys.path.insert(0, os.getcwd())     # the checkout's, not this file's
    with open("BENCHMARK.json") as f:
        names = argv or [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        text = step_text(name)
        print(name, len(text), hashlib.sha256(text.encode()).hexdigest(),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
