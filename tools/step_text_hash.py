#!/usr/bin/env python
"""sha256 of the lowered text of a cell's train step at the cell's
rehearsal sizes, on the CPU, for the checkout in the working directory:
two trees whose hashes agree give that cell the same program, so a change
that is meant to leave a cell alone can show it before any chip time.

    JAX_PLATFORMS=cpu python tools/step_text_hash.py [cell ...]

(every cell of ``BENCHMARK.json`` by default; a four-chip cell wants
``XLA_FLAGS=--xla_force_host_platform_device_count=4``). One line a cell:
its name, the text's length, the hash.
"""
import hashlib
import json
import os
import sys


def step_text(name):
    """The lowered text (no locations) of cell ``name``'s step, built as
    the cell's runner builds it at rehearsal sizes."""
    from benchmark import run
    cell = run.Cell(name, rehearse=True)
    step = cell.module("runners").setup(cell, 7)["step"]
    return step._jit.lower(*step._last_abstract).as_text()


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
