"""The benchmark's own tests. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""
from benchmark import run

SPEC = run._json(run.ROOT, "BENCHMARK.json")


def cell(name, rehearse=False):
    return run.Cell(name, rehearse=rehearse)
