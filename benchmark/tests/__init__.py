"""The benchmark's own tests. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

and, for the cases that want four devices (the reference over a mesh, the
four-chip cell's rehearsals):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m pytest benchmark/tests -q -p no:cacheprovider \
        -k "four or statistic or dp4 or made_up"
"""
from benchmark import run

SPEC = run._json(run.ROOT, "BENCHMARK.json")


def cell(name, rehearse=False):
    return run.Cell(name, rehearse=rehearse)
