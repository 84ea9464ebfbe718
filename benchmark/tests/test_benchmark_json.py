"""``BENCHMARK.json`` against the harness: every cell's configuration,
traffic, limits, runner, model, reference and flops files exist and load,
every metric has its reader, and a made-up cell needs one new traffic file,
one limits file and one entry: no edit of a file that is there."""
import contextlib
import copy
import json
import os
import shutil

import pytest

from benchmark import run
from benchmark.tests import SPEC, cell


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_cell_loads(name):
    c = cell(name)
    for kind in ("runners", "models", "reference", "flops"):
        assert c.module(kind) is not None
    for fn in ("setup", "window", "check", "control"):
        assert callable(getattr(c.module("runners"), fn))
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    specs = c.module("reference").param_specs(c.cfg)
    assert len({s[0] for s in specs}) == len(specs)
    assert c.cfg["reduced"] == [k["reduced"] for k in SPEC["configs"]
                                if k["name"] == c.row["config"]][0]
    rehearsal = cell(name, rehearse=True)
    assert rehearsal.cfg != c.cfg or rehearsal.traffic != c.traffic
    assert set(rehearsal.limits) == set(c.limits)


def test_the_record_is_whole():
    """Every metric lists cells that exist, every cell reports ``setup_s``,
    another end-to-end metric and a per-layer metric."""
    record = SPEC
    cells = {w["name"] for w in record["workloads"]}
    for m in record["end_to_end"] + record["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells and m.get(
            "workloads", cells)
    for name in cells:
        c = run.Cell(name)
        assert len(c.end_to_end) >= 2 and c.per_layer
    assert {c["name"] for c in record["configs"]} == {
        w["config"] for w in record["workloads"]}


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_has_a_reader(metric):
    read = run.reader(metric)
    ctx = {"cell": None, "trace": None, "peak": None,
           "window": {"end_to_end": {}, "spans": {}},
           "compile_clock": type("C", (), {"seconds": 1.5,
                                           "cache_hits": 2})()}
    # with nothing to read, a reader returns nothing (or the clock's count)
    assert read(ctx) in (None, 1.5, 2)


def test_metrics_name_known_things():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    assert json.dumps(SPEC).count("MXTPU_") == 0


@contextlib.contextmanager
def _made_up_cell():
    """What a four-chip cell over files that exist takes: a traffic file and
    a limits file of its own, and its entries. -> (record, name)"""
    new = {"traffic": os.path.join(run.HERE, "traffic", "made_up_b256.json"),
           "limits": os.path.join(run.HERE, "limits",
                                  "resnet50_v1.made_up.json")}
    base = cell("resnet50_v1.train_b128")
    try:
        with open(new["traffic"], "w") as f:
            json.dump(dict(base.traffic, batch=256), f)
        shutil.copy(os.path.join(run.HERE, "limits",
                                 "resnet50_v1.train_b128.json"), new["limits"])
        spec = copy.deepcopy(SPEC)
        spec["workloads"].append({
            "name": "resnet50_v1.made_up", "config": "resnet50_v1",
            "traffic": "made_up_b256", "chips": 4, "why": "a test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "resnet50_v1.train_b128" in m.get("workloads", []):
                m["workloads"].append("resnet50_v1.made_up")
        yield spec, "resnet50_v1.made_up"
    finally:
        for path in new.values():
            if os.path.exists(path):
                os.remove(path)


def test_a_made_up_cell_needs_only_new_files():
    """No file that is there is edited, and nothing in the harness names
    the cell."""
    base = cell("resnet50_v1.train_b128")
    with _made_up_cell() as (spec, name):
        made = run.Cell(name, spec=spec)
        assert made.traffic["batch"] == 256 and made.chips == 4
        assert [m["name"] for m in made.end_to_end] == \
            [m["name"] for m in base.end_to_end]
        assert [m["name"] for m in made.per_layer] == \
            [m["name"] for m in base.per_layer]
        assert made.module("runners") is base.module("runners")


def test_a_made_up_cell_on_four_chips_runs_through_the_check():
    """Not the loader alone: set-up, a window and the check, the reference
    over the four devices the cell names (of the eight the tier-1 run has;
    of one, a mesh of one)."""
    import jax
    with _made_up_cell() as (spec, name):
        lines = []
        result = run.run_cell(run.Cell(name, rehearse=True, spec=spec), 7,
                              1.0, 0, out=lines.append)
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert sum(line.startswith("check ") for line in lines) == 4
    assert result["device"]["count"] == len(jax.devices())


# the reference's own cases, collected here so that the tier-1 run, which
# takes this file whole (tests/test_benchmark_suite.py), takes them too
from benchmark.tests.reference_checks import *      # noqa: E402,F401,F403
