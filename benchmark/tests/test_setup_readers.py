"""The readers of a restart (``benchmark/setup_ring.py`` and the seven
``layer_metrics`` files on it), each on a hand-made ring: an import with a
program compiled inside it, the harness's own programs under no span, a
parameter load, a ``train_step.init`` with a stray ``set_data`` inside it,
three first steps with their fetches, then the window and the reference.
The phases and ``setup_outside_program_s`` add up to the ``setup_s`` given;
nothing is returned from a ring without the spans, or one that has wrapped.
"""
import time

import pytest

from benchmark import run, setup_ring
from benchmark.tests import SPEC
from benchmark.tests.test_span_readers import _call, _ctx
from benchmark.tests.test_span_readers import _ring as _ring_without_setup

SETUP_S = 40.0
READERS = {
    "setup_import_s": 2.0,
    "setup_param_load_s": 0.46,        # a cast and three leaves
    "setup_step_init_s": 1.0,
    "setup_first_steps_s": 0.91,       # roots less the build, three fetches
    "setup_eager_compile_s": 0.73,     # 0.3 + 0.08 + 0.3 + 0.05
    "setup_eager_programs": 4,
    "setup_outside_program_s": 15.63,  # 40 - (2 + 0.46 + 1 + 20 + 0.91)
}
PHASES = ["setup_import_s", "setup_param_load_s", "setup_step_init_s",
          "setup_first_steps_s", "setup_outside_program_s"]
FIRST_BUILD_S = 20.0


def _compiled(events, t, trace=0, lower=0, backend=0):
    """One program's compile events from ``t`` on, as JAX reports them."""
    for name, dur in (("jax.trace", trace), ("jax.lower", lower),
                      ("jax.backend_compile", backend)):
        if dur:
            events.append((name, "compile", t, dur, 1))
            t += dur


def _ring(window=40):
    events = []
    _compiled(events, 1_500_000, trace=100_000, backend=200_000)
    events.append(("mxtpu.import", "setup", 1_000_000, 2_000_000, 1))
    # seeded weights and batches: the harness's, under no span
    _compiled(events, 3_100_000, trace=400_000, backend=300_000)
    events.append(("gluon.cast", "setup", 4_000_000, 10_000, 1))
    for i in range(3):
        t = 4_100_000 + i * 200_000
        if i == 0:                    # the first gradient buffer's program
            _compiled(events, t + 10_000, trace=20_000, lower=10_000,
                      backend=50_000)
        if i == 1:                    # a deferred shape settles inside it
            events.append(("gluon.param.init", "setup", t + 10_000, 100_000,
                           1))
        events.append(("gluon.param.set_data", "setup", t, 150_000, 1))
    # ShardedTrainStep.__init__: a child's events end before the root's
    events.append(("gluon.param.set_data", "setup", 5_020_000, 100_000, 1))
    events.append(("train_step.init.place_params", "setup", 5_010_000,
                   300_000, 1))
    events.append(("jax.trace", "compile", 5_330_000, 50_000, 1))   # nested
    _compiled(events, 5_320_000, trace=100_000)
    _compiled(events, 5_450_000, backend=200_000)
    events.append(("train_step.init.create_states", "setup", 5_310_000,
                   500_000, 1))
    events.append(("train_step.init.place_states", "setup", 5_810_000,
                   180_000, 1))
    events.append(("train_step.init", "setup", 5_000_000, 1_000_000, 1))
    t = 7_000_000
    for n in range(3):
        t = _call(events, t, build=(n == 0))
        if n == 0:                    # the fetch's own convert program
            _compiled(events, t + 10, trace=20_000, backend=30_000)
        events.append(("ndarray.asnumpy", "sync", t, 300_000, 1))
        t += 300_400
    t += 2_000_000                    # first_grad, delta_norms: no span
    for n in range(window):
        t = _call(events, t + 100)
    for _ in range(5):                # the reference
        t += 1_000
        _compiled(events, t, trace=800, backend=50)
    return events


@pytest.fixture()
def restart_ring(monkeypatch):
    from mxtpu import telemetry
    events = _ring()
    monkeypatch.setattr(telemetry, "events", lambda: list(events))
    return events


def _setup_ctx(attempted=40, setup_s=SETUP_S):
    ctx = _ctx(attempted)
    if setup_s is not None:
        ctx["window"]["end_to_end"]["setup_s"] = setup_s
    return ctx


def test_every_setup_metric_has_a_case_here():
    cells = [w["name"] for w in SPEC["workloads"]]
    mine = [m for m in SPEC["per_layer"] if m["name"].startswith("setup_")]
    assert {m["name"] for m in mine} == set(READERS)
    for m in mine:
        assert (m["moves"], m["source"], m["better"]) == (
            "setup_s", "program_counter", "lower")
        assert m["workloads"] == cells
    # appended: nothing that was there moved
    assert SPEC["per_layer"][-len(mine):] == mine


@pytest.mark.parametrize("metric", sorted(READERS))
def test_setup_reader_on_a_hand_made_restart(restart_ring, metric):
    assert run.reader(metric)(_setup_ctx()) == pytest.approx(READERS[metric])


def test_the_parts_add_up_to_setup_s(restart_ring):
    ctx = _setup_ctx()
    parts = setup_ring.split(ctx)
    assert setup_ring.seconds(parts["first_build"]) == FIRST_BUILD_S
    total = sum(run.reader(m)(ctx) for m in PHASES) + FIRST_BUILD_S
    assert total == pytest.approx(SETUP_S, abs=1e-9)
    # disjoint by construction: no microsecond is in two parts
    lists = [parts[k] for k in ("import", "step_init", "first_build",
                                "first_steps", "param_load")]
    assert setup_ring.seconds(setup_ring.merged(
        [iv for part in lists for iv in part])) == pytest.approx(
            sum(setup_ring.seconds(part) for part in lists))
    # the eager programs lie inside the phases, not beside them
    assert run.reader("setup_eager_compile_s")(ctx) <= sum(
        run.reader(m)(ctx) for m in PHASES[:4])


def test_a_span_no_phase_counts_shows_in_the_sum(restart_ring):
    # program code under a span of another name, before the window: the
    # remainder shrinks by it and the parts no longer reach ``setup_s``
    ring = restart_ring
    at = [i for i, e in enumerate(ring) if e[0] == "train_step.init"][0]
    ring.insert(at + 1, ("trainer.step", "phase", 6_200_000, 500_000, 1))
    ctx = _setup_ctx()
    assert run.reader("setup_outside_program_s")(ctx) == pytest.approx(15.13)
    total = sum(run.reader(m)(ctx) for m in PHASES) + FIRST_BUILD_S
    assert total == pytest.approx(SETUP_S - 0.5)


def test_what_is_outside_a_program_span_is_not_an_eager_program(restart_ring):
    # the harness's 0.7 s and the build's events are in the ring and in
    # neither number; a program compiled under a span no phase counts is
    ctx, ring = _setup_ctx(), restart_ring
    at = [i for i, e in enumerate(ring) if e[0] == "train_step.init"][0]
    new = [("jax.backend_compile", "compile", 6_300_000, 100_000, 1),
           ("trainer.step", "phase", 6_200_000, 500_000, 1)]
    ring[at + 1:at + 1] = new
    assert run.reader("setup_eager_compile_s")(ctx) == pytest.approx(0.83)
    assert run.reader("setup_eager_programs")(ctx) == 5


def test_only_what_ended_before_the_window_counts(restart_ring):
    # a leaf set, and a program compiled for it, inside the window
    ring = restart_ring
    _events, roots = setup_ring.span_ring.ring(_setup_ctx())
    inside = roots[10][1] + 10
    at = [i for i, e in enumerate(ring) if e[2] > inside][0]
    ring[at:at] = [("jax.backend_compile", "compile", inside + 5, 50, 1),
                   ("gluon.param.set_data", "setup", inside, 80, 1)]
    for metric, want in READERS.items():
        assert run.reader(metric)(_setup_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_nothing_from_a_ring_that_cannot_be_split(restart_ring, monkeypatch,
                                                  metric):
    from mxtpu import telemetry
    read, ring = run.reader(metric), restart_ring
    assert read(_setup_ctx(attempted=None)) is None  # no window
    assert read(_setup_ctx(attempted=44)) is None    # fewer calls than made
    # a ring at its capacity has lost its head, the restart first of all
    monkeypatch.setattr(telemetry, "EVENT_RING_CAP", len(ring))
    assert read(_setup_ctx()) is None
    monkeypatch.setattr(telemetry, "EVENT_RING_CAP", len(ring) + 1)
    assert read(_setup_ctx()) is not None
    # a program without the restart's spans: the parent of the PR that
    # brought them has the steps' spans and no ``mxtpu.import``
    old = _ring_without_setup()
    monkeypatch.setattr(telemetry, "events", lambda: list(old))
    assert read(_setup_ctx()) is None


def test_no_setup_s_no_remainder(restart_ring):
    ctx = _setup_ctx(setup_s=None)
    assert run.reader("setup_outside_program_s")(ctx) is None
    assert run.reader("setup_import_s")(ctx) == pytest.approx(2.0)


def test_interval_arithmetic():
    a = setup_ring.merged([[5, 9], [0, 3], [2, 4], [9, 10], [20, 20]])
    assert a == [[0, 4], [5, 10]]
    assert setup_ring.minus(a, []) == a
    assert setup_ring.minus(a, [[1, 2], [3, 6], [8, 30]]) == [
        [0, 1], [2, 3], [6, 8]]
    assert setup_ring.minus(a, [[0, 10]]) == []
    assert setup_ring.seconds([[0, 1_500_000]]) == 1.5


def test_a_real_restart_fills_the_ring_the_readers_read():
    """The names the readers look for are the ones the program writes."""
    import numpy as np
    import mxtpu as mx
    from mxtpu import gluon, telemetry
    from mxtpu.gluon import nn
    from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh
    telemetry.reset()
    t_start, t0 = time.time(), time.perf_counter_ns()
    time.sleep(0.01)
    # ``import mxtpu`` records this once a process; here it has long run
    telemetry.record_interval("mxtpu.import", t0, cat="setup")
    net = nn.HybridSequential(prefix="restart_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4), nn.Dense(3, in_units=8))
    net.cast("float32")
    for i, p in enumerate(net.collect_params().values()):
        p.set_data(mx.nd.array(np.full(p.shape, 0.1 * (i + 1), np.float32)))
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            data_parallel_mesh(), optimizer="adam")
    x = mx.nd.array(np.ones((8, 4), np.float32))
    y = mx.nd.array(np.zeros((8,), np.float32))
    for _ in range(2):
        step(x, y).asnumpy()
    setup_s = time.time() - t_start
    for _ in range(10):
        step(x, y)
    ctx = _setup_ctx(attempted=10, setup_s=setup_s)
    values = {m: run.reader(m)(ctx) for m in READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    build = setup_ring.seconds(setup_ring.split(ctx)["first_build"])
    assert build > 0
    # the window's first call came a moment after ``setup_s`` was read:
    # nothing ran under a program span in between
    assert sum(values[m] for m in PHASES) + build == pytest.approx(setup_s)
    assert values["setup_eager_compile_s"] <= sum(values[m]
                                                  for m in PHASES[:4])
    assert values["setup_eager_programs"] >= 1
    telemetry.reset()
