"""The readers of the program's own spans (``benchmark/span_ring.py`` and
the nine ``layer_metrics`` files on it), each on a hand-made ring: the
set-up's calls left out, the first 15% of the window's taken, and nothing
returned from a ring that is short, has wrapped, or belongs to a program
without the spans."""
import pytest

from benchmark import run, span_ring
from benchmark.tests import SPEC

CHILD_MS = {"place": 1.0, "rng": 0.5, "launch": 2.0, "commit": 0.25}
READERS = {
    "step_call_ms.train": 4.0,
    "step_call_place_ms.train": 1.0,
    "step_call_rng_ms.train": 0.5,
    "step_call_launch_ms.train": 2.0,
    "step_call_commit_ms.train": 0.25,
    "window_compiles.train": 0,
    "step_trace_s": 9.0,            # 5 s, then 4 s with 2 s nested in them
    "step_lower_s": 3.0,
    "step_backend_compile_s": 0.5,
}


def _call(events, t, build=False, slow=1.0):
    """One ``train_step`` tree from ``t`` (us) on, as the program appends
    it: children as they end, then the root. Returns the end."""
    start = t
    for kid, ms in CHILD_MS.items():
        if kid == "launch" and build:
            kid, dur = "build", 20_000_000
            events += [("jax.trace", "compile", t + 10, 5_000_000, 1),
                       ("jax.trace", "compile", t + 6_000_010, 2_000_000, 1),
                       ("jax.trace", "compile", t + 6_000_000, 4_000_000, 1),
                       ("jax.lower", "compile", t + 11_000_000, 3_000_000, 1),
                       ("jax.backend_compile", "compile", t + 15_000_000,
                        500_000, 1)]
        else:
            dur = int(ms * 1e3 * slow)
        events.append(("train_step." + kid, "phase", t, dur, 1))
        t += dur
    # the root outlasts its children by a quarter of a millisecond
    events.append(("train_step", "phase", start, t - start + 250, 1))
    return t + 400


def _ring(window=40, setup=3):
    """Set-up (a build, then launches, and a program compiled outside any
    step), a window whose calls get twice as slow after the first 15%
    (the profiler), and the reference's compiles after it."""
    events, t = [], 1_000
    events.append(("jax.trace", "compile", t, 900_000, 1))       # not the step's
    t += 1_000_000
    for n in range(setup):
        t = _call(events, t, build=(n == 0))
    events.append(("ndarray.asnumpy", "phase", t, 40, 1))
    for n in range(window):
        t = _call(events, t + 100, slow=1.0 if n < int(0.15 * window)
                  else 2.0)
    for _ in range(5):                                           # the reference
        t += 1_000
        events.append(("jax.trace", "compile", t, 800, 1))
        events.append(("jax.backend_compile", "compile", t + 900, 50, 1))
    return events


@pytest.fixture()
def ring(monkeypatch):
    from mxtpu import telemetry
    events = _ring()
    monkeypatch.setattr(telemetry, "events", lambda: list(events))
    return events


def _ctx(attempted=40):
    window = {"end_to_end": {}, "spans": {}}
    if attempted is not None:
        window["attempted"] = attempted
    return {"cell": None, "trace": None, "peak": None, "window": window,
            "compile_clock": None}


def test_every_span_metric_has_a_case_here():
    mine = {m["name"] for m in SPEC["per_layer"]
            if m["name"].startswith(("step_", "window_compiles"))
            and m["name"] != "step_device_ms.train"}
    assert mine == set(READERS)
    for m in SPEC["per_layer"]:
        if m["name"] in READERS:
            assert m["source"] == "program_counter" and m["workloads"]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_on_a_hand_made_ring(ring, metric):
    read = run.reader(metric)
    assert read(_ctx()) == pytest.approx(READERS[metric])


def test_children_sum_to_most_of_the_call(ring):
    kids = sum(run.reader("step_call_%s_ms.train" % k)(_ctx())
               for k in CHILD_MS)
    assert 0.9 * run.reader("step_call_ms.train")(_ctx()) <= kids \
        <= run.reader("step_call_ms.train")(_ctx())


def test_only_the_first_15_percent_of_the_window_count(ring):
    # 40 calls: 6 at the plain speed, 34 at half of it. A median over all
    # of them, or over the set-up's calls too, would read 7.75 or more
    assert run.reader("step_call_ms.train")(_ctx()) == pytest.approx(4.0)
    # a window of 10 (here the last, slow ones) takes its first call
    # alone, never none
    assert span_ring.call_ms(_ctx(10), "train_step") == pytest.approx(7.75)


def test_a_compile_in_the_window_is_counted(ring):
    _events, roots = span_ring.ring(_ctx())
    inside = roots[20][0] + 5
    ring.insert(len(ring) - 12, ("jax.trace", "compile", inside, 700, 1))
    ring.insert(len(ring) - 12,
                ("jax.backend_compile", "compile", inside + 800, 900, 1))
    ring.insert(len(ring) - 12, ("jax.lower", "compile", inside + 750, 40, 1))
    assert run.reader("window_compiles.train")(_ctx()) == 2
    # the set-up's build and the reference's compiles never count
    assert run.reader("step_trace_s")(_ctx()) == pytest.approx(9.0)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_nothing_from_a_ring_that_cannot_be_trusted(ring, monkeypatch,
                                                    metric):
    from mxtpu import telemetry
    read = run.reader(metric)
    assert read(_ctx(attempted=None)) is None        # no window
    assert read(_ctx(attempted=0)) is None
    assert read(_ctx(attempted=44)) is None          # fewer calls than made
    # a ring at its capacity has lost its head
    monkeypatch.setattr(telemetry, "EVENT_RING_CAP", len(ring))
    assert read(_ctx()) is None
    monkeypatch.setattr(telemetry, "EVENT_RING_CAP", len(ring) + 1)
    assert read(_ctx()) is not None
    # a program without these spans (the parent of the PR that added them)
    monkeypatch.setattr(telemetry, "events", lambda: [
        ("trainer.step", "phase", 10, 5, 1)] * 50)
    assert read(_ctx()) is None


def test_a_real_step_fills_the_ring_the_readers_read():
    """The names the readers look for are the ones the program writes."""
    import numpy as np
    import mxtpu as mx
    from mxtpu import gluon, telemetry
    from mxtpu.gluon import nn
    from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh
    telemetry.reset()
    net = nn.HybridSequential(prefix="ring_")
    with net.name_scope():
        net.add(nn.Dense(8))
    net.initialize()
    x = mx.nd.array(np.ones((8, 4), np.float32))
    y = mx.nd.array(np.zeros((8,), np.float32))
    net(x)
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            data_parallel_mesh())
    for _ in range(12):
        step(x, y)
    ctx = _ctx(attempted=10)
    for metric in READERS:
        value = run.reader(metric)(ctx)
        assert value is not None and value >= 0, metric
    assert run.reader("window_compiles.train")(ctx) == 0
    assert run.reader("step_trace_s")(ctx) > 0
    telemetry.reset()
