"""A restart as the program's own ring sees it (ISSUE 34): ``mxtpu.import``,
the parameter load's spans, ``train_step.init`` with its three children, and
every compile of the process as a ring event, those before the first span
among them. One fresh interpreter records a restart (the import's event and
the listeners' registration cannot be seen again in a process that has
already imported the program); the rest runs here.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import gluon, telemetry
from mxtpu.gluon import nn
from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INIT = "train_step.init"
KIDS = [INIT + ".place_params", INIT + ".create_states",
        INIT + ".place_states"]
COMPILE = ("jax.trace", "jax.lower", "jax.backend_compile")

RESTART = r"""
import json, sys
import numpy as np
import jax
import jax.numpy as jnp
import mxtpu as mx
from mxtpu import gluon, telemetry
from mxtpu.gluon import nn
from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh
# a program of this restart's own, compiled before any span has opened
jnp.tanh(jnp.arange(7.0) * 0.37).block_until_ready()
net = nn.HybridSequential(prefix="restart_")
with net.name_scope():
    net.add(nn.Dense(8, in_units=4), nn.Dense(3, in_units=8))
net.cast("float32")
leaves = [np.full(p.shape, 0.01 * (i + 1), np.float32)
          for i, p in enumerate(net.collect_params().values())]
for p, leaf in zip(net.collect_params().values(), leaves):
    p.set_data(mx.nd.array(leaf))
step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                        data_parallel_mesh(), optimizer="adam")
x = mx.nd.array(np.ones((8, 4), np.float32))
y = mx.nd.array(np.zeros((8,), np.float32))
step(x, y)
with telemetry.span("probe.root", new_trace=True):
    with telemetry.span("probe.child"):
        jax.jit(lambda a: jnp.sin(a) * 2.5)(jnp.arange(11.0))
json.dump({"events": telemetry.events(),
           "trace": telemetry.trace_events(),
           "tags": {k: telemetry.tagged("compile.%s_s" % k)
                    for k in ("trace", "lower", "backend")}}, sys.stdout)
"""


@pytest.fixture(scope="module")
def restart():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("MXTPU_TELEMETRY", None)
    env.pop("MXTPU_TRACE", None)
    out = subprocess.run([sys.executable, "-c", RESTART], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout[out.stdout.index('{"events"'):])


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_the_import_is_the_ring_s_first_span(restart):
    events = restart["events"]
    (imp,) = _named(events, "mxtpu.import")
    assert imp[1] == "setup" and imp[3] > 0
    spans = [e for e in events if e[0] not in COMPILE]
    assert min(spans, key=lambda e: e[2]) == imp
    # the first line of ``mxtpu/__init__.py`` to its last: nothing of the
    # program's own ran before it began
    assert all(e[2] >= imp[2] for e in events)


def test_a_compile_before_any_span_is_in_the_ring(restart):
    events = restart["events"]
    imp_end = sum(_named(events, "mxtpu.import")[0][2:4])
    first_span = min(e[2] for e in events
                     if e[0] not in COMPILE and e[0] != "mxtpu.import")
    early = [e for e in events if e[0] in COMPILE
             and imp_end <= e[2] and e[2] + e[3] <= first_span]
    assert {e[0] for e in early} == set(COMPILE)
    assert all(e[1] == "compile" for e in early)
    # under no span: the counters file it as ``untraced``
    assert restart["tags"]["backend"]["untraced"] > 0


def test_parameter_load_spans_one_a_leaf(restart):
    events = restart["events"]
    assert len(_named(events, "gluon.param.set_data")) == 4
    assert len(_named(events, "gluon.cast")) == 1
    assert all(e[1] == "setup" for e in events
               if e[0].startswith(("gluon.param.", "gluon.cast")))


def test_step_init_is_a_root_with_three_children(restart):
    events, trace = restart["events"], restart["trace"]
    (root,) = _named(events, INIT)
    kids = [_named(events, k)[0] for k in KIDS]
    end = root[2]
    for kid in kids:                  # inside the root, in order, disjoint
        assert end <= kid[2] and kid[2] + kid[3] <= root[2] + root[3]
        end = kid[2] + kid[3]
    # the ring ends a child before its root, and the root before the step
    order = [e[0] for e in events if e[0] in KIDS + [INIT, "train_step"]]
    assert order == KIDS + [INIT, "train_step"]
    by_name = {t["name"]: t for t in trace if t["kind"] == "span"}
    assert by_name[INIT]["parent"] == 0              # a trace of its own
    for k in KIDS:
        assert by_name[k]["parent"] == by_name[INIT]["span"]
        assert by_name[k]["trace"] == by_name[INIT]["trace"]
    assert by_name["train_step"]["trace"] != by_name[INIT]["trace"]


def test_a_compile_s_parent_is_the_span_open_on_its_thread(restart):
    trace = restart["trace"]
    (child,) = [t for t in trace if t["name"] == "probe.child"]
    inside = [t for t in trace if t["name"] in COMPILE
              and child["ts_us"] <= t["ts_us"] and t["ts_us"] + t["dur_us"]
              <= child["ts_us"] + child["dur_us"]]
    assert {t["name"] for t in inside} == set(COMPILE)
    assert all(t["parent"] == child["span"] and t["trace"] == child["trace"]
               for t in inside)
    for kind in ("trace", "lower", "backend"):
        assert restart["tags"][kind]["probe.child"] > 0
    # the parameter load's programs (a gradient buffer a trainable leaf)
    # are filed under its span: no trace was open there, the tag is all
    assert restart["tags"]["backend"]["gluon.param.set_data"] > 0


def _small_net(prefix):
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        inner = nn.HybridSequential(prefix="inner_")
        with inner.name_scope():
            inner.add(nn.Dense(8, in_units=4), nn.BatchNorm(in_channels=8))
        net.add(inner, nn.Dense(3, in_units=8))
    return net


def _batch():
    rng = np.random.RandomState(0)
    return (mx.nd.array(rng.randn(16, 4).astype(np.float32)),
            mx.nd.array(rng.randint(0, 3, (16,)).astype(np.float32)))


def test_spans_of_a_block_built_here():
    telemetry.reset()
    net = _small_net("here_")
    net.cast("float32")
    net.initialize()
    leaves = list(net.collect_params().values())
    events = telemetry.events()
    # one ``gluon.cast`` for the whole tree, not one a block
    assert len(_named(events, "gluon.cast")) == 1
    assert len(_named(events, "gluon.param.init")) == len(leaves)
    for p in leaves:
        p.set_data(p.data() * 1)
    assert len(_named(telemetry.events(), "gluon.param.set_data")) \
        == len(leaves)
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            data_parallel_mesh(), optimizer="sgd",
                            optimizer_params={"momentum": 0.9})
    events = telemetry.events()
    (root,) = _named(events, INIT)
    assert [len(_named(events, k)) for k in KIDS] == [1, 1, 1]
    assert sum(_named(events, k)[0][3] for k in KIDS) <= root[3]
    assert len(step.optimizer_states()) == sum(
        p.grad_req != "null" for p in leaves)
    assert telemetry.open_span() is None
    telemetry.reset()


def test_a_deferred_init_is_a_span_where_the_array_is_made():
    telemetry.reset()
    dense = nn.Dense(5)               # the weight's input width not known
    dense.initialize()
    assert len(_named(telemetry.events(), "gluon.param.init")) == 1
    dense(mx.nd.array(np.ones((2, 3), np.float32)))
    assert len(_named(telemetry.events(), "gluon.param.init")) == 2
    telemetry.reset()


def test_record_interval_is_a_span_whose_start_was_read_earlier():
    import time
    telemetry.reset()
    t0 = time.perf_counter_ns()
    with telemetry.span("outer", new_trace=True) as outer:
        time.sleep(0.002)
        telemetry.record_interval("late.name", t0, cat="setup")
    (ev,) = _named(telemetry.events(), "late.name")
    assert ev[1] == "setup" and ev[2] == t0 // 1000 and ev[3] >= 2000
    hist = telemetry.snapshot()["histograms"]["late.name"]
    assert hist["count"] == 1
    assert hist["sum"] == pytest.approx(ev[3] / 1e6, abs=2e-6)
    (rec,) = [t for t in telemetry.trace_events() if t["name"] == "late.name"]
    assert rec["parent"] == outer.ctx.span_id
    telemetry.reset()


def test_watch_compiles_registers_once():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(5.0)
    telemetry.reset()
    assert telemetry.watch_compiles() is telemetry.watch_compiles()
    jax.jit(lambda a: jnp.cos(a) * 1.2345)(x)
    assert len(_named(telemetry.events(), "jax.backend_compile")) == 1
    telemetry.reset()


def test_telemetry_off_records_nothing_and_the_step_still_trains(monkeypatch):
    telemetry.reset()
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    import time
    telemetry.record_interval("off.name", time.perf_counter_ns())
    net = _small_net("off_")
    net.cast("float32")
    net.initialize()
    for p in net.collect_params().values():
        p.set_data(p.data() * 1)
    step = ShardedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                            data_parallel_mesh(), optimizer="sgd",
                            optimizer_params={"learning_rate": 0.5})
    x, y = _batch()
    losses = [float(step(x, y).asnumpy()) for _ in range(8)]
    assert losses[-1] < losses[0]
    assert telemetry.events() == [] and telemetry.trace_events() == []
    assert "off.name" not in telemetry.snapshot()["histograms"]
    telemetry.reset()
