"""``ShardedTrainStep.__call__`` from the inside: one ``train_step`` span tree
a call, JAX's compile events recorded where they happen, the spans mirrored
into the profiler's trace, and stable names on the device side that change
no operation. CPU, the 8-device virtual mesh; counts and structure only,
never a time."""
import contextlib
import glob
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import gluon, telemetry
from mxtpu.gluon import nn
from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh

CHILDREN = ("train_step.place", "train_step.rng", "train_step.build",
            "train_step.launch", "train_step.commit")


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.reset()
    yield
    telemetry.reset()


def _step(optimizer="sgd", prefix="spans_"):
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(8))
    net.initialize()
    net(mx.nd.array(np.zeros((16, 16), np.float32)))
    return ShardedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), data_parallel_mesh(),
        optimizer=optimizer,
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9}
        if optimizer == "sgd" else {"learning_rate": 0.01})


def _batch(n=16):
    rng = np.random.RandomState(n)
    return (mx.nd.array(rng.uniform(size=(n, 16)).astype(np.float32)),
            mx.nd.array(rng.randint(0, 8, size=(n,)).astype(np.float32)))


def _trees():
    """The trace ring as [{name: event}] per ``train_step`` tree, in call
    order; compile events aside."""
    by_trace = {}
    for e in telemetry.trace_events():
        if not e["name"].startswith("jax."):
            by_trace.setdefault(e["trace"], []).append(e)
    trees = [evs for evs in by_trace.values()
             if any(e["name"] == "train_step" for e in evs)]
    return sorted(trees, key=lambda evs: min(e["ts_us"] for e in evs))


def test_one_call_is_one_tree_with_its_children():
    step, batch = _step(), _batch()
    for _ in range(3):
        step(*batch)
    trees = _trees()
    assert len(trees) == 3
    for n, evs in enumerate(trees):
        names = [e["name"] for e in evs]
        assert len({e["trace"] for e in evs}) == 1
        root = [e for e in evs if e["name"] == "train_step"]
        assert len(root) == 1 and root[0]["parent"] == 0
        kids = [e for e in evs if e["name"] != "train_step"]
        assert all(e["parent"] == root[0]["span"] for e in kids)
        assert set(names) - {"train_step"} <= set(CHILDREN)
        # the first call builds, every later one launches
        want = "train_step.build" if n == 0 else "train_step.launch"
        assert sorted(names) == sorted(
            ["train_step", "train_step.place", "train_step.rng", want,
             "train_step.commit"])
        # the children lie inside the root, one after the other
        kids.sort(key=lambda e: e["ts_us"])
        assert kids[0]["ts_us"] >= root[0]["ts_us"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts_us"] + a["dur_us"] <= b["ts_us"]
        assert kids[-1]["ts_us"] + kids[-1]["dur_us"] <= \
            root[0]["ts_us"] + root[0]["dur_us"] + 1
    hists = telemetry.snapshot()["histograms"]
    assert hists["train_step"]["count"] == 3
    assert hists["train_step.build"]["count"] == 1
    assert hists["train_step.launch"]["count"] == 2


def test_a_shape_change_builds_again_and_its_compile_is_recorded_there():
    step = _step()
    step(*_batch(16))
    step(*_batch(16))
    before = {k: dict(telemetry.tagged(k)) for k in
              ("compile.trace_s", "compile.lower_s", "compile.backend_s")}
    step(*_batch(32))                      # a new input shape: a real compile
    step(*_batch(32))
    names = [[e["name"] for e in evs] for evs in _trees()]
    assert ["train_step.build" in n for n in names] == \
        [True, False, True, False]
    third = sorted(telemetry.trace_events(), key=lambda e: e["ts_us"])
    build = [e for e in third if e["name"] == "train_step.build"][1]
    inside = [e for e in third if e["name"].startswith("jax.")
              and e["trace"] == build["trace"]
              and e["ts_us"] >= build["ts_us"]]
    assert {"jax.trace", "jax.lower", "jax.backend_compile"} <= \
        {e["name"] for e in inside}
    # parented under the span that was open: the build of THAT call
    assert all(e["parent"] == build["span"] for e in inside)
    for e in inside:
        assert build["ts_us"] <= e["ts_us"] and e["dur_us"] >= 0
    for k, was in before.items():
        assert telemetry.tagged(k)["train_step.build"] > \
            was["train_step.build"] > 0
    # and the event ring holds them too, on the spans' clock
    ring = [e[0] for e in telemetry.events()]
    assert ring.count("train_step.build") == 2
    assert "jax.backend_compile" in ring
    # nothing compiled inside a launch
    assert "train_step.launch" not in telemetry.tagged("compile.backend_s")


def test_no_device_to_host_sync_in_a_step():
    step, batch = _step(), _batch()
    step(*batch)                           # the build, outside the guard
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            loss = step(*batch)
    assert telemetry.value("train_step.d2h") == 0
    assert np.isfinite(float(loss.asnumpy()))


def test_telemetry_off_records_nothing_and_the_step_still_runs(monkeypatch):
    # off before the step is built: ``train_step.init`` and the parameter
    # load's spans (tests/test_setup_spans.py) are spans like the call's
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    step, batch = _step(), _batch()
    first = float(step(*batch).asnumpy())
    second = float(step(*batch).asnumpy())
    assert second < first
    assert telemetry.events() == [] and _trees() == []
    assert "train_step" not in telemetry.snapshot()["histograms"]


def test_spans_are_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    step, batch = _step(), _batch()
    step(*batch)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            loss = step(*batch)
        loss.asnumpy()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    found = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("train_step"):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert len(found["train_step.launch"]) == 2
    assert len(found["train_step"]) == 2
    assert set(found) == {"train_step", "train_step.place", "train_step.rng",
                          "train_step.launch", "train_step.commit"}
    # every mirrored span carries its category: what tells the program's
    # spans from JAX's own host events
    assert all(s.get("cat") == "phase" for stats in found.values()
               for s in stats)
    # the root is a step annotation numbered by the update it makes
    assert sorted(s["step_num"] for s in found["train_step"]) == [2, 3]


def test_device_side_names():
    step, batch = _step(), _batch()
    step(*batch)
    text = step.compiled().as_text()
    assert re.search(r"HloModule jit_sharded_train_step\b", text)
    ops = re.findall(r'op_name="([^"]*)"', text)
    assert any(o.startswith("jit(sharded_train_step)/") for o in ops)
    assert any("/jvp(forward)/" in o for o in ops)
    assert any("/transpose(jvp(forward))/" in o for o in ops)    # backward
    assert any("/optimizer/" in o for o in ops)
    assert not any("forward" in o and "/optimizer/" in o for o in ops)


def _stablehlo(step, batch):
    step(*batch)
    return step.lowered().as_text()


def test_the_scopes_change_no_operation(monkeypatch):
    """The lowered step, which carries no names unless asked for them, is
    the same text with the scopes and without."""
    batch = _batch()
    with_scopes = _stablehlo(_step(prefix="a_"), batch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _stablehlo(_step(prefix="a_"), batch)
    assert "stablehlo." in with_scopes
    assert with_scopes == without
    named = _step(prefix="a_")
    monkeypatch.undo()
    named(*batch)
    debug = named.lowered().as_text(debug_info=True)
    assert "optimizer" in debug and "forward" in debug


@pytest.mark.parametrize("kernel", ["flash_attention_fwd",
                                    "flash_attention_bwd"])
def test_kernels_carry_their_name(kernel, monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    q = jnp.ones((1, 2, 128, 128), jnp.float32)
    if kernel == "flash_attention_fwd":
        fn = lambda q: fa._fa_forward_pallas(q, q, q, fa.Mask(), 1.0, 128,
                                             128)
    else:
        fn = lambda q: fa._fa_backward_pallas(
            q, q, q, q, q[..., 0], q, fa.Mask(), 1.0, 128, 128)
    assert re.search(r"name=%s\b" % kernel, str(jax.make_jaxpr(fn)(q)))
    assert kernel in jax.jit(fn).lower(q).as_text(debug_info=True)


@pytest.mark.parametrize("path", ["blockwise", "pallas"])
def test_flash_backward_is_found_by_its_scope(path, monkeypatch):
    """Either backward lies whole under the scope ``flash_attention_bwd``:
    the join of PERF.md §5 finds the kernel with its prologue."""
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    fa = importlib.import_module("mxtpu.ops.pallas.flash_attention")
    q = jnp.ones((1, 2, 128, 64))
    lse = jnp.ones((1, 2, 128))
    if path == "blockwise":
        fn = lambda q: fa._fa_backward_blockwise(
            q, q, q, q, lse, q, fa.Mask(), 1.0, 128)
    else:
        fn = lambda q: fa._fa_backward_pallas(
            q, q, q, q, lse, q, fa.Mask(), 1.0, 128, 128)
    text = jax.jit(fn).lower(q).as_text(debug_info=True)
    assert "flash_attention_bwd" in text
    # the prologue (delta's reduction) is inside the scope too
    assert re.search(r"flash_attention_bwd/[^\"]*reduce_sum", text)


def test_optimizer_states_are_the_trainable_leaves_in_order():
    step, batch = _step("adam"), _batch()
    step(*batch)
    states = step.optimizer_states()
    trainable = [p for p in step._params if p.grad_req != "null"]
    assert len(states) == len(trainable) == 4
    for st, p in zip(states, trainable):
        leaves = jax.tree_util.tree_leaves(st)
        assert len(leaves) == 2                       # Adam: mean, variance
        assert all(tuple(l.shape) == tuple(p.shape) for l in leaves)
    # live state: after one step the first moment is no longer zero
    assert float(jnp.abs(jax.tree_util.tree_leaves(states[0])[0]).sum()) > 0


def test_compile_events_without_a_span_are_untraced():
    telemetry.watch_compiles()
    assert telemetry.watch_compiles() is telemetry.watch_compiles()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,)))
    assert telemetry.tagged("compile.trace_s").get("untraced", 0) > 0
    assert telemetry.tagged("compile.backend_s").get("untraced", 0) > 0
    evs = [e for e in telemetry.trace_events() if e["name"] == "jax.lower"]
    assert evs and evs[-1]["trace"] is None and evs[-1]["parent"] is None
    with telemetry.span("outer.region"):
        jax.jit(lambda x: x * 5 + 2)(jnp.ones((5,)))
    assert telemetry.tagged("compile.lower_s")["outer.region"] > 0


def test_nested_traces_are_nested_events():
    """A function jitted inside another reports its own trace inside the
    outer one's interval: a reader adds intervals up by their union."""
    inner = jax.jit(lambda x: jnp.tanh(x) * 2)
    with telemetry.span("nest", new_trace=True):
        jax.jit(lambda x: inner(x) + inner(x * 2))(jnp.ones((7,)))
    traces = sorted((e for e in telemetry.trace_events()
                     if e["name"] == "jax.trace"),
                    key=lambda e: -e["dur_us"])
    outer = traces[0]
    assert len(traces) >= 2
    assert any(outer["ts_us"] <= e["ts_us"] and e["ts_us"] + e["dur_us"]
               <= outer["ts_us"] + outer["dur_us"] for e in traces[1:])


def test_perf_trace_finds_the_span_open_at_a_moment(tmp_path):
    """``tools/perf_trace.py`` attributes a moment of a trace (an idle gap
    of the device, on the chip) to the program span open on the host."""
    import os
    import sys
    from jax.profiler import ProfileData
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        perf_trace = importlib.import_module("perf_trace")
    finally:
        sys.path.pop(0)
    step, batch = _step(), _batch()
    step(*batch)
    jax.profiler.start_trace(str(tmp_path))
    try:
        step(*batch).asnumpy()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = list(ProfileData.from_file(files[0]).planes)
    index = perf_trace.host_index(planes)
    launch = [ev for p in planes for ln in p.lines for ev in ln.events
              if ev.name == "train_step.launch"][0]
    mid = launch.start_ns + launch.duration_ns / 2
    stacks = [prog for _thread, prog, _inner in
              perf_trace.host_stacks(index, mid) if prog]
    assert stacks == [["train_step", "train_step.launch"]]
    assert all(not prog for _t, prog, _i in perf_trace.host_stacks(
        index, launch.start_ns - 10 ** 12))
    perf_trace.print_op_aggregates(files)     # reads a CPU trace too
    perf_trace.print_gap_spans(files)         # no device plane: prints none
