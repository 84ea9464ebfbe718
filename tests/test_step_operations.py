"""The operation table of the executable a train step runs
(``xprof.operation_table`` / ``xprof.step_operations``): the parser on a
small recorded compiled text (a step of two layers compiled for a described
v5e, kept with the benchmark's tests, whose readers join it with a trace),
and a ``ShardedTrainStep`` whose handle and table cost no trace and no
lowering and outlive the step."""
import gc
import os
import re

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import gluon, telemetry, xprof
from mxtpu.base import MXNetError
from mxtpu.gluon import nn
from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "benchmark", "tests", "data",
                        "step_small_tpu.hlo.txt")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        text = f.read()
    return text, xprof.operation_table(text)


def test_every_instruction_of_the_text_is_in_the_table(recorded):
    text, table = recorded
    lines = [l for l in text.splitlines()
             if re.match(r"^\s+(ROOT )?%[\w.\-]+ = ", l)]
    assert len(table) == len(lines) == 387
    entry = table["cond.11"]["computation"]
    assert re.search(r"^ENTRY %%%s " % re.escape(entry), text, re.M)
    # in the entry computation nothing runs inside anything
    assert all(row["inside"] is None for row in table.values()
               if row["computation"] == entry)
    assert {row["opcode"] for row in table.values()} >= {
        "fusion", "conditional", "while", "custom-call", "copy",
        "convolution", "parameter"}


@pytest.mark.parametrize("name,opcode,inside,op_name", [
    # a switch of the routed layer and what its branches run
    ("cond.11", "conditional", None,
     "jit(step)/jvp(forward)/net_/h_/decoderblock1_/moe_/moe.experts/cond"),
    ("cond.17", "conditional", None,
     "jit(step)/transpose(jvp(forward))/net_/h_/decoderblock1_/moe_/"
     "moe.experts/cond"),
    # a loop of the second layer's mixer, forward and backward
    ("while.6", "while", None,
     "jit(step)/jvp(forward)/net_/h_/decoderblock1_/attn_/while"),
    ("while.7", "while", None,
     "jit(step)/transpose(jvp(forward))/net_/h_/decoderblock1_/attn_/while"),
    # the Pallas kernel, under its own name
    ("double_fwd.1", "custom-call", None,
     "jit(step)/jvp(forward)/net_/head_/double_fwd/pallas_call"),
    # the recomputed block's second forward, and its backward
    ("multiply_reduce_fusion.1", "fusion", None,
     "jit(step)/transpose(jvp(forward))/net_/jvp(forward)/net_/checkpoint/"
     "rematted_computation/h_/decoderblock0_/attn_/dot_general"),
    ("fusion.19", "fusion", None,
     "jit(step)/transpose(jvp(forward))/net_/jvp(forward)/net_/checkpoint/"
     "h_/decoderblock0_/attn_/dot_general"),
])
def test_an_instruction_carries_its_path(recorded, name, opcode, inside,
                                         op_name):
    row = recorded[1][name]
    assert (row["opcode"], row["inside"], row["op_name"]) == (
        opcode, inside, op_name)


def test_what_runs_in_a_switch_or_a_loop_names_it(recorded):
    text, table = recorded
    inside = {}
    for name, row in table.items():
        if row["inside"] is not None:
            inside.setdefault(row["inside"], []).append(name)
    assert set(inside) == {"cond.11", "cond.17", "while.6", "while.7"}
    # both branches of a switch, a loop's condition and body
    for switch, computations in (("cond.11", 2), ("while.6", 2)):
        assert len({table[n]["computation"] for n in inside[switch]}) \
            == computations
    # a branch's own operations carry the switch's path and their own
    named = [table[n]["op_name"] for n in inside["cond.11"]
             if table[n]["op_name"]]
    assert named and all("/moe.experts/cond/branch_" in o for o in named)
    # and a copy the compiler put in carries none: a reader counts it
    # under the switch's (``benchmark/step_scopes.py``)
    assert any(table[n]["opcode"] == "copy" and not table[n]["op_name"]
               for n in inside["cond.11"])
    # a fused computation's instructions are in no trace: inside nothing
    assert all(row["inside"] is None for row in table.values()
               if row["computation"].startswith("fused_computation"))


def test_a_fusion_without_a_name_takes_its_roots(recorded):
    text, table = recorded
    lines = {m.group(1): line for line in text.splitlines()
             for m in [re.match(r"^\s+(?:ROOT )?%([\w.\-]+) = .* fusion\(",
                                line)] if m}
    nameless = [n for n, line in lines.items() if "op_name=" not in line]
    # the text's own nameless fusions hold nothing named (bitcasts, a
    # select of constants): they stay nameless
    assert nameless and all(table[n]["op_name"] == "" for n in nameless)
    # the same text with one fusion's own metadata taken off: it reads its
    # fused root's name, or failing that the last name inside it
    every = text.splitlines()
    for name in ("fusion.19", "multiply_reduce_fusion.1"):
        line = lines[name]
        bare = re.sub(r", metadata=\{[^}]*\}", "", line)
        assert bare != line and "op_name" not in bare
        callee = re.search(r"calls=%([\w.\-]+)", line).group(1)
        start = every.index(next(
            l for l in every if l.startswith("%%%s (" % callee)))
        body = every[start + 1:every.index("}", start)]
        names = re.findall(r'op_name="([^"]*)"', "\n".join(body))
        root = re.findall(r'op_name="([^"]*)"', next(
            l for l in body if l.lstrip().startswith("ROOT ")))
        again = xprof.operation_table(text.replace(line, bare))
        assert names and again[name]["op_name"] == (root or names[-1:])[0]
        assert len(again) == len(table)


@pytest.mark.parametrize("op_name,transform", [
    ("jit(step)/jvp(forward)/net_/h_/decoderblock0_/attn_/tanh", "forward"),
    ("jit(step)/transpose(jvp(forward))/net_/jvp(forward)/net_/checkpoint/"
     "rematted_computation/h_/decoderblock0_/attn_/tanh", "recomputed"),
    ("jit(step)/transpose(jvp(forward))/net_/jvp(forward)/net_/checkpoint/"
     "h_/decoderblock0_/attn_/mul", "backward"),
    ("jit(step)/optimizer/sub", "optimizer"),
    ("param_datas[3]", None),       # an argument's own name
    ("", None),
])
def test_a_path_names_its_transform(op_name, transform):
    assert xprof.transform_of(op_name) == transform


def test_a_scope_is_found_between_slashes_and_inside_brackets():
    assert xprof.scope_path(
        "jit(step)/transpose(jvp(kda_conv))/jit(_backward)/moe.route/mul") \
        == ["jit", "step", "transpose", "jvp", "kda_conv", "jit",
            "_backward", "moe.route", "mul"]


# ------------------------------------------------------ the running step
def _step(prefix="ops_"):
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu", prefix="up_"))
        net.add(nn.Dense(8))
    net.initialize()
    net(mx.nd.array(np.zeros((16, 16), np.float32)))
    return ShardedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), data_parallel_mesh(),
        optimizer="adam", optimizer_params={"learning_rate": 0.01})


def _batch(n=16):
    rng = np.random.RandomState(n)
    return (mx.nd.array(rng.uniform(size=(n, 16)).astype(np.float32)),
            mx.nd.array(rng.randint(0, 8, size=(n,)).astype(np.float32)))


def _compile_events(since=0):
    return [e[0] for e in telemetry.events()[since:]
            if e[0] in ("jax.trace", "jax.lower", "jax.backend_compile")]


def test_the_step_is_built_once_and_its_table_costs_no_trace():
    telemetry.reset()
    step, batch = _step(), _batch()
    with pytest.raises(MXNetError):
        step.compiled()                 # nothing has run yet
    before = len(telemetry.events())
    losses = [float(step(*batch).asnumpy()) for _ in range(3)]
    assert losses[2] < losses[0]
    # set-up holds one lowering and one backend compile of the step, in
    # the first call's build (a function jitted inside it reports its own
    # trace nested in the step's, never a lowering of its own)
    events = telemetry.events()[before:]
    build = [(ts, ts + dur) for n, _c, ts, dur, _t in events
             if n == "train_step.build"]
    assert len(build) == 1
    inside = [n for n, _c, ts, dur, _t in events
              if n.startswith("jax.") and build[0][0] <= ts
              and ts + dur <= build[0][1]]
    assert inside.count("jax.lower") == 1
    assert inside.count("jax.backend_compile") == 1
    assert inside.count("jax.trace") >= 1
    after = len(telemetry.events())
    assert "jax.lower" not in _compile_events(before + len(events))

    handle = step.compiled()
    assert handle is step.compiled()
    assert handle.as_text().startswith("HloModule jit_sharded_train_step")
    table = xprof.step_operations()
    assert table is xprof.step_operations()         # parsed once, kept
    assert step.compiled_step_flops() > 0
    # the ledger's analyses resolve from the same handle and leave it
    assert xprof.ledger("parallel.train_step")[-1]["flops"] > 0
    assert xprof.step_operations() is table
    assert _compile_events(after) == []             # none of it compiled

    names = {row["op_name"] for row in table.values()}
    for path in ("/jvp(forward)/ops_/up_/dot_general",
                 "/transpose(jvp(forward))/ops_/up_/dot_general",
                 "/jvp(forward)/ops_/up_/relu0_/max",
                 "/jvp(forward)/ops_/dense0_/dot_general"):
        assert any(path in n for n in names), path
    assert any("/optimizer/" in n for n in names)
    recorded = xprof.ledger("parallel.train_step",
                            resolve=False)[-1]["operations"]
    assert recorded["instructions"] == len(table)
    assert recorded["text_bytes"] == len(handle.as_text())

    # the readers run after the program is freed: the table still answers
    del step, handle
    gc.collect()
    assert xprof.step_operations() is table
    assert _compile_events(after) == []


def test_the_lowered_text_is_one_public_call_and_a_trace_of_its_own():
    step, batch = _step(prefix="low_"), _batch()
    with pytest.raises(MXNetError):
        step.lowered()
    step(*batch)
    before = len(telemetry.events())
    builds = telemetry.value("retrace.parallel.train_step")
    text = step.lowered().as_text()
    assert "stablehlo." in text and "low_" not in text      # no names
    assert "low_/up_" in step.lowered().as_text(debug_info=True)
    assert _compile_events(before).count("jax.lower") == 2
    assert "jax.backend_compile" not in _compile_events(before)
    step(*batch)                        # and the step runs on as it was
    assert telemetry.value("retrace.parallel.train_step") == builds


def test_the_eager_path_opens_no_scope(monkeypatch):
    """A block's name is a scope in a traced region alone."""
    import jax
    opened = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: opened.append(name) or real(name))
    net = nn.Dense(4, in_units=3, prefix="eager_")
    net.initialize()
    net(mx.nd.array(np.ones((2, 3), np.float32)))
    assert opened == []


def test_a_block_without_a_prefix_takes_its_key():
    net = nn.HybridSequential(prefix="")
    with net.name_scope():
        net.add(nn.Dense(4, in_units=3))
    assert net._own_name == ""          # the top block: it has no parent
    top = nn.HybridSequential(prefix="top_")
    top.features = net
    assert net._own_name == "features"
    assert top._own_name == "top_"
    assert list(net._children.values())[0]._own_name == "dense0_"


def test_without_a_handle_the_table_is_none(monkeypatch):
    import jax
    import jax.numpy as jnp
    fn = telemetry.record_retrace("demo.plain", None,
                                  compiled=jax.jit(lambda a: a + 1))
    fn(jnp.ones((2,)))
    assert xprof.step_operations("demo.plain") is None
    assert xprof.step_operations("demo.nobody") is None


# ------------------------------------------------------------- the printer
@pytest.mark.parametrize("by_transform", [False, True])
def test_the_tool_prints_the_matrix(recorded, tmp_path, by_transform):
    """``tools/trace_by_scope.py`` over a kept trace and the text of the
    same executable (the join is the benchmark's, the parser the
    program's), and over the ``step_by_scope.json`` a traced run wrote."""
    import importlib.util
    import json
    from benchmark import step_scopes
    spec = importlib.util.spec_from_file_location(
        "trace_by_scope", os.path.join(ROOT, "tools", "trace_by_scope.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    table = recorded[1]
    trace = {"planes": 1, "modules": {"jit_step": [0.1, 0.1]},
             "ops": {"cond.11": 6e-3, "fusion.19": 2e-3,
                     "multiply_reduce_fusion.1": 4e-3, "double_fwd.1": 1e-3,
                     **{n: 1e-3 for n, row in table.items()
                        if row["inside"] == "cond.11"
                        and row["opcode"] == "copy"}}}
    kept = tmp_path / "trace_ops.json"
    kept.write_text(json.dumps(trace))
    args = [str(kept), RECORDED] + ["--by-transform"] * by_transform
    lines = []
    assert tool.main(args, out=lines.append) == 0
    text = "\n".join(lines)
    assert "names matched 100.00%" in text
    by_scope = text.split("-- by scope")[1].split("-- by layer kind")[0]
    assert ("recomputed" in by_scope) == by_transform
    assert "multiply_reduce_fusion.1" in text.split("-- the longest")[1]
    row = next(l for l in lines if "h_/decoderblock*_/attn_" in l).split()
    assert float(row[1]) == pytest.approx(3.0)       # 2 recomputed, 1 back
    if by_transform:
        assert [float(x) for x in row[2:]] == pytest.approx([0, 2, 1, 0])
    # the same from the file a traced run writes
    found = step_scopes.join(trace, table)
    written = tmp_path / "step_by_scope.json"
    written.write_text(json.dumps(dict(found, cell="made_up.train")))
    again = []
    assert tool.main([str(written)] + args[2:], out=again.append) == 0
    assert again[1:] == lines[1:] and again[0].startswith("made_up.train")
    # another executable's names: nothing to join
    trace["ops"] = {"x." + k: v for k, v in trace["ops"].items()}
    kept.write_text(json.dumps(trace))
    assert tool.main(args, out=lines.append) == 1
