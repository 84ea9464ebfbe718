"""The step's device time by the program's own names
(``benchmark/step_scopes.py`` and the thirteen ``layer_metrics`` files on
it), on a made-up trace over a small recorded compiled text
(``data/step_small_tpu.hlo.txt``: a step of two layers compiled for a
described v5e, with a ``conditional``, two ``while`` loops, a Pallas custom
call, a recomputed block and fusions without a name of their own): a
switch counts its self time, nothing is counted twice, the rows and the
columns add up to the step, and a program without the table reads None."""
import json
import os

import pytest

from benchmark import run, step_scopes
from mxtpu import xprof

HERE = os.path.dirname(os.path.abspath(__file__))
RUNS = 4            # the step module's runs in the made-up stretch


@pytest.fixture(scope="module")
def table():
    with open(os.path.join(HERE, "data", "step_small_tpu.hlo.txt")) as f:
        found = xprof.operation_table(f.read())
    # the compiler fused this small step's update into a gradient's fusion,
    # which kept the gradient's name: an update of its own is added by hand
    found["fusion.update"] = {
        "computation": found["cond.11"]["computation"], "opcode": "fusion",
        "op_name": "jit(step)/optimizer/sub", "inside": None}
    return found


def _trace(table):
    """Every operation of the entry computation and of the switches' and
    loops' branches, one millisecond a run each; a switch or a loop spans
    what runs inside it plus half a millisecond of its own; and one
    operation no table holds."""
    events = {n: RUNS * 1e-3 for n, row in table.items()
              if row["opcode"] in ("fusion", "copy", "custom-call",
                                   "convolution")
              and not row["computation"].startswith(("fused_", "bitcast_"))}
    for name, row in table.items():
        if row["opcode"] in ("conditional", "while"):
            events[name] = RUNS * 0.5e-3 + sum(
                s for n, s in events.items() if table[n]["inside"] == name)
    events["copy.unknown"] = RUNS * 2e-3
    return {"ops": events, "planes": 1,
            "modules": {"jit_step": [0.1] * RUNS, "jit_small": [1e-6] * 9}}


class _Cell:
    name = "made_up.train"

    def __init__(self, out_dir):
        self.out_dir = str(out_dir)


def _ctx(trace, tmp_path):
    return {"cell": _Cell(tmp_path), "trace": trace, "peak": None,
            "window": {"end_to_end": {}, "spans": {}, "attempted": 40},
            "compile_clock": None}


@pytest.fixture()
def program(table, monkeypatch):
    """A program whose newest train step holds ``table``."""
    monkeypatch.setattr(xprof, "step_operations", lambda: table,
                        raising=False)
    monkeypatch.setattr(xprof, "ledger", lambda *a, **k: [
        {"operations": {"instructions": len(table), "text_bytes": 60917,
                        "parse_s": 0.01}}])


def test_a_switch_counts_its_self_time_and_nothing_twice(table):
    trace = _trace(table)
    found = step_scopes.join(trace, table)
    switches = [n for n, r in table.items()
                if r["opcode"] in ("conditional", "while")]
    inside = [n for n in trace["ops"] if n in table
              and table[n]["inside"] is not None]
    assert len(switches) == 4 and len(inside) >= 8
    # the step is every operation once: the switches' half milliseconds,
    # a millisecond for each other, and the unknown copy's two
    others = len(trace["ops"]) - len(switches) - 1
    assert found["step_ms"] == pytest.approx(0.5 * 4 + others + 2.0)
    assert found["runs_a_chip"] == RUNS
    assert found["inside_switches_ms"] == pytest.approx(len(inside))
    # the routed layer's switches: their own half millisecond each and
    # what their branches ran, the nameless copies in them included
    experts = [n for n in inside
               if table[table[n]["inside"]]["opcode"] == "conditional"]
    assert sum(found["by_kind"]["moe_experts"].values()) >= \
        len(experts) + 2 * 0.5
    # a loop of the second layer's mixer is read through the same way
    assert found["by_layer"]["1"]["mixer"] > 0


def test_rows_and_columns_add_up_to_the_step(table):
    found = step_scopes.join(_trace(table), table)
    step = found["step_ms"]
    by_transform = found["by_transform"]
    assert sum(by_transform.values()) + found["unattributed_ms"] == \
        pytest.approx(step)
    assert all(by_transform[t] > 0 for t in step_scopes.TRANSFORMS)
    kinds = sum(sum(row.values()) for row in found["by_kind"].values())
    assert kinds + by_transform["optimizer"] + found["unattributed_ms"] \
        == pytest.approx(step)
    assert sum(sum(r.values()) for r in found["by_scope"].values()) \
        == pytest.approx(step - found["unattributed_ms"])
    # what no table holds, and the copies under no name outside a switch
    assert found["unattributed_ms"] >= 2.0
    assert found["matched_pct"] == pytest.approx(100 * (step - 2.0) / step)
    # the recomputed block is the first layer's: its mixer and its norm
    recomputed = {k: row["recomputed"] for k, row in found["by_kind"].items()
                  if row["recomputed"]}
    assert set(recomputed) == {"mixer", "layer_glue"}
    # the Pallas kernel ran under the head's name, in the forward
    assert found["by_scope"]["net_/head_/double_fwd"]["forward"] == 1.0
    top = found["top_operations"]
    assert 0 < len(top) <= step_scopes.TOP
    assert {"operation": "double_fwd.1", "opcode": "custom-call", "ms": 1.0,
            "transform": "forward", "scope": "net_/head_/double_fwd"} in top
    assert [op["ms"] for op in top] == sorted((op["ms"] for op in top),
                                               reverse=True)
    assert found["outside_blocks_pct"] < 100 * 3 / step


@pytest.mark.parametrize("words,kind,layer", [
    ("jit step jvp forward net_ h_ decoderblock3_ attn_ q_ dot_general",
     "mixer", 3),
    ("jit step transpose jvp forward net_ jvp forward net_ h_ checkpoint "
     "rematted_computation decoderblock12_ kda_ kda_conv jit _where select_n",
     "mixer", 12),
    ("jit step jvp forward net_ h_ transformerblock0_ layernorm1_ rsqrt",
     "layer_glue", 0),
    ("jit step jvp forward net_ h_ decoderblock2_ add", "layer_glue", 2),
    ("jit step jvp forward net_ h_ decoderblock2_ mlp_ up_ dot_general",
     "ffn_dense", 2),
    ("jit step jvp forward net_ h_ decoderblock2_ moe_ moe.shared shared_ "
     "up_ dot_general", "ffn_dense", 2),
    ("jit step jvp forward net_ h_ decoderblock2_ moe_ moe.shared_gate "
     "sgate_ dot_general", "ffn_dense", 2),
    ("jit step jvp forward net_ h_ decoderblock2_ moe_ moe.route sort",
     "moe_route", 2),
    ("jit step jvp forward net_ h_ decoderblock2_ moe_ moe.experts cond "
     "branch_3_fun dot_general", "moe_experts", 2),
    ("jit step jvp forward net_ h_ decoderblock2_ moe_ reshape",
     "moe_experts", 2),
    ("jit step jvp forward net_ wte_ gather", "head_loss", None),
    ("jit step jvp forward net_ dense0_ dot_general", "head_loss", None),
    ("jit step jvp forward softmaxcrossentropyloss0_ softmax_ce exp",
     "head_loss", None),
])
def test_a_path_names_its_kind_and_layer(words, kind, layer):
    scopes = step_scopes.scopes_of(words.split())
    assert step_scopes.kind_of(scopes) == (kind, layer)
    assert "jit" not in scopes and "_where" not in scopes


@pytest.mark.parametrize("words,kind", [
    ("jit step jvp forward resnetv10_ features stage2_ 1 conv2d3_ "
     "conv_general_dilated", "conv"),
    ("jit step transpose jvp forward resnetv10_ features stage2_ 1 "
     "downsample batchnorm0_ reduce_sum", "batchnorm"),
    ("jit step jvp forward resnetv10_ features stage2_ 1 relu0_ max",
     "other"),
    ("jit step jvp forward resnetv10_ dense0_ dot_general", "other"),
])
def test_a_convolutional_path_names_its_kind(words, kind):
    assert step_scopes.conv_kind_of(
        step_scopes.scopes_of(words.split())) == kind


READERS = ["fwd_ms.train", "recomputed_ms.train", "bwd_ms.train",
           "optimizer_ms.train", "unattributed_step_pct.train",
           "mixer_ms.train", "ffn_dense_ms.train", "moe_route_ms.train",
           "moe_experts_ms.train", "head_loss_ms.train",
           "layer_glue_ms.train", "conv_ms.train", "batchnorm_ms.train"]


def test_every_reader_takes_a_row_or_a_column(table, program, tmp_path):
    ctx = _ctx(_trace(table), tmp_path)
    got = {name: run.reader(name)(ctx) for name in READERS}
    with open(os.path.join(str(tmp_path), "step_by_scope.json")) as f:
        found = json.load(f)
    assert found["cell"] == "made_up.train"
    assert found["table"]["instructions"] == len(table)
    step = found["step_ms"]
    transforms = READERS[:4]
    assert [got[n] for n in transforms] == [
        found["by_transform"][t] for t in step_scopes.TRANSFORMS]
    assert sum(got[n] for n in transforms) \
        + got["unattributed_step_pct.train"] * step / 100 \
        == pytest.approx(step)
    kinds = READERS[5:11]
    assert sum(got[n] for n in kinds) + got["optimizer_ms.train"] \
        + got["unattributed_step_pct.train"] * step / 100 \
        == pytest.approx(step)
    # the made-up step has no router, no MLP and no convolution: 0, not None
    assert got["moe_route_ms.train"] == got["ffn_dense_ms.train"] == 0.0
    assert got["conv_ms.train"] == got["batchnorm_ms.train"] == 0.0
    assert got["moe_experts_ms.train"] > 0 and got["mixer_ms.train"] > 0
    # joined once a run: the matrix is kept in the context
    assert ctx["_step_scopes"]["step_ms"] == step


@pytest.mark.parametrize("case", ["no table", "no trace", "no such function",
                                  "another executable's names"])
def test_without_a_table_every_reader_reads_none(case, table, monkeypatch,
                                                 tmp_path):
    trace = _trace(table)
    if case == "no table":          # the step arrived as a plain jit
        monkeypatch.setattr(xprof, "step_operations", lambda: None,
                            raising=False)
    elif case == "no such function":    # the parent of the PR that brought it
        monkeypatch.delattr(xprof, "step_operations", raising=False)
    else:
        monkeypatch.setattr(xprof, "step_operations", lambda: table,
                            raising=False)
    if case == "no trace":
        trace = None
    if case == "another executable's names":    # a cache served the parent's
        trace["ops"] = {"renamed." + k: v for k, v in trace["ops"].items()}
    ctx = _ctx(trace, tmp_path)
    assert [run.reader(name)(ctx) for name in READERS] == [None] * 13
    assert not os.path.exists(os.path.join(str(tmp_path),
                                           "step_by_scope.json"))
