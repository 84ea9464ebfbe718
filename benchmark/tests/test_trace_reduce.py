"""The trace reduction's arithmetic, on hand-made intervals and on a small
recorded trace (``data/recorded_planes.json``: a TPU v5e run of a training
cell as ``read_xplane`` gave it, cut to a few hundred events)."""
import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "recorded_planes.json")


@pytest.mark.parametrize("intervals,union,gaps", [
    ([], 0, []),
    ([(0, 10)], 10, []),
    ([(0, 10), (20, 30)], 20, [(10, 10)]),                 # disjoint
    ([(0, 10), (5, 15)], 15, []),                          # overlapping
    ([(0, 30), (5, 10), (12, 20)], 30, []),                # nested
    ([(20, 30), (0, 10), (10, 20)], 30, []),               # touching, unsorted
    ([(0, 4), (2, 6), (10, 12), (11, 15), (20, 21)], 12, [(6, 4), (15, 5)]),
])
def test_union_and_gaps(intervals, union, gaps):
    assert tr.union_ns(intervals) == union
    assert tr.gaps_ns(intervals) == gaps


def _plane(ops, modules):
    return {"XLA Ops": [[n, s, d] for n, s, d in ops],
            "XLA Modules": [[n, s, d] for n, s, d in modules],
            "Steps": [["0", 0, 10 ** 9]]}


def test_reduce_on_hand_made_planes():
    # two steps of 40 ns with a 10 ns hole inside the first and 20 ns
    # between them; a second chip that is busy throughout
    chip0 = _plane([("%a = f32[] add()", 0, 10), ("%b = f32[] mul()", 20, 20),
                    ("%a = f32[] add()", 60, 40)],
                   [("jit_step(1)", 0, 40), ("jit_step(1)", 60, 40)])
    chip1 = _plane([("%a = f32[] add()", 0, 100)], [("jit_step(1)", 0, 100)])
    out = tr.reduce({"/device:TPU:0": chip0, "/device:TPU:1": chip1,
                     "/device:TPU:0 view": {"Other": [["x", 0, 5]]}})
    assert out["busy_s"] == pytest.approx((70 + 100) / 2 / 1e9)
    assert out["window_s"] == pytest.approx(100 / 1e9)
    assert out["idle_share"] == pytest.approx(0.15)
    assert out["modules"]["jit_step(1)"] == pytest.approx(
        [40e-9, 40e-9, 100e-9])
    assert out["top_ops"][0][0] == "a"            # cut to the op's name
    assert out["top_ops"][0][1] == pytest.approx((50 + 100) / 2 / 1e9)
    gaps = dict(out["top_gaps"])
    assert gaps["inside jit_step(1)"] == pytest.approx(10 / 2 / 1e9)
    assert gaps["between jit_step(1) and jit_step(1)"] == pytest.approx(
        20 / 2 / 1e9)


def _kernel_table(planes=1):
    """A step that ran twice a chip: ten operations longer than the flash
    forward kernel's two calls (5 and 7 ns a run), which rank 11th and
    12th, and on a second chip the same again."""
    ops, at = [], 0
    for _run in range(2):
        for k in range(10):
            ops.append(("%%big.%d = f32[] fusion()" % k, at, 100 + k))
            at += 100 + k
        for n, d in ((1, 5), (2, 7)):
            ops.append(("%%flash_attention_fwd.%d = bf16[] custom-call()" % n,
                        at, d))
            at += d
        ops.append(("%all-reduce.3 = f32[] all-reduce()", at, 24))
        at += 24
    half = at // 2
    plane = _plane(ops, [("jit_step(1)", 0, half), ("jit_step(1)", half,
                                                    half)])
    return {"/device:TPU:%d" % i: plane for i in range(planes)}, at


@pytest.mark.parametrize("planes", [1, 4])
def test_readers_see_a_kernel_under_the_ten_longest(planes):
    """The readers get the whole operation table: a kernel's time a call is
    read wherever it ranks, over the step's runs a chip, on one plane or
    the mean of four; ``top_ops`` stays the ten longest."""
    from benchmark import kernel_share, run
    table, busy = _kernel_table(planes)
    out = tr.reduce(table)
    assert out["planes"] == planes and len(out["top_ops"]) == 10
    assert not any(n.startswith("flash") for n, _s in out["top_ops"])
    assert out["ops"]["flash_attention_fwd.2"] == pytest.approx(14e-9)
    assert dict(out["top_ops"]) == {
        k: v for k, v in out["ops"].items() if k.startswith("big.")}
    # (5 + 7) ns a run over two calls: 6 ns a call
    assert kernel_share.seconds_a_call(out, "flash_attention_fwd") == \
        pytest.approx(6e-9)
    assert kernel_share.seconds_a_call(out, "flash_attention") is None
    flops = type("F", (), {"flash_fwd_flops": staticmethod(lambda cfg: 3.0)})
    ctx = {"trace": out, "peak": {"bf16_flops_per_s": 1e9},
           "cell": type("C", (), {"cfg": {}, "module": lambda self, k: flops})()}
    assert run.reader("flash_fwd_mxu_pct.train")(ctx) == pytest.approx(50.0)
    # a configuration without the windowed count, a trace without the
    # kernel: nothing, never 0
    assert run.reader("flash_window_fwd_mxu_pct.train")(ctx) is None
    assert run.reader("flash_bwd_mxu_pct.train")(ctx) is None
    assert run.reader("collective_pct.train")(ctx) == pytest.approx(
        100.0 * 48 / busy)
    out["ops"].pop("all-reduce.3")
    assert run.reader("collective_pct.train")(ctx) is None


with open(os.path.join(os.path.dirname(DATA), "recorded_ops.json")) as _f:
    RECORDED_OPS = json.load(_f)["cells"]


@pytest.mark.parametrize("name", sorted(RECORDED_OPS))
def test_kernel_readers_on_a_recorded_operation_table(name):
    """Two traced chip runs' tables. In kanana's the forward kernel ranks
    12th (under six backward kernels and five backward switches), which is
    why its share read ``null`` while the readers saw ten operations; in
    smallthinker's the windowed forward ranks under ten too. The readers
    give what those runs' lines gave."""
    from benchmark import run
    rec = RECORDED_OPS[name]
    assert (name.startswith("kanana")) == (
        rec["first_forward_kernel_rank"] > 10)
    ctx = {"trace": rec["trace"], "cell": run.Cell(name),
           "peak": run.peak("TPU v5 lite")}
    for metric, value in rec["expected"].items():
        assert run.reader(metric)(ctx) == pytest.approx(value, rel=1e-9)
        assert 0 < value < 100


def test_no_device_plane_reduces_to_nothing():
    assert tr.reduce({}) is None
    assert tr.reduce({"/device:TPU:0": {"Steps": [["0", 0, 5]]}}) is None


def test_recorded_trace():
    with open(DATA) as f:
        rec = json.load(f)
    out = tr.reduce(rec["planes"])
    ops = rec["planes"]["/device:TPU:0"]["XLA Ops"]
    assert 100 <= len(ops) <= 1000
    # busy can never pass the stretch, and the recorded stretch of a
    # back-to-back training step is nearly all busy
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["idle_share"] == pytest.approx(rec["expected"]["idle_share"],
                                              abs=1e-9)
    assert out["busy_s"] == pytest.approx(rec["expected"]["busy_s"])
    assert out["top_ops"][0][0] == rec["expected"]["top_op"]
    # the sum of the ops' durations is at least the union of them
    assert sum(d for _n, _s, d in ops) / 1e9 >= out["busy_s"]


def test_read_xplane_reads_what_the_profiler_wrote(tmp_path):
    """On the CPU there is no device plane: the reader returns none, and
    the tracer reduces to nothing instead of inventing a number."""
    import jax
    import jax.numpy as jnp
    t = tr.Tracer(str(tmp_path / "trace"), 0.0, 0.0)
    t.tick(0.0)
    jnp.ones((64, 64)).sum().block_until_ready()
    t.stop()
    assert t.overhead_s > 0
    if jax.devices()[0].platform == "cpu":
        assert t.reduce() is None
