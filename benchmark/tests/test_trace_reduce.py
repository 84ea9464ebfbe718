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
