"""The levers that remain, pinned at their defaults: RNN hoist ON, the
staged ones (ring-flash, the s2d stem's policy mode) OFF until a cell
stands on either side of them (ROADMAP.md Queue D1), the numerics guard
and the divergence sentinel OFF. A default drifting here silently changes
every user's performance — this test makes that a visible decision, not
an accident."""
import os

import pytest


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for var in ("MXTPU_RING_FLASH", "MXTPU_RNN_HOIST", "BENCH_S2D_STEM",
                "BENCH_LAYOUT", "MXTPU_FUSED_OPTIMIZER", "MXTPU_S2D_STEM",
                "MXTPU_NUMERICS_GUARD", "MXTPU_LOSS_SCALE",
                "MXTPU_FAULT_INJECT", "MXTPU_CKPT_RETRIES",
                "MXTPU_DIVERGENCE_EVERY", "MXTPU_TRAIN_STEP_TIMEOUT_X",
                "MXTPU_POISON_STREAK", "MXTPU_CKPT_KEEP",
                "MXTPU_FLASH_INTERPRET"):
        monkeypatch.delenv(var, raising=False)


def test_policy_key_defaults_are_the_measured_best():
    from mxtpu.ops.registry import policy_key
    # (ring_flash, rnn_hoist, s2d_stem, numerics_guard, divergence_every,
    #  flash_interpret)
    assert policy_key() == ("0", "1", "0", "0", "0", "0")


def test_read_sites_mirror_policy_key():
    from mxtpu.contrib.s2d_stem import stem_mode
    from mxtpu.ops.pallas.flash_attention import _interpret
    from mxtpu.ops.rnn_ops import _hoist_enabled
    from mxtpu.resilience import divergence_every, guard_enabled
    assert _hoist_enabled() is True
    assert stem_mode() == 0             # plain stem until measured
    assert _interpret() is False        # test-only interpreter path
    assert divergence_every() == 0
    # numerics sentinel OFF by default without a loss scaler: the guarded
    # jit is a different executable, so the default must be a decision
    # (guard_overhead bench tracks its <2% cost), not an accident
    assert guard_enabled() is False


def test_numerics_guard_and_loss_scale_defaults():
    """The resilience levers' env defaults, pinned like every other lever:
    guard off, initial loss scale 2**15, 3 checkpoint retries, no faults,
    and the ISSUE-14 survivability levers all opt-in (0 = off)."""
    import mxtpu.resilience as res
    assert res.guard_enabled() is False
    assert res.default_loss_scale() == 2.0 ** 15
    assert res.ckpt_retries() == 3
    assert res.DynamicLossScaler().config() == (2.0, 0.5, 2000, 2.0 ** 24,
                                                1.0)
    # survivability layer (ISSUE 14): every piece is opt-in — a default
    # flipping here changes the hot path (divergence bakes into the
    # update jit) or deletes checkpoints (keep), so it must be a decision
    assert res.divergence_every() == 0
    assert res.train_step_timeout_x() == 0.0
    assert res.poison_streak() == 0
    assert res.ckpt_keep() == 0


def test_guard_overhead_bench_emits_the_benchline_schema(monkeypatch):
    """bench.py's guard_overhead config must emit per-(config, guard) JSON
    lines plus a summary in the standard schema — the artifact the <2%
    sentinel-cost acceptance bound is read from."""
    import json
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import bench
    assert "guard_overhead" in bench.CONFIGS
    monkeypatch.setenv("BENCH_GUARD_PARAMS", "4")
    monkeypatch.setenv("BENCH_GUARD_PARAM_SIZE", "32")
    monkeypatch.setenv("BENCH_GUARD_STEPS", "2")
    monkeypatch.setenv("BENCH_GUARD_CONFIGS", "optimizer_step")
    lines = []
    rec = bench.bench_guard_overhead(
        emit=lambda r: lines.append(bench._stamp(r)))
    assert {"metric", "value", "unit", "vs_baseline", "mfu",
            "hfu"} <= set(rec)
    assert rec["metric"] == "guard_overhead"
    assert rec["unit"] == "overhead_frac"
    assert len(lines) == 2  # guard on + guard off for optimizer_step
    for l in lines:
        json.dumps(l)
        assert l["guard"] in ("on", "off")
        assert "platform" in l and "policy_key" in l
        assert l["value"] > 0 and l["unit"] == "steps/sec"
    # the A/B must restore the ambient defaults
    assert os.environ.get("MXTPU_NUMERICS_GUARD") is None


def test_fused_optimizer_is_the_measured_default():
    """The fused whole-model optimizer step (one donated jit per
    Trainer.step, mxtpu/optimizer_fused.py) is the measured default; the
    eager per-param loop is reachable only via MXTPU_FUSED_OPTIMIZER=0."""
    from mxtpu.optimizer_fused import FusedUpdater, fused_enabled
    from mxtpu import optimizer as opt
    assert fused_enabled() is True
    assert isinstance(opt.get_updater(opt.SGD()), FusedUpdater)


def test_optimizer_step_bench_emits_the_benchline_schema(monkeypatch):
    """bench.py's optimizer_step config must emit the same JSON-line schema
    the BENCH_r*.json harness parses ({metric, value, unit, vs_baseline,
    mfu, hfu}), with the fused/eager comparison riding as extra keys."""
    import json
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import bench
    assert "optimizer_step" in bench.CONFIGS
    monkeypatch.setenv("BENCH_OPT_PARAMS", "6")
    monkeypatch.setenv("BENCH_OPT_PARAM_SIZE", "32")
    monkeypatch.setenv("BENCH_OPT_STEPS", "2")
    rec = bench.bench_optimizer_step()
    assert {"metric", "value", "unit", "vs_baseline", "mfu",
            "hfu"} <= set(rec)
    assert rec["metric"].startswith("optimizer_step")
    assert rec["unit"] == "params_updated/sec"
    assert rec["fused_params_per_s"] == rec["value"]
    assert rec["eager_params_per_s"] > 0
    json.dumps(rec)  # one parseable JSON line
    # the measurement must restore the ambient default (fused on)
    assert os.environ.get("MXTPU_FUSED_OPTIMIZER") is None


def test_bench_lines_are_stamped_with_platform_and_policy(monkeypatch):
    """Every bench.py JSON line carries the resolved platform + active
    lever set — wedge-skips and CPU fallbacks must be distinguishable
    from real TPU measurements in BENCH_r*.json after the fact."""
    import bench
    from mxtpu.ops.registry import policy_key
    rec = bench._stamp({"metric": "x"})
    assert rec["platform"] in ("cpu", "tpu", "unknown")
    assert rec["policy_key"] == list(policy_key())
    # pre-stamped records (the preflight probe knows its platform) win
    assert bench._stamp({"platform": "tpu"})["platform"] == "tpu"


def test_bench_defaults_measure_the_best_config(monkeypatch):
    """A plain `python bench.py` resnet run must measure the best-known
    config: the s2d stem defaults ON for NHWC (and off elsewhere —
    the transform requires NHWC), overridable by BENCH_S2D_STEM."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import bench
    assert bench._default_s2d("NHWC") == "1"
    assert bench._default_s2d("NCHW") == "0"
    monkeypatch.setenv("BENCH_S2D_STEM", "0")
    assert bench._default_s2d("NHWC") == "0"
