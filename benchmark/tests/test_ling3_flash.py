"""The ling3_flash cell at its rehearsal sizes on the CPU: a sound run is
correct (with the share of moved selections on a note line and the new
counters and readers read), the fp8 control has to fail the cell's
rehearsal limits, and a whole run whose timed path is broken in the PROGRAM
only has to come out NOT correct: the decay dropped, ``beta`` taken as 1,
the state reset at every chunk, the filter acausal by one tap, the group
limit ignored, the head gate dropped, one expert zeroed."""
import importlib

import pytest

from benchmark import control, run
from benchmark.tests import cell

CELL = "ling3_flash.train_b1_s8192"
COUNTERS = ("kda_attention.calls", "kda_attention.fallbacks",
            "train_step.blocks_recomputed", "moe.group_limited")


@pytest.fixture(autouse=True)
def one_chip(monkeypatch):
    """The cell has one chip and a batch of one: where the run has more
    devices (the tier-1 run has eight on the host), the program's mesh is
    the first, as the reference's is (``train_steps._devices``)."""
    import jax
    from benchmark.models import common
    from mxtpu.parallel import data_parallel_mesh
    monkeypatch.setattr(common, "data_parallel_mesh",
                        lambda: data_parallel_mesh(jax.devices()[:1]))


@pytest.fixture(autouse=True, scope="module")
def served():
    """JAX's cache in the checkout, as ``benchmark.run`` has it: a dozen
    whole runs compile the same reference, pool and check programs, and
    each after the first is served (a third of a run's time here)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from mxtpu import compile_service
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = [getattr(jax.config, k) for k in keys]
    compile_service.use_checkout_xla_cache()
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], 0)
    cc.reset_cache()
    yield
    for k, v in zip(keys, was):     # the worker's next file finds it as it was
        jax.config.update(k, v)
    cc.reset_cache()


def _rehearse(seed):
    lines = []
    result = run.run_cell(cell(CELL, rehearse=True), seed, 0.3, 0,
                          out=lines.append)
    return result, lines


def _reset():
    from mxtpu import telemetry
    for name in COUNTERS:
        telemetry.reset_metric(name)


@pytest.mark.parametrize("seed", [11])
def test_control_fails_the_rehearsal_limits(seed):
    rows = control.control(cell(CELL, rehearse=True), seed)
    failed = [n for n, value, limit in rows if not value <= limit]
    assert "first_grad_distance" in failed, rows


def test_a_sound_run_is_correct_and_counts_what_it_traced(capsys):
    from mxtpu import telemetry
    _reset()
    result, lines = _rehearse(21)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    notes = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("note moe_selection_flip_share_")]
    assert len(notes) == 1 and 0.0 <= float(notes[0].split(" = ")[1]) < 0.05
    # every block under a checkpoint; on the CPU every KDA call took the
    # plain path, and the readers say so
    n = len(cell(CELL, rehearse=True).module("reference").kinds(
        cell(CELL, rehearse=True).cfg))
    window = {"window": {"attempted": 1}}
    assert telemetry.value("train_step.blocks_recomputed") == n
    assert run.reader("blocks_recomputed.train")(window) == n
    calls = telemetry.value("kda_attention.calls")
    assert calls >= 2 and run.reader("kda_fallbacks.train")(window) == calls
    assert telemetry.value("moe.group_limited") > 0
    for metric in ("kda_fallbacks.train", "blocks_recomputed.train"):
        assert run.reader(metric)({"window": {"attempted": 0}}) is None
    _reset()
    assert run.reader("kda_fallbacks.train")(window) is None
    assert run.reader("blocks_recomputed.train")(window) is None
    # the kernels' shares read nothing without a trace
    for metric in ("kda_fwd_roofline_pct.train",
                   "kda_bwd_roofline_pct.train"):
        assert run.reader(metric)({"trace": None, "peak": None,
                                   "cell": cell(CELL)}) is None


def test_the_roofline_readers_sum_the_stages():
    """A made-up trace: the forward as two stages over 12 calls a step and
    2 steps, the backward as one kernel: the stages' seconds a call add,
    and the share is the bytes' bound over them."""
    c = cell(CELL)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = {"kda_fwd_wy": 0.012, "kda_fwd_wy.1": 0.012,
           "kda_fwd_state.3": 0.024, "kda_bwd.7": 0.060, "fusion.1": 1.0}
    ctx = {"cell": c, "peak": peak,
           "trace": {"ops": ops, "planes": 1, "modules": {"step": [1, 1]}}}
    flops = c.module("flops")
    floor = flops.kda_fwd_bytes(c.cfg) / 819e9
    assert run.reader("kda_fwd_roofline_pct.train")(ctx) == pytest.approx(
        100 * floor / (0.006 + 0.012))
    assert run.reader("kda_bwd_roofline_pct.train")(ctx) == pytest.approx(
        100 * flops.kda_bwd_bytes(c.cfg) / 819e9 / 0.030)
    ctx["trace"]["ops"] = {"fusion.1": 1.0}
    assert run.reader("kda_fwd_roofline_pct.train")(ctx) is None


# ------------------------------------------------------------ planted faults
def _kernel(monkeypatch, name, wrong):
    """``mxtpu.ops.pallas.kda.<name>`` replaced in the program."""
    kda = importlib.import_module("mxtpu.ops.pallas.kda")
    right = getattr(kda, name)
    monkeypatch.setattr(kda, name, lambda *a: wrong(right, *a))


def _decay_dropped(monkeypatch):
    """``a_t = 1``: the state never forgets."""
    import jax.numpy as jnp
    _kernel(monkeypatch, "kda_attention",
            lambda right, q, k, v, g, beta, chunk: right(
                q, k, v, jnp.zeros_like(g), beta, chunk))


def _beta_one(monkeypatch):
    import jax.numpy as jnp
    _kernel(monkeypatch, "kda_attention",
            lambda right, q, k, v, g, beta, chunk: right(
                q, k, v, g, jnp.ones_like(beta), chunk))


def _state_reset(monkeypatch):
    """Every chunk starts from ``S = 0``: nothing passes between chunks."""
    import jax.numpy as jnp
    _kernel(monkeypatch, "_apply",
            lambda right, wide, s0, *parts: right(
                wide, jnp.zeros_like(s0), *parts))


def _filter_acausal(monkeypatch):
    """Every tap reads one position later: the last tap reads ahead."""
    import jax.numpy as jnp
    nn = importlib.import_module("mxtpu.ops.nn")
    right = nn._causal_taps
    monkeypatch.setattr(nn, "_causal_taps", lambda z, w: right(
        jnp.pad(z[..., 1:, :], [(0, 0)] * (z.ndim - 2) + [(0, 1), (0, 0)]),
        w))


def _group_limit_ignored(monkeypatch):
    moe = importlib.import_module("mxtpu.parallel.moe")
    monkeypatch.setattr(moe, "_group_limited",
                        lambda biased, n_group, topk_group: biased)


def _planted(monkeypatch, change):
    """The cell's model with ``change(net)`` applied to the program's block
    after its leaves are loaded: the reference knows nothing of it."""
    model = cell(CELL, rehearse=True).module("models")
    build = model.build

    def broken(cfg, specs, leaves):
        net = build(cfg, specs, leaves)
        change(net)
        return net

    monkeypatch.setattr(model, "build", broken)


def _head_gate_dropped(monkeypatch):
    """The latent layer as kanana's cell runs it: no gate before ``Wo``."""
    from mxtpu.gluon.model_zoo import latent_moe

    def ungated(self, F, x):
        r = self._kv_rank
        ckr = self.kv_a(x)
        c = F.slice_axis(ckr, axis=-1, begin=0, end=r)
        k_rope = F.slice_axis(ckr, axis=-1, begin=r, end=r + self._rope_dim)
        return self.proj(F._contrib_latent_attention(
            self.q(x), self.kv_b(self.kv_norm(c)), k_rope, **self._attrs))

    monkeypatch.setattr(latent_moe.MultiHeadLatentAttention,
                        "hybrid_forward", ungated)


def _one_expert_zeroed(monkeypatch):
    import mxtpu as mx

    def zero(net):
        down = [p for name, p in net.collect_params().items()
                if name.endswith("moe_w_down")][0]
        down.set_data(mx.nd.NDArray(down.data()._data.at[0].set(0)))
    _planted(monkeypatch, zero)


_REFERENCE = {}      # the reference's three steps on seed 22, followed once


def _faulty_run(monkeypatch, fault):
    """One whole run with ``fault`` planted in the program. What no fault
    here concerns is not paid for seven times: the note line's two
    reference forwards are left out, and the reference, which knows
    nothing of the program, follows its steps once for all seven."""
    import jax.numpy as jnp
    rehearsal = cell(CELL, rehearse=True)
    monkeypatch.setattr(rehearsal.module("reference"), "selection_flip_share",
                        lambda cfg, params, tokens: jnp.float32(0.0))
    runner = rehearsal.module("runners")
    steps = runner.reference_steps

    def once(cell_, seed, batches, precision):
        key = (cell_.name, seed, precision)
        if key not in _REFERENCE:
            _REFERENCE[key] = steps(cell_, seed, batches, precision)
        return _REFERENCE[key]

    monkeypatch.setattr(runner, "reference_steps", once)
    fault(monkeypatch)
    return _rehearse(22)


def _names(faults):
    return {"argvalues": faults, "ids": [f.__name__.strip("_") for f in faults]}


@pytest.mark.parametrize("fault", **_names([
    _decay_dropped, _beta_one, _state_reset, _filter_acausal]))
def test_a_fault_in_the_operator_is_not_correct(monkeypatch, fault):
    result, lines = _faulty_run(monkeypatch, fault)
    assert result["correct"] is False, lines


@pytest.mark.parametrize("fault", **_names([
    _group_limit_ignored, _head_gate_dropped, _one_expert_zeroed]))
def test_a_fault_beside_the_operator_is_not_correct(monkeypatch, fault):
    result, lines = _faulty_run(monkeypatch, fault)
    assert result["correct"] is False, lines
