"""The qwen3_next_80b_a3b cell at its rehearsal sizes on the CPU: a sound
run is correct (with the rows its experts draw and the share of moved
selections on note lines, and the new counters and readers read), the fp8
control has to fail the cell's rehearsal limits, the Pallas kernels (the
interpreter) run inside the cell's own step, and a run whose timed path is
broken in the PROGRAM only has to come out NOT correct: the decay dropped,
beta taken as 1, the state reset at each chunk, value head ``j`` reading
key head ``j % Hk``, the filter acausal by a tap, the elementwise gate
dropped, rotary over the whole head, ``w`` for ``1 + w`` in one norm, the
shared expert's gate dropped, the weights' renormalisation dropped.

Each fault twice: as a whole run (``test_a_planted_fault_is_not_correct``),
and as the check's own numbers (the loss's gap, the first gradient's
distance overall and of the worst leaf, against the reference's) of one
eager step of the same model at the same sizes
(``test_a_planted_fault_moves_the_checks_numbers``). The tier-1 run takes
the sound run, the first fault as a whole run and the quick form of the
other nine (``tests/test_benchmark_qwen3_next_80b_a3b.py``)."""
import importlib

import numpy as np
import pytest

from benchmark import control, run
from benchmark.tests import cell

CELL = "qwen3_next_80b_a3b.train_b1_s16384"
COUNTERS = ("gated_delta.calls", "gated_delta.fallbacks",
            "gated_delta.chunks", "attention.element_gated",
            "train_step.blocks_recomputed", "kda_conv.calls", "kda_conv.xla")


# the cell has one chip and a batch of one, and its whole runs compile the
# same reference, pool and check programs: the ling3 cell's two fixtures (the
# program's mesh on the first device; JAX's cache in the checkout)
from benchmark.tests.test_ling3_flash import one_chip, served  # noqa: E402,F401


def _rehearse(seed):
    lines = []
    result = run.run_cell(cell(CELL, rehearse=True), seed, 0.3, 0,
                          out=lines.append)
    return result, lines


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_the_rehearsal_limits(seed):
    rows = control.control(cell(CELL, rehearse=True), seed)
    failed = [n for n, value, limit in rows if not value <= limit]
    assert "first_grad_distance" in failed, rows


def test_a_sound_run_is_correct_and_counts_what_it_traced(capsys):
    from mxtpu import telemetry
    for name in COUNTERS:
        telemetry.reset_metric(name)
    result, lines = _rehearse(21)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    notes = {line.split(" = ")[0]: line.split(" = ")[1]
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("note moe_")}
    assert 0.0 <= float(
        notes["note moe_selection_flip_share_bfloat16_vs_float32"]) < 0.05
    # four layers' rows on the two experts held, of the whole (short)
    # sequence, an even share 72 x 4 x 2 / 16
    rows = notes["note moe_rows_held_by_layer"]
    assert rows.endswith("of 72 positions (an even share: 36.0)")
    assert rows.count(",") == 3
    # three layers' rule, traced for the forward and for the recomputed
    # one: on the CPU every call took the plain path, and the readers say so
    window = {"window": {"attempted": 1}}
    c = cell(CELL, rehearse=True).cfg
    calls = telemetry.value("gated_delta.calls")
    assert calls in (3, 6)
    assert telemetry.value("gated_delta.chunks") == calls * -(
        -c["seq_len"] // c["gdn_chunk"])
    assert run.reader("gdn_fallbacks.train")(window) == calls
    assert run.reader("blocks_recomputed.train")(window) == 4
    assert telemetry.value("attention.element_gated") in (1, 2)
    # the shared filter counts where ling3's does
    assert run.reader("kda_conv_fallbacks.train")(window) \
        == telemetry.value("kda_conv.calls") > 0
    assert run.reader("gdn_fallbacks.train")(
        {"window": {"attempted": 0}}) is None
    for name in COUNTERS:
        telemetry.reset_metric(name)
    assert run.reader("gdn_fallbacks.train")(window) is None
    # the kernels' shares read nothing without a trace
    for metric in ("gdn_fwd_roofline_pct.train",
                   "gdn_bwd_roofline_pct.train"):
        assert run.reader(metric)({"trace": None, "peak": None,
                                   "cell": cell(CELL)}) is None


def test_the_roofline_readers_read_the_new_kernels():
    """A made-up trace: three layers' calls over two steps; the share is
    the bytes' bound over a call's seconds; a program without such a
    kernel (the parent's) reads nothing and does not raise, and neither
    does a configuration whose flops file has no such count."""
    c = cell(CELL)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = {"gdn_fwd": 0.024, "gdn_fwd.1": 0.026, "gdn_fwd.2": 0.028,
           "gdn_bwd.7": 0.060, "gdn_bwd.8": 0.060, "gdn_bwd.9": 0.060,
           "kda_fwd": 0.5, "fusion.1": 1.0}
    ctx = {"cell": c, "peak": peak,
           "trace": {"ops": ops, "planes": 1, "modules": {"step": [1, 1]}}}
    flops = c.module("flops")
    assert run.reader("gdn_fwd_roofline_pct.train")(ctx) == pytest.approx(
        100 * flops.gdn_fwd_bytes(c.cfg) / 819e9 / 0.013)
    assert run.reader("gdn_bwd_roofline_pct.train")(ctx) == pytest.approx(
        100 * flops.gdn_bwd_bytes(c.cfg) / 819e9 / 0.030)
    ctx["trace"]["ops"] = {"kda_fwd": 0.5, "fusion.1": 1.0}
    assert run.reader("gdn_fwd_roofline_pct.train")(ctx) is None
    assert run.reader("gdn_bwd_roofline_pct.train")(ctx) is None
    other = dict(ctx, cell=cell("ling3_flash.train_b1_s8192"),
                 trace={"ops": ops, "planes": 1,
                        "modules": {"step": [1, 1]}})
    assert run.reader("gdn_fwd_roofline_pct.train")(other) is None


def test_the_kernels_run_the_rehearsal(monkeypatch):
    """Both delta-rule kernels and the short filter's pair (the
    interpreter, as tier-1 runs them) in the cell's own step, two value
    heads a key head over a sequence that is no whole number of chunks:
    correct, and no call of the rule on the plain path."""
    from mxtpu import telemetry
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    for name in COUNTERS:
        telemetry.reset_metric(name)
    result, lines = _rehearse(25)
    assert result["correct"] is True, lines
    assert telemetry.value("gated_delta.calls") >= 3
    assert telemetry.value("gated_delta.fallbacks") == 0
    assert run.reader("gdn_fallbacks.train")(
        {"window": {"attempted": 1}}) == 0


# --------------------------------------------------------- planted faults
def _kernel(monkeypatch, name, wrong):
    """``mxtpu.ops.pallas.kda.<name>`` replaced in the program."""
    kda = importlib.import_module("mxtpu.ops.pallas.kda")
    right = getattr(kda, name)
    monkeypatch.setattr(kda, name, lambda *a: wrong(right, *a))


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


def _decay_dropped(monkeypatch):
    """``exp(g_t) = 1``: the state never forgets."""
    import jax.numpy as jnp
    _kernel(monkeypatch, "gated_delta_rule",
            lambda right, q, k, v, g, beta, hk, chunk: right(
                q, k, v, jnp.zeros_like(g), beta, hk, chunk))


def _beta_one(monkeypatch):
    import jax.numpy as jnp
    _kernel(monkeypatch, "gated_delta_rule",
            lambda right, q, k, v, g, beta, hk, chunk: right(
                q, k, v, g, jnp.ones_like(beta), hk, chunk))


def _state_reset(monkeypatch):
    """Every chunk starts from ``S = 0``: nothing passes between chunks."""
    import jax.numpy as jnp
    _kernel(monkeypatch, "_apply",
            lambda right, wide, s0, *parts: right(
                wide, jnp.zeros_like(s0), *parts))


def _key_head_wrapped(monkeypatch):
    """Value head ``j`` reads key head ``j % Hk`` (a tiling of the key
    heads) where the source's ``repeat_interleave`` says ``j // 2``."""
    import jax.numpy as jnp

    def wrong(right, q, k, v, g, beta, hk, chunk):
        h = beta.shape[-1]

        def tiled(x):
            heads = x.reshape(x.shape[:-1] + (hk, -1))
            return jnp.tile(heads, (1, 1, h // hk, 1)).reshape(
                x.shape[:-1] + (-1,))

        return right(tiled(q), tiled(k), v, g, beta, h, chunk)

    _kernel(monkeypatch, "gated_delta_rule", wrong)


def _filter_acausal(monkeypatch):
    """Every tap reads one position later: the last tap reads ahead."""
    import jax.numpy as jnp
    nn = importlib.import_module("mxtpu.ops.nn")
    right = nn._causal_taps
    monkeypatch.setattr(nn, "_causal_taps", lambda z, w: right(
        jnp.pad(z[..., 1:, :], [(0, 0)] * (z.ndim - 2) + [(0, 1), (0, 0)]),
        w))


def _element_gate_dropped(monkeypatch):
    """The attention layer's output goes to ``Wo`` ungated."""
    from mxtpu.gluon.model_zoo import hybrid_lm
    monkeypatch.setattr(hybrid_lm, "gate_elements",
                        lambda F, out, gate: out)


def _whole_head_turned(monkeypatch):
    """``partial_rotary_factor`` 1 on the attention layer."""
    def whole(net):
        assert net.blocks[3].op._attrs["rotary_dim"] > 0
        net.blocks[3].op._attrs["rotary_dim"] = 0

    _planted(monkeypatch, whole)


def _norm_scale_not_from_one(monkeypatch):
    """``w`` for ``1 + w`` in ONE norm: the second layer's, ahead of its
    experts."""
    def plain(net):
        assert net.blocks[1].norm2._zero_centered is True
        net.blocks[1].norm2._zero_centered = False

    _planted(monkeypatch, plain)


def _shared_gate_dropped(monkeypatch):
    """Every layer's shared expert is added as it is."""
    def ungated(net):
        for blk in net.blocks:
            assert blk.ffn.shared_gate is not None
            object.__setattr__(blk.ffn, "shared_gate", None)

    _planted(monkeypatch, ungated)


def _renorm_dropped(monkeypatch):
    """The chosen experts' weights are the softmax's own entries, not
    renormalised to sum to one."""
    import jax
    import jax.numpy as jnp
    moe = importlib.import_module("mxtpu.parallel.moe")
    right = moe.route_top_k

    def wrong(x, router_w, score_bias, top_k, *a, **kw):
        idx, _ = right(x, router_w, score_bias, top_k, *a, **kw)
        p = jax.nn.softmax(jnp.einsum(
            "td,ed->te", x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), -1)
        return idx, jnp.take_along_axis(p, idx, axis=-1)

    monkeypatch.setattr(moe, "route_top_k", wrong)


FAULTS = [_decay_dropped, _beta_one, _state_reset, _key_head_wrapped,
          _filter_acausal, _element_gate_dropped, _whole_head_turned,
          _norm_scale_not_from_one, _shared_gate_dropped, _renorm_dropped]


def _names(faults):
    return {"argvalues": faults,
            "ids": [f.__name__.strip("_") for f in faults]}


@pytest.mark.parametrize("fault", **_names(FAULTS))
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result, lines = _rehearse(22)
    assert result["correct"] is False, lines


# ---------------------------------------- the check's numbers of one step
_WANT = {}      # the reference's loss and first gradient, followed once


def _one_step(seed=22):
    """The check's first three numbers of ONE eager step of the cell's model
    (as ``model.build`` gives it now) against the reference's, at the
    rehearsal sizes in the cell's dtype: ``{number: value}``. The whole
    run's check reads the gradient out of Adam's state after a compiled
    step; here it is the program's eager autograd, which needs no compile.
    """
    import jax
    import mxtpu as mx
    from mxtpu import autograd, gluon
    from benchmark.reference import common as rc
    from benchmark.runners import train_steps
    c = cell(CELL, rehearse=True)
    ref, model = c.module("reference"), c.module("models")
    specs = ref.param_specs(c.cfg)
    x, y = train_steps._pool(c, seed)[0]
    t_idx = [i for i, s in enumerate(specs) if s[3]]
    if seed not in _WANT:
        leaves = rc.init_params(specs, seed)
        loss_fn = ref.forward_loss(c.cfg)

        def of(train):
            full = list(leaves)
            for i, w in zip(t_idx, train):
                full[i] = w
            return loss_fn(full, x, y, "float32")[0]

        loss, grads = jax.jit(jax.value_and_grad(of))(
            [leaves[i] for i in t_idx])
        _WANT[seed] = float(loss), [np.asarray(g, np.float32) for g in grads]
    want_loss, want = _WANT[seed]
    net = model.build(c.cfg, specs, rc.init_params(specs, seed))
    model._FIRST.clear()
    cross_entropy = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = cross_entropy(
            net(mx.nd.NDArray(x)).reshape((-1, c.cfg["vocab_size"])),
            mx.nd.NDArray(y).reshape((-1,))).mean()
    loss.backward()
    got = [np.asarray(p.grad().asnumpy(), np.float32)
           for p in net.collect_params().values() if p.grad_req != "null"]
    per_leaf, overall = rc.leaf_distances(got, want)
    return {"loss_rel_gap": abs(float(loss.asnumpy()) - want_loss)
            / abs(want_loss),
            "first_grad_distance": overall,
            "first_grad_distance_worst_leaf": float(np.max(per_leaf))}


def _over(numbers):
    limits = cell(CELL, rehearse=True).limits
    return sorted(n for n, v in numbers.items() if not v <= limits[n])


def test_a_sound_step_is_inside_the_rehearsal_limits():
    numbers = _one_step()
    assert _over(numbers) == [], numbers


@pytest.mark.parametrize("fault", **_names(FAULTS))
def test_a_planted_fault_moves_the_checks_numbers(monkeypatch, fault):
    fault(monkeypatch)
    numbers = _one_step()
    assert _over(numbers), numbers
