"""``train_reference`` against the form it had before it was taken apart
(one jitted step that held the start leaves, the moments and the gradients
together; a copy lives here), and over four devices against one. Not
collected under this name: ``test_benchmark_json.py`` imports these cases,
and through it the tier-1 run does (``tests/test_benchmark_suite.py``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as rc
from benchmark.reference import resnet50_v1
from benchmark.runners import train_steps
from benchmark.tests import SPEC, cell

# the four-chip cell has the one-chip ResNet cell's reference and sizes here
CELLS = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]


def _one_program_reference(forward_loss, specs, opt, seed, batches,
                           precision):
    """``train_reference`` as PRs 23-35 had it: gradient and update in ONE
    jitted step, the start leaves kept beside the parameters. Compiled with
    ``xla_allow_excess_precision`` off: the rule rounds a gradient to the
    weight's dtype before it reads it (``opt_update``), and inside one
    program the compiler may drop that float32 -> bf16 -> float32 pair,
    which it did in the lfm2 cell (10% of the first gradient's elements one
    bf16 step away, a distance of 0.0025). Between two programs the
    gradient IS a bf16 array, so the rounding happens as stated."""
    t_idx = [i for i, s in enumerate(specs) if s[3]]

    def step(params, states, t, x, y):
        def of(train):
            full = list(params)
            for i, w in zip(t_idx, train):
                full[i] = w
            return forward_loss(full, x, y, precision)
        (loss, aux), grads = jax.value_and_grad(of, has_aux=True)(
            [params[i] for i in t_idx])
        new, new_states = list(params), []
        for j, i in enumerate(t_idx):
            new[i], st = rc.opt_update(opt, t, params[i], grads[j], states[j])
            new_states.append(st)
        for i, a in aux.items():
            new[i] = a.astype(params[i].dtype)
        return new, new_states, loss

    step = jax.jit(step, compiler_options={
        "xla_allow_excess_precision": False})
    start = rc.init_params(specs, seed)
    params = start
    states = [rc.opt_init(opt, start[i]) for i in t_idx]
    losses, grad = [], None
    for t, (x, y) in enumerate(batches, 1):
        params, states, loss = step(params, states, jnp.float32(t), x, y)
        losses.append(float(loss))
        if t == 1:
            grad = [rc.first_grad(opt, s) for s in states]
    delta = np.asarray(jax.jit(rc.delta_norms)(params, start))
    return {"losses": losses, "grads": grad, "delta_norms": delta}


@functools.lru_cache(maxsize=None)
def _steps(name, seed, devices, reference=None, loss=None):
    """The reference's steps of a cell at its rehearsal sizes, over
    ``devices`` (a tuple), or those of ``reference`` in its place. Kept: two
    cases read the same one-device run."""
    c = cell(name, rehearse=True)
    ref = c.module("reference")
    c.chips = len(devices)
    batches = train_steps._pool(c, seed)[:c.traffic["reference_steps"]]
    args = (loss or ref.forward_loss(c.cfg), ref.param_specs(c.cfg),
            c.traffic["optimizer"], seed, batches, "float32")
    if reference is not None:
        return reference(*args)
    return rc.train_reference(*args, list(devices))


def _agree(got, want, loss_rtol, grad_rtol, delta_rtol):
    """The three things ``correct`` reads of a reference. A leaf's norm is
    held against that leaf's or the median leaf's, as ``leaf_gaps`` does:
    some leaves hardly move."""
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=loss_rtol)
    assert np.max(rc.leaf_gaps(rc.host_norms(got["grads"]),
                               rc.host_norms(want["grads"]))) <= grad_rtol
    assert np.max(rc.leaf_gaps(got["delta_norms"],
                               want["delta_norms"])) <= delta_rtol


@pytest.mark.parametrize("name", CELLS)
def test_the_reference_in_two_programs_follows_the_one_program_form(name):
    """The same arithmetic in the same order; what may differ is how the
    compiler fuses two programs where it fused one, which moves a float32
    sum by its rounding: 1e-6 on a loss (a float32's half-ulp is 6e-8, over
    three steps of thousands of terms). A leaf's norm is read from a state
    stored in the configuration's dtype, where an element whose float32
    value moved across a bf16 boundary moves by 2**-8 of itself: one such
    element in a leaf of 1,000 moves the norm by about 4e-6, so 1e-5."""
    one = tuple(jax.devices()[:1])
    got = _steps(name, 7, one)
    want = _steps(name, 7, one, _one_program_reference)
    _agree(got, want, 1e-6, 1e-5, 1e-5)
    held = got["bytes"]
    assert held["parameters"] == sum(
        int(np.prod(s[1])) for s in cell(name).module(
            "reference").param_specs(cell(name, rehearse=True).cfg))
    assert held["program_arguments"] >= held["leaves"] > 0
    assert held["program_outputs"] > 0


def _four():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")
    return tuple(jax.devices()[:4])


RESNET = "resnet50_v1.train_b128"
# Four devices against one: a float32 sum over the batch is taken in another
# order (partial sums a device, then across them), which moved the first
# loss by 2e-7 and a gradient leaf's norm by 5e-6 here. A bf16 weight's
# change over three SGD steps is a few of that weight's own 2**-8 steps, so
# elements that fall the other way move a small leaf's change by parts in a
# thousand (1.5e-3 read here) and the losses after them by 2e-5: those two
# tolerances are their storage's. A statistic by shard reads 1e-2 and more.
REORDERED = (1e-4, 1e-4, 1e-2)


def test_the_reference_over_four_devices_is_the_one_device_reference():
    """Leaves replicated, the batch of 8 two rows a device."""
    got = _steps(RESNET, 7, _four())
    _agree(got, _steps(RESNET, 7, tuple(jax.devices()[:1])), *REORDERED)


def test_a_batch_statistic_taken_by_shard_is_found():
    """Batch norm's mean and variance are over the whole batch, whatever
    the devices: with each device normalising its own two rows (planted
    through ``shard_map``), the reference no longer agrees with itself."""
    devices = _four()
    c = cell(RESNET, rehearse=True)
    whole = resnet50_v1.forward_loss(c.cfg)
    mesh = rc.by_batch(list(devices)).mesh
    P = jax.sharding.PartitionSpec

    def by_shard(params, x, y, precision):
        def local(params, x, y):
            loss, aux = whole(params, x, y, precision)
            return jax.lax.pmean(loss, "data"), jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, "data"), aux)
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(P(), P("data"), P("data")),
                             out_specs=P())(params, x, y)

    got = _steps(RESNET, 7, devices, loss=by_shard)
    want = _steps(RESNET, 7, tuple(jax.devices()[:1]))
    with pytest.raises(AssertionError):
        _agree(got, want, *REORDERED)
    # and by a margin no tolerance would cover
    gap = max(abs(a - b) / abs(b)
              for a, b in zip(got["losses"], want["losses"]))
    assert gap > 1e-2, gap
