"""The sparse-label softmax cross-entropy as ``data[label] - logsumexp(data)``
in one float32 pass (op ``_contrib_log_softmax_pick``, ``ops/nn.py:
log_softmax_at``): against a float64 oracle, against the spelling it
replaced (``pick(log_softmax(pred), label)``), and through every front end
of ``gluon.loss.SoftmaxCrossEntropyLoss``. CPU; counts and values only."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import autograd, gluon, telemetry
from mxtpu.ops.nn import log_softmax_at

ONE_PASS, MATERIALIZED = ("loss.softmax_ce.one_pass",
                          "loss.softmax_ce.materialized")
# relative error allowed: bf16 results are float32 ones rounded once (half a
# unit in the last place); float32 ones carry their own sums' rounding
ULP = {"float32": 2.0 ** -23, "bfloat16": 2.0 ** -8}


def _case(axis, label_dtype, seed=0, classes=37):
    """Logits [5, 37] (axis -1) or [5, 37, 6] (axis 1) and their labels."""
    rng = np.random.RandomState(seed)
    shape = (5, classes) if axis == -1 else (5, classes, 6)
    x = (3.0 * rng.randn(*shape)).astype("float32")
    label = rng.randint(0, classes, shape[:1] + shape[2:]).astype(label_dtype)
    return x, label


def _oracle(x, label, axis):
    """float64: (log_softmax(x)[label] kept at size 1, softmax, onehot)."""
    x = np.asarray(x, "float64")
    top = x.max(axis, keepdims=True)
    logp = x - top - np.log(np.exp(x - top).sum(axis, keepdims=True))
    idx = np.expand_dims(np.clip(np.asarray(label, "int64"), 0,
                                 x.shape[axis] - 1), axis)
    onehot = np.arange(x.shape[axis]).reshape(
        [-1 if a == axis % x.ndim else 1 for a in range(x.ndim)]) == idx
    return (np.take_along_axis(logp, idx, axis), np.exp(logp),
            onehot.astype("float64"))


@pytest.mark.parametrize("label_dtype", ["int32", "float32"])
@pytest.mark.parametrize("axis", [-1, 1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_value_and_gradient_against_float64(dtype, axis, label_dtype):
    x, label = _case(axis, label_dtype)
    data = jnp.asarray(x).astype(dtype)
    want, p, onehot = _oracle(np.asarray(data.astype("float32")), label, axis)
    g = np.random.RandomState(1).rand(*want.shape) + 0.5   # the scale

    out, vjp = jax.vjp(lambda d: log_softmax_at(d, jnp.asarray(label), axis),
                       data)
    assert out.dtype == data.dtype and out.shape == want.shape
    np.testing.assert_allclose(np.asarray(out.astype("float32")), want,
                               rtol=ULP[dtype], atol=2e-6)
    (grad,) = vjp(jnp.asarray(g).astype(dtype))
    assert grad.dtype == data.dtype
    g = np.asarray(jnp.asarray(g).astype(dtype).astype("float32"))
    np.testing.assert_allclose(np.asarray(grad.astype("float32")),
                               g * (onehot - p), rtol=ULP[dtype], atol=2e-6)


def test_bfloat16_is_no_less_exact_than_the_log_softmax_array():
    """The replaced spelling rounds the sum of exponentials and the
    subtraction to bf16; this one rounds once."""
    x, label = _case(-1, "int32", seed=3, classes=4096)
    data = jnp.asarray(x).astype("bfloat16")
    want, _, _ = _oracle(np.asarray(data.astype("float32")), label, -1)
    new = np.asarray(log_softmax_at(data, jnp.asarray(label))
                     .astype("float32"))
    old = np.asarray(jnp.take_along_axis(
        jax.nn.log_softmax(data, axis=-1), jnp.asarray(label)[:, None],
        axis=-1).astype("float32"))
    assert np.abs(new - want).max() <= np.abs(old - want).max()
    assert np.abs(new - want).max() <= ULP["bfloat16"] * np.abs(want).max()


@pytest.mark.parametrize("keepdims", [True, False])
@pytest.mark.parametrize("axis", [-1, 1])
def test_op_is_pick_of_log_softmax_labels_clipped(axis, keepdims):
    """The registered op from the NDArray front end: out-of-range labels
    answer as ``pick``'s ``mode="clip"`` does."""
    x, label = _case(axis, "float32")
    label.flat[0], label.flat[1], label.flat[2] = -3, 37, 1000
    got = mx.nd._contrib_log_softmax_pick(
        mx.nd.array(x), mx.nd.array(label), axis=axis, keepdims=keepdims)
    want = mx.nd.pick(mx.nd.log_softmax(mx.nd.array(x), axis=axis),
                      mx.nd.array(label), axis=axis, keepdims=keepdims)
    assert got.shape == want.shape
    np.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=1e-6,
                               atol=1e-6)


def _replaced(pred, label, axis=-1, weight=None, sample_weight=None,
              batch_axis=0):
    """``SoftmaxCrossEntropyLoss`` as the parent commit spelled it."""
    loss = -mx.nd.pick(mx.nd.log_softmax(pred, axis), label, axis=axis,
                       keepdims=True)
    if sample_weight is not None:
        loss = mx.nd.broadcast_mul(loss, sample_weight)
    if weight is not None:
        loss = loss * weight
    return mx.nd.mean(loss, axis=batch_axis, exclude=True)


@pytest.mark.parametrize("how", ["eager", "hybridized", "symbol"])
@pytest.mark.parametrize("axis,weighted", [(-1, False), (-1, True),
                                           (1, False), (1, True)])
def test_loss_block_equals_the_replaced_spelling(axis, weighted, how):
    x, label = _case(axis, "float32", seed=5)
    kept = list(label.shape) + [1] if axis == -1 else [5, 1, 6]
    sw = np.random.RandomState(6).rand(*kept).astype("float32")
    kw = {"weight": 0.7} if weighted else {}
    block = gluon.loss.SoftmaxCrossEntropyLoss(axis=axis, **kw)
    pred, lab = mx.nd.array(x), mx.nd.array(label)
    sample_weight = mx.nd.array(sw) if weighted else None
    want = _replaced(pred, lab, axis, kw.get("weight"), sample_weight)
    if how == "symbol":
        names = ["pred", "label"] + (["sw"] if weighted else [])
        out = block.hybrid_forward(mx.sym, *[mx.sym.var(n) for n in names])
        feed = dict(zip(names, [pred, lab, sample_weight]))
        got = out.eval(**feed)[0]
    else:
        if how == "hybridized":
            block.hybridize()
        got = block(pred, lab, sample_weight) if weighted else block(pred, lab)
    assert got.shape == want.shape == (5,)
    np.testing.assert_allclose(got.asnumpy(), want.asnumpy(), rtol=2e-6,
                               atol=1e-6)


@pytest.mark.parametrize("hybridize", [False, True],
                         ids=["eager", "hybridized"])
def test_loss_block_gradient_is_softmax_minus_onehot(hybridize):
    x, label = _case(-1, "float32", seed=7)
    _, p, onehot = _oracle(x, label, -1)
    block = gluon.loss.SoftmaxCrossEntropyLoss()
    if hybridize:
        block.hybridize()
    pred = mx.nd.array(x)
    pred.attach_grad()
    with autograd.record():
        loss = block(pred, mx.nd.array(label))
    loss.backward()
    np.testing.assert_allclose(pred.grad.asnumpy(), p - onehot, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("kind", ["from_logits", "dense", "dense_from_logits"])
def test_other_branches_are_bit_for_bit_what_they_were(kind):
    """``from_logits=True`` and dense labels keep their spelling."""
    x, label = _case(-1, "float32", seed=9)
    pred, lab = mx.nd.array(x), mx.nd.array(label)
    dense = mx.nd.one_hot(lab, 37)
    logp = mx.nd.log_softmax(pred, -1)
    if kind == "from_logits":
        got = gluon.loss.SoftmaxCrossEntropyLoss(from_logits=True)(logp, lab)
        want = mx.nd.mean(-mx.nd.pick(logp, lab, axis=-1, keepdims=True),
                          axis=0, exclude=True)
    else:
        from_logits = kind == "dense_from_logits"
        got = gluon.loss.SoftmaxCrossEntropyLoss(
            sparse_label=False, from_logits=from_logits)(
                logp if from_logits else pred, dense)
        want = mx.nd.mean(-mx.nd.sum(logp * dense, axis=-1, keepdims=True),
                          axis=0, exclude=True)
    assert np.array_equal(got.asnumpy(), want.asnumpy())


@pytest.mark.parametrize("kind,moved", [
    ("sparse", (1, 0)), ("dense", (0, 1)), ("from_logits", (0, 0)),
    ("dense_from_logits", (0, 0)), ("scalar_sum_op", (1, 0))])
def test_counters_say_which_path_a_call_took(kind, moved):
    x, label = _case(-1, "float32")
    pred, lab = mx.nd.array(x), mx.nd.array(label)
    before = telemetry.value(ONE_PASS), telemetry.value(MATERIALIZED)
    if kind == "scalar_sum_op":
        got = mx.nd.softmax_cross_entropy(pred, lab)
        want, _, _ = _oracle(x, label, -1)
        np.testing.assert_allclose(got.asnumpy(), -want.sum(), rtol=1e-6)
    else:
        gluon.loss.SoftmaxCrossEntropyLoss(
            sparse_label=not kind.startswith("dense"),
            from_logits=kind.endswith("from_logits"))(
                pred, mx.nd.one_hot(lab, 37) if kind.startswith("dense")
                else lab)
    after = telemetry.value(ONE_PASS), telemetry.value(MATERIALIZED)
    assert (after[0] - before[0], after[1] - before[1]) == moved


def test_traced_gradient_has_no_gather_and_no_scatter():
    """What the replaced spelling traced: a gather of one element a row
    from the log-softmax array, and its transpose, a scatter-add into a
    zero array of the logits' size. (What the chip's compiler makes of the
    head is in ``test_tpu_compile.py``.)"""
    def loss(d, lab):
        return jnp.sum(log_softmax_at(d, lab))

    specs = (jax.ShapeDtypeStruct((64, 4096), jnp.bfloat16),
             jax.ShapeDtypeStruct((64,), jnp.float32))
    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(*specs))
    assert "gather" not in text and "scatter" not in text
    # the scope the by-hand join of PERF.md's section 5 looks for
    assert "softmax_ce" in jax.jit(loss).lower(*specs).as_text(
        debug_info=True)


def test_benchmark_reader_reads_the_counters():
    """``loss_materialized.train``: nothing from a program that counted
    neither path (the parent commit), else the materialised calls."""
    from benchmark import run
    read = run.reader("loss_materialized.train")
    for name in (ONE_PASS, MATERIALIZED):
        telemetry.reset_metric(name)
    ran = {"window": {"attempted": 1}}
    assert read(ran) is None
    x, label = _case(-1, "float32")
    gluon.loss.SoftmaxCrossEntropyLoss()(mx.nd.array(x), mx.nd.array(label))
    assert read(ran) == 0
    assert read({"window": {"attempted": 0}}) is None
    gluon.loss.SoftmaxCrossEntropyLoss(sparse_label=False)(
        mx.nd.array(x), mx.nd.one_hot(mx.nd.array(label), 37))
    assert read(ran) == 1
