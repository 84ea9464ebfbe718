"""Counting in a jaxpr, sub-jaxprs included: the Pallas kernel calls by
their ``name=`` and the ``lax.scan`` loops by direction; and a model's loss
through the trainer's traced forward, to count in. What the tests of
recomputation read: how often a differentiated function holds a kernel's
forward. Shared by ``test_recompute_keeps.py`` and the two recomputed
models' test files."""
import collections

import jax
import jax.numpy as jnp


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def calls(closed):
    """-> Counter: a Pallas kernel's name -> its calls in ``closed`` (a
    ``ClosedJaxpr``); ``scan`` / ``scan.reverse`` -> the scans."""
    out = collections.Counter()
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] += 1
        elif eqn.primitive.name == "scan":
            out["scan.reverse" if eqn.params["reverse"] else "scan"] += 1
    return out


def traced_loss(net, forward, x, y):
    """-> (``mean(forward(net, x, y))`` as a function of every leaf of
    ``net``, the leaves), through the whole-step trainer's own traced
    forward, so a model built with ``recompute`` has its blocks under
    their checkpoints."""
    import mxtpu as mx
    from mxtpu.gluon.block import _run_traced
    params = list(net.collect_params().values())

    def loss_of(datas):
        out, _ = _run_traced(params, datas, jax.random.PRNGKey(0), True,
                             lambda: forward(net, mx.nd.NDArray(x),
                                             mx.nd.NDArray(y)))
        return jnp.mean(out._data)

    return loss_of, [p.data()._data for p in params]


def differentiated(net, forward, x, y):
    """The jaxpr of that loss's gradient in every leaf; traced, nothing
    runs."""
    loss_of, datas = traced_loss(net, forward, x, y)
    return jax.make_jaxpr(jax.grad(loss_of))(datas)
