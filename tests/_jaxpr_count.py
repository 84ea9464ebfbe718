"""Counting in a jaxpr, sub-jaxprs included: the Pallas kernel calls by
their ``name=`` and the ``lax.scan`` loops by direction, and a router's
``top_k``, sort and score product; and a model's loss
through the trainer's traced forward, to count in. What the tests of
recomputation read: how often a differentiated function holds a kernel's
forward. Shared by ``test_recompute_keeps.py`` and the two recomputed
models' test files."""
import collections

import jax
import jax.numpy as jnp


def _eqns(jaxpr, scope=""):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold,
    each with the scopes it stands under (a sub-jaxpr's name stacks start
    anew, so the holder's is carried along)."""
    for eqn in jaxpr.eqns:
        here = "%s/%s" % (scope, eqn.source_info.name_stack)
        yield eqn, here
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, here)


def calls(closed):
    """-> Counter: a Pallas kernel's name -> its calls in ``closed`` (a
    ``ClosedJaxpr``); ``scan`` / ``scan.reverse`` -> the scans."""
    out = collections.Counter()
    for eqn, _ in _eqns(closed.jaxpr):
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] += 1
        elif eqn.primitive.name == "scan":
            out["scan.reverse" if eqn.params["reverse"] else "scan"] += 1
    return out


def router_ops(closed, experts):
    """-> Counter of what a routed layer's router stands for in ``closed``,
    for a router over ``experts``: ``top_k`` (every one), ``top_k.full``
    (those over all the experts: the choice itself, not the group limit's),
    ``sort`` (the plan's argsort), ``score`` (the ``HIGHEST`` product
    under ``moe.route`` that gives a score an expert, not its transposes)
    and ``picked`` (the gathers under ``moe.route``: the scores at the
    chosen experts)."""
    out = collections.Counter()
    for eqn, scope in _eqns(closed.jaxpr):
        name, routing = eqn.primitive.name, "moe.route" in scope
        if name == "top_k":
            out["top_k"] += 1
            out["top_k.full"] += eqn.invars[0].aval.shape[-1] == experts
        elif name == "sort":
            out["sort"] += 1
        elif name == "gather" and routing:
            out["picked"] += 1
        elif (name == "dot_general" and routing
              and "HIGHEST" in str(eqn.params["precision"])):
            out["score"] += eqn.outvars[0].aval.shape[-1] == experts
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
