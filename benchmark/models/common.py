"""What the model builders share: putting the benchmark's seeded leaves
into a Gluon block, and the whole-step trainer. Public entry points of the
program only, with one exception named below."""
import mxtpu as mx
from mxtpu.parallel import ShardedTrainStep, data_parallel_mesh


def load_leaves(net, specs, leaves):
    """``leaves`` (the reference's order) into ``net.collect_params()`` (the
    program's order), one by one; a shape that differs is an error, since
    it means the two no longer describe the same network. Deferred shapes
    are settled by ``set_data`` itself: no eager forward, no random init."""
    params = list(net.collect_params().values())
    if len(params) != len(specs):
        raise RuntimeError("the program's block has %d leaves, the "
                           "reference %d" % (len(params), len(specs)))
    for p, spec, leaf in zip(params, specs, leaves):
        known = tuple(p.shape or ())
        want = tuple(spec[1])
        if len(known) != len(want) or any(
                k not in (0, w) for k, w in zip(known, want)):
            raise RuntimeError("leaf %s: the program's %s has shape %s, the "
                               "reference's %s" % (spec[0], p.name, known,
                                                   want))
        if (p.grad_req != "null") != bool(spec[3]):
            raise RuntimeError("leaf %s: trainable in one, not in the other"
                               % spec[0])
        p.set_data(mx.nd.NDArray(leaf))
    return net


def whole_step(net, loss, optimizer, forward=None):
    opt = dict(optimizer)
    name = opt.pop("name")
    return ShardedTrainStep(net, loss, data_parallel_mesh(), optimizer=name,
                            optimizer_params=opt, forward=forward)


def trained_leaves(net):
    return [p.data()._data for p in net.collect_params().values()]


def optimizer_state(step, specs):
    """The state of each trainable leaf, in order. ``ShardedTrainStep`` has
    no public accessor for it (PERF.md, Open questions), so this reads
    ``_opt_states``: the one private name the benchmark touches."""
    return [s for s, spec in zip(step._opt_states, specs) if spec[3]]
