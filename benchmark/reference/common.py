"""What the plain references share: seeded weights, the two optimizer
rules with storage in the configuration's dtype, the fp8 control, and the
per-leaf norms that ``correct`` compares. Plain ``jax.numpy``; imports
nothing of the program.
"""
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _sharding(devices, *axes):
    """Over ``devices`` (the cell's) on one axis, ``data``; where none are
    given, nothing is said and JAX places as it does by default."""
    if devices is None:
        return None
    return jax.sharding.NamedSharding(
        jax.sharding.Mesh(np.array(devices), ("data",)),
        jax.sharding.PartitionSpec(*axes))


def replicated(devices=None):
    return _sharding(devices)


def by_batch(devices=None):
    """Along the leading axis, over the devices."""
    return _sharding(devices, "data")


def draw(key, shape, dtype, kind, arg):
    """One leaf of seeded weights. ``kind`` says how it is drawn:
    ``normal`` (std ``arg``), ``uniform`` (``arg`` = (lo, hi))."""
    if kind == "normal":
        v = arg * jax.random.normal(key, shape, jnp.float32)
    elif kind == "uniform":
        v = jax.random.uniform(key, shape, jnp.float32, arg[0], arg[1])
    else:
        raise ValueError("unknown weight kind %r" % (kind,))
    return v.astype(dtype)


def init_params(specs, seed, devices=None):
    """Every leaf on every one of ``devices``, in the dtype it is stored in,
    in ONE jitted call from the seed. ``specs``: [(name, shape, dtype,
    trainable, kind, arg)]."""
    def make(key):
        keys = jax.random.split(key, len(specs))
        return [draw(k, s[1], s[2], s[4], s[5]) for k, s in zip(keys, specs)]
    return jax.jit(make, out_shardings=replicated(devices))(
        jax.random.PRNGKey(np.uint32(seed % (2 ** 32))))


def _round(x, dtype, top):
    """``x`` through an 8-bit float with a per-tensor scale (the kindest
    use of the format: its whole range covers the tensor)."""
    x = x.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def product(precision):
    """``product(op)(x, w)`` computes the matmul or convolution ``op`` at
    ``precision``: ``float32`` as it stands; ``fp8`` the control, the hybrid
    recipe of Micikevicius et al. (arXiv:2209.05433): both operands of the
    forward product in float8_e4m3fn, the incoming gradient of the two
    backward products in float8_e5m2, accumulation in float32."""
    if precision == "float32":
        return lambda op: lambda x, w: op(x.astype(jnp.float32),
                                          w.astype(jnp.float32))
    if precision != "fp8":
        raise ValueError("unknown precision %r" % (precision,))

    def wrap(op):
        @jax.custom_vjp
        def f(x, w):
            return op(_round(x, jnp.float8_e4m3fn, 448.0),
                      _round(w, jnp.float8_e4m3fn, 448.0))

        def fwd(x, w):
            xq = _round(x, jnp.float8_e4m3fn, 448.0)
            wq = _round(w, jnp.float8_e4m3fn, 448.0)
            return op(xq, wq), (xq, wq)

        def bwd(kept, dy):
            return jax.vjp(op, *kept)[1](_round(dy, jnp.float8_e5m2, 57344.0))

        f.defvjp(fwd, bwd)
        return f
    return wrap


# ------------------------------------------------------------- optimizers
# The program's stated arithmetic (parallel/train.py: f32 update, storage in
# the parameter's dtype, state in the parameter's dtype, zeros at start).
def opt_init(opt, w):
    if opt["name"] == "sgd":
        return jnp.zeros_like(w)
    if opt["name"] == "adam":
        return (jnp.zeros_like(w), jnp.zeros_like(w))
    raise ValueError("unknown optimizer %r" % (opt["name"],))


def opt_update(opt, t, w, g, state):
    """One update of one leaf; ``t`` counts from 1. Returns (w, state)."""
    lr = jnp.float32(opt["learning_rate"])
    w32, g32 = w.astype(jnp.float32), g.astype(w.dtype).astype(jnp.float32)
    if opt["name"] == "sgd":
        mom = opt["momentum"] * state.astype(jnp.float32) - lr * g32
        return (w32 + mom).astype(w.dtype), mom.astype(w.dtype)
    b1, b2, eps = opt.get("beta1", 0.9), opt.get("beta2", 0.999), \
        opt.get("epsilon", 1e-8)
    tt = jnp.float32(t)
    lr_t = lr * jnp.sqrt(1.0 - b2 ** tt) / (1.0 - b1 ** tt)
    m = b1 * state[0].astype(jnp.float32) + (1 - b1) * g32
    v = b2 * state[1].astype(jnp.float32) + (1 - b2) * jnp.square(g32)
    w_new = w32 - lr_t * m / (jnp.sqrt(v) + eps)
    return w_new.astype(w.dtype), (m.astype(w.dtype), v.astype(w.dtype))


def first_grad(opt, state):
    """The first gradient as the optimizer got it, worked out from one
    leaf's state after ONE step (the same formula for the program's state
    and the reference's), as a float32 host array."""
    if opt["name"] == "sgd":
        return np.asarray(state).astype(np.float32) / -opt["learning_rate"]
    return np.asarray(state[0]).astype(np.float32) \
        / (1.0 - opt.get("beta1", 0.9))


def norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves])


def delta_norms(after, before):
    return norms([a.astype(jnp.float32) - b.astype(jnp.float32)
                  for a, b in zip(after, before)])


def leaf_gaps(got, ref):
    """Per leaf, the gap between a norm and the reference's, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves hardly move)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    floor = max(float(np.median(ref)), 1e-30)
    return np.abs(got - ref) / np.maximum(ref, floor)


def host_norms(leaves):
    return np.asarray([np.sqrt(np.sum(np.square(x, dtype=np.float64)))
                       for x in leaves])


def leaf_distances(got, ref):
    """Per leaf, the norm of the DIFFERENCE of two lists of host arrays
    against the reference's norm of that leaf or of the median leaf."""
    far = host_norms([a - b for a, b in zip(got, ref)])
    size = host_norms(ref)
    return far / np.maximum(size, max(float(np.median(size)), 1e-30)), \
        float(np.sqrt(np.sum(far ** 2) / np.sum(size ** 2)))


def _nbytes(leaves):
    """Bytes ONE device holds of ``leaves``: a shard of each."""
    return sum(int(np.prod(x.sharding.shard_shape(x.shape)))
               * x.dtype.itemsize for x in jax.tree_util.tree_leaves(leaves))


def train_reference(forward_loss, specs, opt, seed, batches, precision,
                    devices=None):
    """Follow ``len(batches)`` steps from the seeded weights. ``forward_loss
    (params, x, y, precision) -> (loss, aux)`` where ``aux`` maps the index
    of a non-trainable leaf to its new value. Returns host numbers: each
    step's loss, the first gradient per trainable leaf (from the state
    after one step), the norm of every leaf's change at the end, and the
    bytes one device held while the gradient program ran (the leaves and
    the batch it was given; the compiler's count of the program's arguments,
    outputs, the gradients among them, and temporaries).

    Two programs a step, so that the check fits where the program fits. The
    gradient program takes the leaves (replicated over ``devices``, the
    cell's) and a batch (sharded along its leading axis: an annotation, the
    arithmetic is over the whole batch) and gives each trainable leaf's
    gradient, in the leaf's dtype as JAX has it. The optimizer's rule then
    runs leaf by leaf, each call given that leaf's weight and state to
    overwrite; between steps the state waits on the host. While the
    backward runs a device therefore holds the weights and their gradients,
    4 bytes a bf16 parameter, and neither the start leaves nor the moments
    (12 with those, in one program)."""
    t_idx = [i for i, s in enumerate(specs) if s[3]]
    rest_idx = [i for i, s in enumerate(specs) if not s[3]]

    def loss_and_grads(train, rest, x, y):
        def of(train):
            full = [None] * len(specs)
            for i, w in zip(t_idx, train):
                full[i] = w
            for i, w in zip(rest_idx, rest):
                full[i] = w
            return forward_loss(full, x, y, precision)
        return jax.value_and_grad(of, has_aux=True)(train)

    update = jax.jit(lambda t, w, g, state: opt_update(opt, t, w, g, state),
                     donate_argnums=(1, 3))
    rep, rows = replicated(devices), by_batch(devices)
    params = init_params(specs, seed, devices)
    states = [opt_init(opt, params[i]) for i in t_idx]
    losses, grad, program, held = [], None, None, None
    for t, (x, y) in enumerate(batches, 1):
        args = ([params[i] for i in t_idx], [params[i] for i in rest_idx],
                jax.device_put(x, rows), jax.device_put(y, rows))
        if program is None:
            program = jax.jit(loss_and_grads, out_shardings=rep).lower(
                *args).compile()
            m = program.memory_analysis()
            held = {"parameters": sum(x.size for x in params),
                    "leaves": _nbytes(params), "batch": _nbytes(args[2:]),
                    "program_arguments": int(m.argument_size_in_bytes),
                    "program_outputs": int(m.output_size_in_bytes),
                    "program_temporaries": int(m.temp_size_in_bytes)}
        (loss, aux), grads = program(*args)
        del args
        for j, i in enumerate(t_idx):
            state = states[j] if t == 1 else jax.device_put(states[j], rep)
            params[i], state = update(jnp.float32(t), params[i], grads[j],
                                      state)
            grads[j] = None
            # to the host: the next backward runs without the moments
            states[j] = state if t == len(batches) else jax.device_get(state)
        for i, a in aux.items():
            params[i] = a.astype(params[i].dtype)
        losses.append(float(loss))
        if t == 1:
            grad = [first_grad(opt, s) for s in states]
    del states
    delta = np.asarray(jax.jit(delta_norms)(params,
                                            init_params(specs, seed, devices)))
    return {"losses": losses, "grads": grad, "delta_norms": delta,
            "bytes": held}
