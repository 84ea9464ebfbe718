"""Fused whole-model optimizer step: ONE donated jit per Trainer.step.

The eager update path (Optimizer.update driven from Updater.__call__) issues
3-10 tiny XLA dispatches *per parameter per step* — exactly the
consecutive-small-ops anti-pattern the reference engine exists to bulk
(SURVEY §1; "Operator Fusion in XLA" shows this elementwise chain is where
fusion pays). This module is the update-path analog of CachedOp for
forward/backward: every optimizer's update rule is restated as a pure
``step(weight, grad, state, hyper, rescale, static) -> (new_w, new_state)``
function; the whole parameter list is stacked into one pytree and compiled
as a single ``jax.jit`` with ``donate_argnums`` on weights and states, so
XLA updates every buffer in place with no copies and no per-param host
round trips ("Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" treats the weight update as the same first-class
fusion target).

Cache key = (optimizer class, static config like momentum/betas/clip,
per-param shapes+dtypes+state structure). Hyperparameters that move between
steps — lr (schedules!), wd, rescale_grad=1/batch, bias-correction terms of
the update count t — enter as *traced* scalars, so an lr-schedule tick or a
batch-size change never retriggers compilation.

Fallback to the eager per-param loop: sparse (row_sparse) grads, optimizers
with host-side control flow (SGLD's rng draw, LBSGD's norm-driven LARS
ratio), aliased buffers (donation would invalidate a live input twice), or
``MXTPU_FUSED_OPTIMIZER=0``.

Numerics sentinel (mxtpu/resilience.py): with ``MXTPU_NUMERICS_GUARD=1``
or a :class:`~mxtpu.resilience.DynamicLossScaler` attached, the SAME
donated jit additionally computes one fused all-params finite flag + the
global grad norm and applies every update under ``jnp.where`` — a
non-finite step is a no-op on params and optimizer state (including the
bias-correction step count, which moves to a DEVICE scalar ``t_good`` so
the skip costs no host sync), and the loss-scaler growth/backoff runs
in-graph on traced scalars (flag flips never recompile; guard on/off is
exactly one extra compile — the guard bit is part of the jit cache key).

Mesh-native stepping (ISSUE 7): :meth:`FusedUpdater.set_mesh` adopts a
:class:`MeshPlan` — parameters live as ONE logical replicated array on a
``jax.sharding.Mesh`` and the cross-replica weight-update sharding of
arXiv:2004.13336 (ZeRO-1) moves INTO this donated jit: the gradient is
constrained to a data-axis shard (reduce-scatter, or a free slice when it
arrives replicated from the eager backward), the optimizer update runs
shard-local on 1/N of the rows, only the weight is all-gathered back, and
the optimizer state STAYS sharded — state memory and update FLOPs divide
by the replica count. The sharding layout (per-buffer tokens + the plan
fingerprint) is part of the jit cache key, the down payment on ROADMAP
item 5's one-compile-cache engine.
"""
from __future__ import annotations

import collections
import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as _P

from . import resilience
from . import telemetry
from .ndarray import NDArray
from .ops import optimizer_ops as _uo
from .optimizer import (SGD, Adam, AdaGrad, RMSProp, AdaDelta, Ftrl, Adamax,
                        Nadam, NAG, Signum, FTML, DCASGD, Test, GroupAdaGrad,
                        Updater)

__all__ = ["FusedUpdater", "MeshPlan", "fused_enabled", "cache_size",
           "reset", "FUSED_STATS", "functional_rule", "traced_rule_names"]


def fused_enabled():
    """Measured default ON; MXTPU_FUSED_OPTIMIZER=0 is the escape hatch
    (read per call, so it can be flipped mid-process for A/Bs)."""
    return os.environ.get("MXTPU_FUSED_OPTIMIZER", "1") != "0"


# fused_steps: fused jit invocations; traces: actual retraces (bumped at
# trace time INSIDE the jitted fn — the recompile counter tests assert on);
# compiles: misses of the executable cache; eager_updates: per-param
# fallback updates
FUSED_STATS = {"fused_steps": 0, "traces": 0, "compiles": 0,
               "eager_updates": 0}


class _ServiceCacheView(dict):
    """Hot-path L1 view over the compile service's ``fused_optimizer``
    entries: steady-state dispatch is one plain dict hit; misses
    resolve through :mod:`mxtpu.compile_service` (reporting, disk
    cache, LRU). ``clear()`` drops the service entries too, so a test
    reset forces real recompiles instead of silent service hits."""

    def clear(self):
        super().clear()
        from . import compile_service
        compile_service.drop(site="fused_optimizer")


_JIT_CACHE = _ServiceCacheView()


def cache_size():
    return len(_JIT_CACHE)


def reset():
    """Test hook: drop compiled executables and zero the counters."""
    _JIT_CACHE.clear()
    for k in FUSED_STATS:
        FUSED_STATS[k] = 0


# --------------------------------------------------------------------- rules
class _Rule:
    """One optimizer class's pure functional update.

    ``static(opt)`` -> hashable config baked into the trace (part of the jit
    cache key); ``hyper(opt, index, t)`` -> per-param scalars traced as
    arguments (lr/wd after lr_mult/wd_mult, bias-correction terms of t);
    ``step(w, g, state, hyper, rescale, static)`` -> (new_w, new_state) with
    ``state`` the same tuple/None structure the Updater stores.

    ``thyper(static, lr, wd, t)`` is the guarded-mode twin of ``hyper``: it
    rebuilds the hyper tuple IN-GRAPH from traced (lr, wd, t) so the
    effective update count can live on device (a skipped step must not
    advance it, and fetching it per step would be a host sync). ``None``
    marks optimizers whose hyper depends on order-dependent host state
    (Nadam's m_schedule) — those take the guarded-eager path instead.
    """

    __slots__ = ("static", "hyper", "step", "thyper")

    def __init__(self, static, hyper, step, thyper=None):
        self.static = static
        self.hyper = hyper
        self.step = step
        self.thyper = thyper


def _clip_of(opt):
    return float(opt.clip_gradient) if opt.clip_gradient else -1.0


def _lr_wd(opt, index, _t=None):
    return float(opt._get_lr(index)), float(opt._get_wd(index))


def _sgd_static(opt):
    return (float(opt.momentum), _clip_of(opt))


def _sgd_step(w, g, state, hyper, rescale, static):
    lr, wd = hyper
    momentum, clip = static
    if state is None:
        return _uo.sgd_update_fn(w, g, lr, wd=wd, rescale_grad=rescale,
                                 clip_gradient=clip), None
    return _uo.sgd_mom_update_fn(w, g, state, lr, momentum=momentum, wd=wd,
                                 rescale_grad=rescale, clip_gradient=clip)


def _nag_step(w, g, state, hyper, rescale, static):
    lr, wd = hyper
    momentum, clip = static
    if state is None:
        return _uo.sgd_update_fn(w, g, lr, wd=wd, rescale_grad=rescale,
                                 clip_gradient=clip), None
    return _uo.nag_mom_update_fn(w, g, state, lr, momentum=momentum, wd=wd,
                                 rescale_grad=rescale, clip_gradient=clip)


def _signum_static(opt):
    return (float(opt.momentum), float(opt.wd_lh), _clip_of(opt))


def _signum_step(w, g, state, hyper, rescale, static):
    lr, wd = hyper
    momentum, wd_lh, clip = static
    if state is None:
        return _uo.signsgd_update_fn(w, g, lr, wd=wd, rescale_grad=rescale,
                                     clip_gradient=clip), None
    return _uo.signum_update_fn(w, g, state, lr, momentum=momentum, wd=wd,
                                rescale_grad=rescale, clip_gradient=clip,
                                wd_lh=wd_lh)


def _beta_eps_static(opt):
    return (float(opt.beta1), float(opt.beta2), float(opt.epsilon),
            _clip_of(opt))


def _ftml_hyper(opt, index, t):
    lr, wd = _lr_wd(opt, index)
    return (lr, wd, 1.0 - opt.beta1 ** t, 1.0 - opt.beta2 ** t)


def _ftml_step(w, g, state, hyper, rescale, static):
    lr, wd, bc1, bc2 = hyper  # 1 - beta1^t, 1 - beta2^t (host-computed)
    beta1, beta2, eps, clip = static
    d, v, z = state
    g = _uo._rescale_clip(g, rescale, clip, wd, w)
    v_new = beta2 * v + (1 - beta2) * jnp.square(g)
    d_new = bc1 / lr * (jnp.sqrt(v_new / bc2) + eps)
    sigma = d_new - beta1 * d
    z_new = beta1 * z + (1 - beta1) * g - sigma * w
    return -z_new / d_new, (d_new, v_new, z_new)


def _dcasgd_static(opt):
    return (float(opt.momentum), float(opt.lamda), _clip_of(opt))


def _dcasgd_step(w, g, state, hyper, rescale, static):
    lr, wd = hyper
    momentum, lamda, clip = static
    mom, prev = state
    g = _uo._rescale_clip(g, rescale, clip, wd, w)
    comp = g + lamda * g * g * (w - prev)
    if mom is None:
        new_mom, delta = None, -lr * comp
    else:
        new_mom = momentum * mom - lr * comp
        delta = new_mom
    return w + delta, (new_mom, w)  # prev <- pre-update weight, like eager


def _adam_hyper(opt, index, t):
    lr, wd = _lr_wd(opt, index)
    lr_t = lr * math.sqrt(1.0 - opt.beta2 ** t) / (1.0 - opt.beta1 ** t)
    return (lr_t, wd)


def _adam_step(w, g, state, hyper, rescale, static):
    lr_t, wd = hyper
    beta1, beta2, eps, clip = static
    mean, var = state
    nw, nm, nv = _uo.adam_update_fn(w, g, mean, var, lr_t, beta1=beta1,
                                    beta2=beta2, epsilon=eps, wd=wd,
                                    rescale_grad=rescale, clip_gradient=clip)
    return nw, (nm, nv)


def _adagrad_static(opt):
    return (float(opt.float_stable_eps), _clip_of(opt))


def _adagrad_step(w, g, state, hyper, rescale, static):
    lr, wd = hyper
    eps, clip = static
    return _uo.adagrad_update_fn(w, g, state, lr, epsilon=eps, wd=wd,
                                 rescale_grad=rescale, clip_gradient=clip)


def _rmsprop_static(opt):
    return (float(opt.gamma1), float(opt.gamma2), float(opt.epsilon),
            bool(opt.centered), _clip_of(opt),
            float(opt.clip_weights) if opt.clip_weights else -1.0)


def _rmsprop_step(w, g, state, hyper, rescale, static):
    lr, wd = hyper
    gamma1, gamma2, eps, centered, clip, clip_w = static
    if centered:
        n, g_avg, delta = state
        nw, nn, ng, nd = _uo.rmspropalex_update_fn(
            w, g, n, g_avg, delta, lr, gamma1=gamma1, gamma2=gamma2,
            epsilon=eps, wd=wd, rescale_grad=rescale, clip_gradient=clip,
            clip_weights=clip_w)
        return nw, (nn, ng, nd)
    (n,) = state
    nw, nn = _uo.rmsprop_update_fn(w, g, n, lr, gamma1=gamma1, epsilon=eps,
                                   wd=wd, rescale_grad=rescale,
                                   clip_gradient=clip, clip_weights=clip_w)
    return nw, (nn,)


def _adadelta_static(opt):
    return (float(opt.rho), float(opt.epsilon), _clip_of(opt))


def _adadelta_hyper(opt, index, t):
    return (float(opt._get_wd(index)),)  # AdaDelta has no lr


def _adadelta_step(w, g, state, hyper, rescale, static):
    (wd,) = hyper
    rho, eps, clip = static
    acc_g, acc_d = state
    g = _uo._rescale_clip(g, rescale, clip, wd, w)
    ag = rho * acc_g + (1 - rho) * jnp.square(g)
    delta = jnp.sqrt(acc_d + eps) / jnp.sqrt(ag + eps) * g
    ad = rho * acc_d + (1 - rho) * jnp.square(delta)
    return w - delta, (ag, ad)


def _ftrl_static(opt):
    return (float(opt.lamda1), float(opt.beta), _clip_of(opt))


def _ftrl_step(w, g, state, hyper, rescale, static):
    lr, wd = hyper
    lamda1, beta, clip = static
    z, n = state
    nw, nz, nn = _uo.ftrl_update_fn(w, g, z, n, lr, lamda1=lamda1, beta=beta,
                                    wd=wd, rescale_grad=rescale,
                                    clip_gradient=clip)
    return nw, (nz, nn)


def _adamax_static(opt):
    return (float(opt.beta1), float(opt.beta2), _clip_of(opt))


def _adamax_hyper(opt, index, t):
    lr, wd = _lr_wd(opt, index)
    return (lr / (1.0 - opt.beta1 ** t), wd)


def _adamax_step(w, g, state, hyper, rescale, static):
    lr_t, wd = hyper
    beta1, beta2, clip = static
    m, u = state
    g = _uo._rescale_clip(g, rescale, clip, wd, w)
    m_new = beta1 * m + (1 - beta1) * g
    u_new = jnp.maximum(beta2 * u, jnp.abs(g))
    return w - lr_t * m_new / (u_new + 1e-8), (m_new, u_new)


def _nadam_hyper(opt, index, t):
    lr, wd = _lr_wd(opt, index)
    momentum_t = opt.beta1 * (1.0 - 0.5 * 0.96 ** (t * opt.schedule_decay))
    momentum_t_1 = opt.beta1 * (
        1.0 - 0.5 * 0.96 ** ((t + 1) * opt.schedule_decay))
    opt.m_schedule *= momentum_t  # same host-side bookkeeping as eager
    return (lr, wd, momentum_t, momentum_t_1, opt.m_schedule,
            opt.m_schedule * momentum_t_1, 1.0 - opt.beta2 ** t)


def _nadam_step(w, g, state, hyper, rescale, static):
    lr, wd, momentum_t, momentum_t_1, m_sch, m_sch_next, bc2 = hyper
    beta1, beta2, eps, clip = static
    m, v = state
    g = _uo._rescale_clip(g, rescale, clip, wd, w)
    m_new = beta1 * m + (1 - beta1) * g
    v_new = beta2 * v + (1 - beta2) * jnp.square(g)
    g_prime = g / (1 - m_sch)
    m_prime = m_new / (1 - m_sch_next)
    v_prime = v_new / bc2
    m_bar = (1 - momentum_t) * g_prime + momentum_t_1 * m_prime
    return w - lr * m_bar / (jnp.sqrt(v_prime) + eps), (m_new, v_new)


def _groupadagrad_static(opt):
    return (float(opt.float_stable_eps), _clip_of(opt))


def _groupadagrad_hyper(opt, index, t):
    return (float(opt._get_lr(index)),)  # eager GroupAdaGrad ignores wd


def _groupadagrad_step(w, g, state, hyper, rescale, static):
    (lr,) = hyper
    eps, clip = static
    g = _uo._rescale_clip(g, rescale, clip)
    red = tuple(range(1, w.ndim))
    h_new = state + jnp.mean(jnp.square(g), axis=red)
    div = jnp.sqrt(h_new + eps)
    return w - lr * g / div.reshape((-1,) + (1,) * (g.ndim - 1)), h_new


def _test_step(w, g, state, hyper, rescale, static):
    nw = w + g * rescale
    return nw, nw


# ------------------------------------------------- guarded (traced-t) hyper
# Guarded-mode hyper twins: same tuples the host-side hyper fns produce, but
# built from traced (lr, wd, t) so the bias-correction step count can stay
# on device (resilience sentinel: a skipped step must not advance t, and a
# host-side t would cost one sync per step to keep honest).
def _t_lr_wd(static, lr, wd, t):
    return (lr, wd)


def _adam_thyper(static, lr, wd, t):
    beta1, beta2, _eps, _clip = static
    return (lr * jnp.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t), wd)


def _ftml_thyper(static, lr, wd, t):
    beta1, beta2, _eps, _clip = static
    return (lr, wd, 1.0 - beta1 ** t, 1.0 - beta2 ** t)


def _adadelta_thyper(static, lr, wd, t):
    return (wd,)


def _adamax_thyper(static, lr, wd, t):
    beta1, _beta2, _clip = static
    return (lr / (1.0 - beta1 ** t), wd)


def _groupadagrad_thyper(static, lr, wd, t):
    return (lr,)


def _test_thyper(static, lr, wd, t):
    return ()


# SGLD (per-step rng draw) and LBSGD (host-side weight/grad norms for the
# LARS trust ratio) keep the eager path: their updates are not pure
# functions of (weight, grad, state, scalars). Exact-type lookup also sends
# unknown Optimizer subclasses to the eager loop — a subclass overriding
# update() must not silently get its base class's fused rule.
_RULES = {
    SGD: _Rule(_sgd_static, _lr_wd, _sgd_step, _t_lr_wd),
    NAG: _Rule(_sgd_static, _lr_wd, _nag_step, _t_lr_wd),
    Signum: _Rule(_signum_static, _lr_wd, _signum_step, _t_lr_wd),
    FTML: _Rule(_beta_eps_static, _ftml_hyper, _ftml_step, _ftml_thyper),
    DCASGD: _Rule(_dcasgd_static, _lr_wd, _dcasgd_step, _t_lr_wd),
    Adam: _Rule(_beta_eps_static, _adam_hyper, _adam_step, _adam_thyper),
    AdaGrad: _Rule(_adagrad_static, _lr_wd, _adagrad_step, _t_lr_wd),
    RMSProp: _Rule(_rmsprop_static, _lr_wd, _rmsprop_step, _t_lr_wd),
    AdaDelta: _Rule(_adadelta_static, _adadelta_hyper, _adadelta_step,
                    _adadelta_thyper),
    Ftrl: _Rule(_ftrl_static, _lr_wd, _ftrl_step, _t_lr_wd),
    Adamax: _Rule(_adamax_static, _adamax_hyper, _adamax_step,
                  _adamax_thyper),
    # Nadam: m_schedule is ORDER-dependent host state — no traced-t twin;
    # guarded mode routes Nadam through the guarded-eager path
    Nadam: _Rule(_beta_eps_static, _nadam_hyper, _nadam_step),
    GroupAdaGrad: _Rule(_groupadagrad_static, _groupadagrad_hyper,
                        _groupadagrad_step, _groupadagrad_thyper),
    Test: _Rule(lambda opt: (), lambda opt, i, t: (), _test_step,
                _test_thyper),
}


def functional_rule(optimizer):
    """The pure functional update rule for an Optimizer INSTANCE (exact
    class match — a subclass overriding ``update`` must not inherit its
    base rule), or None for the eager-only set (sparse/SGLD/LBSGD/unknown).
    ONE registry serves both jit surfaces: this module's fused Trainer
    step and ``mxtpu.parallel.ShardedTrainStep``."""
    return _RULES.get(type(optimizer))


def traced_rule_names():
    """Registry names of optimizers with a traced-t hyper twin — the set a
    fully-in-graph step (guarded fused update, ShardedTrainStep) supports."""
    return sorted(k.__name__.lower()
                  for k, r in _RULES.items() if r.thyper is not None)


# ------------------------------------------------------------ mesh placement
class MeshPlan:
    """Weight-update placement plan for the fused step on a mesh.

    Parameters are ONE logical replicated array; ``zero1`` additionally
    shards the optimizer state (and the update computation) over the
    ``data_axis`` — the cross-replica weight-update sharding of
    arXiv:2004.13336: reduce-scatter(grad) -> shard-local update ->
    all-gather(weight), optimizer-state memory / replica count, loss
    trajectory bit-identical. Params whose dim 0 does not divide the axis
    keep replicated state (and a replicated update)."""

    __slots__ = ("mesh", "data_axis", "zero1", "axis_size")

    def __init__(self, mesh, data_axis="data", zero1=True):
        if data_axis not in mesh.shape:
            raise ValueError("data_axis %r not in mesh axes %s"
                             % (data_axis, tuple(mesh.shape)))
        self.mesh = mesh
        self.data_axis = data_axis
        self.zero1 = bool(zero1)
        self.axis_size = int(mesh.shape[data_axis])

    def fingerprint(self):
        """Hashable jit-cache-key component: the SAME step traced for a
        different mesh/axis/ZeRO setting — or the same axis shape over
        DIFFERENT devices (the constraint shardings are closed over the
        concrete mesh) — is a different executable."""
        return (tuple(self.mesh.shape.items()), self.data_axis, self.zero1,
                _mesh_dev_ids(self.mesh))

    def replicated(self):
        return NamedSharding(self.mesh, _P())

    def shard0(self):
        return NamedSharding(self.mesh, _P(self.data_axis))

    def _dim0_ok(self, shape):
        return bool(shape) and shape[0] % self.axis_size == 0

    def zero_eligible(self, w_shape, state):
        """ZeRO-1 eligibility for one param: dim 0 of the weight AND of
        every state leaf must divide the data axis (GroupAdaGrad's (dim0,)
        history and the mp f32 master both qualify with the weight)."""
        if not (self.zero1 and self.axis_size > 1
                and self._dim0_ok(tuple(w_shape))):
            return False
        shapes = []
        _leaf_shapes(state, shapes)
        return all(self._dim0_ok(s) for s in shapes)


def _leaf_shapes(s, acc):
    if s is None:
        return acc
    if isinstance(s, NDArray):
        acc.append(tuple(s.shape))
        return acc
    if hasattr(s, "shape"):  # raw jax array leaf
        acc.append(tuple(s.shape))
        return acc
    for x in s:
        _leaf_shapes(x, acc)
    return acc


def _mesh_dev_ids(mesh):
    # process-local ordinals, not global ids: an identical per-host mesh
    # on a replacement host must produce the same cache key as the peer
    # that spilled the blob (compile_service.device_token rationale)
    from . import compile_service as csvc
    return tuple(csvc._local_ordinal(d) for d in mesh.devices.flat)


def _shard_token(arr):
    """Hashable sharding descriptor for the jit cache key: the layout is
    part of the compiled executable's contract, so two steps over the same
    shapes but different placements — including the same axis shape over
    different device subsets — must not share an entry (ROADMAP item 5 —
    sharding enters the key)."""
    sh = getattr(arr, "sharding", None)
    if isinstance(sh, NamedSharding):
        return (tuple(sh.mesh.shape.items()), str(sh.spec),
                _mesh_dev_ids(sh.mesh))
    return None


def _tree_shard_token(s):
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(_tree_shard_token(x) for x in s)
    return _shard_token(s)


# ----------------------------------------------------- state pytree helpers
def _tree_data(s):
    if s is None:
        return None
    if isinstance(s, NDArray):
        return s._data
    return tuple(_tree_data(x) for x in s)


def _tree_spec(s):
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(_tree_spec(x) for x in s)
    return (tuple(s.shape), str(s.dtype))


def _tree_writeback(state, new):
    if state is None:
        return
    if isinstance(state, NDArray):
        state._set_data(new)
        return
    for s, n in zip(state, new):
        _tree_writeback(s, n)


def _split_aliased(items, states, eager_items):
    """Donation invalidates input buffers; a jax.Array appearing under more
    than one item (tied parameters, Test's state==weight aliasing) or under
    an eager-bound item must not be donated — the other holder would read a
    deleted buffer. EVERY item of such an alias group takes the eager loop
    (where nothing is invalidated); the rest of the batch still fuses."""

    def buf_key(arr):
        # the DEVICE buffer, not the Python wrapper: XLA output aliasing can
        # hand two distinct jax.Array objects one buffer (Test's
        # state==weight contract does exactly that), and donating it twice
        # is a runtime error on TPU. Sharded arrays have no single pointer —
        # fall back to object identity there.
        try:
            return arr.unsafe_buffer_pointer()
        except Exception:
            return id(arr)

    def leaves(x, acc):
        if isinstance(x, NDArray):
            acc.append(buf_key(x._data))
        elif x is not None:
            for c in x:
                leaves(c, acc)
        return acc

    counts = {}      # donated leaves: weights + states of fused candidates
    protected = set()  # must survive the call: grads + eager items' buffers
    item_ids = []
    for item in items:
        ids = leaves(item[2], leaves(states[item[0]], []))
        item_ids.append(ids)
        for b in ids:
            counts[b] = counts.get(b, 0) + 1
        protected.update(leaves(item[1], []))
    for i, g, w in eager_items:
        protected.update(leaves(w, leaves(g, leaves(states.get(i), []))))
    clean, aliased = [], []
    for item, ids in zip(items, item_ids):
        if all(counts[b] == 1 and b not in protected for b in ids):
            clean.append(item)
        else:
            aliased.append(item)
    return clean, aliased


def _tree_where(ok, new, old):
    """Per-leaf ``where(ok, new, old)`` over the Updater's tuple/None state
    structure — the skip-step select that makes a non-finite step a no-op."""
    if new is None:
        return None
    if isinstance(new, tuple):
        return tuple(_tree_where(ok, n, o) for n, o in zip(new, old))
    return jnp.where(ok, new, old)


def _fingerprint(values):
    """Divergence-sentinel fingerprint of a list of arrays / state trees
    (mxtpu/resilience.py): ONE f32 sum plus ONE wrapping int32
    bitcast-fold over every leaf — the fold catches sign flips and
    NaN-payload corruption that a float sum can absorb (x + (-x) == 0).
    Computed INSIDE the donated update jit from the post-update values,
    so it is a pure function of each device's own operands: a replica
    whose replicated buffers silently diverged computes a different copy
    of this (replicated) output, which the host-side
    ``DivergenceSentinel`` compares off the async scalars."""
    fsum = jnp.float32(0.0)
    fold = jnp.int32(0)

    def add(x):
        nonlocal fsum, fold
        if x is None:
            return
        if isinstance(x, tuple):
            for c in x:
                add(c)
            return
        xf = x.astype(jnp.float32)
        fsum = fsum + jnp.sum(xf)
        fold = fold + jnp.sum(
            jax.lax.bitcast_convert_type(xf, jnp.int32))

    for v in values:
        add(v)
    return fsum, fold


def _portable_build():
    """True when the fused jit must stay inside XLA:CPU's
    serialization-safe class: no donation, no sharding constraints.

    Measured on jaxlib 0.4.37 CPU: a serialized executable loaded in a
    FRESH process silently corrupts when it declares input-output
    aliasing (donation — wrong values from the second call on) or mixes
    sharding-constraint custom-calls with the bitcast fingerprint
    reduction (wrong values immediately, plus heap corruption). The
    same HLO without donation/constraints round-trips bit-exact, and on
    a single CPU device both are pure memory hints anyway: dropping
    them changes no value. Only the single-device CPU build is held to
    this (the fault was measured there; a multi-device CPU build's
    donated, constrained form round-trips bit-equal on jax 0.9.0 now
    that the reload names its devices — tests/test_compile_service.py);
    TPU/GPU keep the donated, constrained build — there the aliasing is the whole point
    of fusing the update. Local (not global) device count: on the CPU
    fleet tier every host jits over its own local mesh, so a 2-host
    world of 1-device hosts still builds — and disk-serves — the
    1-device portable form."""
    return jax.default_backend() == "cpu" and len(jax.local_devices()) == 1


def _donation():
    """donate_argnums for the fused update jits — () on CPU (see
    :func:`_portable_build`), weights+states everywhere else. The same
    tuple rides the compile-service canonical key, so a CPU blob and a
    TPU blob of one site can never alias."""
    return () if _portable_build() else (0, 2)


def _zero_shards(plan, zf):
    """The (shard, gather, tree-shard) constraint trio for one param under
    the plan — identity functions when the param is not ZeRO-eligible.

    ZeRO-1 inside the donated jit (arXiv:2004.13336): constrain grad,
    weight, and state to the data-axis shard (a reduce-scatter when the
    grad arrives sharded from an in-jit backward, a free dynamic-slice
    when it arrives replicated from the eager autograd), run the update
    rule shard-local, then all-gather ONLY the weight; the state keeps the
    sharded layout, so its memory divides by the replica count."""
    if plan is None or not zf or _portable_build():
        ident = lambda x: x  # noqa: E731
        return ident, ident, ident
    sh0, repl = plan.shard0(), plan.replicated()

    def shard(x):
        return jax.lax.with_sharding_constraint(x, sh0)

    def gather(x):
        return jax.lax.with_sharding_constraint(x, repl)

    def tree_shard(s):
        if s is None:
            return None
        if isinstance(s, tuple):
            return tuple(tree_shard(x) for x in s)
        return shard(s)

    return shard, gather, tree_shard


def _build(rule, static, mp_flags, out_dtypes, plan=None, zflags=None,
           emit_fp=False):
    zflags = zflags or (False,) * len(mp_flags)

    def fused(w_list, g_list, s_list, h_list, rescale):
        # trace-time only (host-side): counts real recompiles (the
        # registry's twin is ``retrace.fused_optimizer``)
        FUSED_STATS["traces"] += 1
        new_w, new_s = [], []
        for w, g, s, h, mp, odt, zf in zip(w_list, g_list, s_list, h_list,
                                           mp_flags, out_dtypes, zflags):
            shard, gather, tshard = _zero_shards(plan, zf)
            w, g, s = shard(w), shard(g), tshard(s)
            if mp:
                # multi-precision: state = (f32 master, base state); the
                # update runs in f32 and storage keeps the bf16/f16 dtype
                # (the reference's mp_sgd_update pattern, optimizer.py:500)
                master, base = s
                nm, nb = rule.step(master, g.astype(jnp.float32), base, h,
                                   rescale, static)
                new_w.append(gather(nm).astype(odt))
                new_s.append((tshard(nm), tshard(nb)))
            else:
                nw, ns = rule.step(w, g, s, h, rescale, static)
                new_w.append(gather(nw))
                new_s.append(tshard(ns))
        if emit_fp:
            # divergence sentinel (MXTPU_DIVERGENCE_EVERY > 0): the
            # fingerprint rides the SAME executable — emit_fp is part of
            # the cache key and registry.policy_key, so a flip is one
            # recompile and steady-state compiles stay flat
            return new_w, new_s, _fingerprint(new_w + new_s)
        return new_w, new_s

    return jax.jit(fused, donate_argnums=_donation())


def _build_guarded(rule, static, mp_flags, out_dtypes, scaler_cfg,
                   plan=None, zflags=None, emit_fp=False):
    """The guarded twin of :func:`_build`: same donated whole-model update,
    plus (inside the SAME jit, so the guard costs no extra dispatches or
    host syncs) the fused finite flag, the global grad norm, the skip-step
    ``where`` select on params/state/t, loss-scale unscaling, and the
    scaler's growth/backoff. ``scaler_cfg`` is the STATIC policy tuple
    (part of the jit cache key); the scale value itself is traced. The
    ZeRO-1 constraints compose: the skip select runs shard-local too."""
    thyper = rule.thyper
    zflags = zflags or (False,) * len(mp_flags)

    def fused(w_list, g_list, s_list, lw_list, rescale, gstate, ext_sq):
        # trace-time only (host-side): counts real recompiles (the
        # registry's twin is ``retrace.fused_optimizer``)
        FUSED_STATS["traces"] += 1
        scale, streak, t_good = gstate
        # ONE fused reduction serves flag AND norm: the sum of squares is
        # finite iff every grad element is (an f32 overflow of the sum also
        # trips it — a grad norm beyond f32 range is a skip-worthy step).
        # ext_sq carries the eager-bound items' contribution (a device
        # scalar, no sync), so both the flag and the reported norm are
        # global across a mixed fused+eager batch.
        sq = jnp.float32(0.0) + ext_sq
        for g in g_list:
            sq = sq + jnp.sum(jnp.square(g.astype(jnp.float32)))
        ok = jnp.isfinite(sq)
        inv = rescale / scale  # loss-scale unscaling folded into rescale
        grad_norm = jnp.sqrt(sq) * inv
        t_eff = (t_good + 1).astype(jnp.float32)
        new_w, new_s = [], []
        for w, g, s, lw, mp, odt, zf in zip(w_list, g_list, s_list, lw_list,
                                            mp_flags, out_dtypes, zflags):
            lr, wd = lw
            h = thyper(static, lr, wd, t_eff)
            shard, gather, tshard = _zero_shards(plan, zf)
            w, g, s = shard(w), shard(g), tshard(s)
            if mp:
                master, base = s
                nm, nb = rule.step(master, g.astype(jnp.float32), base, h,
                                   inv, static)
                nm = jnp.where(ok, nm, master)
                nb = _tree_where(ok, nb, base)
                new_w.append(gather(nm).astype(odt))
                new_s.append((tshard(nm), tshard(nb)))
            else:
                nw, ns = rule.step(w, g, s, h, inv, static)
                new_w.append(gather(jnp.where(ok, nw, w)))
                new_s.append(tshard(_tree_where(ok, ns, s)))
        new_t = jnp.where(ok, t_good + 1, t_good)
        if scaler_cfg is not None:
            gf, bf, gi, max_s, min_s = scaler_cfg
            streak2 = jnp.where(ok, streak + 1, 0)
            grow = streak2 >= gi
            new_scale = jnp.where(ok, jnp.where(grow, scale * gf, scale),
                                  scale * bf)
            new_scale = jnp.clip(new_scale, min_s, max_s)
            new_streak = jnp.where(ok & grow, 0, streak2)
        else:
            new_scale, new_streak = scale, streak
        if emit_fp:
            # same-executable divergence fingerprint as _build: the skip
            # select already ran, so a skipped step fingerprints the
            # UNTOUCHED buffers — replicas agree on skips too
            return (new_w, new_s, (new_scale, new_streak, new_t), ok,
                    grad_norm, _fingerprint(new_w + new_s))
        return new_w, new_s, (new_scale, new_streak, new_t), ok, grad_norm

    # gstate is NOT donated: the scale scalar is aliased by user code
    # (DynamicLossScaler.scale multiplies the loss by it) and by the
    # no-scaler cached constant — donating would delete a live buffer
    return jax.jit(fused, donate_argnums=_donation())


class FusedUpdater(Updater):
    """Updater whose ``update_batch`` compiles the whole optimizer step into
    one donated jit (the update-path CachedOp). ``__call__`` keeps the
    per-index eager semantics, so kvstore servers, serialization, and code
    driving single-param updates behave exactly as before."""

    # capability marker read by the kvstore's donation-safety copies: True
    # even under MXTPU_FUSED_OPTIMIZER=0 — the env flag is read per call
    # and may flip mid-process, so buffers must stay safe to donate
    donates = True

    def __init__(self, optimizer):
        super().__init__(optimizer)
        # resilience surface (mxtpu/resilience.py): attach a
        # DynamicLossScaler (Trainer(loss_scaler=...)) and/or set
        # MXTPU_NUMERICS_GUARD=1 to run every step under the in-jit
        # sentinel. last_step_ok / last_grad_norm are DEVICE scalars from
        # the latest guarded step, fetched asynchronously by callers.
        self.scaler = None
        self.health = resilience.StepHealth()
        self.last_step_ok = None
        self.last_grad_norm = None
        # divergence sentinel (MXTPU_DIVERGENCE_EVERY > 0): the latest
        # fused step's (f32 sum, i32 fold) fingerprint as async device
        # scalars — compared per-replica by resilience.DivergenceSentinel
        # at check cadence, never fetched in the hot loop
        self.last_fingerprint = None
        self._t_good = None     # device good-step count (guarded mode)
        self._noscaler_state = None  # cached (1.0, 0) scalars, never donated
        self._step_count = 0    # dispatched update_batch calls (fault index)
        # step index -> owning trace id (bounded): the poison-batch
        # quarantine attributes skipped steps back to their step traces
        self._step_traces = collections.OrderedDict()
        self._plan = None       # MeshPlan (Trainer(mesh=...) sets it)

    def _guard_active(self):
        return self.scaler is not None or resilience.guard_enabled()

    # ------------------------------------------------------- mesh placement
    def set_mesh(self, mesh, data_axis="data", zero1=True):
        """Adopt a :class:`MeshPlan` (or drop it with ``mesh=None``).
        Called by ``gluon.Trainer(mesh=...)`` at kvstore init; any state
        that already exists is re-placed onto the plan."""
        self._plan = MeshPlan(mesh, data_axis, zero1) \
            if mesh is not None else None
        for i in list(self.states):
            self._place_state(i)

    def ensure_state(self, index, weight):
        """Create (and mesh-place) the optimizer state for one param now —
        the Trainer calls this at ``_init_kvstore`` so every NamedSharding
        lands before the first step, not lazily inside it."""
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
            self._place_state(index, weight)

    def _place_state(self, index, weight=None):
        """Lay one param's state out per the plan: data-axis sharded for
        ZeRO-eligible params, replicated otherwise. In-place on the stored
        NDArray leaves, so serialization and eager fallbacks see the same
        objects."""
        if self._plan is None:
            return
        st = self.states.get(index)
        if st is None:
            return
        if weight is None:
            weight = self.optimizer.param_dict.get(index) \
                if isinstance(self.optimizer.param_dict, dict) else None
        zok = weight is not None and getattr(weight, "shape", None) \
            and self._plan.zero_eligible(tuple(weight.shape), st)
        sh = self._plan.shard0() if zok else self._plan.replicated()

        from .parallel.mesh import place_global

        def put(x):
            if x is None:
                return
            if isinstance(x, NDArray):
                # place_global: device_put single-process; on a fleet
                # (process-spanning) mesh it assembles the global array
                # from this host's full copy — valid for both layouts
                # here, since every host creates identical initial state
                x._set_data(place_global(x._data, sh))
                return
            for c in x:
                put(c)

        put(st)

    def update_batch(self, indices, grads, weights):
        if not indices:
            return  # no-op like the base Updater, guarded or not
        opt = self.optimizer
        step_idx = self._step_count
        self._step_count += 1
        # step -> trace attribution (bounded): Trainer.step roots a trace
        # per step (ISSUE 10); recording the owning id here lets the
        # poison-batch quarantine name the offending batches' traces
        ctx = telemetry.current_trace()
        if ctx is not None:
            self._step_traces[step_idx] = ctx.trace_id
            while len(self._step_traces) > 4096:
                self._step_traces.popitem(last=False)
        if grads and resilience.inject("nan_grad", step_idx):
            # poison ONE gradient buffer — pure data, no retrace, and it
            # flows through the exact production sentinel path
            grads[0]._set_data(grads[0]._data * float("nan"))
        guarded = self._guard_active()
        rule = _RULES.get(type(opt)) if fused_enabled() else None
        if guarded and rule is not None and rule.thyper is None:
            rule = None  # Nadam: t-hyper can't move in-graph -> guarded-eager
        from .ndarray.sparse import RowSparseNDArray
        fused, eager = [], []
        for i, g, w in zip(indices, grads, weights):
            self.ensure_state(i, w)
            if rule is None or isinstance(g, RowSparseNDArray) \
                    or isinstance(w, RowSparseNDArray):
                eager.append((i, g, w))
            else:
                fused.append((i, g, w))
        if fused:
            fused, aliased = _split_aliased(fused, self.states, eager)
            eager.extend(aliased)
        if guarded:
            self._guarded_step(rule, fused, eager, step_idx)
            return
        self.last_step_ok = None  # unguarded steps report no verdict
        self.last_fingerprint = None  # _fused_apply re-emits when enabled
        if fused and eager and isinstance(opt, Nadam):
            # Nadam's m_schedule is ORDER-dependent host state (one multiply
            # per param update): a mixed batch must keep the exact eager
            # call order, so run the whole batch eagerly in index order
            fused, eager = [], list(zip(indices, grads, weights))
        if fused:
            self._fused_apply(rule, fused)
        for i, g, w in eager:
            opt.update_multi_precision(i, w, g, self.states[i])
            FUSED_STATS["eager_updates"] += 1

    def _gather_items(self, items, hyper_of):
        """Per-item device buffers + the jit cache-key specs, ONE copy
        shared by the plain and guarded fused paths — a spec change must
        not silently fork the two cache-key semantics. ``hyper_of(i)``
        builds the traced per-param hyper tuple."""
        opt = self.optimizer
        plan = self._plan
        w_datas, g_datas, s_datas, hypers = [], [], [], []
        mp_flags, out_dtypes, specs, zflags = [], [], [], []
        for i, g, w in items:
            hypers.append(hyper_of(i))
            mp = bool(opt.multi_precision
                      and w.dtype in (jnp.float16, jnp.bfloat16))
            sd = _tree_data(self.states[i])
            zf = plan is not None \
                and plan.zero_eligible(tuple(w.shape), self.states[i])
            w_datas.append(w._data)
            g_datas.append(g._data)
            s_datas.append(sd)
            mp_flags.append(mp)
            out_dtypes.append(w._data.dtype)
            zflags.append(zf)
            # sharding tokens ride the spec: a layout change (mesh attach,
            # ZeRO flip, a restored-replicated state) is a new executable,
            # never a silent reuse of one traced for another placement
            specs.append((tuple(w.shape), str(w.dtype), str(g.dtype),
                          _tree_spec(sd), mp, zf, _shard_token(w._data),
                          _tree_shard_token(sd)))
        return (w_datas, g_datas, s_datas, hypers, tuple(mp_flags),
                tuple(out_dtypes), tuple(specs), tuple(zflags))

    def _cached_jit(self, key, build, example_args=None):
        fn = _JIT_CACHE.get(key)
        if fn is None:
            # retrace watchdog (mxtpu/telemetry.py): every executable-cache
            # miss reports its cache-key provenance — optimizer class,
            # guard bit, param count, and the policy levers active now —
            # so a steady-state recompile is attributable without a rerun.
            # The build resolves through the compile service: the jit
            # rides compiled= into the xprof ledger and comes back
            # wrapped — the wrapper IS what both caches hold — and with
            # MXTPU_COMPILE_CACHE_DIR set the executable persists, so a
            # restarted trainer's first step loads it with zero compiles.
            # policy participation: guard/divergence bits ride the key
            # explicitly (they are the levers this trace consults) — the
            # FULL policy_key must NOT join it, or every conv/BN lever
            # flip would needlessly recompile the optimizer step.
            from . import compile_service as csvc
            from .ops.registry import policy_key
            plan = self._plan
            ckey = csvc.canonical_key(
                site="fused_optimizer", fn_id="fused:%s" % key[0],
                signature=key,
                sharding=plan.fingerprint() if plan is not None else None,
                donation=_donation(),
                device=csvc.device_token(
                    mesh=plan.mesh if plan is not None else None))
            entry = csvc.get_or_build(
                ckey, build,
                provenance={"optimizer": key[0], "guard": "guard" in key,
                            "divergence": "div" in key,
                            "n_params": len(key[2]),
                            "mesh": key[3] is not None,
                            "policy_key": list(policy_key())},
                example_args=csvc.concrete_args(example_args)
                if example_args is not None else None)
            fn = entry.fn
            if entry.origin == "built":
                # bumped only after build() succeeded: a failed
                # trace/compile must leave compiles == retrace count (a
                # disk-restored executable is a load, not a compile)
                FUSED_STATS["compiles"] += 1
            _JIT_CACHE[key] = fn
        return fn

    def _fused_apply(self, rule, items):
        opt = self.optimizer
        # bump every count first so _get_lr sees the post-step num_update for
        # ALL params (the eager loop's first update already bumps it before
        # any lr is read)
        for i, _, _ in items:
            opt._update_count(i)

        def hyper_of(i):
            t = opt._index_update_count[i]
            return tuple(float(h) for h in rule.hyper(opt, i, t))

        (w_datas, g_datas, s_datas, hypers, mp_flags, out_dtypes,
         specs, zflags) = self._gather_items(items, hyper_of)
        static = rule.static(opt)
        plan = self._plan
        # divergence-sentinel bit: emitting the fingerprint changes the
        # traced program, so it rides the cache key (and policy_key) the
        # way the guard bit does — a cadence flip is one recompile
        emit_fp = resilience.divergence_every() > 0
        key = (type(opt).__name__, static, specs,
               plan.fingerprint() if plan else None) \
            + (("div",) if emit_fp else ())
        fn = self._cached_jit(
            key, lambda: _build(rule, static, mp_flags, out_dtypes,
                                plan, zflags, emit_fp),
            example_args=(w_datas, g_datas, s_datas, hypers,
                          float(opt.rescale_grad)))
        out = fn(w_datas, g_datas, s_datas, hypers,
                 float(opt.rescale_grad))
        if emit_fp:
            new_w, new_s, self.last_fingerprint = out
        else:
            new_w, new_s = out
            self.last_fingerprint = None
        FUSED_STATS["fused_steps"] += 1
        for (i, _, w), nw, ns in zip(items, new_w, new_s):
            w._set_data(nw)
            _tree_writeback(self.states[i], ns)

    # ------------------------------------------------------- guarded stepping
    def _guard_state(self):
        """(scale, streak, t_good) device scalars threaded through the
        guarded jit. Without a scaler the (1.0, 0) pair is cached — these
        inputs are never donated, so reuse is safe."""
        if self._t_good is None:
            # warm start (guard enabled mid-run, or an unguarded checkpoint
            # resumed with the guard on): seed from the host update clock so
            # Adam-family bias correction continues at t=N+1 instead of
            # restarting at 1
            self._t_good = jnp.asarray(
                int(getattr(self.optimizer, "num_update", 0)), jnp.int32)
        if self.scaler is not None:
            self.scaler._ensure()
            return (self.scaler._scale, self.scaler._streak, self._t_good)
        if self._noscaler_state is None:
            self._noscaler_state = (jnp.float32(1.0), jnp.int32(0))
        return self._noscaler_state + (self._t_good,)

    def _guarded_step(self, rule, fused, eager, step_idx):
        """One sentinel-guarded optimizer step over a fused+eager split.

        The pure-fused hot path (every param fused — the common case) runs
        with ZERO host syncs: flag, norm, skip select, t bump, and scaler
        update all live inside the donated jit, and the step_ok scalar is
        only fetched when a caller asks. Eager-bound items (sparse grads,
        tied buffers, Nadam/SGLD-class optimizers) cost ONE host sync to
        keep the skip decision global across both halves of the batch."""
        opt = self.optimizer
        scaler = self.scaler
        gstate = self._guard_state()
        scale_used = gstate[0]
        scfg = scaler.config() if scaler is not None else None
        sq_e = jnp.float32(0.0)
        for _, g, _ in eager:
            sq_e = sq_e + jnp.sum(jnp.square(g._data.astype(jnp.float32)))
        if fused:
            # eager items' sum-of-squares rides INTO the jit (async): the
            # global flag/norm need no extra sync here — the one mixed-batch
            # sync is the ok fetch below that gates the eager updates
            ok, grad_norm = self._guarded_fused_apply(rule, fused, gstate,
                                                      scfg, sq_e)
        else:
            # all-eager guarded step: the flag must reach the host anyway
            # (it gates the eager updates); bookkeeping mirrors the in-jit
            # rule, device math stays async. The divergence fingerprint is
            # a fused-path feature — no stale value may survive here.
            self.last_fingerprint = None
            ok = bool(jnp.isfinite(sq_e))  # the documented eager sync
            grad_norm = jnp.sqrt(sq_e) * (
                jnp.float32(float(opt.rescale_grad)) / scale_used)
            if scaler is not None:
                scaler.host_update(ok)
            if ok:
                self._t_good = self._t_good + 1
        self.last_step_ok = ok
        self.last_grad_norm = grad_norm
        self.health.append(step_idx, ok, grad_norm)
        if eager:
            ok_all = bool(ok) if fused else ok  # mixed batches sync once
            if ok_all:
                saved = opt.rescale_grad
                try:
                    if scaler is not None:
                        # eager kernels know nothing of the loss scale:
                        # fold the unscale into rescale_grad for this step
                        opt.rescale_grad = saved / float(scale_used)
                    for i, g, w in eager:
                        opt.update_multi_precision(i, w, g, self.states[i])
                        FUSED_STATS["eager_updates"] += 1
                finally:
                    opt.rescale_grad = saved
            # skipped: eager per-index update counts stay untouched too
            # (the count bumps inside Optimizer.update, which never ran)

    def _guarded_fused_apply(self, rule, items, gstate, scfg, ext_sq):
        opt = self.optimizer
        # host update-count still ticks per DISPATCHED step: it is the lr
        # SCHEDULE clock (and matches how schedules treat skipped steps
        # elsewhere); the bias-correction t is the device t_good, which
        # only good steps advance
        for i, _, _ in items:
            opt._update_count(i)
        (w_datas, g_datas, s_datas, hypers, mp_flags, out_dtypes,
         specs, zflags) = self._gather_items(
            items, lambda i: (float(opt._get_lr(i)), float(opt._get_wd(i))))
        static = rule.static(opt)
        plan = self._plan
        emit_fp = resilience.divergence_every() > 0
        # the guard bit + scaler policy + divergence bit ride the cache
        # key: each flip is exactly one extra compile, flag/scale flips
        # are zero
        key = (type(opt).__name__, static, specs,
               plan.fingerprint() if plan else None, "guard", scfg) \
            + (("div",) if emit_fp else ())
        fn = self._cached_jit(
            key, lambda: _build_guarded(rule, static, mp_flags, out_dtypes,
                                        scfg, plan, zflags, emit_fp),
            example_args=(w_datas, g_datas, s_datas, hypers,
                          float(opt.rescale_grad), gstate, ext_sq))
        out = fn(w_datas, g_datas, s_datas, hypers,
                 float(opt.rescale_grad), gstate, ext_sq)
        if emit_fp:
            new_w, new_s, new_gstate, ok, grad_norm, \
                self.last_fingerprint = out
        else:
            new_w, new_s, new_gstate, ok, grad_norm = out
            self.last_fingerprint = None
        FUSED_STATS["fused_steps"] += 1
        for (i, _, w), nw, ns in zip(items, new_w, new_s):
            w._set_data(nw)
            _tree_writeback(self.states[i], ns)
        new_scale, new_streak, self._t_good = new_gstate
        if self.scaler is not None:
            self.scaler._scale = new_scale
            self.scaler._streak = new_streak
        return ok, grad_norm

    # ----------------------------------------------------------- serialization
    # Loss-scaler + guard scalars ride the optimizer-state blob so
    # Trainer.save_states / contrib.async_checkpoint.save_trainer resume
    # bit-exact. Plain (unguarded) updaters keep the base format.
    _RESILIENCE_TAG = "__mxtpu_resilience_v1__"

    def get_states(self, dump_optimizer=False):
        import pickle

        import numpy as np
        base = super().get_states(dump_optimizer)
        if self.scaler is None and self._t_good is None:
            return base
        payload = {
            "base": base,
            "t_good": None if self._t_good is None
            else np.asarray(self._t_good),
            "scaler": None if self.scaler is None
            else self.scaler.state_dict(),
        }
        return pickle.dumps((self._RESILIENCE_TAG, payload))

    def set_states(self, states):
        import pickle
        obj = pickle.loads(states)
        if not (isinstance(obj, tuple) and len(obj) == 2
                and obj[0] == self._RESILIENCE_TAG):
            super().set_states(states)
            self._replace_states_on_plan()
            return
        payload = obj[1]
        if payload["t_good"] is not None:
            self._t_good = jnp.asarray(payload["t_good"])
        sc = payload["scaler"]
        if sc is not None:
            if self.scaler is None:
                # do NOT auto-attach: the guarded jit would divide grads by
                # the restored scale while nothing scales the loss — a
                # silent stall. The user must pass the scaler explicitly
                # (Trainer(loss_scaler=...)) so their loop scales too.
                import logging
                logging.getLogger("mxtpu.resilience").warning(
                    "checkpoint carries DynamicLossScaler state (scale=%s) "
                    "but no loss scaler is attached — continuing UNSCALED; "
                    "pass loss_scaler= when building the Trainer to resume "
                    "scaled training", float(sc["scale"]))
            else:
                self.scaler.load_state_dict(sc)
        super().set_states(payload["base"])
        self._replace_states_on_plan()

    def _replace_states_on_plan(self):
        """Restored states arrive as host-built single-device arrays; with
        a MeshPlan active they must go back to their mesh layout (ZeRO
        shard or replicated) or the next step would silently trace a new
        executable for the foreign placement. A dump_optimizer blob
        carries a STRIPPED param_dict (see Updater.get_states) under which
        ZeRO eligibility cannot be decided — skip that pass entirely: the
        load paths (Trainer.load_states, async_checkpoint.load_trainer)
        re-invoke after rebinding the live params, and placing twice would
        double the full-state transfers."""
        if self._plan is None:
            return
        if not getattr(self.optimizer, "param_dict", None):
            return
        for i in list(self.states):
            self._place_state(i)
