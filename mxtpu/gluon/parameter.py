"""Parameter & ParameterDict (ref: python/mxnet/gluon/parameter.py — deferred shape
inference, grad_req, per-device copies, row_sparse pull hooks).

TPU-native notes: there are no per-device parameter copies to manage — replication /
sharding across the mesh is expressed with jax.sharding on the single logical value
(SURVEY §2.3 "→ TPU"); ``data()`` returns the one NDArray regardless of ctx.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import initializer as init_mod
from .. import telemetry
from ..base import MXNetError, current_context
from ..ndarray import NDArray
from ..ndarray.ndarray import _as_jax_dtype

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


import threading


class _HybridTrace(threading.local):
    """Active CachedOp trace (mxtpu/gluon/block.py): while a hybridized block is
    being traced, Parameter.data() returns the tracer-backed NDArray for the
    parameter instead of its concrete value, and mutable aux state (BatchNorm
    moving stats) is redirected into ``aux_updates`` so the traced function stays
    pure — the reference instead mutates aux NDArrays inside kernels."""

    def __init__(self):
        self.stack = []


_TRACE = _HybridTrace()


class _TraceFrame:
    def __init__(self):
        self.param_map = {}   # Parameter -> tracer NDArray
        self.aux_updates = {}  # Parameter -> new tracer value (jax array)
        self.extra_params = []  # params discovered during trace, order of first use


def _active_trace():
    return _TRACE.stack[-1] if _TRACE.stack else None


class DeferredInitializationError(MXNetError):
    """Parameter accessed before shape known (ref: parameter.py:DeferredInitializationError)."""


class Parameter:
    """A weight/bias/aux tensor owned by Blocks (ref: gluon/parameter.py:Parameter)."""

    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self._allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        self._grad_stype = grad_stype
        self._data = None  # NDArray
        self._deferred_init = None
        self._trainer = None

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (self.name, self.shape, self.dtype)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._data._grad = None
                self._data._grad_req = "null"
            else:
                self._data.attach_grad(req)

    # ------------------------------------------------------------ initialize
    def initialize(self, init=None, ctx=None, default_init=None, force_reinit=False):
        default_init = default_init or init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if self.shape is None or any(s == 0 for s in self.shape):
            if self._allow_deferred_init:
                self._deferred_init = (init, default_init)
                return
            raise MXNetError("Cannot initialize Parameter %s: unknown shape %s"
                             % (self.name, self.shape))
        self._finish_init(init, default_init)

    def _finish_init(self, init, default_init):
        # where the array is made, for ``initialize`` and a deferred init
        # alike: one ``gluon.param.init`` span a leaf (a restart's account,
        # docs/observability.md)
        with telemetry.span("gluon.param.init", cat="setup"):
            data = NDArray(jnp.zeros(self.shape, _as_jax_dtype(self.dtype)))
            chosen = init or self.init
            if chosen is not None:
                # reference mechanism (gluon/parameter.py
                # _finish_deferred_init): an explicitly-chosen initializer
                # rides the InitDesc attrs and the dispatcher forces it
                # through _init_weight — otherwise the name dispatch would
                # send e.g. bias_initializer=Constant(3) through the *bias →
                # zeros rule and silently ignore it
                desc = init_mod.InitDesc(self.name,
                                         attrs={"__init__": chosen})
            else:
                desc = init_mod.InitDesc(self.name)
            init_mod.create(default_init)(desc, data)
            self._load_init_data(data)
            self._deferred_init = None

    def _load_init_data(self, data: NDArray):
        self._data = data
        if self._grad_req != "null":
            self._data.attach_grad(self._grad_req)

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            raise DeferredInitializationError(
                "Parameter %s was not initialized (deferred init pending; run a "
                "forward pass or provide in_units/in_channels)" % self.name)
        init, default_init = self._deferred_init
        self._finish_init(init, default_init)

    def _shape_resolved(self, shape):
        """Fill unknown dims (deferred init) once the first forward sees real data."""
        if self.shape is None:
            self.shape = tuple(shape)
        else:
            merged = []
            for mine, given in zip(self.shape, shape):
                if mine == 0:
                    merged.append(given)
                elif given != 0 and mine != given:
                    raise MXNetError("shape mismatch for %s: %s vs %s"
                                     % (self.name, self.shape, shape))
                else:
                    merged.append(mine)
            self.shape = tuple(merged)
        if self._data is None and self._deferred_init is not None:
            self._finish_deferred_init()

    # ----------------------------------------------------------------- access
    def data(self, ctx=None) -> NDArray:
        tc = _active_trace()
        if tc is not None and self in tc.param_map:
            return tc.param_map[self]
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    "Parameter %s deferred init not complete" % self.name)
            raise MXNetError("Parameter %s has not been initialized" % self.name)
        return self._data

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None) -> NDArray:
        d = self.data()
        if d._grad is None:
            raise MXNetError("Parameter %s has no gradient (grad_req=null)" % self.name)
        return d._grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        return [current_context()]

    def zero_grad(self):
        d = self.data()
        if d._grad is not None:
            d._grad._set_data(jnp.zeros_like(d._grad._data))

    def set_data(self, data):
        # one span a leaf: a restart loads some hundreds, and where its
        # seconds went is read off these (docs/observability.md)
        with telemetry.span("gluon.param.set_data", cat="setup"):
            if self._data is None:
                if self.shape is None or any(s == 0 for s in self.shape):
                    self._shape_resolved(data.shape)
                self._load_init_data(NDArray(
                    data._data if isinstance(data, NDArray) else data))
            else:
                src = data._data if isinstance(data, NDArray) else data
                d = jnp.asarray(src, dtype=self._data._data.dtype)
                if d is src:
                    # matching dtype aliases the caller's buffer zero-copy;
                    # the fused optimizer step DONATES parameter buffers in
                    # place (optimizer_fused.py), which would delete the
                    # caller's array on the next Trainer.step — take our
                    # own copy
                    d = d.copy()
                self._data._set_data(d)

    def _update_aux(self, new_data):
        """Write mutable aux state (moving stats). Under a hybrid trace the update
        is collected functionally; eagerly it mutates in place like the reference's
        aux-state kernels (src/operator/nn/batch_norm.cc)."""
        tc = _active_trace()
        if tc is not None:
            tc.aux_updates[self] = new_data._data if isinstance(new_data, NDArray) else new_data
        else:
            self.data()._set_data(new_data._data if isinstance(new_data, NDArray) else new_data)

    def row_sparse_data(self, row_id):
        """Pull given rows (ref: parameter.py:row_sparse_data for sparse params)."""
        d = self.data()
        rows = row_id._data.astype(jnp.int32) if isinstance(row_id, NDArray) else row_id
        from ..ndarray.sparse import RowSparseNDArray
        return RowSparseNDArray(NDArray(d._data[rows]), NDArray(rows), d.shape)

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            g = self._data._grad
            self._data = NDArray(self._data._data.astype(_as_jax_dtype(dtype)))
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req)

    def reset_ctx(self, ctx):
        pass  # single logical copy on the mesh

    def var(self):
        from ..symbol import var
        return var(self.name, shape=self.shape, dtype=self.dtype)


class Constant(Parameter):
    """Non-differentiable constant parameter (ref: parameter.py:Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = NDArray(jnp.asarray(value))
        self.value = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=str(value.dtype),
                         init=init_mod.Constant(0.0), differentiable=False)
        self._load_init_data(NDArray(value._data))

    def initialize(self, *args, **kwargs):
        pass


class ParameterDict:
    """Ordered name → Parameter mapping with prefix + shared dict
    (ref: gluon/parameter.py:ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = {}
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __repr__(self):
        s = "%s(\n" % type(self).__name__
        for p in self._params.values():
            s += "  %r\n" % p
        return s + ")"

    def get(self, name, **kwargs) -> Parameter:
        """Create-or-retrieve with prefix (ref: ParameterDict.get)."""
        name = self._prefix + name
        if name in self._params:
            param = self._params[name]
            # update unknown attrs
            for k, v in kwargs.items():
                if k == "shape" and v is not None and param.shape is not None:
                    continue
                if getattr(param, k, None) in (None, 0) and v is not None:
                    setattr(param, k, v)
            return param
        if self._shared is not None and name in self._shared:
            self._params[name] = self._shared[name]
            return self._shared[name]
        param = Parameter(name, **kwargs)
        self._params[name] = param
        return param

    def get_constant(self, name, value=None) -> Constant:
        name = self._prefix + name
        if name in self._params:
            return self._params[name]
        c = Constant(name, value)
        self._params[name] = c
        return c

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError("duplicate parameter %s" % k)
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        for p in self._params.values():
            p.initialize(init=None, ctx=ctx, default_init=init or init_mod.Uniform(),
                         force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            if p.grad_req != "null" and p._data is not None:
                p.zero_grad()

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, filename, strip_prefix=""):
        from ..ndarray import save as nd_save
        arg = {}
        for p in self._params.values():
            name = p.name
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg[name] = p.data()
        nd_save(filename, arg)

    def load(self, filename, ctx=None, allow_missing=False, ignore_extra=False,
             restore_prefix=""):
        from ..ndarray import load as nd_load
        loaded = nd_load(filename)
        # strip the checkpoint kind markers (ref: parameter.py load strips
        # the arg:/aux: prefixes written by export/save_checkpoint)
        loaded = {(k[4:] if k.startswith(("arg:", "aux:")) else k): v
                  for k, v in loaded.items()}
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        if not allow_missing:
            for name in self._params:
                if name not in loaded:
                    raise MXNetError("Parameter %s missing in file %s" % (name, filename))
        for name, v in loaded.items():
            if name not in self._params:
                if ignore_extra:
                    continue
                raise MXNetError("Parameter %s in file is not in this dict" % name)
            self._params[name].set_data(v)
