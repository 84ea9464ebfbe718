"""Block / HybridBlock: the neural-network composition layer.

Reference: ``python/mxnet/gluon/block.py:127-954`` — ``Block`` (eager container with
child/parameter registration), ``HybridBlock`` (``hybridize()`` swaps the imperative
forward for a cached compiled graph via ``CachedOp``, block.py:750-797), and
``SymbolBlock`` (:954).

TPU-native re-design of ``CachedOp`` (src/imperative/cached_op.h:83): instead of
caching an nnvm graph and re-executing it through the engine, ``hybridize()`` traces
the block's forward into a *pure jax function of (inputs, params, rng-key)* and
compiles it with ``jax.jit`` — XLA's ahead-of-time compilation IS the reference's
``static_alloc/static_shape`` mode (memory planning, op fusion and scheduling are the
compiler's job, SURVEY §7 stage 3). The jit cache is keyed per input
signature (shape/dtype/tree structure), which reproduces the reference's
per-shape graph re-planning (``CachedOp::SetForwardGraph``) and the
BucketingModule-style bucketed compile cache for dynamic shapes.

Mutable state stays functional under the trace:

* parameters enter as traced arguments (``_TraceFrame.param_map``),
* aux state (BatchNorm moving stats) is collected via ``_TraceFrame.aux_updates``
  and written back after the compiled call returns,
* RNG draws split from a per-call key argument (mxtpu/random.py key supply), so a
  compiled Dropout stays stochastic across steps.

Training mode integrates with the autograd tape by recording the whole compiled
forward as ONE taped node whose vjp is captured at call time (``jax.vjp`` of the
jitted function — forward and transpose both run as compiled executables), the
analog of ``CachedOp::Backward`` executing the cached backward graph.
"""
from __future__ import annotations

import contextlib
import re
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp

from .. import autograd
from .. import random as _random
from .. import telemetry
from ..base import MXNetError, current_context, numeric_types
from ..ndarray import NDArray
from .parameter import (DeferredInitializationError, Parameter, ParameterDict,
                        _TraceFrame, _TRACE, _active_trace)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp"]


# ------------------------------------------------------------------ tree utils
def _flatten_nd(args, fmt):
    """Flatten nested tuples/lists of NDArrays (the CachedOp input-flattening,
    ref: python/mxnet/gluon/block.py:_flatten)."""
    if isinstance(args, NDArray):
        fmt.append(0)
        return [args]
    if args is None:
        fmt.append(-1)
        return []
    if isinstance(args, (list, tuple)):
        fmt.append(len(args))
        flat = []
        for a in args:
            flat.extend(_flatten_nd(a, fmt))
        return flat
    fmt.append(-2)
    return [args]  # opaque static (scalar/str); kept positionally


def _regroup(flat, fmt, pos=0, idx=0):
    """Inverse of _flatten_nd; returns (value, new_pos, new_idx)."""
    code = fmt[idx]
    if code == 0 or code == -2:
        return flat[pos], pos + 1, idx + 1
    if code == -1:
        return None, pos, idx + 1
    items = []
    idx += 1
    for _ in range(code):
        v, pos, idx = _regroup(flat, fmt, pos, idx)
        items.append(v)
    return tuple(items), pos, idx


class _InTrace(threading.local):
    def __init__(self):
        self.active = 0


_IN_TRACE = _InTrace()


def _run_traced(params, param_datas, rng_key, train, body):
    """Execute `body()` (imperative mxtpu code) as a pure traced region:
    each Parameter in `params` reads from the matching entry of `param_datas`,
    RNG draws split from `rng_key`, autograd taping is off, and BatchNorm-style
    aux writes are collected functionally. Returns (result, aux_updates list
    aligned with params). Single source of truth for CachedOp and
    mxtpu.parallel.ShardedTrainStep."""
    frame = _TraceFrame()
    for p, d in zip(params, param_datas):
        frame.param_map[p] = NDArray(d)
    _TRACE.stack.append(frame)
    _random.push_key_supply(rng_key)
    prev_train = autograd.set_training(train)
    prev_rec = autograd.set_recording(False)
    _IN_TRACE.active += 1
    try:
        result = body()
    finally:
        _IN_TRACE.active -= 1
        autograd.set_recording(prev_rec)
        autograd.set_training(prev_train)
        _random.pop_key_supply()
        _TRACE.stack.pop()
    aux = [frame.aux_updates.get(p) for p in params]
    return result, aux


# ----------------------------------------------------------------- name scope
class _BlockScope(threading.local):
    """Auto-naming of blocks/parameters (ref: gluon/block.py:_BlockScope)."""

    _current = threading.local()

    def __init__(self, block=None):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """-> (the block's full prefix, its ParameterDict, its own name:
        the prefix less the enclosing block's)."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                count = _NameManager.next(hint)
                prefix = "%s%d_" % (hint, count)
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params, prefix
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = "%s%d_" % (hint, count)
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params, prefix

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *a):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


class _NameManager:
    _lock = threading.Lock()
    _counts = {}

    @classmethod
    def next(cls, hint):
        with cls._lock:
            c = cls._counts.get(hint, 0)
            cls._counts[hint] = c + 1
            return c


# ----------------------------------------------------------------------- Block
class Block:
    """Base container for layers & models (ref: gluon/block.py:Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        # ``_own_name``: what ``__call__`` names the block's operations in
        # a traced region (a block without a prefix takes its key among
        # its parent's children, ``register_child``)
        self._prefix, self._params, self._own_name = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)" if self._children else "{name}()"
        modstr = "\n".join("  ({key}): {block}".format(
            key=k, block=_indent(repr(b), 2)) for k, b in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)) and \
                    not isinstance(existing, type(value)):
                raise TypeError("Changing attribute type for %s from %s to %s"
                                " is not allowed." % (name, type(existing), type(value)))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            if hasattr(self, "_reg_params"):
                self._reg_params[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """Name scope manager for child creation (ref: block.py:name_scope)."""
        return self._scope

    @property
    def params(self) -> ParameterDict:
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        """All Parameters of this block and children (ref: block.py:collect_params)."""
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({n: p for n, p in self.params.items() if pattern.match(n)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        if not block._own_name:
            block._own_name = name

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle._id] = hook
        return handle

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle._id] = hook
        return handle

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        # ``gluon.cast`` is the root of the walk alone: a child's cast,
        # called from inside it, opens none of its own
        root = telemetry.open_span() != "gluon.cast"
        with telemetry.span("gluon.cast", cat="setup") if root \
                else contextlib.nullcontext():
            for child in self._children.values():
                child.cast(dtype)
            for p in self.params.values():
                p.cast(dtype)

    def save_parameters(self, filename):
        """Ref: block.py:save_parameters — strips this block's prefix so files are
        architecture-relative."""
        params = self._collect_params_with_prefix()
        from ..ndarray.utils import save as nd_save
        nd_save(filename, {k: v.data() for k, v in params.items()})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False):
        from ..ndarray.utils import load as nd_load
        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError("Parameter %s missing in %s" % (name, filename))
        for name, v in loaded.items():
            if name not in params:
                if ignore_extra:
                    continue
                raise MXNetError("Parameter %s in file not found in Block" % name)
            params[name].set_data(v)
        return self

    # legacy aliases (ref: save_params deprecated in 1.3)
    save_params = save_parameters
    load_params = load_parameters

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        if _IN_TRACE.active and self._own_name:
            # a block's name is a scope: every operation traced here
            # carries the model's own path in its metadata
            # (``.../h_/decoderblock3_/attn_/q_/dot_general``, under
            # ``transpose(jvp(...))`` in a backward, ``rematted_computation``
            # in a recomputed forward), which ``xprof.step_operations``
            # reads back from the executable. Names only: no operation
            # changes, and the eager path pays nothing
            with jax.named_scope(self._own_name):
                out = self.forward(*args)
        else:
            out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):  # pragma: no cover - abstract
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary (ref: block.py:summary)."""
        rows = []

        def hook(block, inp, out):
            first = out[0] if isinstance(out, (list, tuple)) else out
            n_params = sum(p.data().size for p in block.params.values()
                           if p._data is not None)
            rows.append((block.__class__.__name__ + "-" + str(len(rows) + 1),
                         getattr(first, "shape", None), n_params))

        handles = []
        self.apply(lambda b: handles.append(b.register_forward_hook(hook)))
        try:
            self(*inputs)
        finally:
            for h in handles:
                h.detach()
        line = "%-30s %-24s %-12s"
        print(line % ("Layer (type)", "Output Shape", "Param #"))
        print("=" * 68)
        for name, shape, n in rows:
            print(line % (name, str(shape), n))
        print("=" * 68)
        total = sum(p.data().size for p in self.collect_params().values()
                    if p._data is not None)
        print("Total params: %d" % total)


class _HookHandle:
    _next_id = [0]

    def __init__(self, hooks_dict):
        self._hooks = hooks_dict
        self._id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1

    def detach(self):
        self._hooks.pop(self._id, None)


def _indent(s, n):
    pad = " " * n
    return ("\n" + pad).join(s.split("\n"))


# -------------------------------------------------------------------- CachedOp
class CachedOp:
    """Compiled-forward cache for a HybridBlock (ref: src/imperative/cached_op.h:83).

    One jitted executable per (input tree-structure, shapes/dtypes, train-mode) —
    jax.jit handles the shape/dtype keying; we key tree structure + mode.
    """

    def __init__(self, block):
        self._block = block
        self._params = None       # ordered list, fixed at first build
        self._aux_params = None   # params that may receive aux updates
        self._jits = {}  # (fmt_key, train, policy, shapes) -> (fwd, bwd, cell)

    def _ensure_params(self):
        if self._params is None:
            plist = [p for p in self._block.collect_params().values()]
            if any(p._data is None for p in plist):
                return False
            self._params = plist
            self._aux_params = plist  # any may push aux updates; XLA DCEs unused
        return True

    def _make_pure(self, train, cell):
        """The traced forward: one pure function over (rng, inputs,
        params) regrouping through ``cell``. Factored out so the
        companion backward can rebuild it even when the forward
        executable itself was restored from the compile service's disk
        cache (no live closure to share)."""
        block, params = self._block, self._params

        def pure(rng_key, in_datas, param_datas):
            def body():
                args, _, _ = _regroup([NDArray(d) for d in in_datas],
                                      cell["in_fmt"])
                return block._forward_eager(*args)

            out, aux = _run_traced(params, param_datas, rng_key, train, body)
            out_fmt = []
            flat_out = _flatten_nd(out, out_fmt)
            cell["out_fmt"] = out_fmt
            # output avals: the backward's cotangent example signature
            # (persisted with the entry so a disk-warm process can AOT
            # the backward without re-tracing the forward)
            cell["out_specs"] = [(tuple(o._data.shape), str(o._data.dtype))
                                 for o in flat_out]
            return [o._data for o in flat_out], aux

        return pure

    def _get_jit(self, fmt_key, train, rng_key, in_datas, param_datas):
        from .. import compile_service as csvc
        from ..ops.registry import policy_key
        policy_key_now = policy_key()
        # input shapes/dtypes join the key: the compile service may hold
        # a shape-pinned AOT executable (disk-warm start), so a new
        # input signature must be a new entry — previously jax retraced
        # internally, invisible to the watchdog
        shapes = tuple((tuple(d.shape), str(d.dtype)) for d in in_datas)
        key = (fmt_key, train, policy_key_now, shapes)
        if key in self._jits:
            return self._jits[key]
        # retrace watchdog: every CachedOp cache miss is one compile; the
        # provenance names the policy levers active at trace time, so a
        # steady-state recompile (policy env flipped mid-run, unstable
        # input signature) is attributable from telemetry.report() alone
        prov = {"block": type(self._block).__name__,
                "train": train, "policy_key": list(policy_key_now)}
        block, params = self._block, self._params
        # stable identity for the disk digest: block class + forward
        # source hash + parameter structure (an edited model across
        # restarts must miss, not replay stale code)
        struct = tuple((p.name, tuple(p._data._data.shape),
                        str(p._data._data.dtype)) for p in params)
        fn_id = "cached_op:%s:%s" % (type(block).__name__,
                                     csvc.source_token(type(block)))
        dev = csvc.device_token()
        nonce = csvc.instance_nonce(self)
        fkey = csvc.canonical_key(
            site="cached_op", fn_id=fn_id,
            signature=(fmt_key, train, shapes, struct),
            policy=policy_key_now, device=dev, nonce=nonce)

        def build():
            cell = {"in_fmt": list(fmt_key)}
            return jax.jit(self._make_pure(train, cell)), cell

        # ONE retrace count per cache miss (the fwd/bwd pair); the forward
        # executable rides compiled= into the xprof ledger and comes back
        # wrapped (compile wall-time + cost/memory analyses + call count)
        example = csvc.concrete_args((rng_key, in_datas, param_datas))
        entry = csvc.get_or_build(fkey, build, provenance=prov,
                                  example_args=example)
        jitted, cell = entry.fn, entry.meta

        def build_bwd():
            pure = self._make_pure(train, cell)

            def bwd(rng_key, in_datas, param_datas, out_cots):
                """Compiled backward: recomputes the forward inside the jit
                (remat — residuals are traded for FLOPs, the
                HBM-bandwidth-favourable choice on TPU) and applies the
                transpose. A separate executable because linearizing
                *through* a jit boundary breaks for some primitives
                (reduce_window); vjp fully inside jit is always safe."""
                n_in = len(in_datas)

                def f(*diffs):
                    outs, _aux = pure(rng_key, list(diffs[:n_in]),
                                      list(diffs[n_in:]))
                    return outs[0] if len(outs) == 1 else tuple(outs)

                _, vjp_fn = jax.vjp(f, *(list(in_datas) + list(param_datas)))
                return vjp_fn(out_cots)

            return jax.jit(bwd)

        # the companion backward shares the site's single retrace count —
        # ledger-only registration so its FLOPs still feed perf.mfu. Its
        # cotangent example comes from the forward's recorded out_specs,
        # so the backward AOT-compiles (and persists) without waiting for
        # the first autograd call — but only where a backward is
        # plausible (train mode): AOT-compiling inference backwards
        # would pay a compile nobody dispatches.
        bkey = csvc.canonical_key(
            site="cached_op", fn_id=fn_id,
            signature=("bwd", fmt_key, train, shapes, struct),
            policy=policy_key_now, device=dev, nonce=nonce)
        bwd_example = None
        if train and example is not None and cell \
                and cell.get("out_specs"):
            specs = cell["out_specs"]
            cots = [jax.ShapeDtypeStruct(s, d) for s, d in specs]
            bwd_example = example + (cots[0] if len(cots) == 1
                                     else tuple(cots),)
        bentry = csvc.get_or_build(
            bkey, build_bwd, provenance=dict(prov, kind="backward"),
            example_args=bwd_example, companion=True,
            aot=True if bwd_example is not None else None)
        self._jits[key] = (jitted, bentry.fn, cell)
        return jitted, bentry.fn, cell

    def __call__(self, *args):
        if not self._ensure_params():
            # deferred init pending: settle shapes with one eager pass
            # (gluon runs deferred shape inference on first forward too)
            out = self._block._forward_eager(*args)
            self._ensure_params()
            return out
        in_fmt = []
        flat_in = _flatten_nd(args, in_fmt)
        nd_in = [x for x in flat_in if isinstance(x, NDArray)]
        if len(nd_in) != len(flat_in):
            # static (non-NDArray) leaves present: fall back to eager
            return self._block._forward_eager(*args)
        train = autograd.is_training()
        rng_key = _random.next_key()
        in_datas = [x._data for x in nd_in]
        param_datas = [p._data._data for p in self._params]
        jitted, jitted_bwd, cell = self._get_jit(tuple(in_fmt), train,
                                                 rng_key, in_datas,
                                                 param_datas)
        cell["in_fmt"] = in_fmt

        with telemetry.span("gluon.forward"):
            out_list, aux = jitted(rng_key, in_datas, param_datas)
        out_nds = [NDArray(d) for d in out_list]

        if autograd.is_recording():
            # tape ONE node for the whole compiled forward; its vjp is the
            # companion compiled backward (CachedOp::Backward analog)
            primals_out = out_list[0] if len(out_list) == 1 else tuple(out_list)

            def vjp_fn(out_cots):
                return jitted_bwd(rng_key, in_datas, param_datas, out_cots)

            inputs = nd_in + [p._data for p in self._params]
            autograd.record_op(None, inputs, out_nds, name="CachedOp",
                               vjp=vjp_fn, primals_out=primals_out)

        for p, new in zip(self._params, aux):
            if new is not None:
                p.data()._set_data(new)
        out, _, _ = _regroup(out_nds, cell["out_fmt"])
        return out


# ------------------------------------------------------------------ HybridBlock
class HybridBlock(Block):
    """A Block whose forward can be traced & compiled (ref: block.py:HybridBlock).

    Subclasses implement ``hybrid_forward(self, F, x, *, param_name=...)`` where F
    is the op namespace (mx.nd here — under a hybrid trace the same imperative ops
    run on jax tracers, so one code path serves eager and compiled execution; the
    reference instead swaps F between mx.nd and mx.sym)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Activate compiled execution (ref: block.py:hybridize; the static_alloc /
        static_shape knobs are inherent to XLA compilation and accepted for
        compatibility)."""
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape,
                           **kwargs)
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def cast(self, dtype):
        self._cached_op = None
        super().cast(dtype)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from input shapes. Leaf layers
        override (ref: block.py:_deferred_infer_shape via symbolic inference —
        here shape propagation is per-layer and explicit)."""
        raise MXNetError(
            "Deferred initialization failed: %s cannot infer parameter shapes "
            "from inputs. Provide explicit in_units/in_channels or run "
            "a forward pass with fully-specified layers first."
            % self.__class__.__name__)

    def forward(self, *args):
        if self._active and _active_trace() is None and _IN_TRACE.active == 0:
            if self._cached_op is None:
                self._cached_op = CachedOp(self)
            return self._cached_op(*args)
        return self._forward_eager(*args)

    def _forward_eager(self, *args):
        try:
            params = {k: p.data() for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self.infer_shape(*args)
            params = {k: p.data() for k, p in self._reg_params.items()}
        # remember input signatures so export/trace can replay (symbol.py)
        self._in_specs = [(a.shape, a.dtype) for a in args
                          if isinstance(a, NDArray)]
        from .. import ndarray as F
        return self.hybrid_forward(F, *args, **params)

    def hybrid_forward(self, F, *args, **kwargs):  # pragma: no cover - abstract
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Export to symbol-json + params checkpoint (ref: block.py:export).
        Requires the block to have run at least once."""
        from .. import symbol as sym_mod
        sym, arg_names = _trace_to_symbol(self)
        sym.save("%s-symbol.json" % path)
        params = self._collect_params_with_prefix()
        from ..ndarray.utils import save as nd_save
        arg = {}
        for name, p in self.collect_params().items():
            kind = "aux:" if p.grad_req == "null" else "arg:"
            arg[kind + name] = p.data()
        nd_save("%s-%04d.params" % (path, epoch), arg)
        return sym


def _trace_to_symbol(block):
    """Build a Symbol for a hybrid block by tracing with symbolic variables
    (used by export; real implementation lives in mxtpu.symbol)."""
    from ..symbol import trace_block
    return trace_block(block)


class SymbolBlock(HybridBlock):
    """Run a loaded Symbol as a Block (ref: gluon/block.py:SymbolBlock:954)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=None)
        # param names must match the symbol's input names exactly
        # (ref: SymbolBlock.__init__ resets prefix to '')
        self._prefix = ""
        self._params = ParameterDict("", params)
        from .. import symbol as sym_mod
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        self._output_sym = outputs
        self._input_syms = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        input_names = {s.name for s in self._input_syms}
        # every non-input free variable becomes a Parameter
        for name in outputs.list_inputs():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            ret.collect_params().load(param_file, ctx=ctx)
        return ret

    def _forward_eager(self, *args):
        kwargs = {s.name: a for s, a in zip(self._input_syms, args)}
        for name, p in self.params.items():
            if p._data is not None:
                kwargs[name] = p.data()
        out = self._output_sym.eval(**kwargs)
        return out[0] if isinstance(out, (list, tuple)) and len(out) == 1 else out

    def hybrid_forward(self, F, *args, **kwargs):
        raise MXNetError("SymbolBlock executes its symbol directly")
