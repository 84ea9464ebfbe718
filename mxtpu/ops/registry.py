"""Operator registry: the TPU-native analog of the NNVM op registry.

Reference contract: every op registers name + FInferShape/FInferType/FCompute/FGradient
attrs via ``NNVM_REGISTER_OP`` (include/mxnet/op_attr_types.h:198-301; canonical example
src/operator/nn/fully_connected.cc:239-328).

TPU-native re-design: an op is a *pure jax-traceable function* — shape/dtype inference
comes from jax's abstract evaluation (``jax.eval_shape``), the gradient from ``jax.vjp``,
and the kernel from XLA lowering (or a Pallas kernel for hot ops). So a registration
here is just ``(name, fn, aliases)``; the registry exists to

* generate the ``mx.nd.*`` imperative namespace (ref: per-op Python codegen at import,
  python/mxnet/ndarray/register.py:143-157),
* give :mod:`mxtpu.symbol` a name → fn table for deferred graph execution,
* attach NDArray methods (``x.sum()`` etc) the way the reference's frontend codegen does.
"""
from __future__ import annotations

import functools
import inspect
from typing import Callable, Dict, List, Optional

from ..ndarray.ndarray import NDArray, _apply

__all__ = ["Op", "register", "get_op", "list_ops", "invoke", "REGISTRY",
           "register_param_shapes", "get_param_shape_rule", "describe"]


class Op:
    """A registered operator: ``fn`` works on jax arrays / pytrees; wrapper works on
    NDArrays with tape recording."""

    __slots__ = ("name", "fn", "wrapper", "aliases", "as_method", "doc",
                 "num_outputs")

    def __init__(self, name: str, fn: Callable, wrapper: Callable,
                 aliases=(), as_method: bool = False, num_outputs: int = 1):
        self.name = name
        self.fn = fn
        self.wrapper = wrapper
        self.aliases = tuple(aliases)
        self.as_method = as_method
        self.doc = fn.__doc__
        self.num_outputs = num_outputs  # STATIC count (1 = single/unknown;
        # data-dependent counts are fixed up at execution)


REGISTRY: Dict[str, Op] = {}


def register(name: Optional[str] = None, aliases=(), as_method: bool = False,
             wrap: bool = True, num_outputs: int = 1):
    """Register a jnp-level op and return its NDArray-level function.

    The returned wrapper accepts NDArrays (and scalars/attrs), snapshots payloads,
    evaluates, wraps outputs, and tapes the call when autograd is recording — i.e. it
    performs the whole MXImperativeInvokeEx → Imperative::Invoke path
    (src/c_api/c_api_ndarray.cc:81, src/imperative/imperative.cc:87) in one function.
    """

    def deco(fn: Callable):
        op_name = name or fn.__name__

        if wrap:
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                out = kwargs.pop("out", None)
                res = _apply(fn, args, kwargs, name=op_name)
                if out is not None:
                    if isinstance(res, list):
                        for o, r in zip(out if isinstance(out, (list, tuple)) else [out], res):
                            o._set_data(r._data)
                        return out
                    out._set_data(res._data)
                    return out
                return res
        else:
            wrapper = fn

        op = Op(op_name, fn, wrapper, aliases=aliases, as_method=as_method,
                num_outputs=num_outputs)
        REGISTRY[op_name] = op
        for al in aliases:
            REGISTRY[al] = op
        return wrapper

    return deco


def policy_key():
    """Trace-time env policies that get BAKED INTO compiled executables
    (the ring-flash route, the RNN hoist, the stem transform, the numerics
    guard). Every jit cache keyed on shapes/modes must include this tuple,
    or flipping a policy flag mid-process silently reuses executables
    traced under the old policy (an A/B measurement would then compare a
    lever with itself)."""
    import os
    # defaults must MIRROR their read sites — a mismatch would alias unset
    # and the non-default value onto one cache key
    return (os.environ.get("MXTPU_RING_FLASH", "0"),
            os.environ.get("MXTPU_RNN_HOIST", "1"),
            # contrib/s2d_stem.py:stem_mode (policy-mode _StemFn)
            os.environ.get("MXTPU_S2D_STEM", "0"),
            # resilience.guard_enabled: the in-jit numerics sentinel — the
            # skip-step `where` select is baked into the fused-update
            # executable, so a guard flip must recompile (exactly once);
            # the step_ok FLAG and loss-scale VALUE are traced and never do
            os.environ.get("MXTPU_NUMERICS_GUARD", "0"),
            # resilience.divergence_every: the divergence-sentinel
            # fingerprint (f32 sum + i32 bitcast-fold of post-update
            # params+state) is compiled into the SAME fused-update
            # executable when non-zero, so an on/off flip recompiles (at
            # most once per cached executable). Only the ON BIT is
            # trace-time — the cadence VALUE is a host compare schedule,
            # so it is normalized here: retuning 8 -> 16 must not
            # invalidate every policy_key-keyed forward/serving
            # executable that never contained the fingerprint
            "0" if os.environ.get("MXTPU_DIVERGENCE_EVERY", "0")
            in ("", "0") else "1",
            # flash_attention._interpret: the interpret path changes the
            # traced program
            os.environ.get("MXTPU_FLASH_INTERPRET", "0"))


# canonical op name -> fn(attrs) -> int: STATIC output count for ops whose
# count depends on attrs (the reference's FNumOutputs — e.g. RNN emits
# final states only when state_outputs). Consulted by the symbol composer
# so sym[i] works before execution.
NUM_OUTPUT_RULES: Dict[str, Callable] = {}


def register_num_outputs(name: str):
    def deco(fn: Callable):
        NUM_OUTPUT_RULES[name] = fn
        return fn
    return deco


# canonical op name -> fn(input_shapes, attrs) -> {input_index: shape}.
# The FInferShape *backward fill* of the reference registry
# (include/mxnet/op_attr_types.h FInferShape; e.g. fully_connected.cc
# derives weight=(num_hidden, in_units) from the data shape): given the
# known input shapes (None for unknown), a rule returns shapes for the
# op's parameter inputs so symbols with undeclared parameter shapes can
# still be inferred (BucketingModule on unseen buckets depends on this).
PARAM_SHAPE_RULES: Dict[str, Callable] = {}


def register_param_shapes(name: str):
    """Attach a parameter-shape backward-fill rule to a registered op."""

    def deco(fn: Callable):
        PARAM_SHAPE_RULES[name] = fn
        return fn

    return deco


def get_param_shape_rule(name: str) -> Optional[Callable]:
    op = REGISTRY.get(name)
    return PARAM_SHAPE_RULES.get(op.name if op is not None else name)


def get_op(name: str) -> Op:
    if name not in REGISTRY:
        raise KeyError("Operator %s is not registered" % name)
    return REGISTRY[name]


def list_ops() -> List[str]:
    return sorted(REGISTRY)


def invoke(name: str, *args, **kwargs):
    """Invoke a registered op by name (symbol executor / C-ABI entry point)."""
    return get_op(name).wrapper(*args, **kwargs)


def attach_methods(cls=NDArray):
    """Attach registered ops marked ``as_method`` as NDArray methods, mirroring the
    reference's generated method surface (python/mxnet/ndarray/register.py)."""
    for key, op in list(REGISTRY.items()):
        if not op.as_method:
            continue
        if getattr(cls, key, None) is not None:
            continue  # don't clobber hand-written methods

        def make(opw):
            def method(self, *args, **kwargs):
                return opw(self, *args, **kwargs)
            return method

        setattr(cls, key, make(op.wrapper))


def describe(name: str) -> dict:
    """Parameter reflection for a registered op — the dmlc::Parameter /
    DMLC_DECLARE_FIELD analog (SURVEY §5 config system): the reference
    generates Python signatures + docstrings from each op's declared param
    struct; here the op IS a Python function, so its signature is the
    declaration. Returns {"name", "doc", "arguments": [...],
    "attributes": [{"name", "default"}...]}."""
    op = get_op(name)
    sig = inspect.signature(op.fn)
    arguments = []
    attributes = []
    for pname, p in sig.parameters.items():
        if p.kind in (inspect.Parameter.VAR_POSITIONAL,
                      inspect.Parameter.VAR_KEYWORD):
            arguments.append({"name": pname, "variadic": True})
        elif p.default is inspect.Parameter.empty:
            arguments.append({"name": pname})
        else:
            attributes.append({"name": pname, "default": p.default})
    return {"name": op.name, "aliases": list(op.aliases),
            "doc": op.doc, "arguments": arguments,
            "attributes": attributes}
