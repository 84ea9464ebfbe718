"""Runtime kernel compilation: user-authored Pallas kernels as ops.

Reference: python/mxnet/rtc.py ``CudaModule`` — runtime-compiled CUDA
source (NVRTC, src/common/rtc.cc) launched on NDArrays. The TPU-native
escape hatch is Pallas (SURVEY §2.2 "rtc/NVRTC maps to inline Pallas"):
``PallasModule`` execs a Python source string that defines Pallas kernel
function(s) (``*_ref`` arguments, last ref(s) are outputs), and
``Kernel.launch`` wraps it in ``pl.pallas_call`` + jit on NDArrays.

The API shape mirrors the reference —
``module.get_kernel(name, signature).launch(args, ...)`` — with TPU-shaped
launch parameters (out_shapes + optional grid/block specs) instead of CUDA
grid/block dims.
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError
from .ndarray import NDArray

__all__ = ["PallasModule", "Kernel", "CudaModule"]


def CudaModule(source, options=(), exports=()):
    """Reference-name entry point (ref: rtc.py:CudaModule). CUDA C++
    source cannot run on a TPU; raises with the migration path unless the
    source is actually Python (then it routes to PallasModule)."""
    head = source.lstrip()[:64]
    looks_like_cuda = ("__global__" in source or "#include" in head
                       or "extern \"C\"" in source)
    if looks_like_cuda:
        raise MXNetError(
            "mx.rtc.CudaModule received CUDA C++ source; this runtime has "
            "no NVRTC/GPU. Rewrite the kernel as a Pallas function (Refs "
            "in, last args are outputs) and use mx.rtc.PallasModule — see "
            "mxtpu/rtc.py and the examples in tests/test_contrib_python.py.")
    return PallasModule(source, exports=list(exports) or None)


class PallasModule:
    """Compile Pallas kernel source at runtime (ref: rtc.py:CudaModule).

    Parameters
    ----------
    source : str
        Python source. Each kernel is a function taking pallas Refs; by
        convention the final ``num_outputs`` arguments are output Refs.
        The namespace is pre-seeded with ``pl`` (jax.experimental.pallas),
        ``jnp``, and ``jax``.
    exports : list of str, optional
        Kernel names; default = every top-level function defined.
    """

    def __init__(self, source, exports=None):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        self._namespace = {"pl": pl, "jnp": jnp, "jax": jax}
        seeded = set(self._namespace)
        try:
            exec(compile(source, "<mxtpu.rtc>", "exec"), self._namespace)
        except SyntaxError as e:
            raise MXNetError("PallasModule source failed to compile: %s"
                             % e) from e
        import inspect
        fns = {k: v for k, v in self._namespace.items()
               if inspect.isfunction(v) and k not in seeded
               and not k.startswith("__")}
        if exports is not None:
            missing = [e for e in exports if e not in fns]
            if missing:
                raise MXNetError("exports not found in source: %s" % missing)
            fns = {k: fns[k] for k in exports}
        if not fns:
            raise MXNetError("no kernel functions found in source")
        self._kernels = fns

    def get_kernel(self, name, num_outputs=1):
        """Kernel by name (ref: rtc.py:get_kernel — the signature string is
        unnecessary here: Refs carry shapes/dtypes)."""
        if name not in self._kernels:
            raise MXNetError("kernel %r not in module (have: %s)"
                             % (name, sorted(self._kernels)))
        return Kernel(self._kernels[name], name, num_outputs)


class Kernel:
    """A launchable Pallas kernel (ref: rtc.py:CudaModule.Kernel)."""

    def __init__(self, fn, name, num_outputs=1):
        self._fn = fn
        self.name = name
        self._num_outputs = num_outputs

    def launch(self, args, out_shapes, out_dtypes=None, grid=None,
               in_specs=None, out_specs=None, interpret=None):
        """Run the kernel (ref: rtc.py:Kernel.launch — CUDA grid/block dims
        become the pallas grid/BlockSpecs; XLA owns scheduling).

        args : list of NDArray inputs.
        out_shapes : shape tuple or list of shape tuples.
        grid/in_specs/out_specs : forwarded to ``pl.pallas_call``.
        interpret : force interpreter mode (defaults to True off-TPU so
            kernels stay testable on CPU, matching how the test suite runs).
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        if isinstance(out_shapes, (tuple, list)) and (
                not out_shapes or isinstance(out_shapes[0], int)):
            out_shapes = [tuple(out_shapes)]
        n_out = len(out_shapes)
        if out_dtypes is None:
            out_dtypes = [args[0].dtype if args else _np.float32] * n_out
        elif isinstance(out_dtypes, (str, type)) or not hasattr(
                out_dtypes, "__len__"):
            out_dtypes = [out_dtypes] * n_out
        if len(out_dtypes) != n_out:
            raise MXNetError("launch: %d out_dtypes for %d out_shapes"
                             % (len(out_dtypes), n_out))
        if interpret is None:
            interpret = jax.devices()[0].platform != "tpu"
        out_shape = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
                     for s, d in zip(out_shapes, out_dtypes)]
        if len(out_shapes) != self._num_outputs:
            raise MXNetError(
                "kernel %r declared num_outputs=%d but launch got %d "
                "out_shapes" % (self.name, self._num_outputs,
                                len(out_shapes)))
        from . import compile_service as csvc
        # the compile service is the cache (LRU-bounded — the old
        # per-kernel dict was unbounded under launch-signature churn),
        # keyed by kernel source identity + the full launch signature.
        # The source digest is memoized: getsource+sha per LAUNCH would
        # tax the eager-loop use case this API serves
        fn_id = getattr(self, "_fn_token", None)
        if fn_id is None:
            fn_id = self._fn_token = "%s:%s" % (
                self.name, csvc.source_token(self._fn))
        datas = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                 for a in args]
        # a launch nested under an outer trace (tracer inputs) keys a
        # SEPARATE plain-jit entry: an AOT executable compiled by an
        # earlier eager launch of the same signature cannot be invoked
        # with tracers — the variant keeps both worlds correct
        example = csvc.concrete_args(tuple(datas))
        key = csvc.canonical_key(
            site="rtc",
            fn_id=fn_id,
            signature=(tuple((tuple(a.shape), str(a.dtype))
                             for a in args),
                       tuple(tuple(s) for s in out_shapes),
                       tuple(str(d) for d in out_dtypes), repr(grid),
                       bool(interpret), repr(in_specs), repr(out_specs))
            + (("traced",) if example is None else ()),
            device=csvc.device_token(), nonce=csvc.instance_nonce(self))

        def build():
            kwargs = {"out_shape": out_shape if n_out > 1 else out_shape[0],
                      "interpret": interpret}
            if grid is not None:
                kwargs["grid"] = grid
            if in_specs is not None:
                kwargs["in_specs"] = in_specs
            if out_specs is not None:
                kwargs["out_specs"] = out_specs
            return jax.jit(pl.pallas_call(self._fn, **kwargs))

        # retrace watchdog: user kernels compile once per launch
        # signature — a shape-unstable caller shows up here by name
        entry = csvc.get_or_build(
            key, build,
            # interpret: off the chip launch() picks the Pallas
            # interpreter by itself — the provenance says which ran
            provenance=lambda: {"kernel": self.name,
                                "interpret": bool(interpret),
                                "args": [(tuple(a.shape), str(a.dtype))
                                         for a in args]},
            example_args=example)
        res = entry.fn(*datas)
        if isinstance(res, (list, tuple)):
            return [NDArray(r) for r in res]
        return NDArray(res)
