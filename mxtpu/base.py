"""Base types for the TPU-native framework.

Mirrors the role of the reference's ``include/mxnet/base.h`` + ``python/mxnet/base.py``
(Context, dtype codes, error type), re-designed for JAX/PJRT: a Context names a PJRT
device (TPU chip or host CPU) instead of a CUDA device, and there is no ctypes FFI —
the "C API" equivalent is the in-process runtime in :mod:`mxtpu.runtime`.
"""
from __future__ import annotations

import os
import threading

import numpy as _np

__all__ = [
    "MXNetError", "Context", "cpu", "gpu", "tpu", "current_context",
    "DTYPE_TO_CODE", "CODE_TO_DTYPE", "np_dtype", "numeric_types", "string_types",
]

# ref: python/mxnet/base.py numeric_types/string_types
numeric_types = (float, int, _np.generic)
string_types = (str,)


class MXNetError(RuntimeError):
    """Error raised by the framework (ref: python/mxnet/base.py:MXNetError)."""


# dtype integer codes, kept wire-compatible with the reference's mshadow TypeFlag
# (3rdparty/mshadow usage at include/mxnet/ndarray.h / python/mxnet/base.py _DTYPE_NP_TO_MX)
DTYPE_TO_CODE = {
    "float32": 0,
    "float64": 1,
    "float16": 2,
    "uint8": 3,
    "int32": 4,
    "int8": 5,
    "int64": 6,
    # TPU-native additions (no reference counterpart):
    "bfloat16": 7,
    "bool": 8,
}
CODE_TO_DTYPE = {v: k for k, v in DTYPE_TO_CODE.items()}


def np_dtype(dtype):
    """Canonicalize a dtype-ish value to a string name (bfloat16-aware)."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        name = dtype
    else:
        name = _np.dtype(dtype).name if not _is_bfloat16(dtype) else "bfloat16"
    if name == "bfloat16":
        return "bfloat16"
    return _np.dtype(name).name


def _is_bfloat16(dtype) -> bool:
    try:
        return "bfloat16" in str(dtype)
    except Exception:  # pragma: no cover
        return False


class Context:
    """A device context (ref: python/mxnet/context.py:Context).

    Device types:
      * ``cpu``  — host CPU (JAX cpu backend)
      * ``tpu``  — a TPU chip (the accelerator; primary device of this framework)
      * ``gpu``  — alias for the default accelerator so reference-era scripts that
        say ``mx.gpu(0)`` run unmodified on TPU.

    Unlike the reference there is no per-device worker-thread pool to configure:
    async dispatch and per-device ordering are provided by PJRT streams
    (ref engine: src/engine/threaded_engine_perdevice.cc — subsumed by PJRT).
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        elif isinstance(device_type, str):
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        else:
            self.device_typeid = device_type
            self.device_id = device_id

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    # -- PJRT resolution -------------------------------------------------
    def jax_device(self):
        """Resolve this Context to a concrete PJRT device.

        ``tpu``/``gpu`` map to the default accelerator backend; if the process
        is running CPU-only (the virtual multi-device test mesh), they
        resolve to CPU devices so reference-style scripts and the tests
        still run — entry points that measure (``chip_smoke.py``,
        ``bench.py``) assert the platform themselves, once, up front.
        An accelerator id past the devices that exist raises, as the
        reference does for ``mx.gpu(5)`` on a 4-GPU host. CPU ids are
        labels in the reference (``mx.cpu(3)`` is valid on any host) and
        keep wrapping.
        """
        import jax

        dt = self.device_type
        if dt in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                devs = jax.devices("cpu")
            except RuntimeError:
                devs = jax.devices()
            return devs[self.device_id % len(devs)]
        devs = jax.devices()  # tpu / gpu -> default accelerator backend
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%s: invalid device ordinal, this process has %d %s "
                "device(s)" % (self, len(devs), devs[0].platform))
        return devs[self.device_id]

    def empty_cache(self):
        """Release cached device memory (ref: MXStorageEmptyCache). PJRT pools
        internally; provided for API parity."""

    def __enter__(self):
        if not hasattr(Context._default_ctx, "contexts"):
            Context._default_ctx.contexts = [Context("tpu", 0)]
        Context._default_ctx.contexts.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.contexts.pop()


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of :func:`tpu` for reference-script compatibility."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "contexts"):
        Context._default_ctx.contexts = [Context("tpu", 0)]
    return Context._default_ctx.contexts[-1]


def num_gpus() -> int:
    """Number of accelerator devices visible (ref: mx.context.num_gpus)."""
    import jax

    try:
        return len([d for d in jax.devices() if d.platform != "cpu"])
    except RuntimeError:
        return 0


def getenv(name: str, default):
    """Typed env-var lookup (ref: dmlc::GetEnv; catalog docs/faq/env_var.md)."""
    val = os.environ.get(name)
    if val is None:
        return default
    if isinstance(default, bool):
        return val.lower() in ("1", "true", "yes", "on")
    return type(default)(val)
