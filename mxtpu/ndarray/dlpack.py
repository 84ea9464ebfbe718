"""DLPack interchange (ref: python/mxnet/ndarray/ndarray.py:3925-4029
``to_dlpack_for_read`` / ``to_dlpack_for_write`` / ``from_dlpack`` over
src/c_api MXNDArrayToDLPack / MXNDArrayFromDLPack).

TPU-native: the underlying jax.Array already speaks the DLPack protocol;
these functions expose the reference's capsule-based API over it so code
written against ``mx.nd.to_dlpack_for_read(x)`` / ``torch.utils.dlpack``
ports unchanged. One PJRT stream orders reads and writes, so the
read/write variants differ only in their documented intent (the
reference separates them because its dependency engine tracks read and
write queues independently, include/mxnet/engine.h:116).
"""
from __future__ import annotations

import ctypes

import numpy as _np

from ..base import MXNetError
from .ndarray import NDArray, array as _nd_array

__all__ = ["to_dlpack_for_read", "to_dlpack_for_write", "from_dlpack",
           "from_numpy"]

_DLTENSOR = b"dltensor"
# platforms whose buffers jax.Array.__dlpack__ exports in place
_DLPACK_PLATFORMS = ("cpu", "cuda", "rocm", "gpu")


def _host_export(data: NDArray):
    """ONE copy of the host-copy export recipe. copy=True: device_get
    often returns READONLY views, which numpy refuses to export (DLPack
    cannot signal readonly)."""
    host = _np.array(data.asnumpy(), copy=True)
    return host.__dlpack__()


def _capsule_from(data: NDArray):
    if not isinstance(data, NDArray):
        raise MXNetError("to_dlpack expects an NDArray, got %s"
                         % type(data).__name__)
    data.wait_to_read()
    if next(iter(data._data.devices())).platform in _DLPACK_PLATFORMS:
        return data._data.__dlpack__()
    # DLPack names no TPU device type and jax exports CPU and GPU buffers
    # only (on the v5e: ``JaxRuntimeError: INVALID_ARGUMENT: Device TPU_0
    # ... cannot be used as a DLPack device``): stage through a host copy — the consumer gets a CPU DLPack
    # tensor, matching torch_interop's copy-always policy. Decided from
    # the buffer's platform, so a failed export on a platform that CAN
    # export is an error, not a silent copy
    return _host_export(data)


def to_dlpack_for_read(data):
    """NDArray -> PyCapsule("dltensor") of a DLManagedTensor. The capsule
    is one-shot: a consumer (torch.utils.dlpack.from_dlpack, another
    framework's importer) takes ownership."""
    return _capsule_from(data)


def to_dlpack_for_write(data):
    """Reference-parity name; delivers a WRITABLE HOST COPY, and consumer
    writes do NOT propagate back. XLA buffers are immutable — handing a
    consumer a mutable pointer into one would corrupt jit-cached/aliased
    computations, and the reference's in-place write-back contract
    (ndarray.py:3956) cannot hold on a functional runtime. Write into a
    fresh array and assign it back instead
    (``x[:] = mx.nd.from_dlpack(...)``).

    Warns on EVERY call — a ported write-back-dependent code path must fail
    loudly each time, not only on its first buffer (ADVICE r5: the single
    process-wide warning was suppressible by warning filters and then
    silently lost writes). Set ``MXTPU_DLPACK_WRITE_COPY=1`` to acknowledge
    the detached-copy semantics explicitly and silence the warning."""
    import os
    if os.environ.get("MXTPU_DLPACK_WRITE_COPY", "0") != "1":
        import warnings
        # warn_explicit with a FRESH registry: plain warnings.warn is deduped
        # per call site by the default filter, which is exactly the
        # silently-lost-writes failure mode this warning exists to prevent
        warnings.warn_explicit(
            "to_dlpack_for_write exports a host COPY on this runtime: "
            "consumer writes do not propagate back to the NDArray "
            "(XLA buffers are immutable). Assign results back with "
            "x[:] = mx.nd.from_dlpack(...) instead, or set "
            "MXTPU_DLPACK_WRITE_COPY=1 to acknowledge the copy semantics "
            "and silence this warning.",
            UserWarning, __file__, 0, registry={})
    if not isinstance(data, NDArray):
        raise MXNetError("to_dlpack expects an NDArray, got %s"
                         % type(data).__name__)
    data.wait_to_read()
    return _host_export(data)


class _CapsuleDLPack:
    """Adapter: a raw "dltensor" capsule as the modern __dlpack__ protocol
    (jax.dlpack.from_dlpack no longer accepts bare capsules). The device
    is parsed out of the DLManagedTensor header via ctypes."""

    def __init__(self, capsule):
        self._capsule = capsule

    def __dlpack__(self, **_kw):
        return self._capsule

    def __dlpack_device__(self):
        get_ptr = ctypes.pythonapi.PyCapsule_GetPointer
        get_ptr.restype = ctypes.c_void_p
        get_ptr.argtypes = [ctypes.py_object, ctypes.c_char_p]
        ptr = get_ptr(self._capsule, _DLTENSOR)
        # DLManagedTensor starts with DLTensor: { void* data;
        #   DLDevice { int32 device_type; int32 device_id }; ... }
        dev = ctypes.cast(ptr + ctypes.sizeof(ctypes.c_void_p),
                          ctypes.POINTER(ctypes.c_int32))
        return int(dev[0]), int(dev[1])


def from_dlpack(dlpack) -> NDArray:
    """PyCapsule (or any object with ``__dlpack__``) -> NDArray.

    The producer's capsule is CONSUMED (renamed "used_dltensor" by the
    importer, per the DLPack contract) — use the tensor only through the
    returned NDArray afterwards."""
    import jax.dlpack

    if ctypes.pythonapi.PyCapsule_IsValid(
            ctypes.py_object(dlpack), b"dltensor_versioned"):
        # DLPack 1.0 renamed the capsule and prefixed the struct with a
        # version/flags header (DLManagedTensorVersioned); the pre-1.0
        # ctypes parsing below would misread it. Name the case instead of
        # letting jax fail with an obscure "no __dlpack__" error.
        raise MXNetError(
            "from_dlpack got a DLPack-1.0 'dltensor_versioned' capsule; "
            "this importer consumes the pre-1.0 'dltensor' layout. "
            "Re-export from the producer without max_version (the legacy "
            "protocol, e.g. tensor.__dlpack__()), or pass the producer "
            "object itself so the exchange negotiates a version.")
    is_capsule = ctypes.pythonapi.PyCapsule_IsValid(
        ctypes.py_object(dlpack), _DLTENSOR)
    src = _CapsuleDLPack(dlpack) if is_capsule else dlpack
    return NDArray(jax.dlpack.from_dlpack(src))


def from_numpy(ndarray, zero_copy=True):
    """numpy -> NDArray (ref: mx.nd.from_numpy, ndarray.py:4032). The
    reference aliases host memory when ``zero_copy``; device-resident
    arrays cannot alias host numpy buffers, so this always copies and
    ``zero_copy`` is accepted for API compatibility."""
    if not isinstance(ndarray, _np.ndarray):
        raise MXNetError("from_numpy expects a numpy.ndarray")
    if not ndarray.flags["C_CONTIGUOUS"]:
        raise MXNetError("the numpy ndarray must be C-contiguous "
                         "(reference from_numpy raises the same)")
    return _nd_array(ndarray)
