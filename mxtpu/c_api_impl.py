"""Python guts of the C ABI (src/c_api/c_api.cc delegates here).

The reference's src/c_api/*.cc marshals C arguments into its C++ engine;
the TPU-native runtime's orchestrator is this package, so the C layer
marshals into these functions instead. Every function takes/returns only
plain C-friendly values (bytes, tuples, opaque objects used as handles).

A C host picks the jax platform like any process does: ``JAX_PLATFORMS`` in
its environment, or the explicit ``platform`` argument of
``MXTPURuntimeInit``.
"""
from __future__ import annotations

import ast
import os

import numpy as np
import jax.numpy as jnp

from . import ndarray as nd
from . import ops
from .base import MXNetError
from .model import load_checkpoint
from .ndarray import NDArray


def runtime_init(platform=None):
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    import jax

    jax.devices()  # force backend bring-up so later calls are fast
    return True


def ndarray_from_blob(data: bytes, shape: tuple) -> NDArray:
    arr = np.frombuffer(data, dtype=np.float32).reshape(shape)
    return nd.array(arr)


def ndarray_shape(handle: NDArray) -> tuple:
    return tuple(int(d) for d in handle.shape)


def ndarray_to_bytes(handle: NDArray) -> bytes:
    return np.ascontiguousarray(handle.asnumpy().astype(np.float32)).tobytes()


def _parse_attr(v: str):
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        return v


def imperative_invoke(name: str, inputs: list, attrs: dict) -> list:
    kwargs = {k: _parse_attr(v) for k, v in attrs.items()}
    out = ops.invoke(name, *inputs, **kwargs)
    if isinstance(out, (list, tuple)):
        return list(out)
    return [out]


class _Predictor:
    """C-predict-API state (ref: src/c_api/c_predict_api.cc:59-213 — the
    reference binds a static executor; here bind = jit-compiled Symbol
    executor over the same checkpoint format)."""

    def __init__(self, prefix, epoch, input_name, shape):
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        if symbol is None:
            raise MXNetError("no symbol file for prefix %r" % prefix)
        self.input_name = input_name
        self.shape = tuple(int(d) for d in shape)
        args = dict(arg_params)
        args[input_name] = nd.zeros(self.shape)
        self.executor = symbol.bind(args=args, aux_states=aux_params,
                                    grad_req="null")
        self._input = None
        self.outputs = []

    def set_input(self, data: bytes):
        arr = np.frombuffer(data, dtype=np.float32).reshape(self.shape)
        self._input = nd.array(arr)

    def forward(self):
        kwargs = {}
        if self._input is not None:
            kwargs[self.input_name] = self._input
        self.outputs = self.executor.forward(is_train=False, **kwargs)


def pred_create(prefix, epoch, input_name, shape) -> _Predictor:
    return _Predictor(prefix, epoch, input_name, shape)


def pred_set_input(pred: _Predictor, data: bytes):
    pred.set_input(data)
    return True


def pred_forward(pred: _Predictor):
    pred.forward()
    return True


def pred_output_shape(pred: _Predictor, index: int) -> tuple:
    return tuple(int(d) for d in pred.outputs[index].shape)


def pred_output_bytes(pred: _Predictor, index: int) -> bytes:
    return ndarray_to_bytes(pred.outputs[index])


# ---- autograd (ref: c_api_ndarray.cc MXAutogradSetIsRecording /
# MarkVariables / Backward; SURVEY §2.1 imperative+autograd) ----

def autograd_set_recording(flag: int) -> int:
    from . import autograd
    return int(autograd.set_recording(bool(flag)))


def autograd_set_training(flag: int) -> int:
    from . import autograd
    return int(autograd.set_training(bool(flag)))


def ndarray_attach_grad(handle: NDArray) -> None:
    handle.attach_grad()


def ndarray_grad(handle: NDArray) -> NDArray:
    g = handle.grad
    if g is None:
        raise MXNetError("no gradient: attach_grad() was not called or "
                         "backward has not run")
    return g


def ndarray_backward(handle: NDArray, retain_graph: int) -> None:
    handle.backward(retain_graph=bool(retain_graph))


# ---- KVStore (ref: c_api.cc MXKVStoreCreate / Init / Push / Pull /
# SetOptimizer; SURVEY §2.3) ----

def kvstore_create(kind: str):
    from .kvstore import create
    return create(kind or "local")


def kvstore_init(kv, keys: tuple, vals: tuple) -> None:
    kv.init(list(keys), list(vals))


def kvstore_push(kv, keys: tuple, vals: tuple, priority: int) -> None:
    kv.push(list(keys), list(vals), priority=priority)


def kvstore_pull(kv, keys: tuple, outs: tuple, priority: int) -> None:
    kv.pull(list(keys), out=list(outs), priority=priority)


def kvstore_set_optimizer(kv, name: str, attrs: dict) -> None:
    from .optimizer import Optimizer
    kv.set_optimizer(Optimizer.create_optimizer(
        name, **{k: _parse_attr(v) for k, v in attrs.items()}))


# ---- Symbol + Executor (ref: c_api_symbolic.cc MXSymbolCreateVariable /
# CreateAtomicSymbol+Compose / ListArguments / CreateFromJSON;
# c_api_executor.cc MXExecutorBindEX / Forward / Backward / Outputs) ----

def symbol_create_variable(name: str):
    from .symbol import var
    return var(name)


def symbol_create_from_json(json_str: str):
    from .symbol import load_json
    return load_json(json_str)


def symbol_create_from_file(path: str):
    from .symbol import load as sym_load
    return sym_load(path)


def symbol_invoke(op_name: str, attrs: dict, name: str, inputs: tuple):
    """CreateAtomicSymbol + Compose in one call (the reference splits
    these only because nnvm composes lazily — ref c_api_symbolic.cc
    MXSymbolCreateAtomicSymbol + MXSymbolCompose)."""
    from . import symbol as sym_mod
    fn = getattr(sym_mod, op_name, None)
    if fn is None:
        raise MXNetError("unknown symbolic operator %r" % op_name)
    kwargs = {k: _parse_attr(v) for k, v in attrs.items()}
    if name:
        kwargs["name"] = name
    return fn(*inputs, **kwargs)


def symbol_list_arguments(sym) -> tuple:
    return tuple(sym.list_arguments())


def symbol_list_outputs(sym) -> tuple:
    return tuple(sym.list_outputs())


def symbol_tojson(sym) -> str:
    return sym.tojson()


def executor_bind(sym, arg_names: tuple, arg_vals: tuple,
                  grad_req: str):
    args = dict(zip(arg_names, arg_vals))
    return sym.bind(None, args, grad_req=grad_req or "write")


def executor_forward(ex, is_train: int) -> tuple:
    return tuple(ex.forward(is_train=bool(is_train)))


def executor_backward(ex) -> None:
    ex.backward()


def executor_outputs(ex) -> tuple:
    return tuple(ex.outputs)


def executor_arg_grad(ex, name: str) -> NDArray:
    grads = ex.grad_dict if hasattr(ex, "grad_dict") else None
    if grads is None or name not in grads or grads[name] is None:
        raise MXNetError("no gradient for argument %r" % name)
    return grads[name]


# ---- dtype-aware create / save / load (ref: MXNDArrayCreateEx,
# MXNDArraySave, MXNDArrayLoad over src/c_api/c_api.cc:1035-1120) ----

_DTYPE_FLAGS = {0: "float32", 1: "float64", 2: "float16", 3: "uint8",
                4: "int32", 5: "int8", 6: "int64"}
_FLAGS_BY_NAME = {v: k for k, v in _DTYPE_FLAGS.items()}


def ndarray_from_blob_ex(data: bytes, dtype_flag: int, shape: tuple):
    name = _DTYPE_FLAGS.get(int(dtype_flag))
    if name is None:
        raise MXNetError("unknown mshadow dtype flag %d" % dtype_flag)
    a = np.frombuffer(data, dtype=np.dtype(name)).reshape(shape)
    return nd.array(a, dtype=name)


def ndarray_dtype_flag(handle: NDArray) -> int:
    name = str(handle.dtype)
    if name == "bfloat16":  # no reference flag; surfaced as its f32 carrier
        return 0
    flag = _FLAGS_BY_NAME.get(name)
    if flag is None:
        raise MXNetError("dtype %s has no mshadow flag" % name)
    return flag


def ndarray_save(fname: str, handles: tuple, names: tuple) -> None:
    from .ndarray.utils import save as nd_save
    if names:
        if len(set(names)) != len(names):
            # the dict-keyed writer would silently drop all but the last
            # duplicate; refuse loudly instead (the reference would write
            # both records, which this engine's named files cannot)
            raise MXNetError("duplicate keys in NDArray save")
        nd_save(fname, dict(zip(names, handles)))
    else:
        nd_save(fname, list(handles))


def ndarray_load(fname: str):
    from .ndarray.utils import load as nd_load
    out = nd_load(fname)
    if isinstance(out, dict):
        return tuple(out.values()), tuple(out.keys())
    return tuple(out), ()


# ---- introspection / sync (ref: MXGetVersion, MXListAllOpNames,
# MXNDArrayWaitAll) ----

def get_version() -> int:
    """Reference packs MAJOR*10000 + MINOR*100 + PATCH (c_api.cc)."""
    import re
    from .libinfo import __version__
    parts = (__version__.split(".") + ["0", "0"])[:3]
    nums = []
    for part in parts:
        m = re.match(r"\d+", part)  # "0rc1" -> 0 (pre-release suffixes)
        nums.append(int(m.group()) if m else 0)
    return nums[0] * 10000 + nums[1] * 100 + nums[2]


def list_all_op_names() -> tuple:
    from .ops.registry import list_ops
    return tuple(list_ops())


def ndarray_wait_all() -> None:
    # NOT ndarray.waitall(), which swallows: the C contract is that
    # deferred async errors SURFACE here (-1 + MXTPUGetLastError), the
    # reference's MXNDArrayWaitAll semantics
    import jax
    jax.effects_barrier()


# ---- DataIter surface (ref: MXListDataIters/MXDataIterCreateIter/
# MXDataIterNext/MXDataIterGetData..., src/c_api/c_api.cc MXDataIter*) ----

_DATA_ITERS = None


def _data_iter_registry():
    global _DATA_ITERS
    if _DATA_ITERS is None:
        from . import io as io_mod
        from .image import ImageIter
        _DATA_ITERS = {
            "NDArrayIter": io_mod.NDArrayIter,
            "CSVIter": io_mod.CSVIter,
            "LibSVMIter": io_mod.LibSVMIter,
            "ImageRecordIter": ImageIter,  # the reference's registered name
            "ImageIter": ImageIter,
        }
    return _DATA_ITERS


def list_data_iters() -> tuple:
    return tuple(sorted(_data_iter_registry()))


class _CIter:
    """Iterator handle: owns the iter + the current batch (the reference's
    MXDataIterNext caches the batch the Get* calls then read)."""

    def __init__(self, it):
        self.it = it
        self.batch = None


def data_iter_create(name: str, attrs: dict):
    cls = _data_iter_registry().get(name)
    if cls is None:
        raise MXNetError("unknown data iter %r (have: %s)"
                         % (name, ", ".join(sorted(_data_iter_registry()))))
    kwargs = {k: _parse_attr(v) for k, v in attrs.items()}
    return _CIter(cls(**kwargs))


def data_iter_before_first(handle: "_CIter") -> None:
    handle.it.reset()
    handle.batch = None


def data_iter_next(handle: "_CIter") -> int:
    try:
        handle.batch = handle.it.next()
        return 1
    except StopIteration:
        handle.batch = None
        return 0


def _require_batch(handle):
    if handle.batch is None:
        raise MXNetError("no current batch: call MXTPUDataIterNext first")
    return handle.batch


def data_iter_get_data(handle: "_CIter") -> NDArray:
    return _require_batch(handle).data[0]


def data_iter_get_label(handle: "_CIter") -> NDArray:
    return _require_batch(handle).label[0]


def data_iter_get_pad_num(handle: "_CIter") -> int:
    return int(_require_batch(handle).pad or 0)


def data_iter_get_index(handle: "_CIter") -> tuple:
    idx = _require_batch(handle).index
    return tuple(int(i) for i in idx) if idx is not None else ()


# ---- RecordIO surface (ref: MXRecordIOWriterCreate/WriteRecord/Tell,
# MXRecordIOReaderCreate/ReadRecord/Seek, c_api.cc) ----

def recordio_writer_create(path: str):
    from .recordio import MXRecordIO
    return MXRecordIO(path, "w")


def recordio_writer_write(w, data: bytes) -> None:
    w.write(data)


def recordio_writer_tell(w) -> int:
    return int(w.tell())


def recordio_reader_create(path: str):
    from .recordio import MXRecordIO
    return MXRecordIO(path, "r")


def recordio_reader_read(r):
    """(has_record, payload): a zero-length RECORD is (1, b"") — distinct
    from EOF (0, b""), which bare bytes could not express."""
    out = r.read()
    if out is None:
        return (0, b"")
    return (1, bytes(out))


def recordio_reader_seek(r, pos: int) -> None:
    r.seek(pos)


def recordio_reader_tell(r) -> int:
    return int(r.tell())


def recordio_close(h) -> None:
    h.close()


# ---- Symbol attributes / breadth (ref: MXSymbolSetAttr/GetAttr/ListAttr,
# MXSymbolListAuxiliaryStates, MXSymbolInferShape, MXSymbolSaveToFile) ----

def symbol_set_attr(sym, key: str, value: str) -> None:
    if len(sym._heads) != 1:
        raise MXNetError("set_attr needs a single-output symbol")
    sym._heads[0][0].attrs[key] = value


def symbol_get_attr(sym, key: str) -> str:
    v = sym.attr(key)
    if v is None:
        raise MXNetError("symbol has no attribute %r" % key)
    return str(v)


def symbol_list_attr(sym) -> tuple:
    """Flattened (key, value, key, value, ...) like MXSymbolListAttr."""
    flat = []
    for k, v in sorted(sym.list_attr().items()):
        flat += [str(k), str(v)]
    return tuple(flat)


def symbol_list_auxiliary_states(sym) -> tuple:
    return tuple(sym.list_auxiliary_states())


def symbol_save_to_file(sym, path: str) -> None:
    sym.save(path)


def symbol_copy(sym):
    import copy
    return copy.deepcopy(sym)


def symbol_infer_shape(sym, names: tuple, shapes: tuple) -> tuple:
    """Returns (arg_shapes, out_shapes, aux_shapes) each as a flat tuple of
    ('name-free' nested) tuples; unknown shapes come back as ()."""
    hints = {n: tuple(s) for n, s in zip(names, shapes)}
    args, outs, auxs = sym.infer_shape(**hints)
    def _clean(lst):
        return tuple(tuple(s) if s is not None else () for s in (lst or []))
    return _clean(args), _clean(outs), _clean(auxs)


# ---- Executor monitor callback (ref: MXExecutorSetMonitorCallback,
# src/executor/graph_executor.cc:104 monitor path; powers mx.monitor) ----

def executor_set_monitor_callback(ex, pyfun) -> None:
    """pyfun(name: str, ndarray) is invoked for every output each forward
    — the C layer wraps the user's C function pointer in ``pyfun``."""
    ex.set_monitor_callback(pyfun)


# ---- KVStore breadth (ref: MXKVStoreGetRank/GetGroupSize/Barrier) ----

def kvstore_get_rank(kv) -> int:
    return int(kv.rank)


def kvstore_get_group_size(kv) -> int:
    return int(kv.num_workers)


def kvstore_barrier(kv) -> None:
    kv.barrier()


def kvstore_pushpull(kv, keys: tuple, vals: tuple, outs: tuple,
                     priority: int) -> None:
    kv.push(list(keys), list(vals), priority=priority)
    kv.pull(list(keys), list(outs), priority=priority)


# ---- misc breadth ----

def random_seed(seed: int) -> None:
    from . import random as rnd
    rnd.seed(int(seed))


def ndarray_slice(handle: NDArray, begin: int, end: int) -> NDArray:
    return handle[int(begin):int(end)]


def ndarray_reshape(handle: NDArray, shape: tuple) -> NDArray:
    return handle.reshape(tuple(int(s) for s in shape))


def ndarray_sync_copy_from_cpu(handle: NDArray, data: bytes) -> None:
    a = np.frombuffer(data, dtype=np.dtype(str(handle.dtype)))
    handle._set_data(jnp.asarray(a.reshape(handle.shape),
                                 dtype=handle._data.dtype))


def ndarray_context(handle: NDArray) -> str:
    return str(handle.context)


# ---- autograd breadth (ref: MXAutogradIsRecording / IsTraining /
# MarkVariables / MXAutogradBackwardEx, src/c_api/c_api_ndarray.cc) ----

def autograd_is_recording() -> int:
    from . import autograd
    return int(autograd.is_recording())


def autograd_is_training() -> int:
    from . import autograd
    return int(autograd.is_training())


_GRAD_REQ_FLAGS = {0: "null", 1: "write", 2: "add"}


def autograd_mark_variables(variables: tuple, grad_reqs: tuple) -> None:
    for v, r in zip(variables, grad_reqs):
        v.attach_grad(grad_req=_GRAD_REQ_FLAGS.get(int(r), "write"))


def autograd_backward(heads: tuple, ograds: tuple, retain_graph: int) -> None:
    """ograds may be empty (all ones-like seeds) or per-head entries where
    None means a ones-like seed for that head (ref MXAutogradBackwardEx
    NULL-entry semantics)."""
    from . import autograd
    hg = list(ograds) if ograds else None
    if hg is not None and all(g is None for g in hg):
        hg = None
    autograd.backward(list(heads), head_grads=hg,
                      retain_graph=bool(retain_graph))


# ---- CachedOp (ref: MXCreateCachedOpEx / MXInvokeCachedOpEx /
# MXFreeCachedOp, src/c_api/c_api_ndarray.cc; the engine-side analog is
# src/imperative/cached_op.cc — here the cache entry is a jit-compiled
# Executor per input-signature, XLA being the static planner). ----

class _CCachedOp:
    """Inputs are positional in ``symbol.list_inputs()`` order."""

    def __init__(self, sym, flags):
        self.sym = sym
        self.flags = dict(flags)        # static_alloc etc.: jit subsumes
        self.input_names = list(sym.list_inputs())
        self._aux_names = set(sym.list_auxiliary_states())
        self._cache = {}                # (shapes, dtypes) -> Executor

    def invoke(self, inputs):
        from . import autograd
        if len(inputs) != len(self.input_names):
            raise MXNetError(
                "CachedOp expects %d inputs (%s), got %d"
                % (len(self.input_names), ", ".join(self.input_names),
                   len(inputs)))
        feed = dict(zip(self.input_names, inputs))
        is_train = autograd.is_training()
        if autograd.is_recording():
            # eager per-op run: outputs land on the global tape so
            # MXTPUAutogradBackward works (ref MXInvokeCachedOpEx records
            # when Imperative::is_recording, c_api_ndarray.cc). Train-mode
            # BN aux updates write back into the CALLER's arrays (the
            # reference mutates aux in-kernel, batch_norm.cc).
            aux_updates = {} if is_train else None
            outs = tuple(self.sym._execute(feed, is_train=is_train,
                                           collect_aux=aux_updates))
            if aux_updates:
                for n, v in aux_updates.items():
                    feed[n]._set_data(v._data.astype(feed[n]._data.dtype))
            return outs
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in inputs)
        ex = self._cache.get(sig)
        args = {n: v for n, v in feed.items() if n not in self._aux_names}
        aux = {n: v for n, v in feed.items() if n in self._aux_names}
        if ex is None:
            ex = self.sym.bind(None, args, aux_states=aux, grad_req="null")
            self._cache[sig] = ex
        else:
            for n, v in aux.items():  # refresh aux on a cache hit
                ex.aux_dict[n]._set_data(v._data)
        outs = tuple(ex.forward(is_train=is_train, **args))
        if is_train:
            # executor collected BN stat updates into its aux_dict;
            # propagate them to the caller's arrays
            for n, v in aux.items():
                v._set_data(ex.aux_dict[n]._data.astype(v._data.dtype))
        return outs


def cached_op_create(sym, flag_keys: tuple, flag_vals: tuple):
    return _CCachedOp(sym, zip(flag_keys, flag_vals))


def cached_op_invoke(op: _CCachedOp, inputs: tuple) -> tuple:
    return op.invoke(list(inputs))


# ---- NDArray breadth (ref: MXNDArrayCreateNone / At / Detach /
# WaitToRead / WaitToWrite / GetStorageType / SaveRawBytes /
# LoadFromRawBytes / LoadFromBuffer / SyncCopyFromNDArray /
# SyncCheckFormat / CreateSparseEx / GetAux* / GetDataNDArray) ----

def ndarray_create_none() -> NDArray:
    # the reference's deferred-alloc placeholder; here a 0-d f32 zero that
    # SyncCopyFromCPU / op outputs may later replace
    return nd.zeros(())


def ndarray_at(handle: NDArray, idx: int) -> NDArray:
    return handle[int(idx)]


def ndarray_detach(handle: NDArray) -> NDArray:
    return handle.detach()


def ndarray_wait_to_read(handle: NDArray) -> None:
    handle.wait_to_read()


def ndarray_wait_to_write(handle: NDArray) -> None:
    # one PJRT stream: readiness-to-write == readiness-to-read (the
    # reference separates them because its engine queues reads/writes
    # independently, threaded_engine.h:115)
    handle.wait_to_read()


_STYPE_FLAGS = {"default": 0, "row_sparse": 1, "csr": 2}  # ndarray.h:61
_STYPE_NAMES = {v: k for k, v in _STYPE_FLAGS.items()}


def ndarray_storage_type(handle) -> int:
    return _STYPE_FLAGS[getattr(handle, "stype", "default")]


def ndarray_save_raw_bytes(handle) -> bytes:
    """One NDArray as a single V2 record (ref MXNDArraySaveRawBytes —
    the chunk format without the 0x112 list header)."""
    from .ndarray import mxnet_format
    out = []
    if getattr(handle, "stype", "default") == "default":
        mxnet_format._write_dense(out, handle.asnumpy())
    else:
        raise MXNetError("save_raw_bytes: sparse handles unsupported; use "
                         "MXTPUNDArraySave")
    return b"".join(out)


def ndarray_load_from_raw_bytes(data: bytes):
    from .ndarray import mxnet_format
    r = mxnet_format._Reader(data)
    stype, payload = mxnet_format._read_ndarray(r)
    if stype != "default":
        raise MXNetError("load_from_raw_bytes: sparse record; use "
                         "MXTPUNDArrayLoad")
    return nd.array(payload)


def ndarray_load_from_buffer(data: bytes):
    """A whole .params file image from memory (ref MXNDArrayLoadFromBuffer;
    parsed in place — no filesystem round-trip)."""
    import struct
    from .ndarray import mxnet_format
    from .ndarray.utils import _load_mxnet
    if struct.unpack("<Q", data[:8].ljust(8, b"\0"))[0] == \
            mxnet_format.LIST_MAGIC:
        out = _load_mxnet(data)
        if isinstance(out, dict):
            return tuple(out.values()), tuple(out.keys())
        return tuple(out), ()
    # native MXTPU001 images are file-addressed; go through a temp file
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".params", delete=False) as f:
        f.write(data)
        path = f.name
    try:
        return ndarray_load(path)
    finally:
        os.unlink(path)


def ndarray_sync_copy_from_ndarray(dst: NDArray, src: NDArray) -> None:
    if tuple(dst.shape) != tuple(src.shape):
        raise MXNetError("SyncCopyFromNDArray: shape mismatch %s vs %s"
                         % (tuple(dst.shape), tuple(src.shape)))
    dst._set_data(jnp.asarray(src._data, dtype=dst._data.dtype))


def ndarray_sync_check_format(handle, full_check: int) -> None:
    if hasattr(handle, "check_format"):
        handle.check_format(full_check=bool(full_check))


def ndarray_create_sparse(stype_flag: int, data: NDArray,
                          aux: tuple, shape: tuple):
    from .ndarray import sparse as sp
    stype = _STYPE_NAMES.get(int(stype_flag))
    shape = tuple(int(s) for s in shape)
    if stype == "row_sparse":
        (indices,) = aux
        return sp.row_sparse_array((data, indices), shape=shape)
    if stype == "csr":
        indptr, indices = aux
        return sp.csr_matrix((data, indices, indptr), shape=shape)
    raise MXNetError("CreateSparseEx: unsupported stype flag %d" % stype_flag)


def ndarray_get_data_ndarray(handle) -> NDArray:
    if not hasattr(handle, "data"):
        raise MXNetError("GetDataNDArray: dense array has no data blob")
    return handle.data


def ndarray_get_aux_ndarray(handle, i: int) -> NDArray:
    names = (["indices"] if getattr(handle, "stype", None) == "row_sparse"
             else ["indptr", "indices"])
    if not hasattr(handle, "_aux") or i >= len(names):
        raise MXNetError("GetAuxNDArray: no aux %d" % i)
    return getattr(handle, names[i])


def ndarray_get_aux_type(handle, i: int) -> int:
    return ndarray_dtype_flag(ndarray_get_aux_ndarray(handle, i))


# ---- Symbol breadth (ref: MXSymbolCreateAtomicSymbol / CreateGroup /
# GetInternals / GetOutput / GetNumOutputs / GetName / GetChildren /
# InferType / InferShapePartial / ListAtomicSymbolCreators / Print) ----

def symbol_create_atomic(op_name: str, attrs: dict):
    """Uncomposed atomic symbol: compose with no inputs — argument
    variables are auto-created at compose time like the reference's
    nnvm lazy compose (c_api_symbolic.cc MXSymbolCreateAtomicSymbol)."""
    return symbol_invoke(op_name, attrs, "", ())


def symbol_create_group(syms: tuple):
    from .symbol import Group
    return Group(list(syms))


def symbol_get_internals(sym):
    return sym.get_internals()


def symbol_get_output(sym, index: int):
    return sym[int(index)]


def symbol_get_num_outputs(sym) -> int:
    return len(sym.list_outputs())


def symbol_get_name(sym) -> tuple:
    n = sym.name
    return (1, n) if n is not None else (0, "")


def symbol_get_children(sym):
    """Direct-input symbol group (ref MXSymbolGetChildren). Each input's
    (node, output-index) pair is preserved — two distinct outputs of one
    multi-output child are two children."""
    from .symbol.symbol import Symbol
    kids = []
    seen = set()
    for node, _ in sym._heads:
        for cn, idx in getattr(node, "inputs", ()):  # (node, idx) pairs
            key = (id(cn), idx)
            if key not in seen:
                seen.add(key)
                kids.append((cn, idx))
    return Symbol(kids)


def symbol_infer_type(sym, names: tuple, dtype_flags: tuple) -> tuple:
    """Unknowable slots are -1 (jax abstract-eval needs shapes to type
    nodes, symbol.py:_infer — hinted arguments always report their hint,
    so shape-less partial inference still answers for the inputs)."""
    hints = {n: _DTYPE_FLAGS[int(f)] for n, f in zip(names, dtype_flags)}
    args, outs, auxs = sym.infer_type(**hints)
    def _flags(lst):
        return [_FLAGS_BY_NAME.get(str(t), -1) if t is not None else -1
                for t in (lst or [])]
    arg_flags = _flags(args)
    arg_names = sym.list_arguments()
    if len(arg_flags) < len(arg_names):
        arg_flags += [-1] * (len(arg_names) - len(arg_flags))
    for i, n in enumerate(arg_names):
        if arg_flags[i] == -1 and n in hints:
            arg_flags[i] = _FLAGS_BY_NAME[hints[n]]
    return tuple(arg_flags), tuple(_flags(outs)), tuple(_flags(auxs))


def symbol_infer_shape_partial(sym, names: tuple, shapes: tuple) -> tuple:
    """Tolerant inference: unknown shapes come back () instead of raising
    (ref MXSymbolInferShapePartial). The out tuple always has one entry
    per symbol output so C callers can iterate positionally."""
    try:
        return symbol_infer_shape(sym, names, shapes)
    except Exception:
        known = {n: tuple(s) for n, s in zip(names, shapes)}
        args = tuple(known.get(n, ()) for n in sym.list_arguments())
        outs = tuple(() for _ in sym.list_outputs())
        return args, outs, ()


def symbol_list_atomic_creators() -> tuple:
    return list_all_op_names()


def symbol_print(sym) -> str:
    lines = ["Symbol Outputs:"]
    for o in sym.list_outputs():
        lines.append("\toutput[%s]" % o)
    for n in sym.list_arguments():
        lines.append("Variable:%s" % n)
    return "\n".join(lines)


# ---- Executor breadth (ref: MXExecutorSimpleBind / Reshape / Print) ----

def executor_simple_bind(sym, names: tuple, shapes: tuple, grad_req: str):
    from .symbol.executor import Executor
    hints = {n: tuple(int(d) for d in s) for n, s in zip(names, shapes)}
    return Executor.simple_bind(sym, grad_req=grad_req or "write", **hints)


def executor_reshape(ex, names: tuple, shapes: tuple):
    hints = {n: tuple(int(d) for d in s) for n, s in zip(names, shapes)}
    return ex.reshape(**hints)


def executor_print(ex) -> str:
    lines = ["Executor:"]
    for k, v in ex.arg_dict.items():
        lines.append("  arg %s %s %s" % (k, tuple(v.shape), v.dtype))
    for i, o in enumerate(ex.outputs or ()):
        lines.append("  out[%d] %s %s" % (i, tuple(o.shape), o.dtype))
    return "\n".join(lines)


# ---- KVStore breadth (ref: MXKVStoreGetType / SetUpdater /
# SetGradientCompression / PullRowSparse / GetNumDeadNode /
# IsWorkerNode / IsServerNode / IsSchedulerNode) ----

def kvstore_get_type(kv) -> str:
    return str(kv.type)


def kvstore_set_updater(kv, pyfun) -> None:
    """pyfun(key: str, recv: NDArray, local: NDArray) — the C layer wraps
    the user's function pointer; local is updated in place."""
    kv.set_updater(pyfun)


def kvstore_set_gradient_compression(kv, keys: tuple, vals: tuple) -> None:
    kv.set_gradient_compression(
        {k: _parse_attr(v) for k, v in zip(keys, vals)})


def kvstore_pull_row_sparse(kv, keys: tuple, outs: tuple, row_ids: tuple,
                            priority: int) -> None:
    kv.row_sparse_pull(list(keys), out=list(outs), priority=priority,
                       row_ids=list(row_ids))


def kvstore_get_num_dead_node(kv, node_id: int) -> int:
    return int(kv.get_num_dead_node(node_id))


def kvstore_is_worker_node() -> int:
    # symmetric-worker design: every process is a worker (the reference's
    # role env DMLC_ROLE decides; servers were ADR'd out, kvstore.py:272)
    return int(os.environ.get("DMLC_ROLE", "worker") == "worker")


def kvstore_is_server_node() -> int:
    return int(os.environ.get("DMLC_ROLE", "worker") == "server")


def kvstore_is_scheduler_node() -> int:
    return int(os.environ.get("DMLC_ROLE", "worker") == "scheduler")


# ---- profiler (ref: MXSetProfilerConfig / MXSetProfilerState /
# MXDumpProfile / MXProfilePause, src/c_api/c_api_profile.cc) ----

def profiler_set_config(keys: tuple, vals: tuple) -> None:
    from . import profiler
    kw = {}
    for k, v in zip(keys, vals):
        k = {"file_name": "filename", "filename": "filename",
             "profile_all": "profile_all"}.get(k, k)
        kw[k] = _parse_attr(v)
    profiler.set_config(**kw)


def profiler_set_state(state: int) -> None:
    from . import profiler
    if state:
        profiler.start()
    else:
        profiler.stop()


def profiler_dump(finished: int) -> None:
    from . import profiler
    profiler.dump(finished=bool(finished))


# ---- profiler object family (ref: MXProfileCreateDomain / CreateTask /
# CreateFrame / CreateEvent / CreateCounter / DurationStart / DurationStop
# / SetCounter / AdjustCounter / SetMarker / MXAggregateProfileStatsPrint,
# src/c_api/c_api_profile.cc — scoped user timing objects over
# mxtpu/profiler.py ProfileTask/Frame/Event) ----

class _ProfileDomain:
    def __init__(self, name):
        self.name = name


class _ProfileCounter:
    """One aggregate row per counter (its CURRENT value) — per-update
    events would make a 100k-update counter a 100k-row table. Updates are
    lock-guarded: += spans two bytecodes and the GIL may switch between
    them, so concurrent C threads would otherwise lose increments (the
    reference's MXProfileAdjustCounter is atomic for exactly this)."""

    def __init__(self, domain, name):
        import threading
        self.name = ("%s:%s" % (domain.name, name)) if domain else name
        self.value = 0
        self._lock = threading.Lock()
        _LIVE_COUNTERS[self.name] = self

    def set(self, value):
        with self._lock:
            self.value = int(value)

    def adjust(self, delta):
        with self._lock:
            self.value += int(delta)


_LIVE_COUNTERS = {}  # name -> _ProfileCounter (aggregate-stats rows)


def profile_create_domain(name: str):
    return _ProfileDomain(name)


def profile_create_task(domain, name: str):
    from . import profiler
    return profiler.ProfileTask(name, domain=domain)


def profile_create_frame(domain, name: str):
    from . import profiler
    return profiler.ProfileFrame(name, domain=domain)


def profile_create_event(name: str):
    from . import profiler
    return profiler.ProfileEvent(name)


def profile_create_counter(domain, name: str):
    return _ProfileCounter(domain, name)


def profile_duration_start(obj) -> None:
    obj.start()


def profile_duration_stop(obj) -> None:
    obj.stop()


def profile_set_counter(counter, value: int) -> None:
    counter.set(value)


def profile_adjust_counter(counter, delta: int) -> None:
    counter.adjust(delta)


def profile_set_marker(domain, name: str, scope: str) -> None:
    import time as _t
    from . import profiler
    if profiler.is_active():
        nm = ("%s:%s" % (domain.name, name)) if domain else name
        profiler.record_event(nm, "marker:%s" % (scope or "process"),
                              _t.perf_counter_ns() // 1000, 0)


def profile_destroy(obj) -> None:
    """Deregister (ref MXProfileDestroyHandle): a destroyed counter must
    leave the aggregate table — the registry's strong ref would otherwise
    keep every per-phase counter alive and listed forever."""
    name = getattr(obj, "name", None)
    if name is not None and _LIVE_COUNTERS.get(name) is obj:
        del _LIVE_COUNTERS[name]


def profile_aggregate_stats(reset: int) -> str:
    from . import profiler
    table = profiler.dumps(reset=bool(reset))
    if _LIVE_COUNTERS:
        lines = ["", "Counters:"]
        for name in sorted(_LIVE_COUNTERS):
            lines.append("%s=%d" % (name, _LIVE_COUNTERS[name].value))
        table += "\n".join(lines)
    return table


def profiler_pause(paused: int) -> None:
    from . import profiler
    if paused:
        profiler.pause()
    else:
        profiler.resume()


def executor_backward_ex(ex, ograds: tuple) -> None:
    """Backward with explicit head gradients; per-entry None = ones-like
    seed for that output (ref MXExecutorBackwardEx NULL entries)."""
    og = list(ograds) if ograds else None
    if og is not None and any(g is None for g in og):
        outs = ex.outputs or []
        # seed in the HEAD's dtype (ones_like semantics): a float32 seed on
        # a bf16/f16 head would promote every gradient downstream of it
        og = [g if g is not None
              else nd.ones(tuple(outs[i].shape), dtype=outs[i].dtype)
              for i, g in enumerate(og)]
    ex.backward(out_grads=og)


def ndarray_set_grad_state(handle, state: int) -> None:
    """fresh-grad marker (ref MXNDArraySetGradState / NDArray.fresh_grad:
    a frontend bookkeeping bit, stored as-is)."""
    handle._fresh_grad = bool(state)


def ndarray_get_grad_state(handle) -> int:
    return int(getattr(handle, "_fresh_grad", False))


# ---- runtime kernel compilation (ref: MXRtcCudaModuleCreate /
# MXRtcCudaKernelCreate / MXRtcCudaKernelCall, src/c_api/c_api.cc over
# src/common/rtc.cc NVRTC — here mxtpu/rtc.py PallasModule: the source
# string is Python defining Pallas kernel functions) ----

def rtc_module_create(source: str, exports: tuple):
    from .rtc import PallasModule
    return PallasModule(source, exports=list(exports) if exports else None)


def rtc_kernel_create(module, name: str, num_outputs: int):
    return module.get_kernel(name, num_outputs=num_outputs)


def rtc_kernel_call(kernel, inputs: tuple, out_shapes: tuple,
                    out_dtype_flags: tuple):
    dts = [_DTYPE_FLAGS[int(f)] for f in out_dtype_flags]
    outs = kernel.launch(list(inputs),
                         [tuple(int(d) for d in s) for s in out_shapes],
                         out_dtypes=dts)
    return tuple(outs) if isinstance(outs, list) else (outs,)


# ---- misc breadth (ref: MXGetGPUCount / MXGetGPUMemoryInformation64 /
# MXNotifyShutdown / MXEngineSetBulkSize / MXSetNumOMPThreads /
# MXRandomSeedContext / MXDataIterGetIterInfo) ----

def get_device_count() -> int:
    import jax
    return len(jax.devices())


def get_memory_information(dev_id: int) -> tuple:
    """(free, total) bytes for the device (ref MXGetGPUMemoryInformation64;
    here PJRT memory stats — absent stats raise, they don't guess).
    Reads through ``xprof.device_memory`` — the ONE normalizer the
    python-API ``util.get_gpu_memory`` and the ``memory.hbm_*`` gauges
    also use, so the C ABI can never disagree with them."""
    import jax
    devs = jax.devices()
    if dev_id >= len(devs):
        raise MXNetError("no device %d (have %d)" % (dev_id, len(devs)))
    from . import xprof
    m = xprof.device_memory(devs[dev_id])
    if not m["bytes_limit"]:
        raise MXNetError("device %d exposes no memory stats" % dev_id)
    return m["bytes_free"], m["bytes_limit"]


def notify_shutdown() -> None:
    # the reference tears its engine down (MXNotifyShutdown); PJRT clients
    # shut down at process exit — flush pending work so exit is clean
    ndarray_wait_all()


def engine_set_bulk_size(size: int) -> int:
    from . import engine
    prev = engine.set_bulk_size(int(size))
    return int(prev)


def set_num_omp_threads(n: int) -> None:
    # XLA:CPU fixes its thread pool at backend init; honor the call as the
    # documented no-op the engine module explains (engine.py bulk ADR)
    return None


def random_seed_context(seed: int, dev_type: int, dev_id: int) -> None:
    # one functional PRNG stream regardless of device (random.py design)
    random_seed(seed)


def ndarray_to_dlpack(handle):
    """NDArray -> "dltensor" capsule (the C layer unwraps the pointer)."""
    from .ndarray.dlpack import to_dlpack_for_read
    return to_dlpack_for_read(handle)


def ndarray_from_dlpack(capsule):
    from .ndarray.dlpack import from_dlpack
    return from_dlpack(capsule)


# ---- shared-memory NDArrays (ref: MXNDArrayCreateFromSharedMem /
# MXNDArrayGetSharedMemHandle, src/c_api/c_api.cc:1375 — the reference
# addresses segments by (pid, fd); POSIX shared memory is NAME-addressed,
# so this ABI exchanges segment names instead. The gluon multiprocess
# DataLoader workers use the same mechanism, gluon/data/_mp_worker.py.)

def ndarray_get_shared_mem_handle(handle) -> str:
    """Copy the array into a fresh POSIX shared-memory segment and return
    its name. Ownership transfers to the receiving process: the creating
    tracker is unregistered, and CreateFromSharedMem unlinks."""
    from multiprocessing import shared_memory
    a = np.ascontiguousarray(handle.asnumpy())
    seg = shared_memory.SharedMemory(create=True, size=max(1, a.nbytes))
    # direct memoryview copy — no tobytes() temporary (matters at GB sizes)
    seg.buf[:a.nbytes] = memoryview(a).cast("B")
    try:  # receiver owns the segment now (mirrors _mp_worker.to_shm)
        from multiprocessing import resource_tracker
        resource_tracker.unregister(seg._name, "shared_memory")
    except Exception:
        pass
    name = seg.name
    seg.close()
    return name


def ndarray_create_from_shared_mem(name: str, dtype_flag: int,
                                   shape: tuple):
    """Attach, copy to a device array, and unlink (one-shot transfer)."""
    from multiprocessing import shared_memory
    dt = _DTYPE_FLAGS.get(int(dtype_flag))
    if dt is None:
        raise MXNetError("unknown mshadow dtype flag %d" % dtype_flag)
    seg = shared_memory.SharedMemory(name=name)
    try:
        n = int(np.prod(shape)) if shape else 1
        a = np.frombuffer(seg.buf, dtype=np.dtype(dt),
                          count=n).reshape(shape).copy()
    finally:
        seg.close()
        try:
            seg.unlink()
        except FileNotFoundError:
            pass
    return nd.array(a, dtype=dt)


def data_iter_get_iter_info(name: str) -> tuple:
    cls = _data_iter_registry().get(name)
    if cls is None:
        raise MXNetError("unknown data iter %r" % name)
    doc = (cls.__doc__ or "").strip().split("\n")[0]
    return name, doc
