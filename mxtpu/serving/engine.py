"""AOT-bucketed inference engine: BucketSpec + Predictor.

The reference ships a dedicated inference surface — the C predict API
(src/c_api/c_predict_api.cc: create from (symbol-json, params-blob),
set-input, forward, get-output) — built so a deployed model never touches
the training machinery. On a jit-compiled TPU stack the deployment problem
is different: execution is already compiled, but every NEW request shape
means a fresh XLA trace, and a serving box that compiles in the hot path
is down for seconds at a time. The TPU-native answer (TVM's
compile-for-deployment flow, arXiv:1802.04799; PyGraph's capture-once /
replay-forever discipline for CUDA Graphs, arXiv:2503.19779):

* a :class:`BucketSpec` declares the closed set of (batch x seq/spatial)
  shapes the service will ever execute,
* :class:`Predictor` ahead-of-time compiles ONE donated inference jit per
  bucket at startup (``warmup()``), pads each request up to its bucket,
  and slices outputs back — a device-side slice, so the only
  device->host transfer is the caller's explicit output fetch,
* every compile is reported to the PR-4 retrace watchdog at site
  ``serving.predict``; after warmup the compile count at that site is
  <= #buckets by construction, and a mid-traffic compile (off-template
  request shape, policy env flipped under the server) is attributable
  from ``telemetry.report()`` alone.

Three load paths, mirroring the reference's predict-API inputs:

* ``Predictor(block, spec)`` — a gluon ``HybridBlock`` (its compiled
  forward is rebuilt per bucket from the same ``_run_traced`` machinery
  ``CachedOp`` uses, gluon/block.py:375);
* ``Predictor.from_checkpoint(prefix, epoch, spec)`` — symbol-json +
  params checkpoint via ``SymbolBlock`` (the c_predict_api shape);
* ``Predictor.from_trainer_checkpoint(block, directory, spec)`` — the
  params subtree of a ``contrib.async_checkpoint.save_trainer`` orbax
  checkpoint (a training run promotes straight to serving, no format
  hop).

The bf16/policy levers ride along: ``ops.registry.policy_key`` is part of
every bucket's jit cache key, so ``net.cast('bfloat16')`` + policy envs
serve exactly like they train.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import telemetry
from ..base import MXNetError
from ..ndarray import NDArray

__all__ = ["BucketSpec", "Predictor", "pad_nd", "serve_int8_default"]

# Serializes the FIRST invocation of a freshly-built jit (the trace):
# tracing runs the block body, which temporarily binds tracers into the
# SHARED Parameter objects — two replicas' Predictors compiling at once
# (mxtpu/serving/replicas.py spawns one dispatch worker per replica)
# would race on that binding. Warm-path calls never take this lock.
# Since the compile service this IS the service's central trace lock
# (one process-wide python-trace discipline; replicas' identical
# lowerings additionally dedup through the service's group path so N
# replicas trace once, not N times serialized).
from .. import compile_service as _csvc

_TRACE_LOCK = _csvc.trace_lock()


def _dequant_params(qdtypes, param_datas, param_ranges):
    """In-trace reconstruction of compute-dtype parameter buffers from
    the (possibly int8) stored form. Module-level ON PURPOSE: the
    compile service shares ONE build closure across a ReplicaSet's
    identical lowerings, and a closure over a predictor instance would
    pin that replica's device buffers past its retirement. The range is
    a traced argument: ``refresh_params()`` never recompiles."""
    if not any(q is not None for q in qdtypes):
        return list(param_datas)
    from ..ops.registry import get_op
    deq = get_op("dequantize").fn  # raw jnp-level op
    return [d if qdt is None else deq(d, -r, r).astype(qdt)
            for d, r, qdt in zip(param_datas, param_ranges, qdtypes)]


def serve_int8_default():
    """The int8 inference lever (``MXTPU_SERVE_INT8``, default 0): 1 =
    serving stores weights (and decode KV caches) as symmetric int8 +
    per-tensor scale, dequantized in-executable through
    ``ops.quantization.dequantize`` — roughly half the resident bytes per
    replica, so the KV accountant admits ~2x the sequences. Read at
    Predictor/DecodeEngine CONSTRUCTION (host-side, not ``policy_key``):
    the flag is baked per instance, so a mid-run env flip can never alias
    an executable — it only affects predictors built after it."""
    import os
    # == "1" like every other boolean lever (MXTPU_RING_FLASH, ...):
    # "false"/"off" must not silently enable quantization
    return os.environ.get("MXTPU_SERVE_INT8", "0") == "1"


def pad_nd(arr, batch, seq_len=None, seq_axis=1, pad_value=0):
    """Pad ``arr`` (NDArray / jax / numpy) with ``pad_value`` rows up to
    ``batch`` along axis 0 — and, when ``seq_len`` is given and the array
    has a ``seq_axis`` dimension, up to ``seq_len`` along that axis too.
    Device-side (``jnp.pad``): no host sync, so it is safe inside the
    zero-d2h predict span. Returns an NDArray."""
    d = arr._data if isinstance(arr, NDArray) else jnp.asarray(arr)
    pads = [(0, 0)] * d.ndim
    if d.shape[0] > batch:
        raise MXNetError("pad_nd: batch %d exceeds bucket %d"
                         % (d.shape[0], batch))
    pads[0] = (0, batch - d.shape[0])
    if seq_len is not None and d.ndim > seq_axis:
        if d.shape[seq_axis] > seq_len:
            raise MXNetError("pad_nd: axis %d size %d exceeds bucket %d"
                             % (seq_axis, d.shape[seq_axis], seq_len))
        pads[seq_axis] = (0, seq_len - d.shape[seq_axis])
    if not any(p[1] for p in pads):
        return arr if isinstance(arr, NDArray) else NDArray(d)
    return NDArray(jnp.pad(d, pads, constant_values=pad_value))


def _as_nds(args):
    return [a if isinstance(a, NDArray) else NDArray(jnp.asarray(a))
            for a in args]


def _eager_forward(block, nds):
    """One eager forward with taping off — settles deferred parameter
    shapes (shared by Predictor._settle and the pre-restore settle in
    from_trainer_checkpoint)."""
    from .. import autograd
    with autograd.pause():
        block(*nds)


class BucketSpec:
    """The closed set of compiled shapes a Predictor serves.

    ``batch_sizes`` are the batch buckets (ascending); a request of n
    items executes at the smallest bucket >= n (requests larger than the
    max bucket are chunked). ``seq_lens`` optionally adds a second bucket
    axis for variable-length inputs (sequence length / spatial dim along
    ``seq_axis`` of every input that has it); a request whose seq exceeds
    the max seq bucket is refused — sequences, unlike batches, cannot be
    chunked without changing the model's semantics.

    ``decode_slots`` is the third spelling (mutually exclusive with both
    of the above): the CAPACITY buckets of a continuous-batching decode
    cohort (:class:`~mxtpu.serving.decode.DecodeEngine`). A decode slot
    carries KV-cache state across steps, so there is no seq axis to
    bucket (the cache length is fixed at the engine's ``max_len``) and
    no request batch to pad — the buckets say how many LIVE slots a step
    executable covers. A decode spec cannot be served by a
    :class:`Predictor` (and vice versa); both misuses refuse loudly.

    Guidance (docs/serving.md): powers of two up to the throughput knee
    of the model (``tools/serve_bench.py --mode sweep`` finds it);
    #buckets is also the startup compile count and the per-model
    executable-cache footprint, so keep it small (4-8 is typical).
    """

    def __init__(self, batch_sizes=None, seq_lens=None, seq_axis=1,
                 pad_value=0, decode_slots=None):
        if decode_slots is not None:
            # the decode-cohort spelling: capacity buckets ONLY — mixing
            # in prefill-shape axes is a category error and must be as
            # loud as the seq-refusal path (ISSUE 11 satellite)
            if batch_sizes is not None:
                raise MXNetError(
                    "BucketSpec: decode_slots=%r cannot combine with "
                    "batch_sizes=%r — a decode cohort's buckets ARE its "
                    "slot capacities; prefill batch buckets belong to the "
                    "separate prefill BucketSpec (docs/serving.md)"
                    % (decode_slots, batch_sizes))
            if seq_lens is not None:
                raise MXNetError(
                    "BucketSpec: decode_slots=%r cannot combine with "
                    "seq_lens=%r — decode slots carry KV caches of the "
                    "engine's fixed max_len; there is no seq axis to "
                    "bucket (docs/serving.md)" % (decode_slots, seq_lens))
            batch_sizes = decode_slots
        elif batch_sizes is None:
            raise MXNetError(
                "BucketSpec: pass batch_sizes (a served shape set) or "
                "decode_slots (a decode-cohort capacity set)")
        sizes = sorted({int(b) for b in batch_sizes})
        if not sizes or sizes[0] < 1:
            raise MXNetError("BucketSpec: %s must be >= 1, got %r"
                             % ("decode_slots" if decode_slots is not None
                                else "batch_sizes", batch_sizes))
        self.batch_sizes = tuple(sizes)
        self.decode_slots = self.batch_sizes if decode_slots is not None \
            else None
        self.seq_lens = tuple(sorted({int(s) for s in seq_lens})) \
            if seq_lens else None
        self.seq_axis = int(seq_axis)
        self.pad_value = pad_value

    @classmethod
    def pow2(cls, max_batch=None, seq_lens=None, seq_axis=1,
             decode_slots=None):
        """1, 2, 4, ... up to (and including) ``max_batch`` — or, with
        ``decode_slots=n`` instead, the same ladder as decode-cohort
        capacity buckets (``BucketSpec(decode_slots=[1, 2, ..., n])``)."""
        if (max_batch is None) == (decode_slots is None):
            raise MXNetError(
                "BucketSpec.pow2: pass exactly one of max_batch (a "
                "request-batch ladder) or decode_slots (a decode-cohort "
                "capacity ladder), got max_batch=%r decode_slots=%r"
                % (max_batch, decode_slots))
        if decode_slots is not None and seq_lens is not None:
            # same category error the constructor refuses — silently
            # dropping the seq buckets would surface much later as a
            # confusing spec-misuse refusal
            raise MXNetError(
                "BucketSpec.pow2: decode_slots=%r cannot combine with "
                "seq_lens=%r — decode slots carry KV caches of the "
                "engine's fixed max_len (docs/serving.md)"
                % (decode_slots, seq_lens))
        top = int(max_batch if max_batch is not None else decode_slots)
        sizes, b = [], 1
        while b < top:
            sizes.append(b)
            b *= 2
        sizes.append(top)
        if decode_slots is not None:
            return cls(decode_slots=sizes)
        return cls(sizes, seq_lens=seq_lens, seq_axis=seq_axis)

    @property
    def is_decode(self):
        """True for a decode-cohort spec (``decode_slots=`` spelling)."""
        return self.decode_slots is not None

    @property
    def max_slots(self):
        """Largest cohort capacity (decode specs only)."""
        if not self.is_decode:
            raise MXNetError("BucketSpec.max_slots on a non-decode spec "
                             "(declare it with decode_slots=)")
        return self.batch_sizes[-1]

    def slot_bucket(self, n_live):
        """Smallest capacity bucket >= n_live slots (decode specs only;
        None when n_live exceeds the max capacity — the caller queues)."""
        if not self.is_decode:
            raise MXNetError("BucketSpec.slot_bucket on a non-decode spec "
                             "(declare it with decode_slots=)")
        return self.batch_bucket(n_live)

    @property
    def max_batch(self):
        return self.batch_sizes[-1]

    def batch_bucket(self, n):
        """Smallest batch bucket >= n (None when n exceeds the max — the
        caller chunks)."""
        for b in self.batch_sizes:
            if n <= b:
                return b
        return None

    def seq_bucket(self, s):
        """Smallest seq bucket >= s; raises when s exceeds the max."""
        if self.seq_lens is None:
            return None
        for L in self.seq_lens:
            if s <= L:
                return L
        raise MXNetError(
            "request seq length %d exceeds the largest declared bucket %d "
            "(BucketSpec.seq_lens=%s) — sequences cannot be chunked"
            % (s, self.seq_lens[-1], list(self.seq_lens)))

    def buckets(self):
        """Every (batch, seq-or-None) combo — the startup compile set."""
        seqs = self.seq_lens or (None,)
        return [(b, s) for b in self.batch_sizes for s in seqs]

    def __len__(self):
        return len(self.batch_sizes) * len(self.seq_lens or (None,))

    def __repr__(self):
        if self.is_decode:
            return "BucketSpec(decode_slots=%s)" % (list(self.decode_slots),)
        return "BucketSpec(batch=%s%s)" % (
            list(self.batch_sizes),
            ", seq=%s@axis%d" % (list(self.seq_lens), self.seq_axis)
            if self.seq_lens else "")


class Predictor:
    """AOT-bucketed compiled inference over a gluon block.

    One donated ``jax.jit`` per (bucket-shapes, ``policy_key``) — the
    input buffers are freshly materialized padded arrays, so donating
    them back to XLA is free memory headroom; parameters stay
    un-donated and are reused across every call. ``warmup()`` compiles
    the whole :class:`BucketSpec` up front (call it before taking
    traffic; the :class:`~mxtpu.serving.batcher.MicroBatcher` refuses to
    start on a cold predictor unless told otherwise).

    ``predict()`` is thread-compatible after warmup: the jit cache is
    only written on a miss (warmup fills it), and compiled executables
    are safe to invoke concurrently.

    ``device=`` pins the whole predictor — parameters are ``device_put``
    there and every request buffer follows — so a
    :class:`~mxtpu.serving.replicas.ReplicaSet` can run one independent
    replica per device. ``site=`` names the retrace-watchdog site its
    compiles report to (per-replica sites ``serving.predict.r<i>`` keep
    each replica's post-warmup compile count pinned at #buckets; the
    graftlint inventory declares this cache via
    ``tools/graftlint/config.py:JIT_ALLOWLIST``).
    """

    def __init__(self, block, spec, example=None, warmup=False,
                 name="predictor", device=None, site="serving.predict",
                 int8=None, co_resident=None):
        if not hasattr(block, "_forward_eager"):
            raise MXNetError(
                "Predictor serves HybridBlock-family models (got %s); wrap "
                "symbols via Predictor.from_checkpoint" % type(block).__name__)
        if getattr(spec, "is_decode", False):
            raise MXNetError(
                "Predictor cannot serve a decode-cohort BucketSpec "
                "(decode_slots=%s): slot-capacity buckets describe a "
                "continuous-batching DecodeEngine cohort, not request "
                "shapes — declare batch_sizes/seq_lens for a Predictor "
                "(docs/serving.md)" % (list(spec.decode_slots),))
        self._block = block
        self._spec = spec
        self._name = name
        self._device = device
        self._site = site
        self._int8 = serve_int8_default() if int8 is None else bool(int8)
        # zero-arg callable returning bytes ALREADY resident on this
        # device beyond this predictor's own footprint (the zoo passes
        # its co-resident models' ledger totals) — the warmup preflight
        # judges will-it-fit against limit minus this, so overcommit
        # warns BEFORE a page-in OOMs, not after
        self._co_resident = co_resident
        self.param_version = None  # zoo version audit (refresh_params)
        self._params = None        # ordered list, fixed at first build
        self._param_datas = None
        self._param_ranges = None  # per-param int8 range r (None = not quant)
        self._param_qdtypes = None  # per-param original dtype (None = not q)
        self._templates = None     # [(trailing_shape, dtype)] per input
        self._jits = {}            # (padded shapes+dtypes, policy) -> (fn, cell)
        if example is not None:
            self._settle(example if isinstance(example, (tuple, list))
                         else (example,))
        if warmup:
            self.warmup()

    # ------------------------------------------------------------ templates
    def _settle(self, args):
        """Record each input's trailing shape + dtype (the per-bucket zero
        templates warmup compiles against) and fix the parameter list —
        running one eager forward first only if deferred shapes are still
        unsettled."""
        nds = _as_nds(args)
        params = list(self._block.collect_params().values())
        if not params or any(p._data is None for p in params):
            _eager_forward(self._block, nds)
            params = list(self._block.collect_params().values())
        if any(p._data is None for p in params):
            raise MXNetError("Predictor: parameters still uninitialized "
                             "after the example forward")
        self._params = params
        self._snapshot_params()
        self._templates = [(tuple(a._data.shape[1:]), a._data.dtype)
                           for a in nds]

    def param_args(self):
        """The (param_datas, param_ranges) pair every compiled bucket
        takes as its TRACED trailing arguments. Public seam for engines
        that compose extra executables over this predictor's parameters
        (the decode engine's paged prefix-extend and draft/verify jits
        dispatch with exactly these, so ``refresh_params()`` reaches
        them without a recompile): always pass the CURRENT pair at
        dispatch time, never capture the buffers in a closure."""
        return self._param_datas, self._param_ranges

    def _snapshot_params(self):
        """Capture the parameter buffers the jits will run against —
        int8-quantized when the lever is on (shared by _settle and
        refresh_params, so a reload requantizes too)."""
        datas, ranges, qdts = self._quantize_params(
            [p.data()._data for p in self._params],
            sticky=self._param_qdtypes)
        self._param_datas = self._place(datas)
        self._param_ranges = self._place(ranges)
        self._param_qdtypes = qdts

    def _quantize_params(self, datas, sticky=None):
        """``MXTPU_SERVE_INT8`` weight storage: eligible parameter buffers
        (floating, ndim >= 2 — the weight matrices/kernels that dominate
        resident bytes; 1-d biases and BN stats stay exact) become
        symmetric int8 + a per-tensor range via
        ``ops.quantization.quantize``, and the compiled forward
        dequantizes them in-executable with the range as a TRACED argument
        — so ``refresh_params()`` after an in-place weight reload
        requantizes without recompiling a single bucket. ~1/2 the resident
        weight bytes vs bf16 (1/4 vs f32).

        ``sticky`` (the previous per-param dtype list) pins each
        parameter's eligibility after the FIRST snapshot: the
        quantized-vs-exact split is part of every compiled bucket's
        argument STRUCTURE, so a reload that turns a weight degenerate
        (all-zero) must keep its int8 slot (on a unit grid — zeros
        quantize to zeros exactly) rather than silently re-trace every
        executable behind the retrace watchdog's back."""
        n = len(datas)
        if not self._int8:
            return datas, [None] * n, [None] * n
        from ..ops.registry import get_op
        quantize = get_op("quantize").fn  # raw jnp-level op
        out, ranges, qdts = [], [], []
        for i, d in enumerate(datas):
            if sticky is not None:
                eligible = sticky[i] is not None
            else:
                eligible = d.ndim >= 2 and \
                    jnp.issubdtype(d.dtype, jnp.floating)
            r = float(jnp.max(jnp.abs(d))) if eligible else 0.0
            if eligible and not (0.0 < r < float("inf")):
                if sticky is None:
                    # first snapshot: a degenerate tensor simply keeps
                    # exact storage (no grid to land on)
                    eligible = False
                else:
                    r = 1.0  # sticky slot: unit grid, zeros stay exact
            if not eligible:
                out.append(d)
                ranges.append(None)
                qdts.append(None)
                continue
            q, _lo, _hi = quantize(d, -r, r)
            out.append(q)
            ranges.append(jnp.asarray(r, jnp.float32))
            qdts.append(str(d.dtype))
        return out, ranges, qdts

    def _place(self, datas):
        """Commit buffers to this predictor's device (identity when no
        device was pinned — the single-predictor PR-5 path). None entries
        (un-quantized slots of the int8 range list) pass through."""
        if self._device is None:
            return datas
        return [d if d is None else jax.device_put(d, self._device)
                for d in datas]

    @property
    def spec(self):
        return self._spec

    @property
    def device(self):
        return self._device

    @property
    def site(self):
        """The retrace-watchdog site this predictor's compiles report to."""
        return self._site

    @property
    def input_templates(self):
        """[(trailing_shape, dtype)] per input (None before settle)."""
        return self._templates

    @property
    def int8(self):
        """True when this predictor stores weights as int8 + scale."""
        return self._int8

    def refresh_params(self, version=None):
        """Re-snapshot parameter buffers (after an in-place reload) without
        recompiling — the jits close over nothing, params (and their int8
        ranges) are arguments. ``version=`` stamps the live param version
        for audit (``zoo.active_version{model}`` is gauged by the zoo;
        here the refresh itself is counted per site so a param swap is
        attributable from ``telemetry.report()`` alone)."""
        self._snapshot_params()
        if version is not None:
            self.param_version = version
        telemetry.inc("serving.param_refreshes", tag=self._site)

    # ------------------------------------------------------------ compiling
    def _donation(self):
        # donate the request buffers (fresh padded arrays) back to XLA —
        # free memory headroom per in-flight bucket. The CPU backend does
        # not implement donation and would warn per compile, so gate it.
        return (0,) if jax.default_backend() != "cpu" else ()

    def _fn_token(self):
        """Stable block identity for the compile service: class + forward
        source hash + parameter structure incl. the int8 split (an
        edited model or a re-quantized storage layout across restarts
        must miss the disk cache, never replay)."""
        tok = getattr(self, "_fn_token_cache", None)
        if tok is None:
            from .. import compile_service as csvc
            struct = tuple(
                (p.name, tuple(d.shape), str(d.dtype), qdt)
                for p, d, qdt in zip(self._params, self._param_datas,
                                     self._param_qdtypes))
            tok = "predictor:%s:%s:%s" % (
                type(self._block).__name__,
                csvc.source_token(type(self._block)),
                csvc.source_token(struct)[:12])
            self._fn_token_cache = tok
        return tok

    def _service_key(self, shape_key, pol):
        from .. import compile_service as csvc
        return csvc.canonical_key(
            site=self._site, fn_id=self._fn_token(),
            signature=(shape_key, self._int8), policy=pol,
            donation=self._donation(),
            device=csvc.device_token(device=self._device),
            nonce=csvc.instance_nonce(self))

    def _group_token(self, shape_key, pol):
        """Lowering-group token: everything in the service key EXCEPT
        site/device/nonce — a ReplicaSet's members differ only there, so
        their buckets share one traced artifact and compile per
        device."""
        return ("predict", self._fn_token(), shape_key, self._int8, pol,
                self._donation())

    def _prov(self, shape_key, pol):
        return {"predictor": self._name,
                "block": type(self._block).__name__,
                "device": str(self._device) if self._device is not None
                else None,
                "shapes": [list(s) for s, _ in shape_key],
                "int8": self._int8,
                "policy_key": list(pol)}

    def _build_for(self, shape_key):
        """Build closure for one bucket signature. Closes over the
        SHARED block/params/qdtypes only — never over this predictor
        instance — so the compile service can reuse it across a
        ReplicaSet's identical lowerings without pinning any one
        replica's device buffers."""
        block, params = self._block, self._params
        qdtypes = tuple(self._param_qdtypes or ())
        fixed_key = jax.random.PRNGKey(0)  # deterministic inference: no
        # stochastic layers are live under train=False
        donate = self._donation()

        def build():
            cell = {}

            def pure(in_datas, param_datas, param_ranges):
                from ..gluon.block import _flatten_nd, _run_traced

                param_datas = _dequant_params(qdtypes, param_datas,
                                              param_ranges)

                def body():
                    return block(*[NDArray(d) for d in in_datas])

                out, _aux = _run_traced(params, param_datas, fixed_key,
                                        False, body)
                fmt = []
                flat = _flatten_nd(out, fmt)
                cell["out_fmt"] = fmt
                return [o._data for o in flat]

            return jax.jit(pure, donate_argnums=donate), cell

        return build

    def _get_jit(self, shape_key, example_datas=None):
        from .. import compile_service as csvc
        from ..ops.registry import policy_key
        pol = policy_key()
        key = (shape_key, pol)
        hit = self._jits.get(key)
        if hit is not None:
            return hit
        # retrace watchdog: every serving compile is a served-request stall
        # — after warmup this site MUST stay at #buckets (an off-template
        # request shape or a policy env flip under the server shows up
        # here with full provenance). The site name is per-instance so a
        # ReplicaSet member reports at serving.predict.r<i>; the static
        # lint declares this cache via JIT_ALLOWLIST (docs/serving.md).
        example = None
        if example_datas is not None:
            example = csvc.concrete_args(
                (list(example_datas), self._param_datas,
                 self._param_ranges))
        entry = csvc.get_or_build(
            self._service_key(shape_key, pol), self._build_for(shape_key),
            provenance=self._prov(shape_key, pol), example_args=example,
            group=self._group_token(shape_key, pol))
        self._jits[key] = (entry.fn, entry.meta)
        return self._jits[key]

    def _bucket_datas(self, b, s):
        datas = [jnp.zeros((b,) + self._bucket_trailing(t, s), dt)
                 for t, dt in self._templates]
        return self._place(datas)

    def warmup_entries(self):
        """The declared AOT warmup set: one compile-service entry per
        bucket, group-tagged so identical replicas share the trace. A
        ReplicaSet collects every member's entries into ONE concurrent
        ``compile_service.warmup`` call."""
        if self._templates is None:
            raise MXNetError("Predictor.warmup needs input templates: pass "
                             "example= at construction")
        from .. import compile_service as csvc
        from ..ops.registry import policy_key
        pol = policy_key()
        entries = []
        for b, s in self._spec.buckets():
            datas = self._bucket_datas(b, s)
            shape_key = tuple((tuple(d.shape), str(d.dtype))
                              for d in datas)
            entries.append(csvc.WarmupEntry(
                key=self._service_key(shape_key, pol),
                build=self._build_for(shape_key),
                example_args=(datas, self._param_datas,
                              self._param_ranges),
                provenance=self._prov(shape_key, pol),
                group=self._group_token(shape_key, pol)))
        return entries

    def finish_warmup(self):
        """Adopt warmed entries into the instance cache by DISPATCHING
        each bucket once (zero-filled templates, blocking) — the
        executables are already compiled (service hits), so these are
        pure replays, but a model that compiles yet cannot EXECUTE on
        this device (HBM exhausted by workspace allocation) must fail
        here, at startup, not on the first live request. Closes with
        the gauges and the memory pre-flight."""
        for b, s in self._spec.buckets():
            flat, _ = self._run_padded(self._bucket_datas(b, s))
            jax.block_until_ready([o._data for o in flat])
        telemetry.gauge("serving.buckets", len(self._spec))
        # will-it-fit pre-flight over the freshly-warmed bucket
        # executables (no-op on limit-less backends — zero extra
        # lowering on the CPU tier) + the live HBM gauges
        from .. import xprof
        xprof.ensure_memwatch()
        extra = int(self._co_resident()) if self._co_resident else 0
        xprof.preflight(self._site,
                        device=self._device if self._device is not None
                        else 0, extra_bytes=extra)
        return self

    def warmup(self):
        """AOT-compile every bucket in the spec through the compile
        service — concurrent lowers/compiles on the service pool, disk
        hits cost zero compiles. Returns self. Idempotent: warm buckets
        are cache hits."""
        from .. import compile_service as csvc
        csvc.warmup(self.warmup_entries())
        return self.finish_warmup()

    def _bucket_trailing(self, trailing, seq):
        if seq is None:
            return trailing
        ax = self._spec.seq_axis - 1  # trailing shape drops the batch dim
        if ax < len(trailing):
            t = list(trailing)
            t[ax] = seq
            return tuple(t)
        return trailing

    # ------------------------------------------------------------ predicting
    def _run_padded(self, datas):
        """Dispatch already-bucket-shaped jax arrays; returns (flat output
        NDArrays at bucket batch, cell)."""
        shape_key = tuple((tuple(d.shape), str(d.dtype)) for d in datas)
        jitted, cell = self._get_jit(shape_key, example_datas=datas)
        from .. import resilience, xprof
        try:
            resilience.maybe_oom()
            if "out_fmt" not in cell:
                # first invocation of this executable traces the shared
                # block (see _TRACE_LOCK): serialize across replicas'
                # predictors
                with _TRACE_LOCK:
                    out = jitted(list(datas), self._param_datas,
                                 self._param_ranges)
            else:
                out = jitted(list(datas), self._param_datas,
                             self._param_ranges)
        except Exception as e:
            if xprof.is_oom(e):
                # HBM OOM on the predict dispatch: artifact (ledger +
                # per-device memory stats) first, then fail LOUD — the
                # batcher's dispatch error path completes the cohort's
                # futures with this error, never hangs them
                ctx = telemetry.current_trace()
                xprof.oom_flight(self._site, e,
                                 trace_ids=[ctx.trace_id] if ctx else [])
            raise
        return [NDArray(d) for d in out], cell

    def predict_flat(self, args):
        """Pad ``args`` (a tuple of per-input arrays sharing batch axis 0)
        to their bucket, run the compiled forward, and slice back: returns
        ``(flat_outputs, out_fmt, bucket_batch)`` where flat_outputs are
        device NDArrays sliced to the request's n — NO host sync happens
        here; fetching the outputs is the caller's declared d2h.

        Requests larger than the max bucket are chunked through it and
        re-concatenated on device."""
        if self._templates is None:
            self._settle(args)
        spec = self._spec
        # the jit DONATES its input buffers; a caller's live buffer reaching
        # it un-padded (exact bucket fit) would be invalidated under the
        # caller — protect every buffer the caller still holds a reference
        # to (NDArray._data, and raw jax arrays where asarray is identity;
        # numpy inputs become fresh device buffers and need no copy)
        datas, user_bufs = [], set()
        for a in args:
            d = a._data if isinstance(a, NDArray) else jnp.asarray(a)
            protect = isinstance(a, NDArray) or d is a
            if self._device is not None:
                # pinned predictor (ReplicaSet member): commit the request
                # buffers to the replica's device. device_put MAY alias
                # the input buffer (uncommitted array already resident on
                # this device), so protection is never dropped here — the
                # worst case is one extra jnp.copy on an exact-bucket-fit
                # caller buffer, never a donated-out-from-under caller
                d = jax.device_put(d, self._device)
            if protect:
                user_bufs.add(id(d))
            datas.append(d)
        n = int(datas[0].shape[0])
        if n == 0:
            raise MXNetError("predict on an empty batch")
        seq = None
        if spec.seq_lens is not None:
            seq = spec.seq_bucket(int(datas[0].shape[spec.seq_axis])
                                  if datas[0].ndim > spec.seq_axis else 0)
        with telemetry.span("serving.predict", d2h=True):
            b = spec.batch_bucket(n)
            if b is None:
                # chunk through the max bucket, concat on device
                chunks, fmt, bucket = [], None, spec.max_batch
                for lo in range(0, n, bucket):
                    part = [d[lo:lo + bucket] for d in datas]
                    flat, fmt, _ = self._dispatch_one(part, seq, bucket,
                                                      user_bufs)
                    chunks.append(flat)
                flat_out = [NDArray(jnp.concatenate(
                    [c[i]._data for c in chunks], axis=0))
                    for i in range(len(chunks[0]))]
                telemetry.inc("serving.items", n)
                return flat_out, fmt, bucket
            flat, fmt, _ = self._dispatch_one(datas, seq, b, user_bufs)
            telemetry.inc("serving.items", n)
            return flat, fmt, b

    def _dispatch_one(self, datas, seq, bucket, protect=()):
        n = int(datas[0].shape[0])
        padded = [pad_nd(d, bucket, seq_len=seq, seq_axis=self._spec.seq_axis,
                         pad_value=self._spec.pad_value)._data for d in datas]
        padded = [jnp.copy(d) if id(d) in protect else d for d in padded]
        flat, cell = self._run_padded(padded)
        telemetry.observe("serving.batch_fill", n / float(bucket))
        if n != bucket:
            flat = [NDArray(o._data[:n]) for o in flat]
        return flat, cell["out_fmt"], bucket

    def predict(self, *args):
        """The user-facing call: accepts NDArrays / numpy arrays, returns
        the block's output structure (single NDArray or tuple) sliced to
        the request batch. Device outputs — call ``.asnumpy()`` to fetch
        (the one declared d2h of the serving hot path)."""
        from ..gluon.block import _regroup
        flat, fmt, _ = self.predict_flat(args)
        out, _, _ = _regroup(flat, fmt)
        return out

    def _traced_params(self, param_datas, param_ranges):
        """In-trace reconstruction of compute-dtype parameter buffers
        from the (possibly int8) stored form — shared by this predictor's
        own pure fns and the DecodeEngine's step/insert jits (which run
        against the same stored buffers). The range is a traced argument:
        a ``refresh_params()`` re-quantization never recompiles."""
        return _dequant_params(tuple(self._param_qdtypes or ()),
                               param_datas, param_ranges)

    def compile_stats(self):
        """The watchdog's view of THIS predictor's compiles — its own
        retrace site (per-replica for ReplicaSet members):
        {compiles, trips, last} (None before any compile)."""
        return telemetry.retrace_stats(self._site)

    # ------------------------------------------------------------ load paths
    @classmethod
    def from_checkpoint(cls, prefix, epoch, spec, input_names=("data",),
                        example=None, warmup=False, name=None):
        """The c_predict_api shape: (symbol-json, params) checkpoint on
        disk -> a served SymbolBlock. ``prefix``/``epoch`` follow
        ``model.save_checkpoint`` / ``HybridBlock.export`` naming."""
        from .. import symbol as sym_mod
        from ..gluon.block import SymbolBlock
        from ..model import load_checkpoint
        sym, arg_params, aux_params = load_checkpoint(prefix, epoch)
        if sym is None:
            raise MXNetError("no symbol file at %s-symbol.json" % prefix)
        if isinstance(input_names, str):
            input_names = [input_names]
        blk = SymbolBlock(sym, [sym_mod.var(n) for n in input_names])
        pd = blk.collect_params()
        for pname, arr in list(arg_params.items()) + list(aux_params.items()):
            if pname in pd:
                pd[pname].set_data(arr)
        return cls(blk, spec, example=example, warmup=warmup,
                   name=name or ("ckpt:" + str(prefix)))

    @classmethod
    def from_trainer_checkpoint(cls, block, directory, spec, step=None,
                                example=None, warmup=False, name=None):
        """Serve straight from a training run's orbax checkpoint: restores
        ONLY the params subtree of a ``contrib.async_checkpoint.
        save_trainer`` step (latest finalized step when ``step=None``)
        into ``block`` — optimizer state and RNG stay untouched. The
        block must be built + initialized with shapes settled, exactly
        like the trainer that saved (positional keys)."""
        from ..contrib import async_checkpoint as ackpt
        if example is not None and any(
                p._data is None for p in block.collect_params().values()):
            # settle deferred shapes BEFORE the positional-key restore
            _eager_forward(block, _as_nds(
                example if isinstance(example, (tuple, list)) else (example,)))
        ackpt.load_trainer_params_into_block(block, directory, step=step)
        return cls(block, spec, example=example, warmup=warmup,
                   name=name or ("trainer:" + str(directory)))
