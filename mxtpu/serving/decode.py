"""Continuous-batching autoregressive decode: prefill/decode split + KV slots.

The Predictor/MicroBatcher stack (PR 5/8) serves single-shot inference:
one request, one padded forward, one answer. The LLM workload class is
different — a request is a PROMPT plus a loop of single-token steps, and
throughput comes from keeping a decode cohort full ACROSS steps, not from
padding one batch. The PyGraph capture/replay economics (PAPERS.md:
arXiv:2503.19779) say exactly how to build that on a jit stack: ONE
ahead-of-time decode executable per cohort bucket, replayed thousands of
times, with every per-step tensor living in-executable as donated carry
state so a step is pure replay. This module is that engine:

* **Prefill/decode split** — the prompt runs through the existing
  bucketed :class:`~mxtpu.serving.engine.Predictor` path (seq buckets,
  pad-up, device-side slice; compiles pinned at retrace site
  ``serving.prefill``), producing the prompt's KV cache and first token.
  Decode then runs the continuous-batching step loop below.
* **KV-cache slot manager** — a fixed-capacity cohort (``BucketSpec
  (decode_slots=...)``): each slot carries one sequence's KV cache,
  current token, position, and remaining-token budget as DONATED jit
  carry state. Finished sequences free their slot BETWEEN steps and
  queued prefilled sequences join the RUNNING cohort without a
  recompile: a slot insert is a device-side ``dynamic_update_slice``
  with a *traced* slot index, so slot identity never enters a cache key.
* **AOT bucket replay** — ``warmup()`` compiles one step executable per
  cohort capacity bucket and one insert executable per prefill seq
  bucket; after warmup, the ``serving.decode`` retrace site stays at
  that count by construction (watchdog-pinned), and each step runs at
  the smallest capacity bucket covering the live high-water slot.
* **Zero d2h in the decode loop** — the step dispatch runs under a
  d2h-armed ``serving.decode`` span (asserts zero syncs, exactly like
  ``serving.predict``); the one declared fetch per step (sampled tokens
  + done mask, two tiny vectors) happens outside it in the
  ``serving.fetch`` span.
* **KV residency accounting** — a :class:`KVCacheAccountant` tracks
  per-replica KV bytes by cohort bucket and gates admission: overload
  sheds by *KV residency* (``serving.shed{kv_residency}``), not just
  queue depth. The same accountant plugs into
  :class:`~mxtpu.serving.batcher.MicroBatcher` (``admission_gate=``) and
  :class:`~mxtpu.serving.replicas.ReplicaSet` (``attach_accountant``).
* **int8 path** — ``MXTPU_SERVE_INT8`` stores weights (Predictor) and
  the KV cache (here) as symmetric int8 + per-row scales through
  ``ops/quantization.py``, roughly halving resident bytes per replica —
  the accountant then admits ~2x the sequences at equal memory.

Model contract (:class:`DecodeModel`): a ``HybridBlock`` whose

* ``forward(tokens[b, s])`` returns ``(logits[b, s, V], *kv[b, s, ...])``
  — the PREFILL, served through the Predictor machinery unchanged;
* ``decode_step(kv, tok, pos)`` (jnp-level, traced under the same
  ``_run_traced`` machinery, parameters via ``self.<param>.data()``)
  takes the cohort's KV leaves ``[c, L, ...]`` *without* this step's
  token, the current tokens ``[c]`` and cache lengths ``[c]``, and
  returns ``(logits[c, V], new_entries)`` — the k/v rows this token
  appends, which the ENGINE persists at ``pos`` (and quantizes, in int8
  mode). The model never touches slot bookkeeping.

Failure semantics mirror PR 8: a decode step with no answer within
``MXTPU_SERVE_DISPATCH_TIMEOUT_MS`` trips the wedge watchdog — the stuck
sequences' futures fail loud, their trace_ids land in a
``flight_record("decode_wedge", ...)`` artifact, the cohort carry state
is re-allocated, and the engine keeps serving the queue. An injected
``decode_wedge`` fault drives the whole path sleep-free under a fake
clock.
"""
from __future__ import annotations

import collections
import logging
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import telemetry
from ..base import MXNetError
from ..ndarray import NDArray
from ..resilience import inject, maybe_oom
from .batcher import DeadlineExceeded, QueueFull, _Future
from .engine import _TRACE_LOCK, BucketSpec, Predictor, serve_int8_default
from .replicas import dispatch_timeout_ms_default

__all__ = ["DecodeModel", "DecodeEngine", "DecodeFuture", "KVCacheAccountant",
           "decode_slots_default", "decode_queue_default",
           "decode_max_new_default", "kv_overcommit_default",
           "kv_page_tokens_default", "prefix_cache_default",
           "spec_decode_k_default"]

_log = logging.getLogger("mxtpu.serving")


# ------------------------------------------------------------------ policies
def decode_slots_default():
    """Decode-cohort capacity when no ``decode_spec`` is passed
    (``MXTPU_DECODE_SLOTS``, default 8): the engine declares
    ``BucketSpec.pow2(decode_slots=<this>)`` — capacity is also per-slot
    KV bytes x slots of resident HBM, so size it to the memory budget,
    not the offered load (the queue + accountant absorb bursts)."""
    return int(os.environ.get("MXTPU_DECODE_SLOTS", "8"))


def decode_queue_default():
    """Pending-sequence admission bound (``MXTPU_DECODE_QUEUE``, default
    256): submits beyond it shed (``QueueFull`` -> 503) instead of
    growing time-to-first-token without bound."""
    return int(os.environ.get("MXTPU_DECODE_QUEUE", "256"))


def decode_max_new_default():
    """Generation budget when a request names none
    (``MXTPU_DECODE_MAX_NEW``, default 32); generation always also stops
    at the engine's ``max_len`` cache bound and at ``eos_id``."""
    return int(os.environ.get("MXTPU_DECODE_MAX_NEW", "32"))


def kv_overcommit_default():
    """Admitted-sequence overcommit as a multiple of KV pool capacity
    (``MXTPU_SERVE_KV_OVERCOMMIT``, default 2.0): the accountant admits
    (live + queued) sequences up to overcommit x capacity slots — enough
    queue to keep slots full across completions, bounded enough that
    time-to-first-token stays finite under overload."""
    return float(os.environ.get("MXTPU_SERVE_KV_OVERCOMMIT", "2.0"))


def kv_page_tokens_default():
    """KV page size in tokens (``MXTPU_KV_PAGE_TOKENS``, default 0 =
    rowed worst-case slots, the PR 11 layout). A power-of-two > 0 turns
    on PAGED KV: slots carry page tables instead of ``max_len`` rows, so
    HBM residency tracks actual tokens and finished sequences return
    their pages to the pool between steps — the accountant then admits
    by real free-page headroom instead of pessimistic rows."""
    return int(os.environ.get("MXTPU_KV_PAGE_TOKENS", "0"))


def prefix_cache_default():
    """Prefix caching on paged KV (``MXTPU_PREFIX_CACHE``, default off):
    full prompt-aligned pages are registered under a rolling token-chunk
    hash and SHARED (refcounted, read-only) across prompts with the same
    prefix — a templated-prompt cohort stores each system prompt once
    and prefill skips straight to the first novel token."""
    return os.environ.get("MXTPU_PREFIX_CACHE", "0") \
        not in ("0", "", "false", "False")


def spec_decode_k_default():
    """Speculative-decoding draft length (``MXTPU_SPEC_DECODE_K``,
    default 0 = off): a draft model proposes k greedy tokens per step
    and the target executable verifies them in ONE batched pass with
    longest-accepted-prefix commit — tokens/step rises above 1 at
    identical target math (greedy streams are bit-identical with and
    without speculation)."""
    return int(os.environ.get("MXTPU_SPEC_DECODE_K", "0"))


class DecodeFuture(_Future):
    """A decode request's completion handle: ``result()`` returns the
    generated token ids (int32 numpy, eos included when hit). Carries the
    trace identity of the batcher futures plus ``ttft_s`` — the
    time-to-first-token the open-loop bench curves plot."""

    __slots__ = ("ttft_s",)

    def __init__(self):
        super().__init__()
        self.ttft_s = None


class _Sequence:
    __slots__ = ("prompt", "max_new", "deadline", "t_enq", "trace", "future",
                 "tokens", "slot", "pages", "reserved", "pos")

    def __init__(self, prompt, max_new, deadline, t_enq, trace):
        self.prompt = prompt
        self.max_new = max_new
        self.deadline = deadline
        self.t_enq = t_enq
        self.trace = trace
        self.future = DecodeFuture()
        self.tokens = []
        self.slot = None
        self.pages = []     # paged mode: mapped page ids, chunk order
        self.reserved = 0   # paged mode: accountant pages still queued
        self.pos = 0        # paged mode: host mirror of the device pos


class DecodeModel:
    """Marker/contract mixin for autoregressive decode (see the module
    docstring). Concrete models subclass both ``gluon.HybridBlock`` and
    this, implement the prefill ``hybrid_forward`` returning
    ``(logits[b, s, V], *kv[b, s, ...])``, and implement
    :meth:`decode_step`. ``tools/serve_bench.py:build_decode_model`` is
    the executable reference implementation."""

    def decode_step(self, kv, tok, pos):
        """One decode step (jnp-level, traced): ``kv`` — list of cache
        leaves ``[c, L, ...]`` in compute dtype, WITHOUT this step's
        token; ``tok[c]`` int32 current tokens; ``pos[c]`` int32 cache
        lengths (this token's position). Returns ``(logits[c, V],
        entries)`` where ``entries`` is the per-leaf list of new k/v rows
        ``[c, ...]`` — the engine persists them at ``pos``."""
        raise NotImplementedError

    def decode_chunk(self, kv, toks, pos):
        """OPTIONAL: score ``t`` chained tokens in ONE forward (jnp-level,
        traced) — the speculative-verification fast path. ``toks[c, t]``
        are the pending token followed by t-1 draft proposals; the
        position of ``toks[:, j]`` is ``pos + j``. Attention for query j
        spans the cache (rows ``< pos``) plus the chunk's own rows
        ``<= j`` (causal within the chunk) — the chunk rows are NOT in
        ``kv``. Returns ``(logits[c, t, V], entries)`` with per-leaf new
        rows ``[c, t, ...]``; the engine persists/discards them by its
        commit rule. Rows whose position overflows ``L`` may be garbage —
        the engine masks them. Models that do not implement this verify
        through ``decode_step`` chained t times (bit-identical, slower);
        int8 engines always chain so within-chunk reads see the same
        quantize->dequantize grid as step-at-a-time decode."""
        raise NotImplementedError


# ----------------------------------------------------------- KV accounting
class KVCacheAccountant:
    """Per-replica KV residency ledger feeding admission control.

    Engines (or any KV-carrying server) :meth:`register` their pool —
    per-slot bytes x capacity slots, tagged per replica like the
    ``serving.predict.r<i>`` retrace sites. Admission then asks
    :meth:`would_admit`: a sequence is admitted while (live + queued)
    slots stay under ``overcommit`` x capacity; past that the submit
    sheds ``serving.shed{kv_residency}`` — the overload signal is *KV
    residency*, not queue depth, so a fleet dispatcher can route by how
    much cache memory a replica actually has left. Gauges:
    ``serving.kv_capacity_bytes`` / ``serving.kv_resident_bytes``
    (resident = live slots only; queued sequences hold no device bytes
    yet). ``snapshot()`` (surfaced by ``/healthz``) reports per-tag
    bytes plus the per-cohort-bucket byte ladder."""

    def __init__(self, capacity_bytes=None, overcommit=None):
        self._lock = threading.Lock()
        self._pools = {}
        self._capacity_bytes = capacity_bytes
        self._overcommit = float(overcommit if overcommit is not None
                                 else kv_overcommit_default())

    def register(self, tag, per_slot_bytes, slots, bucket_slots=(),
                 page_tokens=0):
        """Declare (or re-declare) a replica's KV pool. ``bucket_slots``
        is the cohort capacity ladder, so the snapshot can report bytes
        by bucket. A PAGED engine registers its page pool here instead:
        ``per_slot_bytes`` is one page's bytes, ``slots`` the pool's page
        count, and ``page_tokens`` the page size — the same ledger then
        admits by real free-page headroom, not worst-case rows."""
        with self._lock:
            cap = self._capacity_bytes
            if cap is None:
                cap = int(per_slot_bytes) * int(slots)
            self._pools[tag] = {
                "per_slot_bytes": int(per_slot_bytes),
                "slots": int(slots),
                "capacity_bytes": int(cap),
                "page_tokens": int(page_tokens),
                "live": 0, "queued": 0,
                "bucket_bytes": {int(b): int(b) * int(per_slot_bytes)
                                 for b in bucket_slots},
            }
            self._gauges_locked()

    def _gauges_locked(self):
        telemetry.gauge("serving.kv_capacity_bytes",
                        sum(p["capacity_bytes"]
                            for p in self._pools.values()))
        telemetry.gauge("serving.kv_resident_bytes",
                        sum(p["live"] * p["per_slot_bytes"]
                            for p in self._pools.values()))

    def _pool(self, tag):
        p = self._pools.get(tag)
        if p is None:
            raise MXNetError("KVCacheAccountant: unregistered pool %r "
                             "(register() at engine warmup)" % (tag,))
        return p

    def would_admit(self, tag, n=1):
        """True while ``n`` more sequences fit the overcommit bound.
        Unregistered tags admit (a Predictor-only replica holds no KV)."""
        with self._lock:
            p = self._pools.get(tag)
            if p is None:
                return True
            have = p["live"] + p["queued"] + n
            return have * p["per_slot_bytes"] <= \
                p["capacity_bytes"] * self._overcommit

    def try_admit(self, tag, n=1):
        """Atomic check-and-admit: the overcommit test and the queued
        increment happen under ONE lock hold, so concurrent submits
        cannot all pass a stale check and overshoot the bound (the
        DecodeEngine's admission path). Unregistered tags admit.
        Returns True when admitted (the caller owes a matching
        occupy/unqueue), False to shed."""
        with self._lock:
            p = self._pools.get(tag)
            if p is None:
                return True
            have = p["live"] + p["queued"] + n
            if have * p["per_slot_bytes"] > \
                    p["capacity_bytes"] * self._overcommit:
                return False
            p["queued"] += n
            return True

    def unqueue(self, tag, n=1):
        """``n`` admitted slots/pages left the queue without going
        resident (expired / shed / engine crash / unused page
        reservation)."""
        with self._lock:
            p = self._pool(tag)
            p["queued"] = max(0, p["queued"] - n)

    def occupy(self, tag, n=1):
        """``n`` queued slots/pages went resident (bytes now on
        device)."""
        with self._lock:
            p = self._pool(tag)
            p["queued"] = max(0, p["queued"] - n)
            p["live"] += n
            self._gauges_locked()

    def release(self, tag, n=1):
        """``n`` resident slots/pages freed (sequence finished, page
        refcount hit zero)."""
        with self._lock:
            p = self._pool(tag)
            p["live"] = max(0, p["live"] - n)
            self._gauges_locked()

    def resident_bytes(self, tag=None):
        """Live KV bytes for one tag (0 when unregistered) or all pools."""
        with self._lock:
            pools = [self._pools.get(tag)] if tag is not None \
                else list(self._pools.values())
            return sum(p["live"] * p["per_slot_bytes"] for p in pools
                       if p is not None)

    def pressure(self):
        """The fleet's KV-residency pressure as a 0..1+ fraction of the
        admission bound: max over pools of (live + queued) / (overcommit
        x capacity slots). The :class:`~mxtpu.serving.controller.
        ServingController` reads this as a scale-up signal — a cache
        near its residency bound sheds next, so capacity should grow
        BEFORE the ``kv_residency`` sheds start. 0.0 with no pools."""
        with self._lock:
            worst = 0.0
            for p in self._pools.values():
                bound = self._overcommit * p["slots"]
                if bound > 0:
                    worst = max(worst, (p["live"] + p["queued"]) / bound)
            return worst

    def gate(self, tag):
        """An ``admission_gate=`` callable for a
        :class:`~mxtpu.serving.batcher.MicroBatcher` guarding ``tag``'s
        pool: returns the shed reason ``kv_residency`` when the pool is
        over budget, None when admissible."""
        def _gate(_n_items):
            return None if self.would_admit(tag) else "kv_residency"
        return _gate

    def snapshot(self):
        """JSON-serializable per-tag view (``/healthz`` surfaces this)."""
        with self._lock:
            out = {}
            for tag, p in self._pools.items():
                out[tag] = {
                    "capacity_bytes": p["capacity_bytes"],
                    "per_slot_bytes": p["per_slot_bytes"],
                    "slots": p["slots"],
                    "page_tokens": p.get("page_tokens", 0),
                    "live": p["live"],
                    "queued": p["queued"],
                    "resident_bytes": p["live"] * p["per_slot_bytes"],
                    "bucket_bytes": dict(p["bucket_bytes"]),
                }
            return out


def _bcast(mask, ndim):
    """Broadcast a [b] mask against a [b, ...] value."""
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def _quantize_rows(x):
    """Per-row symmetric int8 through the quantization op: range = max|x|
    over each row's trailing axes (degenerate rows quantize on a unit
    grid, so all-zero rows stay exactly zero). Returns ``(q int8, r f32
    [rows])`` — THE one KV grid rule, shared by the insert path and the
    step write-back so the two can never desynchronize."""
    from ..ops.registry import get_op
    qfn = get_op("quantize").fn
    xf = jnp.asarray(x, jnp.float32)
    r = jnp.max(jnp.abs(xf), axis=tuple(range(1, xf.ndim))) \
        if xf.ndim > 1 else jnp.abs(xf)
    r = jnp.where(r > 0, r, 1.0)
    q, _lo, _hi = qfn(xf, -_bcast(r, xf.ndim), _bcast(r, xf.ndim))
    return q, r


class _PrefixCache:
    """Host-side index of SHARED read-only prompt pages (paged mode,
    ``MXTPU_PREFIX_CACHE``): a rolling chunk hash chains page-aligned
    token blocks, each entry pinning one pool page by refcount. Shared
    pages are full prompt-aligned chunks and are never written — a
    diverging suffix lives in its own private pages from the first
    unmatched chunk on, so copy-on-write materializes at page
    granularity with zero copies. Entries whose page nobody else
    references are evictable (LRU) when the free list runs dry.
    All calls run under the engine's lock."""

    def __init__(self):
        self._entries = collections.OrderedDict()  # h -> entry

    def __len__(self):
        return len(self._entries)

    @staticmethod
    def chunk_hash(parent, tokens):
        import hashlib
        h = hashlib.sha1()
        h.update(parent.encode("ascii"))
        h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
        return h.hexdigest()

    def lookup(self, prompt, pt):
        """Longest cached page-aligned strict-prefix match: returns
        ``(matched_chunks, [page ids])`` — matched tokens stay <= n-1 so
        the extend path always has a novel token to prefill."""
        n = int(prompt.size)
        jmax = (n - 1) // pt
        pids, h = [], ""
        for j in range(jmax):
            chunk = prompt[j * pt:(j + 1) * pt]
            h = self.chunk_hash(h, chunk)
            e = self._entries.get(h)
            if e is None or not np.array_equal(e["tokens"], chunk):
                break
            self._entries.move_to_end(h)
            pids.append(e["pid"])
        return len(pids), pids

    def put(self, h, tokens, pid):
        """Register a full chunk's page (caller increfs the page for the
        cache's pin). Returns False when the hash is already present (the
        caller keeps its private copy unregistered)."""
        if h in self._entries:
            return False
        self._entries[h] = {"tokens": np.array(tokens, np.int32),
                            "pid": int(pid)}
        self._entries.move_to_end(h)
        return True

    def evict_one(self, page_ref):
        """Drop the least-recently-used entry whose page only the cache
        pins (refcount 1). Returns its pid, or None."""
        for h, e in self._entries.items():
            if page_ref[e["pid"]] == 1:
                del self._entries[h]
                return e["pid"]
        return None

    def drain(self):
        """Clear every entry (wedge reset / close — the device pages
        they pin are gone). Returns the pinned pids."""
        pids = [e["pid"] for e in self._entries.values()]
        self._entries.clear()
        return pids


# ------------------------------------------------------------------- engine
class DecodeEngine:
    """The continuous-batching decode loop (see the module docstring).

    ``prefill_spec`` is an ordinary seq-bucketed :class:`BucketSpec`
    (prompts pad to their seq bucket through the Predictor);
    ``decode_spec`` is the ``decode_slots=`` spelling (cohort capacity
    buckets). ``start=True`` runs a background loop thread + wedge
    monitor; ``start=False`` (tests, fake clock) drives everything
    through :meth:`poll`. One engine owns one device's cohort — tag it
    per replica (``replica_tag``) so the shared
    :class:`KVCacheAccountant` ledgers match the ``serving.predict.r<i>``
    site family."""

    def __init__(self, model, prefill_spec, decode_spec=None, max_len=None,
                 eos_id=None, example=None, warmup=True, name="decode",
                 device=None, site="serving.decode",
                 prefill_site="serving.prefill", int8=None,
                 accountant=None, replica_tag="r0", max_queue=None,
                 max_new_default=None, dispatch_timeout_ms=None,
                 clock=time.monotonic, start=False, continuous=True,
                 page_tokens=None, pool_pages=None, prefix_cache=None,
                 draft_model=None, spec_k=None,
                 draft_site="serving.draft"):
        if not hasattr(model, "decode_step"):
            raise MXNetError(
                "DecodeEngine serves DecodeModel-family blocks (got %s): "
                "implement decode_step(kv, tok, pos) -> (logits, entries) "
                "— docs/serving.md" % type(model).__name__)
        if getattr(prefill_spec, "is_decode", False):
            raise MXNetError(
                "DecodeEngine prefill_spec is a decode-cohort spec %r — "
                "prompts need batch x seq buckets (the Predictor path); "
                "pass the capacity spec as decode_spec=" % (prefill_spec,))
        if prefill_spec.seq_lens is None:
            raise MXNetError(
                "DecodeEngine prefill_spec declares no seq_lens: prompts "
                "are variable-length and MUST be seq-bucketed (a prompt "
                "past the largest bucket is refused, docs/serving.md)")
        if decode_spec is None:
            decode_spec = BucketSpec.pow2(decode_slots=decode_slots_default())
        if not getattr(decode_spec, "is_decode", False):
            raise MXNetError(
                "DecodeEngine decode_spec must use the decode_slots= "
                "spelling (got %r): cohort buckets are SLOT capacities, "
                "not request batches" % (decode_spec,))
        self._model = model
        self._prefill_spec = prefill_spec
        self._decode_spec = decode_spec
        self._capacity = decode_spec.max_slots
        self._max_new_default = int(max_new_default
                                    if max_new_default is not None
                                    else decode_max_new_default())
        self._max_len = int(max_len if max_len is not None
                            else prefill_spec.seq_lens[-1]
                            + self._max_new_default)
        if self._max_len < prefill_spec.seq_lens[-1] + 1:
            raise MXNetError(
                "DecodeEngine max_len=%d leaves no room to decode past "
                "the largest prompt bucket (%d)"
                % (self._max_len, prefill_spec.seq_lens[-1]))
        self._eos = -1 if eos_id is None else int(eos_id)
        self._name = name
        self._site = site
        self._int8 = serve_int8_default() if int8 is None else bool(int8)
        self._acct = accountant
        self._tag = replica_tag
        self._max_queue = int(max_queue if max_queue is not None
                              else decode_queue_default())
        self._timeout_s = float(
            dispatch_timeout_ms if dispatch_timeout_ms is not None
            else dispatch_timeout_ms_default()) / 1e3
        self._clock = clock
        self._continuous = bool(continuous)
        # ---- paged KV / prefix reuse / speculative decoding (ISSUE 16)
        pt = int(page_tokens if page_tokens is not None
                 else kv_page_tokens_default())
        if pt < 0 or (pt and (pt & (pt - 1))):
            raise MXNetError(
                "DecodeEngine page_tokens=%d must be 0 (rowed) or a "
                "power of two (page-offset math is a mask/shift inside "
                "the traced step)" % pt)
        self._pt = pt
        self._maxp = 0 if not pt else -(-self._max_len // pt)
        if pool_pages is not None and not pt:
            raise MXNetError("DecodeEngine pool_pages without "
                             "page_tokens: the rowed layout has no pool")
        self._pool_pages = 0 if not pt else int(
            pool_pages if pool_pages is not None
            else self._capacity * self._maxp)
        if pt and self._pool_pages < self._maxp:
            raise MXNetError(
                "DecodeEngine pool_pages=%d cannot hold even one "
                "max_len=%d sequence (%d pages of %d tokens)"
                % (self._pool_pages, self._max_len, self._maxp, pt))
        self._prefix_on = bool(prefix_cache if prefix_cache is not None
                               else prefix_cache_default())
        self._spec_k = int(spec_k if spec_k is not None
                           else spec_decode_k_default())
        if self._prefix_on and not pt:
            raise MXNetError("DecodeEngine prefix_cache needs paged KV "
                             "(MXTPU_KV_PAGE_TOKENS > 0): shared prompts "
                             "are shared PAGES")
        if self._spec_k and not pt:
            raise MXNetError("DecodeEngine spec_k needs paged KV "
                             "(MXTPU_KV_PAGE_TOKENS > 0)")
        if self._spec_k and draft_model is None:
            raise MXNetError("DecodeEngine spec_k=%d without a "
                             "draft_model: speculation needs a proposer"
                             % self._spec_k)
        if self._spec_k and self._prefix_on:
            raise MXNetError(
                "DecodeEngine prefix_cache with spec_k: a prefix hit "
                "skips the prefill the DRAFT cache also needs — run one "
                "lever per engine (docs/serving.md)")
        if draft_model is not None and not self._spec_k:
            self._spec_k = 0
            draft_model = None
        if draft_model is not None and not hasattr(draft_model,
                                                   "decode_step"):
            raise MXNetError("DecodeEngine draft_model must be a "
                             "DecodeModel (decode_step)")
        self._draft_model = draft_model
        self._draft_site = draft_site
        self._draft_pred = None
        self._dkv_layout = None
        # host page-pool state (guarded by self._cond; the Condition's
        # default RLock makes the ledger helpers re-entrant)
        self._free_pages = []
        self._page_ref = None
        self._ptab = None
        self._prefix = _PrefixCache() if self._prefix_on else None
        if example is None:
            example = np.zeros((1, prefill_spec.seq_lens[0]), np.int32)
        self._pred = Predictor(model, prefill_spec, example=example,
                               warmup=False, name=name + ".prefill",
                               device=device, site=prefill_site,
                               int8=self._int8)
        if self._draft_model is not None:
            # the draft Predictor exists for its param plumbing (the
            # draft prefill itself runs fused inside the insert
            # executables); the per-cohort draft-chain executables
            # report at serving.draft — the site the zero-post-warmup
            # watchdog pins
            self._draft_pred = Predictor(
                self._draft_model, prefill_spec, example=example,
                warmup=False, name=name + ".draft", device=device,
                site=self._draft_site, int8=False)
        self._jits = {}            # (kind, bucket, int8, policy) -> jitted
        self._kv_layout = None     # [(trailing_shape, dtype_str)] per leaf
        self._vocab = None
        self._carry = None
        self._carry_gen = 0        # bumped by every wedge reset: a step
        # dispatched against a superseded carry must not write back
        self._last_logits = None   # most recent step's logits (device; the
        # diagnostic parity hook — never fetched by the loop itself)
        self._cond = threading.Condition()
        self._pending = collections.deque()
        self._slots = [None] * self._capacity
        self._inflight_seq = None  # popped from _pending, not yet slotted
        # (mid-prefill): drain/close must not treat the engine as empty
        self._live = 0
        self._step_index = 0
        self._armed = None         # the in-flight step's watchdog entry
        self._prefill_armed = None  # the in-flight prefill/insert's entry
        self._cycles = 0           # loop/poll progress counter (probation)
        self._probation = None     # (deadline, cycles-at-trip) after a wedge
        self._closed = False
        self._draining = False
        self._crashed = False
        self._thread = None
        self._monitor = None
        self._stop = threading.Event()
        if warmup:
            self.warmup()
        if start:
            self.start()

    # ------------------------------------------------------------ properties
    @property
    def capacity(self):
        return self._capacity

    @property
    def int8(self):
        return self._int8

    @property
    def live_slots(self):
        with self._cond:
            return self._live

    @property
    def pending_count(self):
        with self._cond:
            return len(self._pending)

    @property
    def predictor(self):
        """The prefill Predictor (its compiles report at
        ``serving.prefill``)."""
        return self._pred

    @property
    def accountant(self):
        return self._acct

    @property
    def page_tokens(self):
        """Tokens per KV page (0 = rowed worst-case layout)."""
        return self._pt

    @property
    def pool_pages(self):
        """Page-pool size (0 in rowed mode). Page id 0 is a scratch
        page on top of this count — inactive-slot and overflow writes
        land there, so the pool ids are 1..pool_pages."""
        return self._pool_pages

    @property
    def spec_k(self):
        """Speculative draft length (0 = plain one-token steps)."""
        return self._spec_k

    def per_slot_kv_bytes(self):
        """Resident bytes one slot's KV cache costs (int8: quantized
        leaves + per-position scale rows) — what the accountant ledgers.
        In paged mode this is the WORST-CASE cost (max_len tokens); the
        accountant instead ledgers :meth:`page_bytes` x pages actually
        mapped."""
        if self._kv_layout is None:
            raise MXNetError("per_slot_kv_bytes before warmup()")
        total = 0
        for trail, dt in self._kv_layout:
            n = self._max_len * int(np.prod(trail, dtype=np.int64) or 1)
            if self._int8:
                total += n * 1 + self._max_len * 4  # int8 rows + f32 scales
            else:
                total += n * jnp.dtype(dt).itemsize
        return total

    def page_bytes(self):
        """Resident bytes one pool page costs (``page_tokens`` rows of
        every KV leaf; int8: quantized rows + per-position scales)."""
        if self._kv_layout is None:
            raise MXNetError("page_bytes before warmup()")
        if not self._pt:
            raise MXNetError("page_bytes on a rowed engine "
                             "(page_tokens=0)")
        total = 0
        for trail, dt in self._kv_layout:
            n = self._pt * int(np.prod(trail, dtype=np.int64) or 1)
            if self._int8:
                total += n * 1 + self._pt * 4
            else:
                total += n * jnp.dtype(dt).itemsize
        return total

    # ---------------------------------------------------------------- warmup
    def warmup(self):
        """Settle the prefill templates, derive the KV layout from one
        probe forward, AOT-compile every prefill bucket, every cohort
        step bucket, and every insert bucket, and allocate the cohort
        carry. After this, a compile at ``serving.decode`` is a served
        stall — the watchdog (and the serve_bench gate) pins the site at
        its post-warmup count. Idempotent."""
        if self._kv_layout is not None:
            return self
        flat, _fmt, _b = self._pred.predict_flat(
            (np.zeros((1, self._prefill_spec.seq_lens[0]), np.int32),))
        if len(flat) < 2:
            raise MXNetError(
                "DecodeModel forward must return (logits, *kv_leaves); "
                "got %d output(s) — the KV cache IS the decode state"
                % len(flat))
        logits = flat[0]
        if logits._data.ndim != 3:
            raise MXNetError(
                "DecodeModel prefill logits must be [batch, seq, vocab], "
                "got shape %s" % (tuple(logits._data.shape),))
        self._vocab = int(logits._data.shape[-1])
        layout = []
        for i, leaf in enumerate(flat[1:]):
            d = leaf._data
            if d.ndim < 2 or d.shape[1] != logits._data.shape[1]:
                raise MXNetError(
                    "DecodeModel kv leaf %d must be [batch, seq, ...] "
                    "(got shape %s)" % (i, tuple(d.shape)))
            layout.append((tuple(int(x) for x in d.shape[2:]),
                           str(d.dtype)))
        self._kv_layout = layout
        self._pred.warmup()
        if self._draft_pred is not None:
            dflat, _df, _db = self._draft_pred.predict_flat(
                (np.zeros((1, self._prefill_spec.seq_lens[0]), np.int32),))
            if len(dflat) < 2 or dflat[0]._data.ndim != 3:
                raise MXNetError("draft_model must follow the DecodeModel "
                                 "prefill contract (logits, *kv_leaves)")
            if int(dflat[0]._data.shape[-1]) != self._vocab:
                raise MXNetError(
                    "draft_model vocab %d != target vocab %d — the "
                    "draft proposes TARGET token ids"
                    % (int(dflat[0]._data.shape[-1]), self._vocab))
            self._dkv_layout = [
                (tuple(int(x) for x in leaf._data.shape[2:]),
                 str(leaf._data.dtype)) for leaf in dflat[1:]]
            # no _draft_pred.warmup(): the draft prefill runs FUSED
            # inside the insert executables (warmed below) — the draft
            # Predictor only supplies params and the probe above
        with self._cond:
            if self._pt:
                self._reset_pool_locked()
            self._carry = self._alloc_carry()
        # AOT: one step executable per cohort capacity bucket (replayed
        # on the all-inactive cohort — a no-op step; spec mode compiles
        # the draft-chain + verify pair instead), one insert executable
        # per prefill seq bucket (max_new=0 marks the warmed slot
        # done-at-insert, so warmup leaves no live slot behind), and —
        # prefix mode — one extend executable per seq bucket. First
        # invocations trace the shared block (parameters bind tracers):
        # serialize across engines like the Predictor does.
        with _TRACE_LOCK:
            ptab0 = None if not self._pt else \
                np.zeros((self._capacity, self._maxp), np.int32)
            for b in self._decode_spec.decode_slots:
                if self._spec_k:
                    d_args = (self._carry, self._draft_pred._param_datas,
                              self._draft_pred._param_ranges)
                    self._carry, props = self._get_draft_jit(
                        b, example_args=d_args)(*d_args)
                    v_args = (self._carry, ptab0, props,
                              self._pred._param_datas,
                              self._pred._param_ranges)
                    self._carry, emitted = self._get_verify_jit(
                        b, example_args=v_args)(*v_args)
                elif self._pt:
                    step_args = (self._carry, ptab0,
                                 self._pred._param_datas,
                                 self._pred._param_ranges)
                    self._carry, emitted = self._get_step_jit(
                        b, example_args=step_args)(*step_args)
                else:
                    step_args = (self._carry, self._pred._param_datas,
                                 self._pred._param_ranges)
                    self._carry, emitted = self._get_step_jit(
                        b, example_args=step_args)(*step_args)
                jax.block_until_ready(emitted[0])
            V = self._vocab
            for s in self._prefill_spec.seq_lens:
                seq_kv = [jnp.zeros((1, s) + trail, dt)
                          for trail, dt in layout]
                # the probe forward's ACTUAL logits dtype: a bf16 model
                # warmed against f32 zeros would hit the cached wrapper
                # but retrace inside jax on the first real insert — a
                # mid-serving compile stall invisible to record_retrace
                zl = jnp.zeros((1, s, V), logits._data.dtype)
                if self._pt:
                    pages0 = np.zeros(-(-s // self._pt), np.int32)
                    if self._spec_k:
                        ins_args = (self._carry, seq_kv, zl,
                                    np.zeros(s, np.int32), pages0,
                                    np.int32(0), np.int32(1), np.int32(0),
                                    self._draft_pred._param_datas,
                                    self._draft_pred._param_ranges)
                    else:
                        ins_args = (self._carry, seq_kv, zl, pages0,
                                    np.int32(0), np.int32(1), np.int32(0))
                else:
                    ins_args = (self._carry, seq_kv, zl,
                                np.int32(0), np.int32(1), np.int32(0))
                self._carry, out = self._get_insert_jit(
                    s, example_args=ins_args)(*ins_args)
                jax.block_until_ready(out)
                if self._prefix is not None:
                    ext_args = (self._carry, np.zeros(self._maxp, np.int32),
                                np.zeros(s, np.int32), np.int32(0),
                                np.int32(0), np.int32(0), np.int32(0),
                                self._pred._param_datas,
                                self._pred._param_ranges)
                    self._carry, out = self._get_extend_jit(
                        s, example_args=ext_args)(*ext_args)
                    jax.block_until_ready(out)
        telemetry.gauge("serving.decode.buckets",
                        len(self._decode_spec.decode_slots)
                        + len(self._prefill_spec.seq_lens))
        if self._acct is not None:
            if self._pt:
                # page-granular ledger: one "slot" = one page, so the
                # byte gauges and the admission bound track pages
                # actually mapped, not worst-case rows
                self._acct.register(self._tag, self.page_bytes(),
                                    self._pool_pages,
                                    page_tokens=self._pt)
            else:
                self._acct.register(
                    self._tag, self.per_slot_kv_bytes(), self._capacity,
                    bucket_slots=self._decode_spec.decode_slots)
        # will-it-fit pre-flight (mxtpu/xprof.py): Σ AOT step+insert
        # executable footprints vs the device HBM limit — warmup
        # succeeding bucket-by-bucket does not mean every bucket's
        # residents coexist; skipped (zero extra lowering) when the
        # backend exposes no limit (CPU tier)
        from .. import xprof
        xprof.ensure_memwatch()
        xprof.preflight(self._site)
        return self

    def _alloc_carry(self):
        C, L = self._capacity, self._max_len
        if self._pt:
            # paged: leaves are [pool+1, page_tokens, ...] — page id 0
            # is the scratch page (inactive-slot writes, unmapped table
            # entries, and clamped overflow all land there)
            rows = (self._pool_pages + 1, self._pt)
            if self._int8:
                kv = [jnp.zeros(rows + trail, jnp.int8)
                      for trail, _dt in self._kv_layout]
                scales = [jnp.ones(rows, jnp.float32)
                          for _ in self._kv_layout]
            else:
                kv = [jnp.zeros(rows + trail, dt)
                      for trail, dt in self._kv_layout]
                scales = None
        elif self._int8:
            kv = [jnp.zeros((C, L) + trail, jnp.int8)
                  for trail, _dt in self._kv_layout]
            scales = [jnp.ones((C, L), jnp.float32)
                      for _ in self._kv_layout]
        else:
            kv = [jnp.zeros((C, L) + trail, dt)
                  for trail, dt in self._kv_layout]
            scales = None
        tok = jnp.zeros((C,), jnp.int32)
        pos = jnp.zeros((C,), jnp.int32)
        active = jnp.zeros((C,), jnp.bool_)
        rem = jnp.zeros((C,), jnp.int32)
        carry = (kv, scales, tok, pos, active, rem)
        if self._spec_k:
            # the draft's KV stays ROWED in compute dtype: the draft is
            # small by design, and keeping it worst-case keeps the
            # proposer off the page pool entirely
            carry += ([jnp.zeros((C, L) + trail, dt)
                       for trail, dt in self._dkv_layout],)
        return carry

    # ------------------------------------------------------ page pool (host)
    def _reset_pool_locked(self):
        """(Re)build the free list, refcounts, and page tables — engine
        construction and every carry re-allocation (wedge reset, crash,
        close): the device pages a reset zeroes must never stay mapped."""
        P = self._pool_pages
        self._free_pages = list(range(P, 0, -1))   # pop() -> 1, 2, ...
        self._page_ref = np.zeros(P + 1, np.int32)
        self._ptab = np.zeros((self._capacity, max(1, self._maxp)),
                              np.int32)
        self._page_gauges_locked()

    def _page_gauges_locked(self):
        if not self._pt:
            return
        free = len(self._free_pages)
        telemetry.gauge("serving.kv_page_free", free)
        telemetry.gauge("serving.kv_page_resident", self._pool_pages - free)
        telemetry.gauge("serving.kv_page_shared",
                        int(np.sum(self._page_ref[1:] >= 2)))
        telemetry.gauge("serving.kv_resident_tokens",
                        sum(s.pos for s in self._slots if s is not None))

    def _take_page_locked(self, seq):
        """Allocate one pool page for ``seq`` (ledger + refcount + map).
        Returns the pid, or None on exhaustion — physical (free list dry
        even after evicting cache-only pages) or ledgered (the
        accountant's page headroom is gone and the sequence holds no
        reservation to convert)."""
        if seq.reserved <= 0:
            if self._acct is not None \
                    and not self._acct.try_admit(self._tag):
                return None
            seq.reserved += 1
        if not self._free_pages and self._prefix is not None:
            pid = self._prefix.evict_one(self._page_ref)
            if pid is not None:
                self._decref_locked(pid)
        if not self._free_pages:
            # physically dry: hand the reservation back before refusing
            if self._acct is not None:
                self._acct.unqueue(self._tag)
            seq.reserved -= 1
            return None
        pid = self._free_pages.pop()
        self._page_ref[pid] = 1
        if self._acct is not None:
            self._acct.occupy(self._tag)
        seq.reserved -= 1
        seq.pages.append(pid)
        return pid

    def _share_page_locked(self, seq, pid):
        """Attach a cache-shared page to ``seq`` (refcount only — the
        page's bytes are already ledgered live)."""
        self._page_ref[pid] += 1
        seq.pages.append(pid)

    def _decref_locked(self, pid):
        """Drop one reference; at zero the page returns to the free list
        and its bytes leave the accountant's resident count."""
        self._page_ref[pid] -= 1
        if self._page_ref[pid] <= 0:
            self._page_ref[pid] = 0
            self._free_pages.append(pid)
            if self._acct is not None:
                self._acct.release(self._tag)

    def _free_seq_ledger(self, seq, slotted):
        """THE one teardown ledger for a sequence (normal completion,
        done-at-insert, deadline expiry, wedge casualty, wedge scan,
        crash barrier, close): paged mode derefs every mapped page and
        hands back any unconverted reservation; rowed mode keeps the PR
        11 release-vs-unqueue split. One copy, so no path can leak pool
        pages or drive the free count negative."""
        if self._pt:
            with self._cond:
                for pid in seq.pages:
                    self._decref_locked(pid)
                seq.pages = []
                if seq.reserved > 0 and self._acct is not None:
                    self._acct.unqueue(self._tag, n=seq.reserved)
                seq.reserved = 0
                self._page_gauges_locked()
        elif self._acct is not None:
            if slotted:
                self._acct.release(self._tag)
            else:
                self._acct.unqueue(self._tag)

    def _register_prefix_locked(self, seq, m_chunks):
        """Publish this prompt's FULL chunks into the prefix cache (the
        cache holds one extra reference per entry, so a published page
        outlives its first owner). Only chunks wholly inside the prompt
        register — the page holding the first generated token is private
        by construction, which is what makes shared pages read-only
        without any copy-on-write machinery."""
        if self._prefix is None:
            return
        pt = self._pt
        n = int(seq.prompt.size)
        h = ""
        for j in range(n // pt):
            chunk = seq.prompt[j * pt:(j + 1) * pt]
            h = _PrefixCache.chunk_hash(h, chunk)
            if j >= m_chunks and j < len(seq.pages):
                if self._prefix.put(h, chunk, seq.pages[j]):
                    self._page_ref[seq.pages[j]] += 1
        self._page_gauges_locked()

    # ------------------------------------------------------------- compiling
    def _build_jit(self, kind, bucket, build, donate=(0,),
                   example_args=None):
        """The one compile front door for the decode cache: every miss
        resolves through the compile service (LRU store, disk cache,
        centralized retrace reporting at this engine's site —
        ``serving.decode``; graftlint's JIT_ALLOWLIST declares the cache
        since the site name is per-instance), exactly like
        ``Predictor._get_jit`` — post-warmup the site count stays at
        #cohort-buckets + #insert-buckets by construction, and a
        warm-disk restart reaches it with ZERO compiles."""
        from .. import compile_service as csvc
        from ..ops.registry import policy_key
        pol = policy_key()
        key = (kind, bucket, self._int8, pol)
        hit = self._jits.get(key)
        if hit is not None:
            return hit
        ckey = csvc.canonical_key(
            site=self._site,
            fn_id="decode:%s:%s" % (type(self._model).__name__,
                                    csvc.source_token(type(self._model))),
            # the predictor's param structure joins the signature: two
            # models of the same class but different widths (same
            # kv_layout/vocab) must never alias a disk digest — a
            # shape-mismatched restore would crash, not degrade
            # the paged dims join the signature: a paged and a rowed
            # engine of the same model (or two pool sizes) must never
            # alias a disk digest — a shape-mismatched restore would
            # crash, not degrade
            signature=(kind, bucket, self._int8, self._capacity,
                       self._max_len, self._eos,
                       tuple(self._kv_layout or ()), self._vocab,
                       tuple((tuple(d.shape), str(d.dtype))
                             for d in self._pred._param_datas),
                       self._pt, self._pool_pages, self._spec_k,
                       tuple(self._dkv_layout or ())),
            policy=pol, donation=donate,
            device=csvc.device_token(device=self._pred.device),
            nonce=csvc.instance_nonce(self))
        entry = csvc.get_or_build(
            ckey, lambda: jax.jit(build(), donate_argnums=donate),
            provenance={"engine": self._name, "kind": kind,
                        "bucket": bucket, "int8": self._int8,
                        "capacity": self._capacity,
                        "max_len": self._max_len,
                        "policy_key": list(pol)},
            example_args=csvc.concrete_args(example_args)
            if example_args is not None else None)
        self._jits[key] = entry.fn
        return entry.fn

    def _build_draft_jit(self, kind, bucket, build, donate=(0,),
                         example_args=None):
        """The compile front door for the DRAFT-model executables
        (speculative decoding): same compile-service seam as
        ``_build_jit`` but reporting at the ``serving.draft`` site — the
        sixth entry in graftlint's caches inventory, with its own
        zero-post-warmup watchdog pin. One draft-chain executable per
        cohort capacity bucket; the draft Predictor's prefill buckets
        share the site."""
        from .. import compile_service as csvc
        from ..ops.registry import policy_key
        pol = policy_key()
        key = (kind, bucket, self._int8, pol)
        hit = self._jits.get(key)
        if hit is not None:
            return hit
        ckey = csvc.canonical_key(
            site=self._draft_site,
            fn_id="draft:%s:%s" % (type(self._draft_model).__name__,
                                   csvc.source_token(
                                       type(self._draft_model))),
            signature=(kind, bucket, self._capacity, self._max_len,
                       self._spec_k, tuple(self._dkv_layout or ()),
                       self._vocab,
                       tuple((tuple(d.shape), str(d.dtype))
                             for d in self._draft_pred._param_datas)),
            policy=pol, donation=donate,
            device=csvc.device_token(device=self._pred.device),
            nonce=csvc.instance_nonce(self))
        entry = csvc.get_or_build(
            ckey, lambda: jax.jit(build(), donate_argnums=donate),
            provenance={"engine": self._name, "kind": kind,
                        "bucket": bucket, "spec_k": self._spec_k,
                        "capacity": self._capacity,
                        "max_len": self._max_len,
                        "policy_key": list(pol)},
            example_args=csvc.concrete_args(example_args)
            if example_args is not None else None)
        self._jits[key] = entry.fn
        return entry.fn

    def _kv_read(self, kv, scales, b):
        """The first ``b`` slots' caches in compute dtype (int8:
        dequantized through the quantization op, per-position scale rows
        broadcast against the trailing dims)."""
        if not self._int8:
            return [leaf[:b] for leaf in kv]
        from ..ops.registry import get_op
        deq = get_op("dequantize").fn
        out = []
        for (trail, dt), q, s in zip(self._kv_layout, kv, scales):
            rb = s[:b].reshape((b, self._max_len) + (1,) * len(trail))
            out.append(deq(q[:b], -rb, rb).astype(dt))
        return out

    def _kv_write_rows(self, kv, scales, entries, pos_b, act_b, b):
        """Persist this step's new k/v rows at (slot, pos) — inactive
        slots keep their old bytes (the model's row for them is
        garbage). int8: per-row symmetric quantization through the
        quantization op, scale rows ledgered next to the cache."""
        idx = jnp.arange(b)
        new_kv, new_scales = list(kv), None if scales is None \
            else list(scales)
        for i, entry in enumerate(entries):
            if self._int8:
                q, r = _quantize_rows(entry)
                old_q = new_kv[i][idx, pos_b]
                old_s = new_scales[i][idx, pos_b]
                q = jnp.where(_bcast(act_b, q.ndim), q, old_q)
                r = jnp.where(act_b, r, old_s)
                new_kv[i] = new_kv[i].at[idx, pos_b].set(q)
                new_scales[i] = new_scales[i].at[idx, pos_b].set(r)
            else:
                leaf = new_kv[i]
                old = leaf[idx, pos_b]
                row = jnp.where(_bcast(act_b, entry.ndim),
                                entry.astype(leaf.dtype), old)
                new_kv[i] = leaf.at[idx, pos_b].set(row)
        return new_kv, new_scales

    def _kv_gather(self, kv, scales, ptab_b, b):
        """Dense ``[b, max_len, ...]`` compute-dtype views of the paged
        pool through the slots' page tables (int8: dequantized) — the
        traced gather that makes paging invisible to ``decode_step``.
        Unmapped table entries read the scratch page: stale bytes, but
        the model's position mask never attends past ``pos``."""
        L, pt, maxp = self._max_len, self._pt, self._maxp
        out = []
        if not self._int8:
            for (trail, _dt), leaf in zip(self._kv_layout, kv):
                d = leaf[ptab_b]               # [b, maxp, pt, *trail]
                out.append(d.reshape((b, maxp * pt) + trail)[:, :L])
            return out
        from ..ops.registry import get_op
        deq = get_op("dequantize").fn
        for (trail, dt), q, s in zip(self._kv_layout, kv, scales):
            dq = q[ptab_b].reshape((b, maxp * pt) + trail)[:, :L]
            rs = s[ptab_b].reshape((b, maxp * pt))[:, :L]
            rb = rs.reshape((b, L) + (1,) * len(trail))
            out.append(deq(dq, -rb, rb).astype(dt))
        return out

    def _kv_scatter_rows(self, kv, scales, entries, page_b, off_b, keep_b):
        """Persist one new k/v row per slot at (page, offset); slots with
        ``keep_b`` False redirect to the scratch page — old pool bytes
        are never disturbed, and a page is quantized row-by-row as it
        fills, so old pages never requantize (int8 grids match the rowed
        engine's exactly)."""
        pg = jnp.where(keep_b, page_b, 0)
        new_kv = list(kv)
        new_scales = None if scales is None else list(scales)
        for i, entry in enumerate(entries):
            if self._int8:
                q, r = _quantize_rows(entry)
                new_kv[i] = new_kv[i].at[pg, off_b].set(q)
                new_scales[i] = new_scales[i].at[pg, off_b].set(r)
            else:
                new_kv[i] = new_kv[i].at[pg, off_b].set(
                    entry.astype(new_kv[i].dtype))
        return new_kv, new_scales

    def _kv_row_update(self, kv_b, entries, idx, wp, upd):
        """Refresh a dense gathered view with one sub-step's new rows so
        the NEXT chained forward sees them without re-gathering the
        pool. int8 runs the rows through the same quantize->dequantize
        roundtrip a pool re-gather would apply, so the speculative
        chain stays bit-identical to step-at-a-time decode."""
        out = []
        if not self._int8:
            for leaf, entry in zip(kv_b, entries):
                old = leaf[idx, wp]
                row = jnp.where(_bcast(upd, entry.ndim),
                                entry.astype(leaf.dtype), old)
                out.append(leaf.at[idx, wp].set(row))
            return out
        from ..ops.registry import get_op
        deq = get_op("dequantize").fn
        for (_trail, dt), leaf, entry in zip(self._kv_layout, kv_b,
                                             entries):
            q, r = _quantize_rows(entry)
            rb = _bcast(r, q.ndim)
            row = deq(q, -rb, rb).astype(dt)
            old = leaf[idx, wp]
            out.append(leaf.at[idx, wp].set(
                jnp.where(_bcast(upd, row.ndim), row, old)))
        return out

    def _page_of(self, ptab_b, idx, p):
        """Traced page lookup for position ``p`` (clamped into the
        table; callers mask overflow to the scratch page via keep)."""
        chunk = jnp.minimum(p // self._pt, self._maxp - 1)
        return ptab_b[idx, chunk]

    def _get_step_jit(self, b, example_args=None):
        model, pred = self._model, self._pred
        eos, max_len = self._eos, self._max_len
        pt = self._pt
        engine = self

        def build():
            fixed_key = jax.random.PRNGKey(0)

            def pure_rowed(carry, param_datas, param_ranges):
                from ..gluon.block import _run_traced
                kv, scales, tok, pos, active, rem = carry
                pds = pred._traced_params(param_datas, param_ranges)
                act_b, tok_b, pos_b = active[:b], tok[:b], pos[:b]
                kv_b = engine._kv_read(kv, scales, b)

                def body():
                    return model.decode_step(kv_b, tok_b, pos_b)

                (logits, entries), _aux = _run_traced(
                    pred._params, pds, fixed_key, False, body)
                next_tok = jnp.argmax(
                    jnp.asarray(logits, jnp.float32), axis=-1).astype(
                        jnp.int32)
                next_tok = jnp.where(act_b, next_tok, tok_b)
                new_pos_b = jnp.where(act_b, pos_b + 1, pos_b)
                rem_b = jnp.where(act_b, rem[:b] - 1, rem[:b])
                done_b = act_b & ((next_tok == eos) | (rem_b <= 0)
                                  | (new_pos_b >= max_len))
                kv, scales = engine._kv_write_rows(kv, scales, entries,
                                                   pos_b, act_b, b)
                tok = tok.at[:b].set(next_tok)
                pos = pos.at[:b].set(new_pos_b)
                active = active.at[:b].set(act_b & ~done_b)
                rem = rem.at[:b].set(rem_b)
                return ((kv, scales, tok, pos, active, rem),
                        (next_tok, done_b, logits))

            def pure_paged(carry, ptab, param_datas, param_ranges):
                from ..gluon.block import _run_traced
                kv, scales, tok, pos, active, rem = carry[:6]
                pds = pred._traced_params(param_datas, param_ranges)
                act_b, tok_b, pos_b = active[:b], tok[:b], pos[:b]
                ptab_b, idx = ptab[:b], jnp.arange(b)
                kv_b = engine._kv_gather(kv, scales, ptab_b, b)

                def body():
                    return model.decode_step(kv_b, tok_b, pos_b)

                (logits, entries), _aux = _run_traced(
                    pred._params, pds, fixed_key, False, body)
                next_tok = jnp.argmax(
                    jnp.asarray(logits, jnp.float32), axis=-1).astype(
                        jnp.int32)
                next_tok = jnp.where(act_b, next_tok, tok_b)
                new_pos_b = jnp.where(act_b, pos_b + 1, pos_b)
                rem_b = jnp.where(act_b, rem[:b] - 1, rem[:b])
                done_b = act_b & ((next_tok == eos) | (rem_b <= 0)
                                  | (new_pos_b >= max_len))
                keep = act_b & (pos_b < max_len)
                page_b = engine._page_of(ptab_b, idx, pos_b)
                kv, scales = engine._kv_scatter_rows(
                    kv, scales, entries, page_b, pos_b % pt, keep)
                tok = tok.at[:b].set(next_tok)
                pos = pos.at[:b].set(new_pos_b)
                active = active.at[:b].set(act_b & ~done_b)
                rem = rem.at[:b].set(rem_b)
                return ((kv, scales, tok, pos, active, rem) + carry[6:],
                        (next_tok, done_b, logits))

            return pure_paged if pt else pure_rowed

        return self._build_jit("step", b, build,
                               example_args=example_args)

    def _get_draft_jit(self, b, example_args=None):
        """The speculative proposer for cohort bucket ``b``: k greedy
        draft tokens per live slot, chained inside ONE executable over
        the draft's rowed KV (compiles pinned at ``serving.draft``)."""
        dmodel, dpred = self._draft_model, self._draft_pred
        k, max_len = self._spec_k, self._max_len

        def build():
            fixed_key = jax.random.PRNGKey(0)

            def pure(carry, param_datas, param_ranges):
                from ..gluon.block import _run_traced
                tok, pos, active = carry[2], carry[3], carry[4]
                dkv = list(carry[6])
                pds = dpred._traced_params(param_datas, param_ranges)
                act_b, idx = active[:b], jnp.arange(b)
                cur = tok[:b]
                props = []
                # k + 1 feeds for k proposals: the LAST feed exists only
                # to write d_k's KV row (logits discarded, DCE'd).  On a
                # full accept the commit's bonus token advances pos past
                # pos+k, so without that row the draft cache keeps a
                # permanent hole there and silently diverges after every
                # clean macro — acceptance decays even with draft==target.
                for j in range(k + 1):
                    p_j = pos[:b] + j
                    dkv_b = [leaf[:b] for leaf in dkv]

                    def body(kv_b=dkv_b, c=cur, p=p_j):
                        return dmodel.decode_step(kv_b, c, p)

                    (logits, entries), _aux = _run_traced(
                        dpred._params, pds, fixed_key, False, body)
                    wp = jnp.minimum(p_j, max_len - 1)
                    keep = act_b & (p_j < max_len)
                    for i, entry in enumerate(entries):
                        old = dkv[i][idx, wp]
                        row = jnp.where(_bcast(keep, entry.ndim),
                                        entry.astype(dkv[i].dtype), old)
                        dkv[i] = dkv[i].at[idx, wp].set(row)
                    if j < k:
                        cur = jnp.where(act_b, jnp.argmax(
                            jnp.asarray(logits, jnp.float32),
                            axis=-1).astype(jnp.int32), cur)
                        props.append(cur)
                return (carry[:6] + (dkv,), jnp.stack(props, axis=1))

            return pure

        return self._build_draft_jit("draft", b, build,
                                     example_args=example_args)

    def _get_verify_jit(self, b, example_args=None):
        """The speculative commit for cohort bucket ``b``: the TARGET
        model ingests the pending token plus the k draft proposals in
        one chained executable, emits greedy tokens g_1..g_{k+1}, and
        commits the longest prefix where draft == target — truncated by
        exactly the non-speculative stopping rule (eos / budget /
        max_len), so the committed stream is bit-identical to plain
        greedy decode. Rows written past the commit are stale-but-masked
        and get overwritten when those positions are really reached.

        The pool is gathered ONCE per macro-step. Models that implement
        :meth:`DecodeModel.decode_chunk` (f32 engines only) score all
        k+1 positions in a SINGLE causal forward; otherwise the k+1
        forwards chain over a dense working copy refreshed row-by-row
        (``_kv_row_update``). Either way the whole chain's rows write
        back to the pool in one batched scatter."""
        model, pred = self._model, self._pred
        eos, max_len = self._eos, self._max_len
        pt, k, maxp = self._pt, self._spec_k, self._maxp
        base = DecodeModel.decode_chunk
        chunked = (not self._int8) and getattr(
            type(model), "decode_chunk", base) is not base
        engine = self

        def build():
            fixed_key = jax.random.PRNGKey(0)

            def pure(carry, ptab, props, param_datas, param_ranges):
                from ..gluon.block import _run_traced
                kv, scales, tok, pos, active, rem = carry[:6]
                pds = pred._traced_params(param_datas, param_ranges)
                act_b, tok_b, pos_b = active[:b], tok[:b], pos[:b]
                rem_b = rem[:b]
                ptab_b, idx = ptab[:b], jnp.arange(b)
                kv_b = engine._kv_gather(kv, scales, ptab_b, b)
                if chunked:
                    ctoks = jnp.concatenate(
                        [tok_b[:, None], props], axis=1)   # [b, k+1]

                    def body(kv_j=kv_b, c=ctoks, p=pos_b):
                        return model.decode_chunk(kv_j, c, p)

                    (logits, entries), _aux = _run_traced(
                        pred._params, pds, fixed_key, False, body)
                    outs = jnp.argmax(
                        jnp.asarray(logits, jnp.float32),
                        axis=-1).astype(jnp.int32)         # [b, k+1]
                    stacked = [
                        e.reshape((b * (k + 1),) + tuple(e.shape[2:]))
                        for e in entries]
                else:
                    cur, gs, rows = tok_b, [], []
                    for j in range(k + 1):
                        p_j = pos_b + j

                        def body(kv_j=list(kv_b), c=cur, p=p_j):
                            return model.decode_step(kv_j, c, p)

                        (logits, entries), _aux = _run_traced(
                            pred._params, pds, fixed_key, False, body)
                        rows.append(entries)
                        gs.append(jnp.argmax(
                            jnp.asarray(logits, jnp.float32),
                            axis=-1).astype(jnp.int32))
                        if j < k:
                            wp = jnp.minimum(p_j, max_len - 1)
                            upd = act_b & (p_j < max_len)
                            kv_b = engine._kv_row_update(
                                kv_b, entries, idx, wp, upd)
                            cur = props[:, j]
                    outs = jnp.stack(gs, axis=1)          # [b, k+1]
                    stacked = [
                        jnp.stack([r[i] for r in rows], axis=1).reshape(
                            (b * (k + 1),) + tuple(rows[0][i].shape[1:]))
                        for i in range(len(rows[0]))]
                p_all = pos_b[:, None] + jnp.arange(k + 1)[None, :]
                keep = (act_b[:, None]
                        & (p_all < max_len)).reshape(-1)
                chunk = jnp.minimum(p_all // pt, maxp - 1)
                page = jnp.take_along_axis(
                    ptab_b, chunk, axis=1).reshape(-1)
                off = (p_all % pt).reshape(-1)
                kv, scales = engine._kv_scatter_rows(
                    kv, scales, stacked, page, off, keep)
                acc = jnp.cumprod(
                    (props == outs[:, :k]).astype(jnp.int32), axis=1)
                a = jnp.sum(acc, axis=1)              # accepted drafts
                i1 = jnp.arange(k + 1)[None, :]       # token index - 1
                stop = (outs == eos) \
                    | ((rem_b[:, None] - (i1 + 1)) <= 0) \
                    | ((pos_b[:, None] + i1 + 1) >= max_len)
                within = (i1 <= a[:, None]) & act_b[:, None]
                s_in = stop & within
                prev = jnp.cumsum(s_in, axis=1) - s_in.astype(jnp.int32)
                emit = within & (prev == 0)
                counts = jnp.sum(emit.astype(jnp.int32), axis=1)
                done_b = jnp.any(stop & emit, axis=1)
                last = jnp.maximum(counts - 1, 0)
                new_tok = jnp.where(act_b, outs[idx, last], tok_b)
                new_pos = pos_b + counts
                tok = tok.at[:b].set(new_tok)
                pos = pos.at[:b].set(new_pos)
                active = active.at[:b].set(act_b & ~done_b)
                rem = rem.at[:b].set(rem_b - counts)
                masked = jnp.where(emit, outs, -1)
                # one packed int32 fetch for the host: [b, k+1] masked
                # emitted tokens | counts | done — three d2h syncs per
                # macro-step would eat the dispatch savings speculation
                # exists to win
                packed = jnp.concatenate(
                    [masked, counts[:, None],
                     done_b.astype(jnp.int32)[:, None]], axis=1)
                return ((kv, scales, tok, pos, active, rem) + carry[6:],
                        packed)

            return pure

        return self._build_jit("verify", b, build,
                               example_args=example_args)

    def _get_extend_jit(self, s, example_args=None):
        """The prefix-hit prefill for seq bucket ``s``: the matched
        chunks' pages are SHARED (read-only), so only the novel suffix
        runs — a chained ``decode_step`` loop writing suffix rows into
        the slot's private pages and emitting the first token from the
        last prompt position. Prefill skips straight to the first novel
        token, per ISSUE 16."""
        model, pred = self._model, self._pred
        eos, max_len = self._eos, self._max_len
        pt, maxp = self._pt, self._maxp
        engine = self

        def build():
            fixed_key = jax.random.PRNGKey(0)

            def pure(carry, ptab_row, toks, m, n, slot, max_new,
                     param_datas, param_ranges):
                from ..gluon.block import _run_traced
                kv, scales, tok, pos, active, rem = carry[:6]
                pds = pred._traced_params(param_datas, param_ranges)

                def step_t(t, state):
                    kv, scales, fl = state
                    p = m + t
                    proc = p < n
                    kv_b = engine._kv_gather(kv, scales, ptab_row[None], 1)
                    cur = toks[jnp.minimum(p, s - 1)][None]

                    def body(kv_j=kv_b, c=cur, pp=p[None]):
                        return model.decode_step(kv_j, c, pp)

                    (logits, entries), _aux = _run_traced(
                        pred._params, pds, fixed_key, False, body)
                    keep = jnp.asarray(proc & (p < max_len))[None]
                    chunk = jnp.minimum(p // pt, maxp - 1)
                    page = ptab_row[chunk][None]
                    kv, scales = engine._kv_scatter_rows(
                        kv, scales, entries, page, (p % pt)[None], keep)
                    fl = jnp.where(p == n - 1,
                                   jnp.asarray(logits[0], jnp.float32), fl)
                    return (kv, scales, fl)

                kv, scales, fl = lax.fori_loop(
                    0, s, step_t,
                    (kv, scales, jnp.zeros((engine._vocab,), jnp.float32)))
                first = jnp.argmax(fl).astype(jnp.int32)
                done0 = (first == eos) | (max_new <= 1) | (n >= max_len)
                tok = tok.at[slot].set(first)
                pos = pos.at[slot].set(n)
                active = active.at[slot].set(~done0)
                rem = rem.at[slot].set(max_new - 1)
                out = jnp.stack([first, done0.astype(jnp.int32)])
                return ((kv, scales, tok, pos, active, rem) + carry[6:],
                        out)

            return pure

        return self._build_jit("extend", s, build,
                               example_args=example_args)

    def _get_insert_jit(self, s, example_args=None):
        """Slot insert for prefill seq bucket ``s``: a device-side
        ``dynamic_update_slice`` of the prompt's KV into a TRACED slot
        index — joining the running cohort never recompiles. Also samples
        the first token from the prefill logits at the prompt's true
        length (and marks the slot done-at-insert when that token already
        ends the sequence), so time-to-first-token needs no decode step.
        Paged mode instead scatters the prompt's KV page-chunk by
        page-chunk into the TRACED page ids the host allocated (the
        prefill -> page handoff); spec mode additionally seeds the
        draft's rowed KV — the draft prefill runs FUSED inside this
        executable (prompt tokens + draft params ride as traced args),
        so admitting a request costs one insert dispatch, not a second
        Predictor round-trip for the draft."""
        eos, max_len = self._eos, self._max_len
        pt, spec = self._pt, bool(self._spec_k)
        dmodel, dpred = self._draft_model, self._draft_pred
        engine = self

        def build():
            fixed_key = jax.random.PRNGKey(0)
            def write_rowed(kv, scales, seq_kv, slot):
                for i, leaf in enumerate(seq_kv):
                    row = leaf[0]                      # [s, *trail]
                    if engine._int8:
                        q, r = _quantize_rows(row)
                        kv[i] = lax.dynamic_update_slice(
                            kv[i], q[None],
                            (slot,) + (0,) * (kv[i].ndim - 1))
                        scales[i] = lax.dynamic_update_slice(
                            scales[i], r[None], (slot, 0))
                    else:
                        kv[i] = lax.dynamic_update_slice(
                            kv[i], row[None].astype(kv[i].dtype),
                            (slot,) + (0,) * (kv[i].ndim - 1))
                return kv, scales

            def write_paged(kv, scales, seq_kv, pages):
                chunks = int(pages.shape[0])
                pad = chunks * pt - s
                for i, leaf in enumerate(seq_kv):
                    row = leaf[0]                      # [s, *trail]
                    if pad:
                        row = jnp.pad(row, ((0, pad),)
                                      + ((0, 0),) * (row.ndim - 1))
                    if engine._int8:
                        q, r = _quantize_rows(row)
                        qc = q.reshape((chunks, pt) + q.shape[1:])
                        rc = r.reshape((chunks, pt))
                        for j in range(chunks):
                            kv[i] = kv[i].at[pages[j]].set(qc[j])
                            scales[i] = scales[i].at[pages[j]].set(rc[j])
                    else:
                        rc = row.astype(kv[i].dtype).reshape(
                            (chunks, pt) + row.shape[1:])
                        for j in range(chunks):
                            kv[i] = kv[i].at[pages[j]].set(rc[j])
                return kv, scales

            def finish(carry_rest, tok, pos, active, rem, first, done0,
                       slot, n, max_new):
                tok = tok.at[slot].set(first)
                pos = pos.at[slot].set(n)
                active = active.at[slot].set(~done0)
                rem = rem.at[slot].set(max_new - 1)
                out = jnp.stack([first, done0.astype(jnp.int32)])
                return carry_rest + (tok, pos, active, rem), out

            def pure_rowed(carry, seq_kv, logits, slot, n, max_new):
                kv, scales, tok, pos, active, rem = carry
                first = jnp.argmax(jnp.asarray(logits[0, n - 1],
                                               jnp.float32)).astype(jnp.int32)
                done0 = (first == eos) | (max_new <= 1) | (n >= max_len)
                kv, scales = write_rowed(kv, scales, seq_kv, slot)
                (kv, scales, tok, pos, active, rem), out = finish(
                    (kv, scales), tok, pos, active, rem, first, done0,
                    slot, n, max_new)
                return (kv, scales, tok, pos, active, rem), out

            def pure_paged(carry, seq_kv, logits, pages, slot, n, max_new):
                kv, scales, tok, pos, active, rem = carry[:6]
                first = jnp.argmax(jnp.asarray(logits[0, n - 1],
                                               jnp.float32)).astype(jnp.int32)
                done0 = (first == eos) | (max_new <= 1) | (n >= max_len)
                kv, scales = write_paged(kv, scales, seq_kv, pages)
                (kv, scales, tok, pos, active, rem), out = finish(
                    (kv, scales), tok, pos, active, rem, first, done0,
                    slot, n, max_new)
                return ((kv, scales, tok, pos, active, rem) + carry[6:],
                        out)

            def pure_spec(carry, seq_kv, logits, toks, pages, slot, n,
                          max_new, ddatas, dranges):
                from ..gluon.block import _run_traced
                kv, scales, tok, pos, active, rem = carry[:6]
                dkv = list(carry[6])
                first = jnp.argmax(jnp.asarray(logits[0, n - 1],
                                               jnp.float32)).astype(jnp.int32)
                done0 = (first == eos) | (max_new <= 1) | (n >= max_len)
                kv, scales = write_paged(kv, scales, seq_kv, pages)
                dpds = dpred._traced_params(ddatas, dranges)

                def dbody():
                    return dmodel(NDArray(toks[None, :]))

                dout, _aux = _run_traced(dpred._params, dpds, fixed_key,
                                         False, dbody)
                for i, leaf in enumerate(dout[1:]):
                    row = leaf._data[0][None]          # [1, s, *trail]
                    dkv[i] = lax.dynamic_update_slice(
                        dkv[i], row.astype(dkv[i].dtype),
                        (slot,) + (0,) * (dkv[i].ndim - 1))
                (kv, scales, tok, pos, active, rem), out = finish(
                    (kv, scales), tok, pos, active, rem, first, done0,
                    slot, n, max_new)
                return ((kv, scales, tok, pos, active, rem, dkv), out)

            if spec:
                return pure_spec
            return pure_paged if pt else pure_rowed

        return self._build_jit("insert", s, build,
                               example_args=example_args)

    def compile_stats(self):
        """The watchdog's view of this engine's decode-cache compiles."""
        return telemetry.retrace_stats(self._site)

    # ------------------------------------------------------------- admission
    def submit(self, prompt, max_new=None, deadline_ms=None):
        """Admit one prompt (1-d int token ids). Returns a
        :class:`DecodeFuture` whose ``result()`` is the generated int32
        token array; sheds :class:`QueueFull` past the queue bound or
        the accountant's KV-residency budget."""
        trace = telemetry.new_trace()
        t0 = time.perf_counter()
        with telemetry.trace_handoff(trace), \
                telemetry.span("serving.submit"):
            seq = self._admit(prompt, max_new, deadline_ms, trace)
        telemetry.add_stage(trace, "serving.submit",
                            time.perf_counter() - t0)
        return seq.future

    def _admit(self, prompt, max_new, deadline_ms, trace):
        if self._kv_layout is None:
            # refuse at admission like start() does: a cold engine would
            # otherwise crash opaquely inside the insert jit on a None
            # carry at first poll
            raise MXNetError("submit on a cold DecodeEngine: warmup() "
                             "first (AOT replay needs its executables "
                             "before traffic)")
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size < 1:
            raise MXNetError("submit: prompt must be a non-empty 1-d "
                             "token-id array, got shape %s"
                             % (tuple(prompt.shape),))
        if not np.issubdtype(prompt.dtype, np.integer):
            raise MXNetError("submit: prompt dtype %s is not integer "
                             "token ids" % prompt.dtype)
        prompt = prompt.astype(np.int32)
        self._prefill_spec.seq_bucket(prompt.size)  # loud past-max refusal
        if prompt.size >= self._max_len:
            raise MXNetError(
                "submit: prompt of %d tokens leaves no room to decode "
                "within max_len=%d" % (prompt.size, self._max_len))
        max_new = int(max_new if max_new is not None
                      else self._max_new_default)
        if max_new < 1:
            raise MXNetError("submit: max_new must be >= 1, got %d"
                             % max_new)
        now = self._clock()
        deadline = None if deadline_ms is None else now + deadline_ms / 1e3
        seq = _Sequence(prompt, max_new, deadline, now, trace)
        if trace is not None:
            # the trace identity rides the future from ADMISSION, not
            # delivery: a sequence failed by the wedge watchdog must be
            # correlatable with its flight-recorder artifact
            seq.future.trace_id = trace.trace_id
        with self._cond:
            if self._crashed:
                self._shed("worker_crashed")
            if self._draining or self._closed:
                self._shed("draining")
            if len(self._pending) >= self._max_queue:
                self._shed("queue_full")
            if self._acct is not None:
                # atomic check-and-ledger BEFORE the append, under the
                # admission lock: the loop thread can pop (and
                # occupy/unqueue) the sequence the instant the lock
                # releases, and a separate check would let concurrent
                # submits overshoot the overcommit bound. Paged mode
                # admits by real page headroom: the prompt's pages are
                # reserved here (exact, not worst-case rows) and decode
                # growth draws page-by-page later.
                need = 1 if not self._pt \
                    else -(-min(prompt.size + 1, self._max_len) // self._pt)
                if not self._acct.try_admit(self._tag, n=need):
                    self._shed("kv_residency")
                seq.reserved = need if self._pt else 0
            self._pending.append(seq)
            telemetry.gauge("serving.queue_depth",
                            len(self._pending))
            self._cond.notify_all()
        telemetry.inc("serving.requests")
        return seq

    def _shed(self, reason):
        telemetry.inc("serving.shed", tag=reason)
        raise QueueFull("request shed: %s" % reason)

    # --------------------------------------------------------------- serving
    def poll(self):
        """One engine cycle NOW (wedge scan -> slot admission -> one
        decode step) — the fake-clock test hook and the no-thread drive.
        Returns the number of decode steps executed (0 or 1)."""
        try:
            maybe_oom()  # fault kind 'oom': the decode-loop OOM site
            self._scan_wedges(self._clock())
            self._admit_pending()
            steps = self._step_once()
        except Exception as e:
            # an HBM OOM leaves the artifact here too (the no-thread
            # drive has no crash barrier); the raise stays loud either way
            self._flight_if_oom(e)
            raise
        with self._cond:
            self._cycles += 1
        return steps

    def _flight_if_oom(self, exc):
        """Flight-record a device allocator failure with the KV-cache
        accountant's residency view attached — which cohort/bucket ate
        the headroom is readable post-mortem."""
        from .. import xprof
        if xprof.is_oom(exc):
            xprof.oom_flight(
                "serving.decode", exc,
                extra={"kv": self._acct.snapshot()
                       if self._acct is not None else None})

    def _free_slot_locked(self):
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _admit_pending(self):
        """Move queued prompts into free slots: prefill through the
        bucketed Predictor, then the device-side slot insert — between
        steps, never mid-step, and never with a recompile (the insert
        jit's slot index is traced). The continuous-batching half of the
        throughput story: a restart-per-batch engine
        (``continuous=False``) only refills once the WHOLE cohort
        drained — the idle-slot steps it burns are exactly the tokens/s
        gap serve_bench's decode gate measures."""
        filling = False
        while True:
            with self._cond:
                if not self._pending:
                    return
                if not self._continuous and self._live > 0 and not filling:
                    # restart-per-batch: a draining cohort admits nobody —
                    # but once it fully drains, the whole next cohort
                    # fills in one pass (filling stays True for the rest
                    # of this call)
                    return
                filling = True
                slot = self._free_slot_locked()
                if slot is None:
                    return
                seq = self._pending.popleft()
                self._inflight_seq = seq
                telemetry.gauge("serving.queue_depth", len(self._pending))
            try:
                now = self._clock()
                if seq.deadline is not None and now > seq.deadline:
                    telemetry.inc("serving.deadline_expired")
                    self._free_seq_ledger(seq, slotted=False)
                    self._fail(seq, DeadlineExceeded(
                        "deadline passed before a KV slot freed (queued "
                        "%.1f ms)" % ((now - seq.t_enq) * 1e3)))
                    continue
                telemetry.add_stage(seq.trace, "serving.queue_wait",
                                    max(0.0, now - seq.t_enq), event=True)
                try:
                    self._prefill_into(seq, slot)
                except Exception as e:  # noqa: BLE001 — complete, re-raise
                    # the popped sequence is in neither _pending nor
                    # _slots: without failing it HERE, the crash barrier
                    # would strand its future forever and leak its
                    # accountant queued count
                    if seq.slot is None and not seq.future.done():
                        self._free_seq_ledger(seq, slotted=False)
                        self._fail(seq, MXNetError(
                            "prefill failed: %s: %s"
                            % (type(e).__name__, e)))
                    raise
            finally:
                with self._cond:
                    self._inflight_seq = None

    def _prefill_into(self, seq, slot):
        """Prefill one prompt and insert its KV into ``slot``. The
        ``serving.prefill`` stage covers the bucketed prompt forward AND
        the insert dispatch; the first token's fetch is the
        ``serving.fetch`` d2h that makes TTFT a delivered fact, not a
        device promise."""
        n = int(seq.prompt.size)
        s_bucket = self._prefill_spec.seq_bucket(n)
        # pad HOST-side to the seq bucket: prompts arrive as host numpy
        # with arbitrary raw lengths, and an eager device-side pad would
        # compile one anonymous jnp.pad executable per distinct length —
        # exactly the shape churn the bucket discipline exists to kill
        prompt = seq.prompt if n == s_bucket else np.pad(
            seq.prompt, (0, s_bucket - n),
            constant_values=self._prefill_spec.pad_value)
        # paged mode: map the prompt's pages BEFORE any device work —
        # shared prefix chunks attach by refcount (never re-prefilled,
        # never re-stored), the rest come off the free list against this
        # sequence's admission reservation
        m_chunks = 0
        if self._pt:
            chunks = -(-n // self._pt)
            with self._cond:
                if self._prefix is not None:
                    m_chunks, pids = self._prefix.lookup(seq.prompt,
                                                         self._pt)
                    for pid in pids:
                        self._share_page_locked(seq, pid)
                ok = True
                while len(seq.pages) < chunks:
                    if self._take_page_locked(seq) is None:
                        ok = False
                        break
                if ok:
                    self._ptab[slot, :] = 0
                    self._ptab[slot, :len(seq.pages)] = seq.pages
                self._page_gauges_locked()
            if self._prefix is not None:
                if m_chunks:
                    telemetry.inc("serving.prefix.hits")
                else:
                    telemetry.inc("serving.prefix.misses")
            if not ok:
                # page pool exhausted at prefill: shed loud, exactly the
                # kv_residency degradation row — never a silent park
                telemetry.inc("serving.shed", tag="kv_residency")
                self._free_seq_ledger(seq, slotted=False)
                self._fail(seq, QueueFull(
                    "request shed: kv_residency (KV page pool exhausted "
                    "at prefill)"))
                return
        # the prefill/insert dispatch is device work on the SAME possibly-
        # wedged device the step loop replays: bracket it with its own
        # watchdog entry, or a wedge here would hang the loop thread with
        # no detection at all (the step watchdog only covers steps)
        p_entry = {"seq": seq, "deadline": self._clock() + self._timeout_s,
                   "done": False, "abandoned": False}
        with self._cond:
            self._prefill_armed = p_entry
        try:
            with telemetry.trace_handoff(seq.trace):
                t0 = time.perf_counter()
                # numpy scalars, NOT jnp — a jnp.int32() call is an eager
                # device op per argument, three per insert adds up
                if m_chunks:
                    # prefix HIT: the matched chunks already hold their
                    # KV — skip the Predictor prefill entirely and extend
                    # in-place from the first novel token
                    with self._cond:
                        ptab_row = self._ptab[slot].copy()
                    pd, pr = self._pred.param_args()
                    out, gen, superseded = self._dispatch_carry(
                        self._get_extend_jit(s_bucket), ptab_row,
                        prompt.astype(np.int32, copy=False),
                        np.int32(m_chunks * self._pt), np.int32(n),
                        np.int32(slot), np.int32(seq.max_new), pd, pr)
                else:
                    flat, _fmt, _b = self._pred.predict_flat(
                        (prompt[None, :],))
                    if not self._pt:
                        out, gen, superseded = self._dispatch_carry(
                            self._get_insert_jit(s_bucket),
                            [leaf._data for leaf in flat[1:]],
                            flat[0]._data, np.int32(slot), np.int32(n),
                            np.int32(seq.max_new))
                    else:
                        pages_arg = np.zeros(-(-s_bucket // self._pt),
                                             np.int32)
                        pages_arg[:len(seq.pages)] = seq.pages
                        if self._spec_k:
                            out, gen, superseded = self._dispatch_carry(
                                self._get_insert_jit(s_bucket),
                                [leaf._data for leaf in flat[1:]],
                                flat[0]._data,
                                prompt.astype(np.int32, copy=False),
                                pages_arg, np.int32(slot), np.int32(n),
                                np.int32(seq.max_new),
                                *self._draft_pred.param_args())
                        else:
                            out, gen, superseded = self._dispatch_carry(
                                self._get_insert_jit(s_bucket),
                                [leaf._data for leaf in flat[1:]],
                                flat[0]._data, pages_arg, np.int32(slot),
                                np.int32(n), np.int32(seq.max_new))
                if superseded:
                    # a wedge reset landed mid-insert: this prompt's KV
                    # went into the superseded carry — a wedge casualty,
                    # failed loud like the cohort it would have joined
                    self._fail_wedge_casualty(seq)
                    return
                telemetry.add_stage(seq.trace, "serving.prefill",
                                    time.perf_counter() - t0)
                t0 = time.perf_counter()
                with telemetry.span("serving.fetch", cat="sync"):
                    first_done = NDArray(out).asnumpy()
                telemetry.add_stage(seq.trace, "serving.fetch",
                                    time.perf_counter() - t0)
        finally:
            with self._cond:
                p_entry["done"] = True
                if self._prefill_armed is p_entry:
                    self._prefill_armed = None
        if seq.future.done():
            # a teardown (wedge trip, crash barrier, close) settled this
            # sequence while the device answered late: delivering or
            # touching the ledger again would double-count
            return
        seq.tokens.append(int(first_done[0]))
        ttft = self._clock() - seq.t_enq
        seq.future.ttft_s = ttft
        telemetry.observe("serving.ttft_s", ttft)
        telemetry.inc("serving.decode.tokens")
        if int(first_done[1]):
            # done at insert (eos / max_new==1): the slot was marked
            # inactive in-executable; deliver without ever stepping —
            # but the prompt's full chunks still publish to the prefix
            # cache (the cache pin keeps them alive past the deref)
            if self._pt:
                with self._cond:
                    self._register_prefix_locked(seq, m_chunks)
            self._free_seq_ledger(seq, slotted=False)
            self._deliver(seq)
            return
        with self._cond:
            if self._carry_gen != gen or self._closed or self._crashed \
                    or seq.future.done():
                # a reset/teardown landed AFTER the write-back but BEFORE
                # this registration — or the prefill watchdog already
                # failed this sequence: the fresh carry has
                # active[slot]=False (or the engine/future is gone), so
                # registering would park it forever or double-ledger it
                register = False
            else:
                register = True
                seq.slot = slot
                seq.pos = n
                self._slots[slot] = seq
                self._live += 1
                telemetry.gauge("serving.decode.slots", self._live)
                if self._pt:
                    # pages moved queued->live one at a time as they were
                    # taken; what's left is the prefix publication and the
                    # residency gauges
                    self._register_prefix_locked(seq, m_chunks)
                elif self._acct is not None:
                    # inside the lock: a reset landing right after
                    # registration must find the ledger already moved to
                    # live, so its straggler release balances exactly
                    self._acct.occupy(self._tag)
        if not register:
            self._fail_wedge_casualty(seq)
            return

    def _dispatch_carry(self, jitted, *args):
        """THE wedge-safe carry dispatch protocol (one copy, shared by
        the step and insert paths): snapshot carry + generation under the
        lock, dispatch OUTSIDE it — on a wedged device even the dispatch
        can block, and a blocked dispatch
        holding ``self._cond`` would deadlock every submit and the
        monitor's wedge scan, the exact moment it must run — then write
        the new carry back only if no wedge reset superseded the
        snapshot. Returns ``(emitted, gen, superseded)``; ``gen`` lets
        the caller re-check for resets landing after its own write-back
        (e.g. before slot registration)."""
        with self._cond:
            carry, gen = self._carry, self._carry_gen
        new_carry, out = jitted(carry, *args)
        with self._cond:
            superseded = self._carry_gen != gen
            if not superseded:
                self._carry = new_carry
        return out, gen, superseded

    def _step_once(self):
        """One decode step for the live cohort at its smallest covering
        capacity bucket: pure replay of the AOT executable (donated
        carry), zero d2h inside the armed ``serving.decode`` span; the
        one declared fetch (sampled tokens + done mask) follows in
        ``serving.fetch``; finished sequences free their slots before
        the next admission pass."""
        with self._cond:
            if self._live == 0:
                return 0
            prev = self._armed
            if prev is not None and not prev["done"] \
                    and not prev["abandoned"]:
                # a step is still in flight (a wedge in the making): a
                # new dispatch must NOT clobber its watchdog entry — the
                # unresolved entry would be discarded before it could
                # trip and the wedge would be swallowed silently
                return 0
            casualties = []
            if self._pt:
                # pre-step page allocation: every live sequence must have
                # a page mapped for each position this step writes (one,
                # or k+1 under speculation) BEFORE the dispatch — the
                # executable only gathers/scatters through the table it
                # is handed. Exhaustion shed a sequence loud; its table
                # row zeroes so the zombie slot's writes land on the
                # scratch page until the slot is re-inserted.
                t_step = 1 + self._spec_k
                for s in [x for x in self._slots if x is not None]:
                    hi_chunk = min(s.pos + t_step - 1,
                                   self._max_len - 1) // self._pt
                    ok = True
                    while len(s.pages) <= hi_chunk:
                        if self._take_page_locked(s) is None:
                            ok = False
                            break
                    if ok:
                        self._ptab[s.slot, :len(s.pages)] = s.pages
                    else:
                        self._ptab[s.slot, :] = 0
                        self._slots[s.slot] = None
                        s.slot = None
                        self._live -= 1
                        casualties.append(s)
                        # return the casualty's pages NOW, inside the
                        # pass — the next lane may need only one of
                        # them: shed the minimum, not every grower
                        # caught behind the same dry free list
                        self._free_seq_ledger(s, slotted=True)
                if casualties:
                    telemetry.gauge("serving.decode.slots", self._live)
                    self._page_gauges_locked()
            if self._live == 0:
                alive = False
            else:
                alive = True
                hi = max(i for i, s in enumerate(self._slots)
                         if s is not None) + 1
                b = self._decode_spec.slot_bucket(hi)
                live = [s for s in self._slots[:b] if s is not None]
                idx = self._step_index
                self._step_index += 1
                entry = {"live": live, "idx": idx, "done": False,
                         "abandoned": False,
                         "deadline": self._clock() + self._timeout_s}
                self._armed = entry
                ptab_snap = self._ptab.copy() if self._pt else None
        for s in casualties:
            # pages already came home inside the allocation pass — only
            # the shed accounting and the loud failure happen here
            telemetry.inc("serving.shed", tag="kv_residency")
            self._fail(s, QueueFull(
                "request shed: kv_residency (KV page pool exhausted "
                "mid-decode)"))
        if not alive:
            return 0
        lead = live[0]
        with telemetry.trace_handoff(lead.trace):
            t0 = time.perf_counter()
            wedged = inject("decode_wedge", idx)
            if not wedged:
                with telemetry.span("serving.decode", d2h=True):
                    if self._spec_k:
                        emitted, _gen, _sup = self._dispatch_spec(
                            b, ptab_snap)
                    elif self._pt:
                        emitted, _gen, _sup = self._dispatch_carry(
                            self._get_step_jit(b), ptab_snap,
                            *self._pred.param_args())
                    else:
                        emitted, _gen, _sup = self._dispatch_carry(
                            self._get_step_jit(b),
                            self._pred._param_datas,
                            self._pred._param_ranges)
            dt = time.perf_counter() - t0
            for s in live:
                telemetry.add_stage(s.trace, "serving.decode", dt)
            if wedged:
                # simulated wedge: the device "never answers" — the entry
                # stays armed and the watchdog scan (monitor thread, or
                # the next poll under a fake clock) trips it
                return 1
            t0 = time.perf_counter()
            with telemetry.span("serving.fetch", cat="sync"):
                if self._spec_k:
                    packed = NDArray(emitted).asnumpy()
                    toks = packed[:, :self._spec_k + 1]
                    counts = packed[:, self._spec_k + 1]
                    done = packed[:, self._spec_k + 2]
                else:
                    toks = NDArray(emitted[0]).asnumpy()
                    counts = None
                    done = NDArray(emitted[1]).asnumpy()
            dt = time.perf_counter() - t0
            for s in live:
                telemetry.add_stage(s.trace, "serving.fetch", dt)
        with self._cond:
            stale = entry["abandoned"]
            entry["done"] = True
            if self._armed is entry:
                self._armed = None
        if stale:
            # the wedge watchdog already failed this cohort and reset the
            # carry — a late answer must not resurrect freed slots, skew
            # the replay counter, or leave superseded-carry logits in the
            # diagnostic probe hook
            return 1
        if self._spec_k:
            # accept-rate accounting: each live lane verified k proposals
            # and committed counts-1 of them (the +1 is the free token
            # the verify pass itself produces)
            telemetry.inc("serving.decode.spec_proposed",
                          self._spec_k * len(live))
            telemetry.inc("serving.decode.spec_accepted",
                          int(sum(max(0, int(counts[s.slot]) - 1)
                                  for s in live)))
            self._last_logits = None
        else:
            self._last_logits = emitted[2]
        telemetry.inc("serving.decode.steps")
        self._harvest(live, toks, done, counts)
        return 1

    def _dispatch_spec(self, b, ptab_snap):
        """One speculative macro-step: the draft proposes k tokens
        (rowed draft KV inside the carry), the target verifies the whole
        chain in one paged executable — two dispatches replace k+1,
        and the commit rule keeps the emitted stream bit-identical to
        plain greedy. Composed INSIDE one carry write-back so a wedge
        reset between the halves supersedes both."""
        draft_fn = self._get_draft_jit(b)
        verify_fn = self._get_verify_jit(b)

        def composed(carry, ptab, dpd, dpr, pd, pr):
            carry, props = draft_fn(carry, dpd, dpr)
            return verify_fn(carry, ptab, props, pd, pr)

        return self._dispatch_carry(
            composed, ptab_snap,
            *self._draft_pred.param_args(), *self._pred.param_args())

    def _harvest(self, live, toks, done, counts=None):
        finished = []
        with self._cond:
            for seq in live:
                slot = seq.slot
                if counts is None:
                    seq.tokens.append(int(toks[slot]))
                    telemetry.inc("serving.decode.tokens")
                    seq.pos += 1
                else:
                    c = int(counts[slot])
                    for i in range(c):
                        seq.tokens.append(int(toks[slot][i]))
                    telemetry.inc("serving.decode.tokens", c)
                    seq.pos += c
                if done[slot]:
                    finished.append(seq)
                    self._slots[slot] = None
                    if self._pt:
                        self._ptab[slot, :] = 0
                    seq.slot = None
                    self._live -= 1
            telemetry.gauge("serving.decode.slots", self._live)
            if self._pt:
                self._page_gauges_locked()
            if finished:
                self._cond.notify_all()
        for seq in finished:
            self._free_seq_ledger(seq, slotted=True)
            self._deliver(seq)

    def _deliver(self, seq):
        done = self._clock()
        t0 = time.perf_counter()
        with telemetry.trace_handoff(seq.trace), \
                telemetry.span("serving.deliver"):
            seq.future._value = np.asarray(seq.tokens, np.int32)
        telemetry.add_stage(seq.trace, "serving.deliver",
                            time.perf_counter() - t0)
        if seq.trace is not None:
            seq.future.trace_id = seq.trace.trace_id
            seq.future.breakdown = telemetry.trace_breakdown(seq.trace)
            seq.future.e2e_s = done - seq.t_enq
        seq.future._event.set()
        telemetry.observe("serving.latency_s", done - seq.t_enq)

    @staticmethod
    def _fail(seq, error):
        seq.future._error = error
        seq.future._event.set()

    def _fail_wedge_casualty(self, seq):
        """Fail a mid-insert sequence whose carry was reset out from
        under it (one copy for the write-back and registration checks —
        the ledger call and the message must never diverge)."""
        if seq.future.done():
            return
        self._free_seq_ledger(seq, slotted=False)
        self._fail(seq, DeadlineExceeded(
            "cohort reset by the wedge watchdog during this prompt's "
            "slot insert"))

    def _collect_teardown_locked(self):
        """Under ``self._cond``: collect EVERY unfinished sequence —
        pending, slotted, and the popped-but-unregistered in-flight one
        — clear the slot table and the armed entry, and return
        ``(seqs, slotted_ids)``. One copy of the ledger-critical sweep
        shared by the crash barrier and close(): the release-vs-unqueue
        split and the slot-nulling must never diverge between them."""
        dead = list(self._pending) + [s for s in self._slots
                                      if s is not None]
        slotted = {id(s) for s in self._slots if s is not None}
        if self._inflight_seq is not None:
            dead.append(self._inflight_seq)
            self._inflight_seq = None
        self._pending.clear()
        for s in dead:
            # a later scan/harvest must never see a freed sequence as
            # still slotted (double-release, negative live count)
            s.slot = None
        self._slots = [None] * self._capacity
        self._live = 0
        if self._armed is not None:
            self._armed["abandoned"] = True
            self._armed = None
        if self._prefill_armed is not None:
            self._prefill_armed["abandoned"] = True
            self._prefill_armed = None
        # a late write-back / slot registration / done-at-insert from a
        # thread that resumes after this teardown must see the carry as
        # superseded — the sequences it would touch are failed HERE
        self._carry_gen += 1
        if self._pt:
            # the prefix cache's pins die with the cohort: the teardown
            # invalidated the device pages they point at, and a stale
            # entry surviving here would hand a future prompt garbage KV
            if self._prefix is not None:
                for pid in self._prefix.drain():
                    self._decref_locked(pid)
            self._ptab[:, :] = 0
            self._page_gauges_locked()
        self._cond.notify_all()
        return dead, slotted

    def _fail_collected(self, dead, slotted, err):
        for seq in dead:
            if seq.future.done():
                continue  # e.g. the in-flight seq a racing path handled
            self._free_seq_ledger(seq, id(seq) in slotted)
            self._fail(seq, err)

    # ------------------------------------------------------- wedge watchdog
    def _check_probation(self, now):
        """After a wedge trip in THREADED mode the loop thread may be
        genuinely blocked inside the wedged device call — the one thread
        that serves the queue. Probation gives it one full timeout window
        to make loop progress; no progress means blocked-forever, and
        shed-never-hang demands the crash barrier: fail the pending
        queue loud, refuse new submits. (An injected wedge's loop thread
        keeps cycling, so probation clears and serving resumes.)"""
        with self._cond:
            prob = self._probation
            if prob is None:
                return
            deadline, cycles0 = prob
            if self._cycles != cycles0:
                self._probation = None   # loop progressed: recovered
                return
            if now < deadline:
                return
            self._probation = None
        self._worker_crashed(RuntimeError(
            "decode loop made no progress for %.0f ms after a wedge "
            "trip — blocked inside the wedged device call"
            % (self._timeout_s * 1e3)))

    @staticmethod
    def _entry_due(entry, now):
        return entry is not None and not entry["done"] \
            and not entry["abandoned"] and now >= entry["deadline"]

    def _scan_wedges(self, now):
        """A dispatch with no answer past the timeout is a wedged device:
        a STEP wedge kills its slot cohort, a PREFILL/insert wedge kills
        the in-flight prompt (and, since the same device carries the
        cohort, everything slotted falls to the straggler sweep below).
        Either way the stuck sequences fail LOUD (their futures raise,
        their trace_ids land in the ``decode_wedge`` flight artifact) and
        the carry re-allocates — the device state that never answered is
        unrecoverable, the queue is not."""
        self._check_probation(now)
        with self._cond:
            entry = self._armed
            if self._entry_due(entry, now):
                entry["abandoned"] = True
                self._armed = None
                kind, idx = "step", entry["idx"]
                stuck = list(entry["live"])    # slotted: acct release
                queued_stuck = []
            else:
                entry = self._prefill_armed
                if not self._entry_due(entry, now):
                    return
                entry["abandoned"] = True
                self._prefill_armed = None
                kind, idx = "prefill", -1
                stuck = []
                queued_stuck = [entry["seq"]]  # never slotted: unqueue
                # settle the casualty ATOMICALLY with the abandonment: a
                # late-completing prefill on the loop thread checks
                # future.done() under this same lock, so the ledger
                # moves exactly once (failing it after the flight IO
                # below would leave a window to register/deliver AND be
                # unqueued — a double decrement)
                seq = entry["seq"]
                if not seq.future.done():
                    self._free_seq_ledger(seq, slotted=False)
                    self._fail(seq, DeadlineExceeded(
                        "decode prefill dispatch wedged: no device "
                        "answer within %.0f ms" % (self._timeout_s * 1e3)))
            for seq in stuck:
                if seq.slot is not None:
                    self._slots[seq.slot] = None
                    seq.slot = None
                    self._live -= 1
            telemetry.gauge("serving.decode.slots", self._live)
        telemetry.inc("serving.decode.wedges")
        _log.warning(
            "serving: decode %s dispatch %d wedged (no answer in %.0f ms)"
            " — failing %d stuck sequence(s), resetting the cohort carry",
            kind, idx, self._timeout_s * 1e3,
            len(stuck) + len(queued_stuck))
        telemetry.flight_record(
            "decode_wedge",
            trace_ids=[s.trace.trace_id for s in stuck + queued_stuck
                       if s.trace is not None],
            extra={"kind": kind, "step": idx, "engine": self._name,
                   "stuck": len(stuck) + len(queued_stuck),
                   "timeout_ms": self._timeout_s * 1e3})
        err = DeadlineExceeded(
            "decode %s dispatch wedged: no device answer within %.0f ms"
            % (kind, self._timeout_s * 1e3))
        for seq in stuck:
            telemetry.trace_mark(seq.trace, "serving.wedged")
            self._free_seq_ledger(seq, slotted=True)
            self._fail(seq, err)
        for seq in queued_stuck:
            telemetry.trace_mark(seq.trace, "serving.wedged")
            if not seq.future.done():
                self._free_seq_ledger(seq, slotted=False)
                self._fail(seq, err)
        with self._cond:
            # the reset kills the WHOLE cohort device state: any live
            # slot not in the armed entry (none under the single-driver
            # model, but defensive) loses its KV too — fail it rather
            # than leave it silently pointing at zeroed cache
            stragglers = [s for s in self._slots if s is not None]
            self._slots = [None] * self._capacity
            self._live = 0
            telemetry.gauge("serving.decode.slots", 0)
            self._carry = self._alloc_carry()
            self._carry_gen += 1
            if self._pt:
                # the fresh carry's pages are zeroed device-side: drop
                # the prefix cache's pins (stale KV must never be shared
                # into a future prompt) and unmap every table row; the
                # stuck/straggler sequences still hold their page refs —
                # each _free_seq_ledger below returns them, so the free
                # list balances without a wholesale rebuild
                if self._prefix is not None:
                    for pid in self._prefix.drain():
                        self._decref_locked(pid)
                self._ptab[:, :] = 0
                self._page_gauges_locked()
            if self._thread is not None and self._thread.is_alive():
                # threaded mode: the loop thread may be BLOCKED in the
                # wedged device call — give it one timeout window to
                # prove otherwise (see _check_probation)
                self._probation = (now + self._timeout_s, self._cycles)
            self._cond.notify_all()
        for seq in stragglers:
            self._free_seq_ledger(seq, slotted=True)
            self._fail(seq, err)

    # ---------------------------------------------------------------- worker
    def start(self):
        """Run the engine on a background loop thread + wedge monitor
        (the threaded twin of :meth:`poll`). Returns self."""
        if self._thread is not None:
            return self
        if self._kv_layout is None:
            raise MXNetError("DecodeEngine.start on a cold engine: "
                             "warmup() first (AOT replay needs its "
                             "executables before traffic)")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="mxtpu-serving-decode")
        self._thread.start()
        interval = max(0.005, min(0.25, self._timeout_s / 4))
        self._monitor = threading.Thread(
            target=self._monitor_loop, args=(interval,), daemon=True,
            name="mxtpu-serving-decode-monitor")
        self._monitor.start()
        return self

    def _loop(self):
        try:
            while True:
                with self._cond:
                    while not self._pending and self._live == 0 \
                            and not self._closed:
                        self._cond.wait(0.25)
                    if self._closed and not self._pending \
                            and self._live == 0:
                        return
                self._admit_pending()
                maybe_oom()  # fault kind 'oom': the decode-loop OOM site
                stepped = self._step_once()
                with self._cond:
                    # loop-progress heartbeat: what probation watches to
                    # tell a cycling thread from one blocked in a wedged
                    # device call
                    self._cycles += 1
                    if not stepped and self._live > 0:
                        # live cohort but no step ran (unresolved armed
                        # entry): park briefly instead of spinning until
                        # the watchdog resolves it
                        self._cond.wait(0.005)
        except Exception as e:  # noqa: BLE001 — crash barrier (PR-8)
            # HBM exhaustion in the decode loop: artifact (ledger +
            # per-device memory stats + accountant view) first, then the
            # crash barrier fails every pending future LOUD (no hangs)
            self._flight_if_oom(e)
            self._worker_crashed(e)

    def _monitor_loop(self, interval):
        while not self._stop.is_set():
            self._scan_wedges(self._clock())
            with self._cond:
                if self._closed and not self._pending and self._live == 0:
                    return
            self._stop.wait(interval)

    def _worker_crashed(self, exc):
        """The decode loop died on an unexpected exception: fail every
        pending and live future loud (their worker is gone) and refuse
        new submits — the MicroBatcher crash-barrier discipline."""
        telemetry.inc("serving.worker_crashes")
        _log.error("serving decode loop crashed (%s: %s) — failing queued "
                   "futures and refusing new submits",
                   type(exc).__name__, exc)
        err = MXNetError("serving decode loop crashed: %s: %s"
                         % (type(exc).__name__, exc))
        with self._cond:
            self._crashed = True
            dead, slotted = self._collect_teardown_locked()
        telemetry.flight_record(
            "worker_crash",
            trace_ids=[s.trace.trace_id for s in dead
                       if s.trace is not None],
            extra={"engine": self._name,
                   "error": "%s: %s" % (type(exc).__name__, exc)})
        self._fail_collected(dead, slotted, err)

    def drain(self, timeout=None):
        """Stop admitting (submits shed ``draining``), finish pending +
        live sequences. With no loop thread, outstanding work drains
        synchronously through :meth:`poll` (deadline measured on the
        injected clock). Returns True when empty."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = None if timeout is None else self._clock() + timeout
        while True:
            alive = self._thread is not None and self._thread.is_alive()
            if not alive:
                while self.poll():
                    pass
                self._admit_pending()
            with self._cond:
                if not self._pending and self._live == 0 \
                        and self._inflight_seq is None:
                    return True
                if deadline is not None and self._clock() > deadline:
                    return False
                if not alive:
                    return False
                self._cond.wait(0.05)

    def close(self, timeout=5.0):
        """Drain, then stop the loop + monitor threads. Anything still
        pending after the drain deadline fails loud rather than hanging
        its callers."""
        self.drain(timeout=timeout)
        with self._cond:
            self._closed = True
            self._draining = True
            self._cond.notify_all()
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
        if self._monitor is not None:
            self._monitor.join(timeout)
        # sweep AFTER the joins: only then can no loop iteration race the
        # collection, and a popped-but-unregistered in-flight sequence (a
        # loop thread killed mid-prefill) is caught too instead of
        # leaving its future hanging forever
        with self._cond:
            leftovers, slotted = self._collect_teardown_locked()
        self._fail_collected(leftovers, slotted,
                             DeadlineExceeded("engine closed before "
                                              "completion"))
        return self

    # ------------------------------------------------------------ diagnostics
    def prefill_logits(self, prompt):
        """Diagnostic: the prompt's last-position logits as numpy — the
        int8-vs-f32 logits-parity gate's probe (serve_bench decode mode,
        tests). NOT a serving path: it fetches device output directly."""
        prompt = np.asarray(prompt, np.int32)
        flat, _fmt, _b = self._pred.predict_flat((prompt[None, :],))
        return np.asarray(flat[0]._data[0, prompt.size - 1])

    def step_logits_probe(self, prompt):
        """Diagnostic: prefill + insert into slot of a FRESH probe engine
        state, run one decode step, and return that step's logits row —
        the KV-path half of the int8 parity gate. Uses the engine's real
        executables (the loop's own ``_last_logits`` output, which the
        serving path never fetches), so the probe measures exactly what
        production replays. Serialized against the loop: do not call
        under live traffic."""
        fut = self.submit(prompt, max_new=2)
        for _ in range(64):
            if fut.done():
                break
            self.poll()
        if self._last_logits is None:
            raise MXNetError("step_logits_probe: no decode step ran "
                             "(prompt finished at insert?)")
        out = np.asarray(self._last_logits[0])
        fut.result(timeout=5.0)
        return out
