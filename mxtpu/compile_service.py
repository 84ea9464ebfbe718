"""One compile service: the unified jit-cache engine under every jit
surface (ROADMAP item 5).

Before this module, ten jit caches (``fused_optimizer``, ``cached_op``,
``executor``/``executor.backward``, ``subgraph_exec``,
``parallel.train_step``, ``rtc``, ``serving.predict``/``.r<i>``,
``serving.decode``) each reinvented keying, retrace reporting, and
warmup, and every process restart or replica scale-up paid full
recompilation on the critical path. This module is the one front door
they all resolve through:

* **Canonical key** — :func:`canonical_key` builds the one cache key
  shape every site speaks: ``(site, fn identity, abstract
  shapes/dtypes signature, registry.policy_key, sharding/MeshPlan
  fingerprint, donation discipline, device token)`` plus an in-memory
  instance ``nonce`` that is deliberately EXCLUDED from the on-disk
  digest — two live instances never alias each other's executables,
  but a restarted process (same function identity, same signature)
  warms from the previous process's artifacts.
* **Centralized reporting** — every cache miss routes its freshly-built
  executable through ``telemetry.record_retrace(site, provenance,
  compiled=...)`` exactly as the per-site caches did, so the retrace
  watchdog and the xprof executable ledger see identical surfaces; a
  disk-loaded executable registers ledger-only (``xprof.watch``) and
  bumps ``compile.disk.hits{site}`` instead — a load is not a compile
  and must not trip the watchdog.
* **LRU bound** — the store holds at most ``MXTPU_COMPILE_CACHE_ENTRIES``
  executables (default 1024, ``<= 0`` = unbounded); evictions count
  into ``compile.evictions{site}``. This bounds the previously
  unbounded per-site dicts (``rtc.Kernel._compiled``,
  ``subgraph_exec``, executor ``_jits``) under shape churn.
* **AOT warmup** — :func:`warmup` lowers/compiles a declared entry list
  CONCURRENTLY on a small thread pool (``MXTPU_COMPILE_CACHE_THREADS``)
  instead of the old serial per-replica loops. Python tracing is
  serialized under one lock (tracing executes model code against
  shared blocks — the old serving ``_TRACE_LOCK`` discipline,
  centralized); XLA compiles run in parallel outside it. Entries that
  share a ``group`` token share ONE built jit callable, and jax's
  jaxpr cache then shares the TRACE across per-device lowerings — N
  identical replicas trace once and compile per device
  (``compile.lowering_shares{site}``).
* **Persistent on-disk executable cache** — with
  ``MXTPU_COMPILE_CACHE_DIR`` set, every AOT-compiled executable is
  serialized (jax AOT ``serialize_executable``) into a self-describing
  blob committed tmp+rename, with a best-effort ``manifest.json``
  index. A fresh process probes the digest before building: a hit
  deserializes in milliseconds with ZERO compiles. Every mismatch —
  truncated/corrupt blob, format/jax/backend version skew, key-repr
  collision — degrades to a silent recompile and counts into
  ``compile.disk.drops{reason}``; the cache can never crash a run and
  can never serve a stale-policy executable (the full canonical key
  repr is verified inside the blob, and policy/sharding/donation flips
  change the digest itself).

Degradation matrix, key anatomy, and the disk format live in
``docs/compile_cache.md``.
"""
from __future__ import annotations

import collections
import hashlib
import inspect
import json
import logging
import os
import pickle
import re
import tempfile
import threading
import time

from . import telemetry

__all__ = ["Key", "Entry", "WarmupEntry", "canonical_key", "device_token",
           "source_token", "instance_nonce", "cache_dir", "cache_entries",
           "cache_threads", "get", "get_or_build", "warmup", "drop",
           "stats", "reset", "trace_lock", "digest_of", "disk_path_of",
           "concrete_args", "manifest"]

_log = logging.getLogger("mxtpu.compile_service")

# disk blob format version: bump on any layout change — old blobs then
# drop as version_mismatch and silently recompile
FORMAT_VERSION = 2
_MAGIC = "MXTPU-CC"

_LOCK = threading.Lock()            # store/group/inflight structural ops
_STORE = collections.OrderedDict()  # Key -> Entry (LRU: newest at end)
_GROUPS = collections.OrderedDict()  # group token -> (jit_fn, meta)
_GROUP_BOUND = 64                   # groups hold build closures: keep small
_INFLIGHT = {}                      # Key -> threading.Event

# ONE python-trace lock for the whole process: tracing executes model
# code (shared gluon blocks, deferred init, format cells) that is not
# safe to run concurrently — the serving-layer ``_TRACE_LOCK`` made
# first-class. XLA compilation happens OUTSIDE it, in parallel.
_TRACE_LOCK = threading.RLock()

_HEX_ADDR = re.compile(r"0x[0-9a-fA-F]+")


class Key(collections.namedtuple(
        "Key", ["site", "fn_id", "signature", "policy", "sharding",
                "donation", "device", "nonce"])):
    """The canonical compile-cache key. ``site`` names the retrace
    watchdog site; ``fn_id`` is a STABLE function identity (symbol
    JSON digest, block repr + forward source hash, optimizer class —
    never ``id()``); ``signature`` holds the abstract shapes/dtypes
    and per-site static config; ``policy`` is ``registry.policy_key``;
    ``sharding`` the MeshPlan fingerprint / per-buffer sharding
    tokens; ``donation`` the donate-argnums discipline; ``device`` the
    placement token. ``nonce`` isolates live instances in memory and
    is excluded from the on-disk digest."""

    __slots__ = ()

    def digest_material(self):
        """The stable string the disk digest hashes: everything except
        ``site`` (reporting-only — a replaced replica r9 on device 2
        may reuse retired r2's device-2 artifact) and ``nonce``
        (process-local)."""
        return "|".join((
            "fmt%d" % FORMAT_VERSION, self.fn_id or "",
            repr(self.signature), repr(self.policy), repr(self.sharding),
            repr(self.donation), self.device or ""))


Entry = collections.namedtuple("Entry", ["fn", "meta", "origin"])

# warmup declaration: key + build + example args (concrete or
# ShapeDtypeStruct — anything ``jit.lower`` accepts); ``group`` tokens
# mark entries whose lowering is identical up to device placement
WarmupEntry = collections.namedtuple(
    "WarmupEntry", ["key", "build", "example_args", "provenance", "group"],
    defaults=(None, None))


# ------------------------------------------------------------------ levers
def cache_dir():
    """``MXTPU_COMPILE_CACHE_DIR``: the persistent executable cache home
    (empty/unset = disk cache off)."""
    return os.environ.get("MXTPU_COMPILE_CACHE_DIR") or None


# ------------------------------------------------------- the XLA cache rule
# ONE rule for jax's persistent compilation cache, and ONE writer of
# ``jax_compilation_cache_dir`` in the repo (:func:`use_checkout_xla_cache`):
#
# * ``JAX_COMPILATION_CACHE_DIR`` set -> nothing is written. JAX reads
#   the variable itself and no code of ours overwrites it, so a cache
#   handed in from outside (a machine that keeps one between calls) is
#   the cache that is used.
# * unset -> entry points that run on the chip (chip_smoke.py, bench.py,
#   the bench tools, the examples) call the helper: one FIXED directory
#   inside the checkout, git-ignored. The path is part of XLA's cache
#   key, so a directory named after a pid, a time or ``mkdtemp`` never
#   hits. Library code (``import mxtpu``) sets nothing.
# * the executable store below (``MXTPU_COMPILE_CACHE_DIR``) is opt-in
#   and separate: it never touches jax's cache. A tool that wants jax's
#   cache beside a store of its own (tools/startup_bench.py) hands its
#   children ``JAX_COMPILATION_CACHE_DIR`` like anybody else.
CHECKOUT_XLA_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def use_checkout_xla_cache():
    """Entry-point helper and THE one writer of
    ``jax_compilation_cache_dir``: the fixed in-checkout directory
    (``<checkout>/.jax_cache``) unless ``JAX_COMPILATION_CACHE_DIR``
    placed the cache from outside (then nothing is written). Returns
    the directory in force."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_XLA_CACHE)
    return CHECKOUT_XLA_CACHE


def cache_entries():
    """``MXTPU_COMPILE_CACHE_ENTRIES``: LRU bound on in-memory
    executables (default 1024; ``<= 0`` = unbounded)."""
    try:
        return int(os.environ.get("MXTPU_COMPILE_CACHE_ENTRIES", "1024"))
    except ValueError:
        return 1024


def cache_threads():
    """``MXTPU_COMPILE_CACHE_THREADS``: AOT warmup pool width (default
    ``min(4, cpu_count)``)."""
    try:
        n = int(os.environ.get("MXTPU_COMPILE_CACHE_THREADS", "0"))
    except ValueError:
        n = 0
    if n > 0:
        return n
    return max(1, min(4, os.cpu_count() or 1))


# -------------------------------------------------------------- key helpers
def canonical_key(site, fn_id, signature, policy=None, sharding=None,
                  donation=None, device=None, nonce=None):
    """Build the canonical :class:`Key`. Every component must be
    hashable and have a process-stable ``repr`` (tuples of
    str/int/bool — never live objects)."""
    return Key(site, fn_id, signature, policy, sharding, donation,
               device, nonce)


def _local_ordinal(d):
    """A device's ordinal within its OWN process's device set. Global ids
    bake the host rank into the token (host 1's only CPU device is global
    id 1), which would stop a replacement host from warm-starting off the
    blobs an identical peer spilled; process-local ordinals make
    equivalent per-host placements token-equal across hosts while a
    device-2 mesh still differs from a device-0 mesh on one host."""
    import jax
    try:
        peers = [x.id for x in jax.devices()
                 if x.process_index == d.process_index]
        return int(d.id) - min(peers)
    except Exception:  # noqa: BLE001 — exotic backend: raw id is a token too
        return int(d.id)


def device_token(device=None, mesh=None):
    """Stable placement token: backend kind + device ordinal (or the
    mesh's device-ordinal tuple). Executables are device-pinned — the
    token keeps a device-2 artifact from being offered to a device-0
    restore — but pinned per host, not per fleet (see
    :func:`_local_ordinal`)."""
    import jax
    backend = jax.default_backend()
    if mesh is not None:
        ids = tuple(_local_ordinal(d) for d in mesh.devices.flat)
        return "%s:mesh%s" % (backend, ids)
    if device is not None:
        return "%s:d%d" % (backend, _local_ordinal(device))
    return "%s:default" % backend


def source_token(obj):
    """Best-effort code-identity digest: sha1 of ``inspect.getsource``
    (falls back to an address-stripped repr). Folded into ``fn_id`` so
    an edited model/kernel across restarts misses the disk cache
    instead of replaying stale code."""
    try:
        src = inspect.getsource(obj)
    except (OSError, TypeError):
        src = _HEX_ADDR.sub("0x", repr(obj))
    return hashlib.sha1(src.encode("utf-8", "replace")).hexdigest()[:16]


_NONCES = {"next": 0}


def instance_nonce(obj):
    """Process-local instance isolation token (in-memory key only —
    never part of the disk digest). Monotonic and cached on the
    instance: a raw ``id()`` would recycle after GC and let a fresh
    instance silently inherit a dead one's executables."""
    tok = getattr(obj, "_csvc_nonce", None)
    if tok is None:
        with _LOCK:
            _NONCES["next"] += 1
            tok = "i%d" % _NONCES["next"]
        try:
            obj._csvc_nonce = tok
        except (AttributeError, TypeError):  # __slots__ etc.: degrade to id
            tok = "i%x" % id(obj)
    return tok


def digest_of(key):
    """The on-disk digest for ``key`` (site/nonce excluded)."""
    return hashlib.sha256(
        key.digest_material().encode("utf-8", "replace")).hexdigest()[:32]


def disk_path_of(key, root=None):
    root = root or cache_dir()
    if not root:
        return None
    return os.path.join(root, digest_of(key) + ".mxc")


def concrete_args(args):
    """``args`` when every leaf is concrete (lowerable), else None — a
    site invoked UNDER an outer trace (tracer inputs) must not hand the
    service tracers as example args: the AOT path would try to lower
    against values owned by someone else's trace."""
    import jax

    tracer = jax.core.Tracer
    for leaf in jax.tree_util.tree_leaves(args):
        if isinstance(leaf, tracer):
            return None
    return args


def trace_lock():
    """The process-wide python-trace lock (reentrant). Sites that trace
    outside the service (first dispatch of a cold plain-jit entry)
    serialize here — the centralized successor of the serving-layer
    ``_TRACE_LOCK``."""
    return _TRACE_LOCK


# ------------------------------------------------------------------- store
def _lookup_locked(key):
    e = _STORE.get(key)
    if e is not None:
        _STORE.move_to_end(key)
    return e


def _store_locked(key, entry):
    _STORE[key] = entry
    _STORE.move_to_end(key)
    bound = cache_entries()
    while bound > 0 and len(_STORE) > bound:
        old_key, _old = _STORE.popitem(last=False)
        telemetry.inc("compile.evictions", tag=old_key.site)


def get(key):
    """In-memory lookup only (refreshes LRU position)."""
    with _LOCK:
        return _lookup_locked(key)


def drop(site=None, fn_id=None, nonce=None):
    """Evict matching entries (and group artifacts when a ``fn_id``
    filter is given) WITHOUT counting ``compile.evictions`` — this is
    the explicit invalidation path (test resets, instance teardown),
    not cache pressure. Returns the number dropped."""
    with _LOCK:
        victims = [k for k in _STORE
                   if (site is None or k.site == site
                       or k.site.startswith(site + "."))
                   and (fn_id is None or k.fn_id == fn_id)
                   and (nonce is None or k.nonce == nonce)]
        for k in victims:
            del _STORE[k]
        if fn_id is not None or site is None:
            for g in [g for g in _GROUPS
                      if fn_id is None or (isinstance(g, tuple)
                                           and fn_id in g)]:
                del _GROUPS[g]
    return len(victims)


def reset():
    """Drop every in-memory entry, group artifact, and in-flight marker
    (tests). The disk cache is untouched."""
    with _LOCK:
        _STORE.clear()
        _GROUPS.clear()
        _INFLIGHT.clear()


def stats():
    with _LOCK:
        per_site = {}
        for k in _STORE:
            per_site[k.site] = per_site.get(k.site, 0) + 1
    return {"entries": sum(per_site.values()), "per_site": per_site,
            "groups": len(_GROUPS), "disk_dir": cache_dir(),
            "bound": cache_entries()}


# ------------------------------------------------------------- disk cache
def _env_material():
    """The environment fingerprint a blob must match to load: blob
    format, jax/jaxlib versions (serialized executables are not
    ABI-stable across them), and the backend kind."""
    import jax
    import jaxlib
    return {"format": FORMAT_VERSION, "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend()}


def _drop_blob(reason, site, path=None):
    telemetry.inc("compile.disk.drops", tag=reason)
    _log.debug("compile disk cache: dropped %s (%s)", path, reason)
    return None


def _marker_path(path):
    return path + ".unloadable"


def _known_unloadable(path):
    """True when a previous process marked this digest as
    non-restorable in THIS environment (some backends — XLA CPU with
    certain fusions — serialize executables whose generated-code
    symbols do not survive deserialization). The marker stops every
    later restart from re-paying the failed load AND the re-spill; an
    environment change invalidates it."""
    try:
        with open(_marker_path(path), "r", encoding="utf-8") as f:
            return json.load(f) == _env_material()
    except (OSError, ValueError):
        return False


def _mark_unloadable(path):
    try:
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   suffix=".tmp")
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(_env_material(), f)
        os.replace(tmp, _marker_path(path))
    except Exception:  # noqa: BLE001 — advisory only
        pass


def _device_ids(compiled):
    """Ids, in execution order, of the devices an executable was built
    for. Stored in the blob: ``deserialize_and_load`` otherwise loads
    over EVERY device of the backend, and a single-device executable
    reloaded on an 8-device host then dies at its first call."""
    return [int(d.id) for d in
            compiled.runtime_executable().local_devices()]


def _reloadable(ids):
    """Can an executable built for devices ``ids`` (in execution order)
    be reloaded onto them here? Established per backend, on the chip:

    * one device — yes, anywhere (:func:`_load_executable` names it);
    * several devices on XLA:CPU — yes, any sub-mesh in any order: the
      CPU client takes the assignment from ``execution_devices``;
    * several devices on a TPU — the client reloads onto its DEFAULT
      assignment (the backend's devices in order) whatever
      ``execution_devices`` says, and an assignment handed in through
      ``compile_options`` halted the core on a sub-mesh (four-chip
      v5e host, PR 21). So only the mesh that IS the default assignment
      is served; any other is refused at spill and at load
      (``compile.disk.drops{device_assignment}``) and recompiles."""
    import jax
    if len(ids) == 1 or jax.default_backend() == "cpu":
        return True
    return list(ids) == [int(d.id) for d in jax.devices()]


def _load_executable(rec):
    """``serialize_executable.deserialize_and_load`` with the executable's
    devices named BOTH ways the backends need:

    * ``execution_devices`` — without it jax 0.9.0 loads over every
      device of the backend, and a single-device executable reloaded on
      an 8-device host dies at its first call (``Expected args to
      execute_sharded_on_local_devices to have 8 shards``);
    * for a single-device executable, its device assignment in
      ``compile_options`` — the TPU client does not take the assignment
      from ``execution_devices``: without the option an executable
      built for chip 2 reloads assigned to chip 0 and its first call
      fails with ``Buffer passed to Execute() ... is on device TPU_2
      ... but replica is assigned to device TPU_0`` (found on a
      four-chip host; invisible on one chip and on XLA:CPU). jax's own
      persistent cache passes the option the same way
      (``compilation_cache.get_executable_and_time``). Multi-device
      executables take no option (see :func:`_reloadable`).
    """
    import io

    import jax
    import numpy as np
    from jax._src import compiler
    from jax.experimental import serialize_executable as se

    ids = list(rec["devices"])
    by_id = {d.id: d for d in jax.devices()}
    devices = [by_id[i] for i in ids]
    options = None
    if len(ids) == 1:
        options = compiler.get_compile_options(
            num_replicas=1, num_partitions=1,
            device_assignment=np.array(ids).reshape(1, 1))

    class Unpickler(se._JaxPjrtUnpickler):
        def persistent_load(self, pid):
            if pid[0] == "exec":
                return self.backend.deserialize_executable(
                    pid[1], executable_devices=self.execution_devices,
                    compile_options=options)
            return super().persistent_load(pid)

    unloaded, args_info_flat, no_kwargs = Unpickler(
        io.BytesIO(rec["payload"]), devices[0].client, devices).load()
    return jax.stages.Compiled(
        unloaded.load(), [], rec["in_tree"].unflatten(args_info_flat),
        rec["out_tree"], no_kwargs=no_kwargs)


def _disk_load(key):
    """Probe the disk cache for ``key``. Returns an :class:`Entry` or
    None. EVERY failure mode degrades to None (recompile) with a
    ``compile.disk.drops{reason}`` count — never an exception, never a
    stale executable (the blob's stored key material is compared
    against the probe's)."""
    path = disk_path_of(key)
    if path is None:
        return None
    if not os.path.exists(path):
        return None
    if _known_unloadable(path):
        return _drop_blob("unloadable", key.site, path)
    try:
        with open(path, "rb") as f:
            rec = pickle.load(f)
    except Exception:  # noqa: BLE001 — truncated/garbage blob
        return _drop_blob("corrupt", key.site, path)
    if not isinstance(rec, dict) or rec.get("magic") != _MAGIC:
        return _drop_blob("corrupt", key.site, path)
    if rec.get("env") != _env_material():
        return _drop_blob("version_mismatch", key.site, path)
    if rec.get("key") != key.digest_material():
        # digest collision or a forged rename: the executable was built
        # for a DIFFERENT canonical key (other policy/sharding/donation)
        return _drop_blob("key_mismatch", key.site, path)
    if not _reloadable(rec.get("devices") or ()):
        return _drop_blob("device_assignment", key.site, path)
    try:
        compiled = _load_executable(rec)
    except Exception:  # noqa: BLE001 — topology/backends moved under us,
        # or a backend whose serialized form cannot restore (marked so
        # later restarts skip straight to the recompile)
        _mark_unloadable(path)
        return _drop_blob("load_error", key.site, path)
    from . import xprof
    prov = dict(rec.get("provenance") or {})
    prov["from_disk"] = True
    # ledger-only registration: a disk load is NOT a compile — the
    # retrace watchdog must stay silent (zero-compile warm start is the
    # acceptance pin), but the executable's cost/memory analyses and
    # call counts still feed the observatory
    fn = xprof.watch(key.site, compiled, prov)
    telemetry.inc("compile.disk.hits", tag=key.site)
    meta = rec.get("meta")
    return Entry(fn, dict(meta) if isinstance(meta, dict) else meta,
                 "disk")


def _disk_write(key, compiled, meta, provenance, compile_s):
    """Serialize ``compiled`` under ``key``'s digest, committed
    tmp+rename so a concurrent writer or a mid-write crash can never
    leave a half-blob under the final name. Serialization failures
    count and degrade — the in-memory entry is already good."""
    root = cache_dir()
    if not root:
        return False
    path = disk_path_of(key, root)
    if _known_unloadable(path):
        # a rewrite cannot help: this digest's executables do not
        # restore in this environment — skip BEFORE paying the
        # serialization (that cost per restart is the exact churn the
        # marker exists to stop)
        return False
    ids = _device_ids(compiled)
    if not _reloadable(ids):
        # refuse BEFORE paying serialization: the blob could not be
        # served back (see _reloadable)
        telemetry.inc("compile.disk.drops", tag="device_assignment")
        return False
    try:
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
        rec = {"magic": _MAGIC, "env": _env_material(),
               "key": key.digest_material(), "site": key.site,
               "devices": ids,
               "payload": payload, "in_tree": in_tree,
               "out_tree": out_tree, "meta": meta,
               "provenance": _json_safe(provenance),
               "compile_s": compile_s, "created": time.time()}
        blob = pickle.dumps(rec)
    except Exception:  # noqa: BLE001 — backend without AOT serialization
        telemetry.inc("compile.disk.drops", tag="serialize")
        return False
    try:
        os.makedirs(root, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=root, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except Exception:  # noqa: BLE001 — disk full / perms / races
        telemetry.inc("compile.disk.drops", tag="io")
        return False
    telemetry.inc("compile.disk.writes", tag=key.site)
    _manifest_note(root, digest_of(key), key, len(blob))
    return True


def _json_safe(v):
    try:
        json.dumps(v)
        return v
    except (TypeError, ValueError):
        return repr(v)


def _manifest_note(root, digest, key, nbytes):
    """Best-effort ``manifest.json`` index row (version + key anatomy
    per digest). The manifest is for humans and reports — per-entry
    blobs are self-describing and authoritative, so a lost
    read-modify-write race here costs nothing but a stale index
    line."""
    path = os.path.join(root, "manifest.json")
    try:
        try:
            with open(path, "r", encoding="utf-8") as f:
                man = json.load(f)
        except (OSError, ValueError):
            man = {}
        if not isinstance(man, dict) or "entries" not in man:
            man = {"format": FORMAT_VERSION, "entries": {}}
        man["format"] = FORMAT_VERSION
        man["env"] = _env_material()
        man["entries"][digest] = {
            "site": key.site, "fn_id": key.fn_id,
            "key": key.digest_material(), "bytes": nbytes,
            "created": time.time()}
        fd, tmp = tempfile.mkstemp(dir=root, suffix=".tmp")
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(man, f, indent=1, default=repr)
        os.replace(tmp, path)
    except Exception:  # noqa: BLE001 — advisory only
        pass


def manifest(root=None):
    """The on-disk manifest dict (empty when absent/unreadable)."""
    root = root or cache_dir()
    if not root:
        return {}
    try:
        with open(os.path.join(root, "manifest.json"),
                  "r", encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


# -------------------------------------------------------------- build path
def _group_jit(group, build, site):
    """One built jit callable per lowering group: entries differing only
    in device placement reuse the SAME python callable, so jax's jaxpr
    cache shares the trace across their per-device lowerings."""
    with _LOCK:
        hit = _GROUPS.get(group)
        if hit is not None:
            _GROUPS.move_to_end(group)
    if hit is not None:
        telemetry.inc("compile.lowering_shares", tag=site)
        return hit
    with _TRACE_LOCK:
        # re-check under the trace lock: a concurrent group member may
        # have built while we waited
        with _LOCK:
            hit = _GROUPS.get(group)
        if hit is not None:
            telemetry.inc("compile.lowering_shares", tag=site)
            return hit
        built = _split_build(build())
        with _LOCK:
            _GROUPS[group] = built
            while len(_GROUPS) > _GROUP_BOUND:
                _GROUPS.popitem(last=False)
        return built


def _split_build(raw):
    """``build()`` returns the jit callable, or ``(jit, meta)`` where
    ``meta`` is the site's picklable side-cell (output formats etc.) —
    persisted next to the executable so a disk-warm process needs no
    trace to reconstruct it."""
    if isinstance(raw, tuple):
        jit_fn, meta = raw
        return jit_fn, meta
    return raw, None


def _report(site, provenance, compiled, compile_s, companion):
    """The one watchdog/ledger handoff: companions (a forward's paired
    backward sharing the site's single retrace count) register
    ledger-only; everything else reports the compile."""
    from . import xprof
    if companion:
        return xprof.watch(site, compiled, provenance,
                           compile_s=compile_s)
    return telemetry.record_retrace(site, provenance, compiled=compiled,
                                    compile_s=compile_s)


def _build_entry(key, build, provenance, example_args, aot, companion,
                 group):
    if callable(provenance):
        # lazy provenance: hot sites hand a thunk so the dict is only
        # materialized on a real miss, never on the per-call hit path
        provenance = provenance()
    if group is not None:
        jit_fn, meta = _group_jit(group, build, key.site)
    else:
        jit_fn, meta = _split_build(build())
    do_aot = aot if aot is not None \
        else (example_args is not None and cache_dir() is not None)
    if do_aot and example_args is not None:
        t0 = time.perf_counter()
        with _TRACE_LOCK:
            # python trace serialized; the jaxpr cache makes a grouped
            # re-lower at a new device placement trace-free
            lowered = jit_fn.lower(*example_args)
        compiled = lowered.compile()   # parallel-safe: outside the lock
        dt = time.perf_counter() - t0
        fn = _report(key.site, provenance, compiled, dt, companion)
        _disk_write(key, compiled, meta, provenance, dt)
        return Entry(fn if fn is not None else compiled, meta, "built")
    fn = _report(key.site, provenance, jit_fn, None, companion)
    return Entry(fn if fn is not None else jit_fn, meta, "built")


def get_or_build(key, build, provenance=None, example_args=None,
                 aot=None, companion=False, group=None):
    """THE cache front door. Resolution order: in-memory LRU store →
    on-disk executable cache (zero compiles) → ``build()`` (one
    reported compile). ``example_args`` (anything ``jit.lower``
    accepts) enables the AOT path: explicit lower+compile — required
    for disk spill, and the path :func:`warmup` drives concurrently.
    Without it (or with the disk cache off and ``aot`` unset) the
    freshly-built plain jit is returned exactly as the per-site caches
    did — first dispatch traces and compiles.

    Concurrent misses on the same key build once: losers wait on the
    winner's in-flight event and adopt its entry."""
    with _LOCK:
        e = _lookup_locked(key)
    if e is not None:
        return e
    registered = False
    while True:
        with _LOCK:
            e = _lookup_locked(key)
            if e is not None:
                return e
            waiter = _INFLIGHT.get(key)
            if waiter is None:
                _INFLIGHT[key] = threading.Event()
                registered = True
                break
        if getattr(_TRACE_LOCK, "_is_owned", lambda: False)():
            # lock-order-inversion guard: we hold the process trace
            # lock (a site resolving keys mid-trace/warmup) while the
            # in-flight builder may be BLOCKED waiting for it inside
            # its AOT lower — waiting on its event here would deadlock.
            # Build our own copy instead (the store write is
            # idempotent; a rare duplicate compile beats a wedge).
            break
        waiter.wait()
    try:
        entry = _disk_load(key)
        if entry is None:
            entry = _build_entry(key, build, provenance, example_args,
                                 aot, companion, group)
        with _LOCK:
            _store_locked(key, entry)
        return entry
    finally:
        if registered:
            with _LOCK:
                ev = _INFLIGHT.pop(key, None)
            if ev is not None:
                ev.set()


# ------------------------------------------------------------------ warmup
def warmup(entries, threads=None):
    """AOT-warm a declared entry list concurrently: every entry resolves
    through :func:`get_or_build` with the AOT path forced, so each one
    lands as disk hit (zero compiles), a shared-lowering build (trace
    once per ``group``, compile per device), or a plain reported
    compile. Returns a summary dict; the FIRST entry failure re-raises
    after all entries settle (warmup must not half-succeed
    silently)."""
    entries = list(entries)
    t0 = time.perf_counter()
    summary = {"entries": len(entries), "built": 0, "disk": 0,
               "cached": 0, "errors": 0, "wall_s": 0.0}
    if not entries:
        return summary
    n = threads or cache_threads()
    first_err = None

    def one(e):
        pre = get(e.key)
        entry = get_or_build(e.key, e.build, provenance=e.provenance,
                             example_args=e.example_args, aot=True,
                             group=e.group)
        return "cached" if pre is not None else entry.origin

    if len(entries) == 1 or n <= 1:
        results = map(_catching(one), entries)   # no pool spin-up
    else:
        from concurrent.futures import ThreadPoolExecutor
        pool = ThreadPoolExecutor(
            max_workers=max(1, min(n, len(entries))),
            thread_name_prefix="mxtpu-compile")
        results = pool.map(_catching(one), entries)
    for res in results:
        if isinstance(res, BaseException):
            summary["errors"] += 1
            first_err = first_err or res
        elif res == "disk":
            summary["disk"] += 1
        elif res == "cached":
            summary["cached"] += 1
        else:
            summary["built"] += 1
    if len(entries) > 1 and n > 1:
        pool.shutdown(wait=True)
    summary["wall_s"] = time.perf_counter() - t0
    if first_err is not None:
        raise first_err
    return summary


def _catching(fn):
    def run(e):
        try:
            return fn(e)
        except BaseException as exc:  # noqa: BLE001 — collected, re-raised
            return exc
    return run
