"""Executable observatory: per-jit-site cost/memory ledger, live HBM
accounting, and runtime MFU attribution (``MXTPU_XPROF``, default on).

The telemetry layer (PRs 4/10) gave the runtime full *time* observability;
this module adds *compute and memory*. Every jit-cache owner already
reports compiles via :func:`mxtpu.telemetry.record_retrace` — that call
now takes the freshly-built executable (``compiled=``) and this module
keeps a bounded per-site **ledger** of what each executable costs:

* XLA cost-model FLOPs and bytes-accessed (``cost_analysis()``),
* HBM footprint — argument / output / temp / generated-code bytes and the
  donated-bytes savings (``memory_analysis()``),
* compile wall-time (the first dispatch, which is trace+compile),
* a live call count, so executed-FLOPs (and the Trainer's ``perf.mfu``
  gauge) come from bookkeeping the dispatch path already does.

Resolution discipline: analyses need an AOT ``Compiled`` handle, which
jax only hands out through ``lower().compile()`` — one extra *host-side*
lowering per executable (the repo-accepted cost of
``ShardedTrainStep.compiled_step_flops``). That work is LAZY and runs at
explicit query points only (:func:`ledger`, the warmup pre-flight, the
MFU meter's first tick) — never on a /metrics scrape, never inside a
flight dump (an OOM moment must not invoke the compiler), and never on
the steady-state step path. Everything here is host bookkeeping: zero
device work, zero syncs — the ``trainer.step.d2h == 0`` contract holds
with the observatory ON (transfer-guard test parametrized over
``MXTPU_XPROF``).

Live HBM accounting: :func:`poll_memory` reads ``device.memory_stats()``
into ``memory.hbm_{used,limit,headroom,peak}_bytes{device}`` gauges, an
off-thread monitor (``MXTPU_MEMWATCH_S`` seconds, 0 = off) keeps them
fresh, warmup runs a will-it-fit :func:`preflight` (Σ AOT bucket
footprints vs the device limit → ``memory.overcommit``), and a
``RESOURCE_EXHAUSTED`` anywhere on the dispatch paths triggers
:func:`oom_flight` — a flight-recorder artifact carrying the ledger,
per-device memory stats, and (in serving) the KVCacheAccountant view, so
an HBM OOM leaves a post-mortem instead of just a dead process.

The operation table (:func:`step_operations`): a site whose executable
arrives compiled (``parallel.train_step`` does: the compile service builds
it ahead of time) keeps its ``Compiled`` handle in its ledger entry, and on
first request the entry's table is parsed from ``compiled.as_text()``:
every instruction of the running executable with its computation, opcode,
``op_name`` (the ``jax.named_scope`` path it was traced under: the blocks'
own names, the operators' scopes, the transforms) and the ``conditional`` /
``while`` it runs inside. Joined with a device trace's seconds by
instruction name it is the step's time by the program's own names
(:func:`transform_of`, :func:`scope_path`). Never on the step's path, never
a trace or a lowering: an entry without a handle answers None.

Gating: ``MXTPU_XPROF=0`` skips the wrap at compile-record time (a
construction-time lever like ``MXTPU_SERVE_INT8`` — flipping it mid-run
affects new compiles, not executables already cached) and disables the
memwatch/preflight/MFU surfaces. Host-side only — NOT in ``policy_key``.
"""
from __future__ import annotations

import collections
import itertools
import logging
import numbers
import os
import re
import threading
import time

from . import telemetry

__all__ = ["enabled", "memwatch_interval", "attach", "watch", "ledger",
           "ledger_snapshot", "resolve", "executed_flops", "summary",
           "device_memory", "poll_memory", "ensure_memwatch",
           "stop_memwatch", "preflight", "site_footprint", "is_oom",
           "oom_flight", "operation_table", "step_operations",
           "transform_of", "scope_path",
           "MFUMeter", "TRAIN_SITES", "reset"]

_log = logging.getLogger("mxtpu.xprof")

_LOCK = threading.Lock()
_SITES = {}                    # site -> deque of ledger entries
_SEQ = itertools.count(1)
_PER_SITE = 16                 # bounded: a retrace storm keeps the newest

# jit sites that execute on the training step path — the executed-FLOPs
# numerator of the Trainer's perf.mfu gauge
TRAIN_SITES = ("fused_optimizer", "cached_op", "executor",
               "executor.backward", "parallel.train_step", "subgraph_exec")

_MEMWATCH = {"thread": None, "stop": None, "lock": threading.Lock()}

# substrings that mark a device allocator failure across jaxlib spellings
# (XlaRuntimeError RESOURCE_EXHAUSTED, PJRT "Out of memory", and the
# injected fault kind 'oom' which mimics the first)
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


# ------------------------------------------------------------------ policies
def enabled():
    """Observatory lever: ``MXTPU_XPROF`` default ON (requires the
    telemetry registry, which bare counters keep available always)."""
    return os.environ.get("MXTPU_XPROF", "1") != "0"


def memwatch_interval():
    """Off-thread HBM poll period in seconds (``MXTPU_MEMWATCH_S``);
    0 (default) = no monitor thread."""
    try:
        return float(os.environ.get("MXTPU_MEMWATCH_S", "0"))
    except ValueError:
        return 0.0


def _jsonable(v):
    """Provenance/extra payloads must survive json.dump inside a flight
    artifact: tuples/sets become lists, numpy scalars coerce, everything
    else degrades to repr."""
    if v is None or isinstance(v, (bool, str)):
        return v
    if isinstance(v, numbers.Integral):
        return int(v)
    if isinstance(v, numbers.Real):
        return float(v)
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple, set, frozenset)):
        return [_jsonable(x) for x in v]
    return repr(v)


# ------------------------------------------------------------------- ledger
class _Spec:
    """Captured abstract value of one call argument: shape + dtype (+
    sharding when the leaf was a placed jax.Array — GSPMD analyses differ
    per layout). Holding the spec, never the buffer: capture must not pin
    donated HBM."""

    __slots__ = ("shape", "dtype", "sharding")

    def __init__(self, shape, dtype, sharding):
        self.shape = shape
        self.dtype = dtype
        self.sharding = sharding


def _capture(args, kwargs):
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return _Spec(tuple(x.shape), x.dtype,
                         getattr(x, "sharding", None))
        return x  # python scalars keep their weak-typed signature

    return jax.tree_util.tree_map(leaf, (args, dict(kwargs)))


def _shapes_of(spec_tree, limit=16):
    """Public shape summary of a captured signature: "dtype[d1,d2,...]"
    per array leaf, bounded. This is what the ledger streams; the full
    ``_Spec`` tree stays private for AOT re-lowering."""
    import jax
    out = []
    for x in jax.tree_util.tree_leaves(spec_tree):
        if isinstance(x, _Spec):
            out.append("%s[%s]" % (jnp_name(x.dtype),
                                   ",".join(str(d) for d in x.shape)))
            if len(out) >= limit:
                break
    return out


def jnp_name(dtype):
    try:
        import numpy as np
        return np.dtype(dtype).name
    except Exception:  # noqa: BLE001 — exotic dtypes still summarize
        return str(dtype)


def _to_abstract(spec_tree, with_sharding):
    import jax

    def leaf(x):
        if isinstance(x, _Spec):
            if with_sharding and x.sharding is not None:
                try:
                    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                sharding=x.sharding)
                except (TypeError, ValueError):
                    pass
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(
        leaf, spec_tree, is_leaf=lambda x: isinstance(x, _Spec))


class _WatchedJit:
    """Thin wrapper around a jitted callable: the FIRST invocation is
    timed (trace+compile wall clock — the compile stall a served request
    or training step actually experienced) and its abstract signature
    captured for lazy analysis resolution; later invocations bump the
    ledger entry's call count behind a per-call lever check (one env
    read + one add), so flipping ``MXTPU_XPROF=0`` mid-run stops the
    accounting and ``bench.py telemetry_overhead``'s alternating
    ``xprof`` mode genuinely A/Bs the per-dispatch cost (the wrapper
    frame itself is construction-time and rides every mode). Attribute
    access forwards to the wrapped jit, so ``.lower()``-style AOT
    callers keep working."""

    __slots__ = ("_fn", "_entry", "_pending_first")

    def __init__(self, fn, entry, pending_first=True):
        self._fn = fn
        self._entry = entry
        # pending_first=False: an AOT executable from the compile
        # service (built explicitly or deserialized from disk) — the
        # first dispatch is pure replay, so only call counting remains
        self._pending_first = pending_first

    def __call__(self, *args, **kwargs):
        e = self._entry
        if self._pending_first:
            self._pending_first = False
            try:
                e["_abstract"] = _capture(args, kwargs)
                e["shapes"] = _shapes_of(e["_abstract"])
            except Exception:  # noqa: BLE001 — capture must never break
                pass           # the dispatch it observes
            t0 = time.perf_counter()
            out = self._fn(*args, **kwargs)
            dt = time.perf_counter() - t0
            e["compile_s"] = dt
            e["calls"] += 1
            telemetry.observe("compile.wall_s", dt)
            return out
        out = self._fn(*args, **kwargs)
        if os.environ.get("MXTPU_XPROF", "1") != "0":
            e["calls"] += 1
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


def _new_entry(site, provenance):
    entry = {"site": site, "seq": next(_SEQ),
             "provenance": _jsonable(provenance),
             "calls": 0, "compile_s": None,
             "resolved": False, "error": None}
    with _LOCK:
        dq = _SITES.get(site)
        if dq is None:
            dq = _SITES[site] = collections.deque(maxlen=_PER_SITE)
        dq.append(entry)
    return entry


def attach(site, provenance=None, compiled=None, compile_s=None):
    """Register one executable-cache miss in the ledger and return the
    callable the site should cache. ``compiled`` is either the
    freshly-built jitted callable (wrapped for first-call timing +
    signature capture) or an already-AOT ``Compiled`` object from the
    compile service (analyses fill immediately; the wrapper keeps call
    counting with ``compile_s`` — the service-measured lower+compile
    wall time — recorded up front since the first dispatch is replay).
    Off (``MXTPU_XPROF=0``) this returns ``compiled`` unchanged — zero
    added dispatch layers."""
    if compiled is None:
        return None
    if not enabled():
        return compiled
    entry = _new_entry(site, provenance)
    if hasattr(compiled, "cost_analysis"):
        # an AOT executable from the compile service: analyses resolve
        # LAZILY from the handle we already hold (same discipline as the
        # lower-at-query path — warmup must not pay a cost_analysis per
        # bucket); the wrapper keeps call counting, and compile_s is the
        # service-measured lower+compile wall (first dispatch is replay)
        entry["_compiled"] = compiled
        if compile_s is not None:
            entry["compile_s"] = compile_s
            telemetry.observe("compile.wall_s", compile_s)
        return _WatchedJit(compiled, entry, pending_first=False)
    entry["_fn"] = compiled
    return _WatchedJit(compiled, entry)


def watch(site, compiled, provenance=None, compile_s=None):
    """Ledger-only registration for a companion executable that shares a
    site's retrace count (e.g. CachedOp's compiled backward, reported
    with the forward's single ``record_retrace``) or a disk-restored
    executable (a load is not a compile) — same wrap, no extra
    ``retrace.<site>`` bump."""
    return attach(site, provenance, compiled, compile_s=compile_s)


def _fill_from_compiled(entry, compiled):
    from . import perf_model
    fl = perf_model.flops_of(compiled)
    ba = perf_model.bytes_accessed_of(compiled)
    entry["flops"] = fl
    entry["bytes_accessed"] = ba
    entry.update(perf_model.memory_dict(compiled.memory_analysis()))
    ridge = perf_model.critical_intensity()
    entry["critical_intensity"] = ridge
    entry["intensity"] = (fl / ba) if fl and ba else None
    entry["verdict"] = perf_model.roofline_verdict(fl, ba, ridge)


# serializes analysis resolution: two concurrent resolvers (the MFU
# meter's tick on the training thread, a diagnostic ledger() elsewhere)
# must not race on an entry's one-shot handle pop — the loser would taint
# a successfully-resolved entry with a spurious "never invoked" error
_RESOLVE_LOCK = threading.Lock()


def _resolve_entry(entry):
    """Fill one entry's analyses: re-lower the wrapped jit at its
    captured abstract signature and compile (host work only; the
    executable cache the site already holds is untouched). One attempt —
    an analysis failure is recorded, never raised into the caller."""
    with _RESOLVE_LOCK:
        if entry["resolved"]:
            return
        _resolve_entry_locked(entry)


def _resolve_entry_locked(entry):
    pre = entry.get("_compiled")
    if pre is not None:
        # the AOT handle was captured at attach time (and stays: the
        # operation table is read from it): no re-lowering
        try:
            _fill_from_compiled(entry, pre)
        except Exception as e:  # noqa: BLE001 — diagnostics degrade
            entry["error"] = "%s: %s" % (type(e).__name__, e)
        entry["resolved"] = True
        return
    fn = entry.pop("_fn", None)
    spec = entry.pop("_abstract", None)
    try:
        if fn is None or spec is None:
            raise RuntimeError("executable never invoked before resolve")
        args, kwargs = spec, {}
        try:
            a, kw = _to_abstract(args, True)
            compiled = fn.lower(*a, **kw).compile()
        except Exception:  # noqa: BLE001 — sharding-annotated lowering
            # can refuse on some backends; shapes alone still analyze
            a, kw = _to_abstract(args, False)
            compiled = fn.lower(*a, **kw).compile()
        _fill_from_compiled(entry, compiled)
    except Exception as e:  # noqa: BLE001 — diagnostics degrade, never kill
        entry["error"] = "%s: %s" % (type(e).__name__, e)
    entry["resolved"] = True


# ---------------------------------------------------------- operation table
_HLO_HEAD = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_HLO_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the computations an instruction runs: a fusion's and a call's (``calls``,
# ``to_apply``), a switch's branches, a loop's condition and body
_HLO_CALLEES = re.compile(
    r"(?:calls|to_apply|condition|body|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
# the operations whose computations' instructions are events of a device
# trace in their own right, nested in the operation's own event
CONTROL_FLOW = ("conditional", "while", "call")


def operation_table(text):
    """A compiled module's text (``Compiled.as_text()``) -> {instruction:
    {"computation", "opcode", "op_name", "inside"}}: the computation that
    holds the instruction, its opcode, the ``op_name`` of its metadata
    ("" where it has none; a fusion without one of its own takes its fused
    root's, or failing that the last one inside it), and the ``conditional``
    / ``while`` / ``call`` instruction whose branch or body holds it (None
    in the entry computation, and in a computation only a fusion or a
    reduction applies: those instructions are no events of a trace).
    Instruction names are the compiler's: they match a trace of the same
    executable and no other."""
    table, members, caller, fused = {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        head = _HLO_HEAD.match(line)
        if head:
            comp = head.group(2)
            continue
        instr = _HLO_INSTR.match(line)
        if comp is None or not instr:
            continue
        root, name, opcode = instr.groups()
        op_name = _HLO_OP_NAME.search(line)
        table[name] = {"computation": comp, "opcode": opcode,
                       "op_name": op_name.group(1) if op_name else "",
                       "inside": None}
        members.setdefault(comp, []).append((name, bool(root)))
        for one, several in _HLO_CALLEES.findall(line):
            for callee in [one] if one else re.findall(r"[\w.\-]+", several):
                if opcode == "fusion":
                    fused[name] = callee
                elif opcode in CONTROL_FLOW:
                    caller[callee] = name
    for name, callee in fused.items():
        if not table[name]["op_name"]:
            inner = members.get(callee, ())
            named = [table[n]["op_name"] for n, root in inner if root] + [
                table[n]["op_name"] for n, _ in reversed(inner)]
            table[name]["op_name"] = next((o for o in named if o), "")
    for name, row in table.items():
        row["inside"] = caller.get(row["computation"])
    return table


def transform_of(op_name):
    """Which part of a training step an ``op_name`` was traced under:
    ``"optimizer"`` (the step's own scope), ``"recomputed"`` (a
    ``jax.checkpoint``'s second forward, ``rematted_computation``),
    ``"backward"`` (``transpose(...)``), else ``"forward"``; None for an
    operation without a name."""
    if "/" not in op_name:          # none, or an argument's own name
        return None
    if "/optimizer/" in op_name or op_name.endswith("/optimizer"):
        return "optimizer"
    if "rematted_computation" in op_name:
        return "recomputed"
    if "transpose(" in op_name:
        return "backward"
    return "forward"


def scope_path(op_name):
    """An ``op_name``'s words in order: a scope stands between slashes or
    inside a transform's brackets (``transpose(jvp(kda_conv))/jit(_backward)
    /mul`` -> transpose, jvp, kda_conv, jit, _backward, mul), so a block's
    name (it ends in ``_``, or is its key among its parent's children) and
    an operator's scope are found the same way in the forward, the
    recomputed forward and the backward."""
    return re.findall(r"[\w.\-]+", op_name)


# the parse takes a second or two of a large step's text: its own lock, so
# that an MFU meter's resolve on another thread never waits for it
_TABLE_LOCK = threading.Lock()


def _newest(site):
    with _LOCK:
        dq = _SITES.get(site)
        return dq[-1] if dq else None


def step_operations(site="parallel.train_step"):
    """:func:`operation_table` of the executable ``site`` runs (its newest
    ledger entry's), parsed on first request from the handle the entry
    holds and kept with it; None where the entry holds no ``Compiled``.
    Host work on text the compiler already has: nothing is traced, lowered
    or compiled, and the step object need not be alive."""
    entry = _newest(site)
    if entry is None or entry.get("_compiled") is None:
        return None
    with _TABLE_LOCK:
        if "_operations" not in entry:
            t0 = time.perf_counter()
            text = entry["_compiled"].as_text()
            entry["_operations"] = operation_table(text)
            entry["operations"] = {
                "instructions": len(entry["_operations"]),
                "text_bytes": len(text),
                "parse_s": time.perf_counter() - t0}
        return entry["_operations"]


def _public(entry):
    return {k: v for k, v in entry.items() if not k.startswith("_")}


def ledger(site=None, resolve=True):
    """The per-site executable ledger as a list of dicts (sorted by
    compile order). ``resolve=True`` (the diagnostic default) fills any
    pending cost/memory analyses first — one host-side lowering per
    still-unresolved executable; pass ``resolve=False`` on scrape/dump
    paths that must never invoke the compiler."""
    with _LOCK:
        entries = [e for s, dq in sorted(_SITES.items())
                   if site is None or s == site for e in list(dq)]
    if resolve:
        for e in entries:
            if not e["resolved"]:
                _resolve_entry(e)
    return sorted((_public(e) for e in entries), key=lambda e: e["seq"])


def ledger_snapshot():
    """Resolve-free ledger view — what ``telemetry.snapshot()`` exports
    on ``/metrics`` and what flight artifacts embed (a scrape or an OOM
    dump must never stall on ``lower().compile()``)."""
    return ledger(resolve=False)


def resolve(site=None):
    """Force analysis resolution for ``site`` (or everything)."""
    return ledger(site, resolve=True)


def executed_flops(sites=None):
    """Σ cost-model FLOPs × call count over resolved ledger entries —
    the MFU numerator. ``sites`` filters by exact site name or
    dotted-prefix family (``serving.predict`` matches
    ``serving.predict.r0``)."""
    with _LOCK:
        entries = [e for dq in _SITES.values() for e in list(dq)]
    total = 0.0
    for e in entries:
        fl = e.get("flops")
        if not fl:
            continue
        s = e["site"]
        if sites is not None and not any(
                s == want or s.startswith(want + ".") for want in sites):
            continue
        total += fl * e["calls"]
    return total


def summary():
    """One-line ledger digest for bench JSON stamps: compile count,
    total compile seconds, and the process-peak HBM across devices."""
    with _LOCK:
        entries = [e for dq in _SITES.values() for e in list(dq)]
    comp = [e["compile_s"] for e in entries if e.get("compile_s")]
    out = {"compiles": len(entries),
           "compile_s_total": round(sum(comp), 3) if comp else 0.0}
    peak = 0
    try:
        import jax
        for d in jax.devices():
            peak = max(peak, device_memory(d).get("peak_bytes_in_use", 0))
    except Exception:  # noqa: BLE001 — a dead PJRT client still stamps
        pass
    out["peak_hbm_bytes"] = peak or None
    return out


# --------------------------------------------------------- HBM accounting
def device_memory(device=0):
    """Normalized device memory view — THE one helper every consumer
    (``util.get_gpu_memory``, the C-ABI ``MXGetGPUMemoryInformation``,
    the memwatch gauges) reads, so they can never disagree on key
    fallbacks. ``device`` is a jax Device or an index. Keys:
    ``bytes_in_use`` / ``bytes_limit`` / ``peak_bytes_in_use`` /
    ``bytes_free`` — all 0 when the backend exposes no stats (CPU)."""
    stats = {}
    try:
        if not hasattr(device, "memory_stats"):
            import jax
            device = jax.devices()[int(device)]
        stats = device.memory_stats() or {}
    except Exception:  # noqa: BLE001 — backend not initialized / no stats
        stats = {}
    limit = int(stats.get("bytes_limit")
                or stats.get("bytes_reservable_limit") or 0)
    used = int(stats.get("bytes_in_use") or 0)
    peak = int(stats.get("peak_bytes_in_use") or used)
    return {"bytes_in_use": used, "bytes_limit": limit,
            "peak_bytes_in_use": peak,
            "bytes_free": max(limit - used, 0) if limit else 0}


def poll_memory(stats=None):
    """One HBM sweep into the per-device gauges
    (``memory.hbm_{used,limit,headroom,peak}_bytes`` tagged ``d<i>``).
    ``stats`` (``{tag: device_memory-dict}``) is injectable so tests and
    stats-less backends can drive the gauge path. Devices with no
    exposed stats are skipped — on the CPU tier this is a no-op."""
    if not enabled():
        return {}
    if stats is None:
        try:
            import jax
            devs = jax.devices()
        except Exception:  # noqa: BLE001
            return {}
        stats = {}
        for i, d in enumerate(devs):
            m = device_memory(d)
            if m["bytes_limit"] or m["bytes_in_use"]:
                stats["d%d" % i] = m
    for tag, m in stats.items():
        used = int(m.get("bytes_in_use", 0))
        limit = int(m.get("bytes_limit", 0))
        telemetry.gauge("memory.hbm_used_bytes", used, tag=tag)
        telemetry.gauge("memory.hbm_limit_bytes", limit, tag=tag)
        telemetry.gauge("memory.hbm_headroom_bytes",
                        max(limit - used, 0), tag=tag)
        telemetry.gauge("memory.hbm_peak_bytes",
                        int(m.get("peak_bytes_in_use", used)), tag=tag)
    return stats


def ensure_memwatch():
    """Start the off-thread HBM monitor when ``MXTPU_MEMWATCH_S`` > 0
    (idempotent; called from Trainer init and serving warmup so the
    gauges are live wherever device memory is being committed)."""
    interval = memwatch_interval()
    if interval <= 0 or not enabled():
        return False
    with _MEMWATCH["lock"]:
        t = _MEMWATCH["thread"]
        if t is not None and t.is_alive():
            return True
        stop = threading.Event()
        t = threading.Thread(target=_memwatch_loop, args=(interval, stop),
                             daemon=True, name="mxtpu-memwatch")
        _MEMWATCH["thread"] = t
        _MEMWATCH["stop"] = stop
        t.start()
    return True


def stop_memwatch():
    with _MEMWATCH["lock"]:
        stop, t = _MEMWATCH["stop"], _MEMWATCH["thread"]
        _MEMWATCH["thread"] = None
        _MEMWATCH["stop"] = None
    if stop is not None:
        stop.set()
    if t is not None:
        t.join(timeout=1.0)


def _memwatch_loop(interval, stop):
    while not stop.wait(interval):
        try:
            poll_memory()
        except Exception:  # noqa: BLE001 — a poll error must never kill
            pass           # the monitor (next interval retries)


def site_footprint(site, resolve=True, family=False):
    """A site's steady-state resident-byte estimate from its executable
    ledger. Footprint model (shared with :func:`preflight`): arguments
    are shared across buckets (params + request buffers — counted once
    at the donated-savings-adjusted max), temps are per-dispatch scratch
    (max — buckets never run concurrently), outputs (KV carries, result
    buffers) may all stay live (Σ). ``family=True`` matches the dotted
    prefix too (``serving.predict.zoo.m`` covers its ``.canary``
    subsite) — what the model zoo records as a resident model's HBM
    cost and sums into co-residency preflights."""
    entries = ledger(None if family else site, resolve=resolve)
    args_max = temp_max = out_sum = 0
    for e in entries:
        s = e.get("site")
        if family and not (s == site or (s or "").startswith(site + ".")):
            continue
        if e.get("error"):
            continue
        args_max = max(args_max, (e.get("argument_bytes") or 0)
                       - (e.get("donated_bytes") or 0))
        temp_max = max(temp_max, e.get("temp_bytes") or 0)
        out_sum += e.get("output_bytes") or 0
    return args_max + temp_max + out_sum


def preflight(site, device=0, limit=None, extra_bytes=0):
    """Will-it-fit pre-flight after an AOT warmup: the site's executables'
    combined footprint (:func:`site_footprint`) plus ``extra_bytes``
    already committed by co-residents (the model zoo passes the summed
    ledger footprints of the other models on the device) vs the device
    HBM limit. Past the limit it warns and bumps
    ``memory.overcommit{site}`` — warmup SUCCEEDING does not mean steady
    state fits once every bucket's residents (and neighbours) coexist.

    Returns ``(need_bytes, limit_bytes)``; None when the limit is
    unknown and not supplied (CPU tier) — skipped WITHOUT resolving, so
    host-tier warmups pay zero extra lowering."""
    if not enabled():
        return None
    if limit is None:
        limit = device_memory(device)["bytes_limit"]
    if not limit:
        return None
    need = site_footprint(site, resolve=True) + int(extra_bytes or 0)
    telemetry.gauge("memory.preflight_bytes", need, tag=site)
    if need > limit:
        telemetry.inc("memory.overcommit", tag=site)
        _log.warning(
            "memory pre-flight: site %r AOT footprint ~%.0f MiB "
            "(co-resident %.0f MiB included) exceeds the %.0f MiB device "
            "limit — warmup succeeded but steady state may "
            "RESOURCE_EXHAUST; shrink buckets/capacity, evict a "
            "co-resident model, or enable int8 (docs/observability.md)",
            site, need / 2**20, (extra_bytes or 0) / 2**20, limit / 2**20)
    return need, limit


# ------------------------------------------------------------- OOM flight
def is_oom(exc):
    """True when ``exc`` is a device allocator failure — jaxlib's
    ``RESOURCE_EXHAUSTED``/"Out of memory" spellings and the injected
    ``resilience.ResourceExhausted`` (fault kind ``oom``) all match."""
    if exc is None:
        return False
    s = "%s: %s" % (type(exc).__name__, exc)
    return any(m in s for m in _OOM_MARKERS)


def oom_flight(where, exc, extra=None, trace_ids=()):
    """Flight-record an HBM OOM: the artifact carries the executable
    ledger (resolve-free — the compiler is not invoked at the death
    moment), per-device memory stats, and any caller view (the decode
    path passes its KVCacheAccountant snapshot). Callers re-raise after
    — the flight recorder documents the failure, it does not absorb it."""
    telemetry.inc("memory.oom", tag=where)
    mem = {}
    try:
        import jax
        for i, d in enumerate(jax.devices()):
            mem["d%d" % i] = device_memory(d)
    except Exception:  # noqa: BLE001 — a dying backend still dumps
        pass
    ex = {"where": where, "error": str(exc)[:4000],
          "ledger": ledger_snapshot(), "memory": mem}
    if extra:
        ex.update(_jsonable(extra))
    return telemetry.flight_record("oom", trace_ids=trace_ids, extra=ex)


# ---------------------------------------------------------------- MFU meter
class MFUMeter:
    """Runtime MFU from bookkeeping alone: every ``every`` steps, the
    delta of ledger executed-FLOPs over the wall-clock delta, divided by
    the datasheet peak (``perf_model.peak_flops`` × ``n_devices``), lands
    in the ``perf.mfu`` gauge — zero extra device work, the smoothing is
    the window itself. The first tick resolves the step path's pending
    ledger analyses (one-time host lowering, at warmup-adjacent time);
    later ticks only resolve executables compiled since. Off-TPU the
    gauge appears only under an ``MXTPU_PEAK_TFLOPS`` override."""

    def __init__(self, sites=TRAIN_SITES, every=32, n_devices=1,
                 device=None):
        self._sites = tuple(sites)
        self._every = max(int(every), 1)
        self._n_devices = max(int(n_devices), 1)
        self._device = device
        self._n = 0
        self._t0 = None
        self._fl0 = 0.0
        self.last = None

    def step(self):
        """Count one training step; on window boundaries update the
        gauge. Returns the latest MFU (None until known)."""
        if not enabled():
            return None
        self._n += 1
        if self._n % self._every:
            return self.last
        from . import perf_model
        resolve_sites = set(self._sites)
        for s in list(_SITES):
            if any(s == w or s.startswith(w + ".") for w in self._sites):
                resolve_sites.add(s)
        for s in resolve_sites:
            if s in _SITES:
                resolve(s)
        now = time.perf_counter()
        fl = executed_flops(self._sites)
        if self._t0 is not None:
            peak = perf_model.peak_flops(self._device)
            dt = now - self._t0
            dfl = fl - self._fl0
            if peak and dt > 0 and dfl > 0:
                self.last = dfl / dt / (peak * self._n_devices)
                telemetry.gauge("perf.mfu", self.last)
        self._t0, self._fl0 = now, fl
        return self.last


# -------------------------------------------------------------------- reset
def reset():
    """Test hook: clear the ledger and stop the memwatch thread (wrapped
    executables keep counting into their orphaned entries — they are
    simply no longer listed). ``telemetry.reset()`` calls this."""
    stop_memwatch()
    with _LOCK:
        _SITES.clear()
