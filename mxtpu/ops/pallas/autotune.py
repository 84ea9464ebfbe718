"""Measured Pallas block-shape autotuner (ROADMAP item 1).

The hand kernels in this package ship with hand-picked launch geometry:
``conv.py`` derives its output row-block ``bo`` from a fixed
``_TARGET_M`` and ``flash_attention.py`` defaults to 512/512 q/k blocks.
Those defaults were picked against one chip generation and one model
family; the per-site roofline ledger (``telemetry_report --ledger``,
arXiv:2301.13062) shows which sites are memory-bound enough for block
geometry to matter, and the TVM line of work (arXiv:1802.04799) shows
measured search over a declared parameter space reliably beats
hand-picked schedules. This module is that search engine, generic over
the kernel fleet:

* **Plan spaces** — each kernel registers a :class:`TunableKernel`
  descriptor declaring its candidate plans (block shapes, row splits),
  its hand-picked default, a ``_resolve``-style feasibility check that
  rejects VMEM-overflow plans BEFORE any compile, and a runner that
  dispatches the kernel on real buffers.
* **Measured search** — :func:`search` times every feasible candidate
  with warmup-discarded median-of-rounds dispatches (the first dispatch
  carries trace+compile and is thrown away), bounded by
  ``MXTPU_AUTOTUNE_BUDGET_S`` wall clock. The search runs on whatever
  backend is live: on a chip the real kernel is timed, on the host tier
  the kernel's interpret lever is raised so block geometry still
  executes (slower absolute numbers, same machinery — no plan found
  there is a plan for the chip: plans are keyed by device kind).
* **Persistent plan artifacts** — winning plans serialize under
  ``MXTPU_COMPILE_CACHE_DIR`` next to the compile service's executable
  blobs, keyed by (kernel id, shape class incl. dtype, device kind),
  committed tmp+rename with a self-describing JSON header. Every
  load-time mismatch — truncated/garbage blob, format/device skew, a
  forged or collided digest — degrades to the hand-picked default with
  an ``autotune.drops{reason}`` count (the PR-15 failure-matrix
  discipline): the plan cache can never crash a trace and can never
  serve another device's geometry.
* **Zero warm-start searches** — ``MXTPU_AUTOTUNE=1`` makes the kernels
  consult :func:`lookup` at trace time; the plan table is loaded from
  disk ONCE per process, so a restarted trainer or fresh replica serves
  tuned plans with zero searches. ``compile_service.warmup`` preloads
  the table before any tracing, which ships tuned plans fleet-wide
  through the existing ReplicaSet/Trainer warmup path.
* **Plan identity rides the jit cache key** — :func:`policy_token` is a
  component of ``registry.policy_key()`` (the way ``MeshPlan``
  fingerprints ride the sharding component): installing a different
  tuned plan changes every policy-keyed cache digest, so a plan flip
  can never alias an executable traced under the old geometry; sites
  that key on an explicit policy subset (the fused optimizer) never
  recompile.

Observability: ``autotune.searches`` / ``autotune.plan_hits{source}`` /
``autotune.plan_misses`` / ``autotune.drops{reason}`` counters and the
``pallas.plan{kernel}`` gauge family (fingerprint of the last plan
served per kernel; 0 = hand-picked default). The observe → tune →
persist → serve loop and the artifact format live in docs/autotune.md.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import tempfile
import threading
import time
from typing import Callable, NamedTuple, Optional

__all__ = ["TunableKernel", "register_kernel", "kernels", "enabled",
           "lookup", "active_plan", "plan_id_of", "forced", "search",
           "install_plan", "save_plan", "ensure_loaded", "policy_token",
           "reset"]

FORMAT_VERSION = 1
_MAGIC = "MXTPU-AT"
_PREFIX = "plan_"
_SUFFIX = ".mxp"

_LOCK = threading.RLock()
_PLANS = {}        # (kernel_id, class token) -> {plan, plan_id, source}
_FORCED = {}       # kernel_id -> [plan, ...] (innermost last)
_STATE = {"loaded": False, "digest": None}


class TunableKernel(NamedTuple):
    """One kernel's declared tunable surface.

    ``space(sc)`` yields candidate plan dicts for a shape class,
    ``default(sc)`` the hand-picked plan (always timed first and always
    the degradation target), ``feasible(plan, sc)`` the pre-compile
    VMEM/divisibility gate returning ``(ok, reason)``, ``runner(sc)``
    a ``(fn, args)`` pair dispatching the kernel on real buffers, and
    ``classes(host_tier)`` the representative shape classes a tuning
    session sweeps when the ledger queue names the kernel's sites.
    ``interpret_env`` is the kernel's interpret lever, raised by the
    search off-TPU so candidates execute on the host tier."""
    kernel_id: str
    space: Callable
    default: Callable
    feasible: Callable
    runner: Callable
    classes: Callable
    interpret_env: Optional[str] = None


_KERNELS = {}


def register_kernel(tk: TunableKernel):
    _KERNELS[tk.kernel_id] = tk
    return tk


def kernels():
    return dict(_KERNELS)


# --------------------------------------------------------------- env levers
def enabled():
    """MXTPU_AUTOTUNE=1 serves tuned plans at trace time. Trace-time
    lever: the default mirrors the registry.policy_key entry."""
    return os.environ.get("MXTPU_AUTOTUNE", "0") == "1"


def _rounds(override=None):
    if override is not None:
        return max(1, int(override))
    # host-side search knob (timed rounds per candidate) — read only by
    # search(), never inside a trace
    return max(1, int(os.environ.get("MXTPU_AUTOTUNE_ROUNDS", "3")))  # graftlint: disable=policy-key-coverage


def _budget_s(override=None):
    if override is not None:
        return float(override)
    # host-side search knob (wall budget per search) — never traced
    return float(os.environ.get("MXTPU_AUTOTUNE_BUDGET_S", "30"))  # graftlint: disable=policy-key-coverage


# ------------------------------------------------------------- key material
def class_token(shape_class):
    """Deterministic token for a shape class: sorted ``k=v`` pairs. The
    class dict must already carry the dtype — (kernel, class, dtype,
    device) is the full artifact key."""
    return "|".join("%s=%s" % (k, shape_class[k])
                    for k in sorted(shape_class))


def device_kind():
    """Plan artifacts are geometry, not code, so they key on the chip
    KIND (platform + device_kind), not the jax/jaxlib ABI the
    executable cache must pin."""
    try:
        import jax
        d = jax.devices()[0]
        return "%s/%s" % (d.platform, getattr(d, "device_kind", "?"))
    except Exception:  # noqa: BLE001 — a dead PJRT client still keys
        return "unknown"


def _key_material(kernel_id, token, device):
    return "%s|%s|%s|fmt%d" % (kernel_id, token, device, FORMAT_VERSION)


def _digest(kernel_id, token, device):
    mat = _key_material(kernel_id, token, device)
    return hashlib.sha256(mat.encode("utf-8")).hexdigest()[:20]


def plan_path(kernel_id, shape_class, root=None):
    """Artifact path for (kernel, class, device) under the compile
    service's cache dir, or None when the disk cache is off."""
    from ... import compile_service
    root = root or compile_service.cache_dir()
    if not root:
        return None
    token = class_token(shape_class)
    return os.path.join(root, _PREFIX
                        + _digest(kernel_id, token, device_kind())
                        + _SUFFIX)


def plan_id_of(plan):
    """Stable human-readable plan identity, e.g. ``bo=16`` or
    ``block_k=256,block_q=512`` — what bench lines and artifacts
    stamp."""
    return ",".join("%s=%s" % (k, plan[k]) for k in sorted(plan))


def _plan_fingerprint(plan_id):
    """Small numeric fingerprint for the ``pallas.plan{kernel}`` gauge
    (0 is reserved for the hand-picked default)."""
    h = hashlib.sha256(plan_id.encode("utf-8")).hexdigest()[:6]
    return int(h, 16) or 1


# ------------------------------------------------------------------ serving
def _drop(reason, kernel_id, path=None):
    from ... import telemetry
    telemetry.inc("autotune.drops", tag=reason)
    return None


def _gauge(kernel_id, plan_id):
    from ... import telemetry
    telemetry.gauge("pallas.plan",
                    0 if plan_id is None else _plan_fingerprint(plan_id),
                    tag=kernel_id)


def lookup(kernel_id, shape_class):
    """The kernels' trace-time consult: the tuned plan dict for this
    (kernel, shape class, device), or None → hand-picked default.
    Forced plans (the search / parity tests) win over everything;
    otherwise the table is served only under ``MXTPU_AUTOTUNE=1``.
    Counts ``autotune.plan_hits{source}`` / ``autotune.plan_misses``
    and publishes the ``pallas.plan{kernel}`` gauge."""
    from ... import telemetry
    stack = _FORCED.get(kernel_id)
    if stack:
        plan = dict(stack[-1])
        telemetry.inc("autotune.plan_hits", tag="forced")
        return plan
    if not enabled():
        return None
    ensure_loaded()
    with _LOCK:
        rec = _PLANS.get((kernel_id, class_token(shape_class)))
    if rec is None:
        telemetry.inc("autotune.plan_misses")
        _gauge(kernel_id, None)
        return None
    telemetry.inc("autotune.plan_hits", tag=rec["source"])
    _gauge(kernel_id, rec["plan_id"])
    return dict(rec["plan"])


def plan_infeasible(kernel_id, reason="infeasible"):
    """A served plan failed the kernel's own revalidation (divisor /
    VMEM) — the kernel degrades to its default and the drop counts.
    Exposed for the kernels' consult sites."""
    return _drop(reason, kernel_id)


def active_plan(kernel_id, shape_class):
    """(plan_id, provenance) the kernel would use for this class right
    now — ``("<plan id>", "tuned")`` or ``(None, "default")``. The
    bench stamps this into every JSON line."""
    plan = lookup(kernel_id, shape_class)
    if plan is None:
        return None, "default"
    tk = _KERNELS.get(kernel_id)
    if tk is not None and plan == tk.default(shape_class):
        return plan_id_of(plan), "default"
    return plan_id_of(plan), "tuned"


@contextlib.contextmanager
def forced(kernel_id, plan):
    """Force ``plan`` for every ``lookup`` of ``kernel_id`` inside the
    context — how the search times candidates and how the parity tests
    pin every candidate the search may emit."""
    with _LOCK:
        _FORCED.setdefault(kernel_id, []).append(dict(plan))
    try:
        yield
    finally:
        with _LOCK:
            _FORCED[kernel_id].pop()
            if not _FORCED[kernel_id]:
                del _FORCED[kernel_id]


# -------------------------------------------------------------- persistence
def save_plan(kernel_id, shape_class, plan, meta=None, root=None):
    """Serialize a winning plan tmp+rename under the compile-service
    cache dir. Self-describing JSON: magic + env (format, device kind) +
    the full key material, so a forged rename or a foreign device's
    artifact is detected at load. Returns the committed path or None
    (disk cache off / IO failure — counted, never raised)."""
    path = plan_path(kernel_id, shape_class, root)
    if path is None:
        return None
    token = class_token(shape_class)
    rec = {"magic": _MAGIC,
           "env": {"format": FORMAT_VERSION, "device": device_kind()},
           "kernel": kernel_id,
           "class": token,
           "key": _key_material(kernel_id, token, device_kind()),
           "plan": dict(plan),
           "plan_id": plan_id_of(plan),
           "meta": dict(meta or {}),
           "created": time.time()}
    try:
        root_dir = os.path.dirname(path)
        os.makedirs(root_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=root_dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(rec, f, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except Exception:  # noqa: BLE001 — disk full / perms / races
        return _drop("io", kernel_id, path)
    return path


def _load_blob(path):
    """One artifact → the in-memory table, or a counted drop. The
    degradation matrix mirrors the executable cache's: ``corrupt``
    (unreadable/garbage/bad magic), ``version_mismatch`` (format or
    device-kind skew), ``key_mismatch`` (digest collision or forged
    rename — the stored key material disagrees with the filename)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            rec = json.load(f)
    except Exception:  # noqa: BLE001 — truncated/garbage blob
        return _drop("corrupt", None, path)
    if not isinstance(rec, dict) or rec.get("magic") != _MAGIC:
        return _drop("corrupt", None, path)
    env = rec.get("env")
    if env != {"format": FORMAT_VERSION, "device": device_kind()}:
        return _drop("version_mismatch", rec.get("kernel"), path)
    kernel_id = rec.get("kernel")
    token = rec.get("class")
    plan = rec.get("plan")
    if not (isinstance(kernel_id, str) and isinstance(token, str)
            and isinstance(plan, dict)):
        return _drop("corrupt", kernel_id, path)
    want_key = _key_material(kernel_id, token, device_kind())
    want_name = _PREFIX + _digest(kernel_id, token, device_kind()) + _SUFFIX
    if rec.get("key") != want_key \
            or os.path.basename(path) != want_name:
        return _drop("key_mismatch", kernel_id, path)
    with _LOCK:
        _PLANS[(kernel_id, token)] = {
            "plan": dict(plan),
            "plan_id": rec.get("plan_id") or plan_id_of(plan),
            "source": "disk"}
        _STATE["digest"] = None
    return plan


def ensure_loaded():
    """Scan the cache dir ONCE per process and install every valid plan
    artifact for this device kind — the zero-warm-start-search path. A
    no-op unless ``MXTPU_AUTOTUNE=1`` (the table is never consulted
    when the lever is off, so the scan would be waste)."""
    if not enabled():
        return
    with _LOCK:
        if _STATE["loaded"]:
            return
        _STATE["loaded"] = True
    from ... import compile_service
    root = compile_service.cache_dir()
    if not root or not os.path.isdir(root):
        return
    for name in sorted(os.listdir(root)):
        if name.startswith(_PREFIX) and name.endswith(_SUFFIX):
            _load_blob(os.path.join(root, name))


def install_plan(kernel_id, shape_class, plan, source="search"):
    """Install a plan into the serving table (and invalidate the policy
    token so every policy-keyed executable recompiles under the new
    geometry — a plan flip can never alias)."""
    with _LOCK:
        _PLANS[(kernel_id, class_token(shape_class))] = {
            "plan": dict(plan), "plan_id": plan_id_of(plan),
            "source": source}
        _STATE["digest"] = None


def installed():
    """{(kernel_id, class token): plan_id} — observability/tests."""
    with _LOCK:
        return {k: v["plan_id"] for k, v in _PLANS.items()}


def reset():
    """Drop the in-memory table and the loaded/digest state (tests; a
    fresh process is the real reset)."""
    with _LOCK:
        _PLANS.clear()
        _FORCED.clear()
        _STATE["loaded"] = False
        _STATE["digest"] = None


def policy_token():
    """The plan-identity component of ``registry.policy_key()``: "0"
    when serving is off, else a digest of the installed plan set.
    Loaded once per process, so the token is stable across every trace
    of a serving run; an in-process ``install_plan`` (a live search)
    changes it, forcing exactly the recompile the new geometry needs."""
    if not enabled():
        return "0"
    ensure_loaded()
    with _LOCK:
        if _STATE["digest"] is None:
            items = sorted((k[0], k[1], v["plan_id"])
                           for k, v in _PLANS.items())
            _STATE["digest"] = ("0" if not items else hashlib.sha256(
                repr(items).encode("utf-8")).hexdigest()[:12])
        return _STATE["digest"]


# ------------------------------------------------------------------- search
def _sync(out):
    """Host-fetch sync: waits for the device on every backend (the
    PERF.md methodology; ``block_until_ready`` agrees with it on the
    attached chip — chip_smoke.py ``sync`` phase)."""
    import jax
    import numpy as np
    leaves = [x for x in jax.tree_util.tree_leaves(out)
              if hasattr(x, "ravel")]
    if leaves:
        np.asarray(jax.device_get(leaves[0].ravel()[:1]))


@contextlib.contextmanager
def _env_patch(name, value):
    saved = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = saved


def _time_plan(kernel_id, fn, plan, args, rounds):
    """Warmup-discarded median-of-rounds wall time of one candidate
    dispatch on real buffers. The candidate executables are deliberately
    EPHEMERAL measurement probes — the persisted artifact is the PLAN,
    and the serving-path executables that embed it resolve through
    compile_service.get_or_build at their own sites (JIT_ALLOWLIST:
    autotune.search). Each probe compile still reports through
    ``record_retrace`` so the xprof executable ledger covers the site
    like every other inventory entry; the wrapper's per-call overhead is
    a counter bump, identical across candidates, so the A/B stays
    like-for-like."""
    import jax

    from ... import telemetry
    with forced(kernel_id, plan):
        jitted = jax.jit(lambda *a: fn(*a))
        jitted = telemetry.record_retrace(
            "autotune.search",
            provenance=(kernel_id, plan_id_of(plan)),
            compiled=jitted) or jitted
        _sync(jitted(*args))        # trace+compile — discarded
        ts = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            _sync(jitted(*args))
            ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def search(kernel_id, shape_class, rounds=None, budget_s=None,
           install=True, persist=True):
    """Measured search over one kernel's plan space for one shape class.

    Candidates are feasibility-pruned BEFORE any compile (VMEM
    overflow / non-divisor blocks never reach the backend), the
    hand-picked default is always timed first (it is the baseline the
    not-worse gates compare against), and the wall budget stops the
    sweep with best-so-far. Off-TPU the kernel's interpret lever is
    raised so geometry still executes on the host tier. Returns the
    result record; when the best plan beats the default it is installed
    (and persisted with ``MXTPU_COMPILE_CACHE_DIR`` set)."""
    from ... import telemetry
    tk = _KERNELS[kernel_id]
    telemetry.inc("autotune.searches")
    rounds = _rounds(rounds)
    budget = _budget_s(budget_s)
    default = dict(tk.default(shape_class))
    default_id = plan_id_of(default)

    cands, pruned, seen = [], [], set()
    for plan in [default] + list(tk.space(shape_class)):
        pid = plan_id_of(plan)
        if pid in seen:
            continue
        seen.add(pid)
        ok, reason = tk.feasible(plan, shape_class)
        if ok:
            cands.append(dict(plan))
        else:
            pruned.append({"plan_id": pid, "reason": reason})

    fn, args = tk.runner(shape_class)
    from .flash_attention import _platform
    ctx = (_env_patch(tk.interpret_env, "1")
           if tk.interpret_env and _platform() != "tpu"
           else contextlib.nullcontext())
    timings = []
    budget_exhausted = False
    deadline = time.monotonic() + budget
    with ctx:
        for plan in cands:
            if timings and time.monotonic() > deadline:
                budget_exhausted = True
                break
            secs = _time_plan(kernel_id, fn, plan, args, rounds)
            timings.append({"plan": plan, "plan_id": plan_id_of(plan),
                            "s": secs})
    # candidate probes are throwaway jits; nothing persists past here
    default_s = timings[0]["s"]
    best = min(timings, key=lambda r: r["s"])
    improved = best["plan_id"] != default_id and best["s"] < default_s
    result = {"kernel": kernel_id,
              "class": class_token(shape_class),
              "device": device_kind(),
              "rounds": rounds,
              "candidates": len(cands),
              "pruned": pruned,
              "timed": len(timings),
              "budget_exhausted": budget_exhausted,
              "default_plan_id": default_id,
              "default_s": default_s,
              "best_plan": dict(best["plan"]),
              "best_plan_id": best["plan_id"],
              "best_s": best["s"],
              "speedup_vs_default": (default_s / best["s"]
                                     if best["s"] > 0 else None),
              "improved": improved,
              "timings": timings,
              "persisted": None}
    if improved and install:
        install_plan(kernel_id, shape_class, best["plan"])
        if persist:
            result["persisted"] = save_plan(
                kernel_id, shape_class, best["plan"],
                meta={"default_plan_id": default_id,
                      "default_s": default_s, "best_s": best["s"],
                      "rounds": rounds, "timed": len(timings),
                      "pruned": len(pruned)})
    return result
