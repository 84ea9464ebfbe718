"""Unified compile service (mxtpu/compile_service.py, ISSUE 15): canonical
keying, LRU bounding, concurrent AOT warmup with shared lowerings, and the
persistent on-disk executable cache's full failure matrix — every
degradation lands on a silent recompile with a counted reason, never a
crash, never a stale-policy executable."""
import json
import os
import pickle
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import compile_service as csvc
from mxtpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_service():
    csvc.reset()
    yield
    csvc.reset()


def _counter(name, tag=None):
    return telemetry.value(name, tag=tag)


def _key(site="executor", sig=((4,), "f32"), policy=("p0",), nonce=None,
         fn_id="svc-test", sharding=None, donation=None):
    return csvc.canonical_key(site=site, fn_id=fn_id, signature=sig,
                              policy=policy, sharding=sharding,
                              donation=donation,
                              device=csvc.device_token(), nonce=nonce)


def _build_mul(c=3.0, calls=None):
    def build():
        if calls is not None:
            calls.append(1)

        def f(x):
            return x * c

        return jax.jit(f)

    return build


# ---------------------------------------------------------------- basics
def test_miss_builds_and_reports_then_hits():
    k = _key()
    r0 = _counter("retrace.executor")
    calls = []
    e1 = csvc.get_or_build(k, _build_mul(calls=calls),
                           provenance={"t": 1})
    assert e1.origin == "built" and calls == [1]
    assert _counter("retrace.executor") == r0 + 1
    out = e1.fn(jnp.ones((4,)))
    assert float(out[0]) == 3.0
    e2 = csvc.get_or_build(k, _build_mul(calls=calls))
    assert e2.fn is e1.fn and calls == [1]          # pure hit: no rebuild
    assert _counter("retrace.executor") == r0 + 1   # and no re-report


def test_distinct_key_components_are_distinct_entries():
    base = dict(site="executor", sig=((4,), "f32"))
    ks = [_key(**base),
          _key(**dict(base, policy=("p1",))),
          _key(**dict(base, sharding=("mesh", 8))),
          _key(**dict(base, donation=(0,))),
          _key(**dict(base, nonce="iface2"))]
    for k in ks:
        csvc.get_or_build(k, _build_mul())
    assert csvc.stats()["entries"] == len(ks)


def test_meta_rides_the_entry():
    def build():
        cell = {"in_fmt": [1, 0]}

        def f(x):
            return x + 1

        return jax.jit(f), cell

    e = csvc.get_or_build(_key(), build)
    assert e.meta == {"in_fmt": [1, 0]}


def test_concurrent_misses_build_once():
    k = _key()
    calls, results = [], []
    gate = threading.Barrier(4)

    def slow_build():
        calls.append(1)

        def f(x):
            return x * 2

        return jax.jit(f)

    def worker():
        gate.wait()
        results.append(csvc.get_or_build(k, slow_build))

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(calls) == 1
    assert all(r.fn is results[0].fn for r in results)


# ------------------------------------------------------------------- LRU
def test_lru_bound_evicts_and_counts(monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_ENTRIES", "3")
    ev0 = _counter("compile.evictions", tag="executor")
    keys = [_key(sig=((i + 1,), "f32")) for i in range(5)]
    for k in keys:
        csvc.get_or_build(k, _build_mul())
    assert csvc.stats()["entries"] == 3
    assert _counter("compile.evictions", tag="executor") == ev0 + 2
    # oldest evicted: a re-request is a real (re-counted) compile
    r0 = _counter("retrace.executor")
    again = csvc.get_or_build(keys[0], _build_mul())
    assert again.origin == "built"
    assert _counter("retrace.executor") == r0 + 1
    # the refreshed entry displaced the then-oldest survivor
    assert csvc.stats()["entries"] == 3


def test_lru_hit_refreshes_position(monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_ENTRIES", "2")
    ka, kb, kc = (_key(sig=((i + 1,), "f32")) for i in range(3))
    csvc.get_or_build(ka, _build_mul())
    csvc.get_or_build(kb, _build_mul())
    csvc.get_or_build(ka, _build_mul())    # refresh a
    csvc.get_or_build(kc, _build_mul())    # evicts b, not a
    assert csvc.get(ka) is not None
    assert csvc.get(kb) is None


# ------------------------------------------------------------ disk cache
def test_disk_roundtrip_zero_compiles_bit_parity(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    k = _key()
    x = jnp.asarray(np.random.RandomState(0).randn(4).astype(np.float32))
    cold = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    assert cold.origin == "built"
    ref = np.asarray(cold.fn(x))
    assert _counter("compile.disk.writes", tag="executor") >= 1
    assert os.path.exists(csvc.disk_path_of(k))
    # "fresh process": drop all in-memory state, same dir
    csvc.reset()
    r0 = _counter("retrace.executor")
    h0 = _counter("compile.disk.hits", tag="executor")
    warm = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    assert warm.origin == "disk"
    assert _counter("retrace.executor") == r0        # a load is NOT a compile
    assert _counter("compile.disk.hits", tag="executor") == h0 + 1
    np.testing.assert_array_equal(np.asarray(warm.fn(x)), ref)


@pytest.mark.multidevice
@pytest.mark.parametrize("ids", [(5,), (2, 3, 6, 7)],
                         ids=["one_device", "four_device_mesh"])
def test_disk_reload_runs_on_its_own_devices(tmp_path, monkeypatch, ids):
    """The blob records the executable's devices, and the reload hands
    them to ``deserialize_and_load`` as ``execution_devices``: an
    executable built for device 5 (or a 4-device mesh) of an 8-device
    backend reloads onto exactly those devices. Without that JAX loads
    it over every device of the backend and the first call dies with
    ``Expected args to execute_sharded_on_local_devices to have 8
    shards`` (the multi-device form used to be refused outright as
    "XLA:CPU loads it as garbage" — the same fault)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    devs = [jax.devices()[i] for i in ids]
    sharding = NamedSharding(Mesh(np.array(devs), ("d",)), P("d"))
    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), sharding)
    k = csvc.canonical_key(
        site="executor", fn_id="svc-devs", signature=((8,), "f32"),
        device=csvc.device_token(mesh=sharding.mesh))
    cold = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    ref = np.asarray(cold.fn(x))
    rec = pickle.load(open(csvc.disk_path_of(k), "rb"))
    assert rec["devices"] == list(ids)
    csvc.reset()
    warm = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    assert warm.origin == "disk"
    out = warm.fn(x)
    assert {d.id for d in out.devices()} == set(ids)
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.multidevice
def test_tpu_multidevice_reload_is_default_assignment_only(tmp_path,
                                                           monkeypatch):
    """What the four-chip host showed (PR 21): the TPU client reloads a
    multi-device executable onto its default assignment whatever
    ``execution_devices`` says. So, on a TPU, a single-device executable
    is served on any chip, the all-devices-in-order mesh is served, and
    any other mesh is refused at spill with a counted reason — never a
    blob that would reload onto the wrong chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    every = [d.id for d in jax.devices()]
    assert csvc._reloadable([5]) and csvc._reloadable([2, 3])   # XLA:CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert csvc._reloadable([5])
    assert csvc._reloadable(every)
    assert not csvc._reloadable([2, 3])
    assert not csvc._reloadable(every[::-1])
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    sharding = NamedSharding(Mesh(np.array(jax.devices()[2:4]), ("d",)),
                             P("d"))
    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), sharding)
    k = csvc.canonical_key(
        site="executor", fn_id="svc-submesh", signature=((8,), "f32"),
        device=csvc.device_token(mesh=sharding.mesh))
    d0 = _counter("compile.disk.drops", tag="device_assignment")
    e = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    assert e.origin == "built" and float(e.fn(x)[1]) == 3.0
    assert _counter("compile.disk.drops", tag="device_assignment") == d0 + 1
    assert not os.path.exists(csvc.disk_path_of(k))


def test_disk_meta_persists(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))

    def build():
        def f(x):
            return x - 1

        return jax.jit(f), {"out_fmt": [0], "out_specs": [((4,), "f32")]}

    k = _key()
    csvc.get_or_build(k, build, example_args=(jnp.ones((4,)),))
    csvc.reset()
    warm = csvc.get_or_build(k, build, example_args=(jnp.ones((4,)),))
    assert warm.origin == "disk"
    assert warm.meta == {"out_fmt": [0], "out_specs": [[(4,), "f32"]]} \
        or warm.meta == {"out_fmt": [0], "out_specs": [((4,), "f32")]}


def test_truncated_blob_recompiles(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    k = _key()
    x = jnp.ones((4,))
    csvc.get_or_build(k, _build_mul(), example_args=(x,))
    path = csvc.disk_path_of(k)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:max(4, len(blob) // 3)])
    csvc.reset()
    d0 = _counter("compile.disk.drops", tag="corrupt")
    r0 = _counter("retrace.executor")
    e = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    assert e.origin == "built"                       # degraded, not crashed
    assert float(e.fn(x)[0]) == 3.0
    assert _counter("compile.disk.drops", tag="corrupt") == d0 + 1
    assert _counter("retrace.executor") == r0 + 1
    # the recompile re-spilled a GOOD blob: next probe loads again
    csvc.reset()
    assert csvc.get_or_build(k, _build_mul(),
                             example_args=(x,)).origin == "disk"


def test_garbage_blob_recompiles(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    k = _key()
    with open(csvc.disk_path_of(k), "wb") as f:
        f.write(b"not a pickle at all")
    d0 = _counter("compile.disk.drops", tag="corrupt")
    e = csvc.get_or_build(k, _build_mul(), example_args=(jnp.ones((4,)),))
    assert e.origin == "built"
    assert _counter("compile.disk.drops", tag="corrupt") == d0 + 1


def test_version_mismatch_recompiles(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    k = _key()
    x = jnp.ones((4,))
    csvc.get_or_build(k, _build_mul(), example_args=(x,))
    path = csvc.disk_path_of(k)
    rec = pickle.load(open(path, "rb"))
    rec["env"] = dict(rec["env"], jax="0.0.1-older")
    with open(path, "wb") as f:
        pickle.dump(rec, f)
    csvc.reset()
    d0 = _counter("compile.disk.drops", tag="version_mismatch")
    e = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    assert e.origin == "built"
    assert _counter("compile.disk.drops",
                    tag="version_mismatch") == d0 + 1


def test_unrestorable_blob_marked_and_skipped(tmp_path, monkeypatch):
    """A blob whose executable cannot deserialize in this environment
    (XLA CPU fusion-symbol limitation) recompiles once (load_error),
    gets tombstoned, and every later restart skips straight to the
    recompile — no repeated failed loads, no re-spill churn."""
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    k = _key()
    x = jnp.ones((4,))
    csvc.get_or_build(k, _build_mul(), example_args=(x,))
    path = csvc.disk_path_of(k)
    rec = pickle.load(open(path, "rb"))
    rec["payload"] = b"\x00not an executable"
    with open(path, "wb") as f:
        pickle.dump(rec, f)
    csvc.reset()
    d0 = _counter("compile.disk.drops", tag="load_error")
    w0 = _counter("compile.disk.writes", tag="executor")
    e = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    assert e.origin == "built"
    assert _counter("compile.disk.drops", tag="load_error") == d0 + 1
    # the recompile did NOT re-spill (the digest is marked unloadable)
    assert _counter("compile.disk.writes", tag="executor") == w0
    assert os.path.exists(path + ".unloadable")
    csvc.reset()
    u0 = _counter("compile.disk.drops", tag="unloadable")
    e2 = csvc.get_or_build(k, _build_mul(), example_args=(x,))
    assert e2.origin == "built"
    assert _counter("compile.disk.drops", tag="unloadable") == u0 + 1
    assert _counter("compile.disk.drops", tag="load_error") == d0 + 1


def test_forged_key_blob_never_served(tmp_path, monkeypatch):
    """A blob renamed onto another key's digest (or a digest collision)
    is refused by the in-blob canonical-key check — the cache can never
    serve an executable built for a different policy/sharding/donation."""
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    ka = _key(policy=("pA",))
    kb = _key(policy=("pB",))
    x = jnp.ones((4,))
    csvc.get_or_build(ka, _build_mul(7.0), example_args=(x,))
    os.replace(csvc.disk_path_of(ka), csvc.disk_path_of(kb))
    csvc.reset()
    d0 = _counter("compile.disk.drops", tag="key_mismatch")
    e = csvc.get_or_build(kb, _build_mul(3.0), example_args=(x,))
    assert e.origin == "built"
    assert float(e.fn(x)[0]) == 3.0                  # kb's OWN function
    assert _counter("compile.disk.drops", tag="key_mismatch") == d0 + 1


def test_policy_sharding_donation_flips_change_digest():
    """The stale-policy safety is structural: every canonical-key
    component that changes the traced program changes the DIGEST, so
    flipped configurations can never even find each other's blobs."""
    base = _key(policy=("a",))
    assert csvc.digest_of(base) != csvc.digest_of(_key(policy=("b",)))
    assert csvc.digest_of(base) != csvc.digest_of(
        _key(policy=("a",), sharding=("zero1", 8)))
    assert csvc.digest_of(base) != csvc.digest_of(
        _key(policy=("a",), donation=(0, 2)))
    assert csvc.digest_of(base) != csvc.digest_of(
        _key(policy=("a",), sig=((8,), "f32")))
    # site and instance nonce deliberately do NOT move the digest: a
    # replacement replica r9 on the same device reuses retired r2's blob
    assert csvc.digest_of(base) == csvc.digest_of(
        _key(policy=("a",), site="serving.predict.r9", nonce="iXYZ"))


def test_concurrent_writers_one_dir(tmp_path):
    """Two processes racing the same key into one cache dir: both
    succeed, the committed blob stays loadable (tmp+rename — a torn
    write can never land under the final name)."""
    script = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MXTPU_COMPILE_CACHE_DIR"] = sys.argv[1]
import jax, jax.numpy as jnp
from mxtpu import compile_service as csvc
k = csvc.canonical_key(site="executor", fn_id="race", signature=((64, 64), "f32"),
                       policy=("p",), device=csvc.device_token())
e = csvc.get_or_build(k, lambda: jax.jit(lambda x: x @ x + 1.0),
                      example_args=(jnp.ones((64, 64)),))
print("OK", e.origin, float(e.fn(jnp.ones((64, 64)))[0][0]))
"""
    env = dict(os.environ, PYTHONPATH=REPO)
    procs = [subprocess.Popen([sys.executable, "-c", script,
                               str(tmp_path)],
                              env=env, cwd=REPO, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(2)]
    outs = [p.communicate(timeout=240) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-1500:]
        assert "OK" in out and "65.0" in out, (out, err[-800:])
    # a third process loads what the racers committed — zero compiles
    p3 = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                        env=env, cwd=REPO, capture_output=True, text=True,
                        timeout=240)
    assert p3.returncode == 0, p3.stderr[-1500:]
    assert "OK disk" in p3.stdout, p3.stdout


def test_manifest_written(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    k = _key()
    csvc.get_or_build(k, _build_mul(), example_args=(jnp.ones((4,)),))
    man = csvc.manifest(str(tmp_path))
    assert man["format"] == csvc.FORMAT_VERSION
    assert csvc.digest_of(k) in man["entries"]
    row = man["entries"][csvc.digest_of(k)]
    assert row["site"] == "executor"
    assert row["key"] == k.digest_material()


def test_no_dir_means_plain_jit_path():
    """Without MXTPU_COMPILE_CACHE_DIR (and outside warmup) the service
    returns the freshly-built plain jit exactly as the per-site caches
    did — no AOT, no disk traffic."""
    os.environ.pop("MXTPU_COMPILE_CACHE_DIR", None)
    w0 = _counter("compile.disk.writes", tag="executor")
    e = csvc.get_or_build(_key(), _build_mul(),
                          example_args=(jnp.ones((4,)),))
    assert e.origin == "built"
    assert _counter("compile.disk.writes", tag="executor") == w0
    # a plain jit retraces on new shapes (an AOT Compiled would refuse)
    assert float(e.fn(jnp.ones((9,)))[0]) == 3.0


# ------------------------------------------------------------------ warmup
def test_warmup_concurrent_and_grouped():
    builds = []

    def build():
        builds.append(1)

        def f(x):
            return x + 5

        return jax.jit(f)

    s0 = _counter("compile.lowering_shares", tag="serving.predict.r1")
    entries = [csvc.WarmupEntry(
        key=_key(site="serving.predict.r%d" % i, nonce="i%d" % i),
        build=build, example_args=(jnp.ones((4,)),),
        provenance={"r": i}, group=("g", "sig")) for i in range(3)]
    summary = csvc.warmup(entries, threads=3)
    assert summary["entries"] == 3 and summary["built"] == 3
    assert summary["errors"] == 0
    assert len(builds) == 1                          # ONE trace, N compiles
    assert _counter("compile.lowering_shares",
                    tag="serving.predict.r1") == s0 + 1
    for i in range(3):
        e = csvc.get(_key(site="serving.predict.r%d" % i,
                          nonce="i%d" % i))
        assert e is not None
        assert float(e.fn(jnp.ones((4,)))[0]) == 6.0


def test_warmup_reraises_first_error():
    def bad_build():
        raise RuntimeError("broken bucket")

    entries = [
        csvc.WarmupEntry(key=_key(sig=((1,), "f32")),
                         build=_build_mul(), example_args=(jnp.ones((1,)),),
                         provenance=None),
        csvc.WarmupEntry(key=_key(sig=((2,), "f32")), build=bad_build,
                         example_args=(jnp.ones((2,)),), provenance=None),
    ]
    with pytest.raises(RuntimeError, match="broken bucket"):
        csvc.warmup(entries)
    # the good entry still landed
    assert csvc.get(_key(sig=((1,), "f32"))) is not None


def test_warmup_aot_even_without_dir():
    """warmup forces the AOT path (explicit lower+compile) with or
    without a disk dir — the executable is ready before first
    dispatch."""
    os.environ.pop("MXTPU_COMPILE_CACHE_DIR", None)
    entries = [csvc.WarmupEntry(key=_key(), build=_build_mul(),
                                example_args=(jnp.ones((4,)),),
                                provenance=None)]
    csvc.warmup(entries)
    e = csvc.get(_key())
    assert hasattr(e.fn, "cost_analysis")            # AOT executable


# ------------------------------------------------- end-to-end warm starts
def _run_startup_child(scenario, cache_dir, extra_env=None):
    env = dict(os.environ, PYTHONPATH=REPO,
               BENCH_STARTUP_HIDDEN="8", BENCH_STARTUP_LAYERS="1")
    env.update(extra_env or {})
    env["MXTPU_COMPILE_CACHE_DIR"] = str(cache_dir)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "startup_bench.py"),
         "--child", scenario],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("STARTUP_BENCH ")][0]
    return json.loads(line[len("STARTUP_BENCH "):])


def test_trainer_warm_start_zero_compiles(tmp_path):
    """ISSUE-15 acceptance (a): a restarted trainer reaches its first
    step from a warm MXTPU_COMPILE_CACHE_DIR with ZERO compiles
    (watchdog-pinned across every retrace site) and the identical
    loss."""
    cold = _run_startup_child("trainer", tmp_path)
    warm = _run_startup_child("trainer", tmp_path)
    assert cold["compiles"] > 0 and cold["disk_writes"] > 0
    assert warm["compiles"] == 0, warm
    assert warm["disk_hits"] > 0
    assert warm["loss"] == cold["loss"]              # bit parity


def test_predictor_warm_start_zero_compiles(tmp_path):
    """ISSUE-15 acceptance (b): a fresh Predictor replica finishes
    warmup from a warm dir with ZERO compiles."""
    cold = _run_startup_child("predictor", tmp_path)
    warm = _run_startup_child("predictor", tmp_path)
    assert cold["compiles"] > 0
    assert warm["compiles"] == 0, warm
    assert warm["disk_hits"] > 0


# ------------------------------------------------- the XLA cache rule
_CACHE_RULE_CHILD = """
import jax
import numpy as np
import mxtpu as mx
from mxtpu import compile_service as csvc
from mxtpu.gluon import nn
net = nn.HybridSequential()
net.add(nn.Dense(4))
net.initialize()
net.hybridize()
net(mx.nd.array(np.ones((2, 3), np.float32))).asnumpy()  # service + jit
print("IN_FORCE=%s" % jax.config.jax_compilation_cache_dir)
print("HELPER=%s" % csvc.use_checkout_xla_cache())
print("AFTER_HELPER=%s" % jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("store", [False, True],
                         ids=["store_off", "store_on"])
@pytest.mark.parametrize("placed", [False, True],
                         ids=["env_unset", "env_set"])
def test_xla_cache_dir_has_one_rule(tmp_path, placed, store):
    """``JAX_COMPILATION_CACHE_DIR`` set: it is still what jax uses after
    ``import mxtpu`` and a first dispatch through the compile service,
    with the executable store on or off, and the entry-point helper
    writes nothing. Unset: the library sets nothing, and the helper
    yields the fixed in-checkout directory."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for var in ("JAX_COMPILATION_CACHE_DIR", "MXTPU_COMPILE_CACHE_DIR"):
        env.pop(var, None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    if store:
        env["MXTPU_COMPILE_CACHE_DIR"] = str(tmp_path / "store")
    proc = subprocess.run([sys.executable, "-c", _CACHE_RULE_CHILD],
                          env=env, cwd=str(tmp_path), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = dict(ln.split("=", 1) for ln in proc.stdout.splitlines()
               if "=" in ln)
    fixed = os.path.join(REPO, ".jax_cache")
    if placed:
        want = str(tmp_path / "placed")
        assert got == {"IN_FORCE": want, "HELPER": want,
                       "AFTER_HELPER": want}
    else:
        assert got == {"IN_FORCE": "None", "HELPER": fixed,
                       "AFTER_HELPER": fixed}
    assert csvc.CHECKOUT_XLA_CACHE == fixed


# ---------------------------------------------- site integration details
def test_cached_op_policy_flip_with_disk_never_stale(tmp_path,
                                                     monkeypatch):
    """A policy flip under a live disk cache recompiles; flipping BACK
    disk-hits the original executable with zero new compiles — and both
    directions stay bit-identical to their first runs."""
    from mxtpu.gluon import nn
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_NUMERICS_GUARD", "1")  # policy_key member
    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0).randn(2, 3).astype(np.float32))
    net(x)
    net.hybridize()
    y_a = net(x).asnumpy()
    n0 = len(net._cached_op._jits)
    monkeypatch.setenv("MXTPU_NUMERICS_GUARD", "0")
    y_b = net(x).asnumpy()
    assert len(net._cached_op._jits) == n0 + 1       # flip: one new entry
    monkeypatch.setenv("MXTPU_NUMERICS_GUARD", "1")
    r0 = telemetry.value("retrace.cached_op")
    y_a2 = net(x).asnumpy()
    assert telemetry.value("retrace.cached_op") == r0   # L1 hit, no compile
    np.testing.assert_array_equal(y_a, y_a2)
    np.testing.assert_allclose(y_a, y_b, rtol=1e-6)


def test_rtc_kernel_cache_bounded(monkeypatch):
    """The rtc per-kernel dict was unbounded under launch-signature
    churn; in the service it rides the LRU bound."""
    monkeypatch.setenv("MXTPU_COMPILE_CACHE_ENTRIES", "4")
    from mxtpu import rtc

    mod = rtc.PallasModule("""
def scale_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0
""", exports=["scale_kernel"])
    kern = mod.get_kernel("scale_kernel")
    ev0 = _counter("compile.evictions", tag="rtc")
    for n in range(2, 9):
        out = kern.launch([mx.nd.ones((n,))], out_shapes=(n,))
        assert float(out.asnumpy()[0]) == 2.0
    st = csvc.stats()["per_site"]
    assert st.get("rtc", 0) <= 4
    assert _counter("compile.evictions", tag="rtc") > ev0


def test_executor_entries_live_in_service():
    """Executor signatures are service entries now (bounded, shared
    reporting) — the module path's old private dict is gone."""
    import mxtpu.symbol as sym_mod

    data = sym_mod.var("data")
    out = sym_mod.FullyConnected(data=data, num_hidden=4, name="fc")
    exe = out.simple_bind(data=(2, 3))
    exe.forward(is_train=False, data=mx.nd.ones((2, 3)))
    assert csvc.stats()["per_site"].get("executor", 0) >= 1
    assert not hasattr(exe, "_jits")
