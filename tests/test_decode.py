"""Continuous-batching autoregressive decode (mxtpu/serving/decode) —
ISSUE 11:

* BucketSpec ``decode_slots=`` spelling: capacity ladders, loud refusal
  of every cross-spelling misuse (decode spec in a Predictor, prefill
  spec as a cohort, mixed axes);
* Predictor int8 weight path: logits parity vs f32, refresh-params
  without recompiles;
* DecodeEngine correctness: generated tokens EXACTLY match an eager
  full-prefix reference greedy loop, continuous == restart-per-batch
  token streams (slot insert / donated carry cannot change a sequence's
  math), eos + max_new + max_len stopping, done-at-insert;
* continuous batching: joining sequences reuse freed slots between
  steps — strictly fewer cohort steps than restart-per-batch on the
  same workload, with ZERO post-warmup compiles at ``serving.decode``
  (AOT bucket replay, watchdog-pinned) and ZERO d2h inside the armed
  decode span;
* KVCacheAccountant: kv_residency shedding at the overcommit bound,
  ledger bookkeeping across admit/occupy/release, the MicroBatcher
  ``admission_gate=`` seam, ReplicaSet attach + dispatcher shed;
* decode-step wedge: injected ``decode_wedge`` under a fake clock trips
  the watchdog — stuck futures fail loud, their trace_ids land in the
  ``flight_record("decode_wedge")`` artifact, the engine keeps serving;
* threaded end-to-end + crash barrier;
* the serve_bench decode smoke (deterministic gates only).
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import resilience, telemetry
from mxtpu.base import MXNetError
from mxtpu.gluon import nn
from mxtpu.ndarray import NDArray
from mxtpu.serving import (BucketSpec, DeadlineExceeded, DecodeEngine,
                           KVCacheAccountant, MicroBatcher, Predictor,
                           QueueFull)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import serve_bench as sb  # noqa: E402  (the reference DecodeModel lives there)


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("MXTPU_TELEMETRY", "MXTPU_RETRACE_BUDGET",
                "MXTPU_FAULT_INJECT", "MXTPU_SERVE_INT8",
                "MXTPU_DECODE_SLOTS", "MXTPU_DECODE_QUEUE",
                "MXTPU_DECODE_MAX_NEW", "MXTPU_SERVE_KV_OVERCOMMIT",
                "MXTPU_SERVE_DISPATCH_TIMEOUT_MS", "MXTPU_FLIGHT_DIR"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    resilience.reset_faults()
    yield
    telemetry.reset()
    resilience.reset_faults()


VOCAB, DIM, MAX_LEN = 48, 12, 40


@pytest.fixture(scope="module")
def model():
    return sb.build_decode_model(vocab=VOCAB, dim=DIM, max_len=MAX_LEN,
                                 seed=7)


def _pspec():
    return BucketSpec([1], seq_lens=[6, 12])


def _engine(model, slots=2, eos=None, int8=False, continuous=True,
            accountant=None, clock=time.monotonic, timeout_ms=None,
            max_queue=None, max_len=32):
    return DecodeEngine(model, _pspec(),
                        BucketSpec.pow2(decode_slots=slots),
                        max_len=max_len, eos_id=eos, int8=int8,
                        continuous=continuous, accountant=accountant,
                        clock=clock, dispatch_timeout_ms=timeout_ms,
                        max_queue=max_queue, warmup=True, start=False)


def _run_all(eng, futs, limit=2000):
    n = 0
    while not all(f.done() for f in futs) and n < limit:
        eng.poll()
        n += 1
    return [f.result(timeout=2.0) for f in futs]


def _reference_greedy(model, prompt, max_new, eos=None):
    """Eager full-prefix replay — no KV cache, no buckets, no jit of
    ours: the ground truth the engine must match token for token."""
    import jax.numpy as jnp
    toks, out = list(prompt), []
    for _ in range(max_new):
        logits, _k, _v = model(NDArray(jnp.asarray(
            np.asarray(toks, np.int32)[None, :])))
        nxt = int(jnp.argmax(logits._data[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
        if eos is not None and nxt == eos:
            break
        if len(toks) >= MAX_LEN:
            break
    return out


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


# ------------------------------------------------------- BucketSpec spelling
def test_decode_slots_spelling():
    d = BucketSpec(decode_slots=(2, 8, 4))
    assert d.is_decode and d.decode_slots == (2, 4, 8)
    assert d.max_slots == 8 and d.slot_bucket(3) == 4
    assert d.slot_bucket(9) is None
    assert BucketSpec.pow2(decode_slots=8).decode_slots == (1, 2, 4, 8)
    assert "decode_slots" in repr(d)
    p = BucketSpec.pow2(4)
    assert not p.is_decode
    with pytest.raises(MXNetError, match="decode_slots"):
        p.max_slots
    with pytest.raises(MXNetError, match="decode_slots"):
        p.slot_bucket(1)


@pytest.mark.parametrize("bad", [
    lambda: BucketSpec(batch_sizes=[2], decode_slots=[2]),
    lambda: BucketSpec(decode_slots=[2], seq_lens=[8]),
    lambda: BucketSpec(decode_slots=[0]),
    lambda: BucketSpec(),
    lambda: BucketSpec.pow2(8, decode_slots=8),
    lambda: BucketSpec.pow2(decode_slots=8, seq_lens=[16]),
    lambda: BucketSpec.pow2(),
])
def test_decode_slots_validation_is_loud(bad):
    with pytest.raises(MXNetError):
        bad()


def test_predictor_refuses_decode_spec():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4))
    net.initialize()
    with pytest.raises(MXNetError, match="decode-cohort"):
        Predictor(net, BucketSpec(decode_slots=[2]),
                  example=np.zeros((1, 3), np.float32))


def test_engine_refuses_misdeclared_specs(model):
    with pytest.raises(MXNetError, match="decode_slots= spelling"):
        DecodeEngine(model, _pspec(), BucketSpec.pow2(4), warmup=False)
    with pytest.raises(MXNetError, match="prefill_spec is a decode"):
        DecodeEngine(model, BucketSpec(decode_slots=[2]),
                     BucketSpec(decode_slots=[2]), warmup=False)
    with pytest.raises(MXNetError, match="seq_lens"):
        DecodeEngine(model, BucketSpec([1]),
                     BucketSpec(decode_slots=[2]), warmup=False)
    net = nn.HybridSequential()
    with pytest.raises(MXNetError, match="decode_step"):
        DecodeEngine(net, _pspec(), BucketSpec(decode_slots=[2]),
                     warmup=False)


def test_cold_engine_refuses_submit(model):
    cold = DecodeEngine(model, _pspec(), BucketSpec(decode_slots=[2]),
                        warmup=False)
    with pytest.raises(MXNetError, match="cold DecodeEngine"):
        cold.submit(np.arange(3).astype(np.int32))


# ------------------------------------------------------- Predictor int8 path
def test_predictor_int8_parity_and_refresh():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(8))
    net.initialize()
    spec = BucketSpec.pow2(4)
    ex = np.zeros((1, 10), np.float32)
    pf = Predictor(net, spec, example=ex, warmup=True, name="f32")
    pq = Predictor(net, spec, example=ex, warmup=True, name="q", int8=True)
    assert pq.int8 and not pf.int8
    x = np.random.RandomState(0).randn(3, 10).astype(np.float32)
    a, b = pf.predict(x).asnumpy(), pq.predict(x).asnumpy()
    rel = np.abs(a - b).mean() / (np.abs(a).mean() + 1e-9)
    assert rel < 0.05, rel
    st = telemetry.retrace_stats("serving.predict")
    assert st["compiles"] == 2 * len(spec)
    # re-quantization after an in-place reload: zero recompiles
    pq.refresh_params()
    np.testing.assert_allclose(pq.predict(x).asnumpy(), b)
    assert telemetry.retrace_stats("serving.predict")["compiles"] \
        == 2 * len(spec)


def test_serve_int8_env_lever(monkeypatch):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4))
    net.initialize()
    monkeypatch.setenv("MXTPU_SERVE_INT8", "1")
    pred = Predictor(net, BucketSpec([1]),
                     example=np.zeros((1, 6), np.float32))
    assert pred.int8
    assert any(q is not None for q in pred._param_qdtypes)
    # 1-d bias stays exact storage; 2-d weight quantizes
    kinds = {d.ndim: (qdt is not None) for d, qdt
             in zip([p.data()._data for p in pred._params],
                    pred._param_qdtypes)}
    assert kinds[2] is True and kinds[1] is False


# --------------------------------------------------------- decode correctness
def test_engine_matches_eager_reference(model):
    eng = _engine(model, slots=2)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, VOCAB, size=rng.randint(3, 11))
               .astype(np.int32) for _ in range(5)]
    maxnews = [4, 7, 3, 6, 5]
    futs = [eng.submit(p, max_new=m) for p, m in zip(prompts, maxnews)]
    outs = _run_all(eng, futs)
    for out, p, m in zip(outs, prompts, maxnews):
        assert out.dtype == np.int32
        assert out.tolist() == _reference_greedy(model, p, m)


def test_continuous_equals_restart_tokens(model):
    """Slot insert + donated carry must be invisible to a sequence's
    math: the same workload through a continuous cohort and through
    restart-per-batch produces IDENTICAL token streams."""
    rng = np.random.RandomState(2)
    reqs = [(rng.randint(0, VOCAB, size=rng.randint(3, 11))
             .astype(np.int32), int(rng.randint(2, 9)))
            for _ in range(6)]
    results = {}
    for continuous in (True, False):
        eng = _engine(model, slots=2, continuous=continuous)
        outs = _run_all(eng, [eng.submit(p, max_new=m) for p, m in reqs])
        results[continuous] = [o.tolist() for o in outs]
    assert results[True] == results[False]


def test_eos_stops_generation(model):
    prompt = np.arange(3, 8).astype(np.int32)
    ref = _reference_greedy(model, prompt, 8)
    eos = ref[2]  # force an eos hit at the third generated token
    eng = _engine(model, slots=1, eos=eos)
    out = _run_all(eng, [eng.submit(prompt, max_new=8)])[0]
    assert out.tolist() == _reference_greedy(model, prompt, 8, eos=eos)
    assert out[-1] == eos and len(out) == 3


def test_max_new_one_completes_at_insert(model):
    eng = _engine(model, slots=1)
    steps0 = telemetry.value("serving.decode.steps")
    fut = eng.submit(np.arange(4).astype(np.int32), max_new=1)
    eng.poll()
    out = fut.result(timeout=2.0)
    assert len(out) == 1
    assert out.tolist() == _reference_greedy(model, np.arange(4), 1)
    # done-at-insert: the first token came from the prefill logits, no
    # cohort step ever ran
    assert telemetry.value("serving.decode.steps") == steps0
    assert fut.ttft_s is not None and fut.ttft_s <= fut.e2e_s


def test_submit_validation_is_loud(model):
    eng = _engine(model, slots=1)
    with pytest.raises(MXNetError, match="1-d"):
        eng.submit(np.zeros((2, 3), np.int32))
    with pytest.raises(MXNetError, match="integer"):
        eng.submit(np.zeros(3, np.float32))
    with pytest.raises(MXNetError, match="exceeds the largest declared"):
        eng.submit(np.zeros(13, np.int32))  # past the max seq bucket
    with pytest.raises(MXNetError, match="max_new"):
        eng.submit(np.zeros(3, np.int32), max_new=0)
    # a cache too short to decode past the largest prompt bucket refuses
    # at CONSTRUCTION (which also makes the per-submit length invariant
    # prompt < max_len hold by construction)
    with pytest.raises(MXNetError, match="no room to decode"):
        DecodeEngine(model, _pspec(), BucketSpec(decode_slots=[1]),
                     max_len=12, warmup=False)


# ------------------------------------------------- continuous-batching + AOT
def test_continuous_batching_fewer_steps_flat_compiles(model):
    """The tentpole acceptance, deterministically: same workload, equal
    capacity — the continuous cohort takes strictly fewer steps than
    restart-per-batch (freed slots refill between steps), post-warmup
    compiles at serving.decode are ZERO for both, no watchdog trips, no
    d2h inside the armed span."""
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, VOCAB, size=rng.randint(3, 11))
             .astype(np.int32), int(rng.randint(2, 13)))
            for _ in range(10)]
    steps = {}
    for continuous in (True, False):
        eng = _engine(model, slots=4, continuous=continuous)
        st0 = telemetry.retrace_stats(eng._site)["compiles"]
        s0 = telemetry.value("serving.decode.steps")
        _run_all(eng, [eng.submit(p, max_new=m) for p, m in reqs])
        steps[continuous] = telemetry.value("serving.decode.steps") - s0
        assert telemetry.retrace_stats(eng._site)["compiles"] == st0
        assert telemetry.retrace_stats(eng._site)["trips"] == 0
    assert steps[True] < steps[False], steps
    assert telemetry.value("serving.decode.d2h") == 0


def test_joiner_enters_running_cohort(model):
    """A sequence submitted while the cohort is mid-flight joins between
    steps — no drain, no recompile."""
    eng = _engine(model, slots=2)
    compiles0 = telemetry.retrace_stats(eng._site)["compiles"]
    first = eng.submit(np.arange(3).astype(np.int32), max_new=10)
    for _ in range(3):
        eng.poll()   # cohort is running
    assert eng.live_slots == 1 and not first.done()
    joiner = eng.submit(np.arange(5).astype(np.int32), max_new=5)
    eng.poll()
    assert eng.live_slots == 2   # joined the RUNNING cohort
    outs = _run_all(eng, [first, joiner])
    assert outs[0].tolist() == _reference_greedy(model, np.arange(3), 10)
    assert outs[1].tolist() == _reference_greedy(model, np.arange(5), 5)
    assert telemetry.retrace_stats(eng._site)["compiles"] == compiles0


def test_breakdown_and_ttft(model):
    eng = _engine(model, slots=2)
    fut = eng.submit(np.arange(6).astype(np.int32), max_new=4)
    _run_all(eng, [fut])
    bd = fut.breakdown
    for stage in ("serving.submit", "serving.queue_wait", "serving.prefill",
                  "serving.decode", "serving.fetch", "serving.deliver"):
        assert stage in bd, (stage, sorted(bd))
    assert fut.trace_id is not None
    assert fut.ttft_s is not None and 0 <= fut.ttft_s <= fut.e2e_s
    assert telemetry.value("serving.decode.tokens") >= 4


# ----------------------------------------------------------------- int8 path
def test_engine_int8_parity_and_kv_bytes(model):
    eng_f = _engine(model, slots=2)
    eng_q = _engine(model, slots=2, int8=True)
    prompt = np.arange(2, 9).astype(np.int32)
    lf, lq = eng_f.prefill_logits(prompt), eng_q.prefill_logits(prompt)
    rel = np.abs(lf - lq).mean() / (np.abs(lf).mean() + 1e-9)
    assert rel < 0.05, rel
    sf, sq = eng_f.step_logits_probe(prompt), eng_q.step_logits_probe(prompt)
    rel_s = np.abs(sf - sq).mean() / (np.abs(sf).mean() + 1e-9)
    assert rel_s < 0.05, rel_s
    # the residency dividend: int8 KV (+ per-position scales) costs at
    # most ~half the bytes per slot (≈1/4 vs this f32 model)
    assert eng_q.per_slot_kv_bytes() <= 0.55 * eng_f.per_slot_kv_bytes()
    # and the int8 engine still generates (stream math differs from f32
    # by quantization noise, so token equality is NOT asserted)
    out = _run_all(eng_q, [eng_q.submit(prompt, max_new=5)])[0]
    assert out.shape == (5,) and out.dtype == np.int32
    assert telemetry.value("serving.decode.d2h") == 0


# ------------------------------------------------------------- KV accounting
def test_kv_residency_shed_at_overcommit(model):
    acct = KVCacheAccountant()    # default overcommit 2.0
    eng = _engine(model, slots=1, accountant=acct)
    cap = acct.snapshot()["r0"]
    assert cap["per_slot_bytes"] == eng.per_slot_kv_bytes()
    assert cap["bucket_bytes"] == {1: eng.per_slot_kv_bytes()}
    futs = [eng.submit(np.arange(3).astype(np.int32), max_new=4)
            for _ in range(2)]   # 2 x capacity(1 slot) = the bound
    with pytest.raises(QueueFull, match="kv_residency"):
        eng.submit(np.arange(3).astype(np.int32), max_new=4)
    assert telemetry.value("serving.shed", tag="kv_residency") == 1
    _run_all(eng, futs)
    # completions release residency: admissible again
    fut = eng.submit(np.arange(3).astype(np.int32), max_new=2)
    _run_all(eng, [fut])
    snap = acct.snapshot()["r0"]
    assert snap["live"] == 0 and snap["queued"] == 0
    assert acct.resident_bytes("r0") == 0


def test_accountant_gauges_track_residency(model):
    acct = KVCacheAccountant(overcommit=10.0)
    eng = _engine(model, slots=2, accountant=acct)
    assert telemetry.snapshot()["gauges"]["serving.kv_capacity_bytes"] \
        == 2 * eng.per_slot_kv_bytes()
    fut = eng.submit(np.arange(3).astype(np.int32), max_new=6)
    eng.poll()   # prefill -> slot occupied
    assert telemetry.snapshot()["gauges"]["serving.kv_resident_bytes"] \
        == eng.per_slot_kv_bytes()
    _run_all(eng, [fut])
    assert telemetry.snapshot()["gauges"]["serving.kv_resident_bytes"] == 0


def test_microbatcher_admission_gate():
    """The accountant's gate plugs into the PLAIN batcher: admission
    sheds by the gate's reason without subclassing."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4))
    net.initialize()
    pred = Predictor(net, BucketSpec([2]),
                     example=np.zeros((1, 6), np.float32), warmup=True)
    acct = KVCacheAccountant(capacity_bytes=100, overcommit=1.0)
    acct.register("r0", per_slot_bytes=100, slots=1)
    bat = MicroBatcher(pred, start=False, admission_gate=acct.gate("r0"))
    bat.submit(np.zeros((1, 6), np.float32))   # pool empty: admits
    assert acct.try_admit("r0")
    acct.occupy("r0")                          # pool now full
    with pytest.raises(QueueFull, match="kv_residency"):
        bat.submit(np.zeros((1, 6), np.float32))
    assert telemetry.value("serving.shed", tag="kv_residency") == 1
    acct.release("r0")
    bat.submit(np.zeros((1, 6), np.float32))   # freed: admits again


def test_replicaset_accountant_surface():
    from mxtpu.serving import ReplicaDispatcher, ReplicaSet
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4))
    net.initialize()
    rset = ReplicaSet(net, BucketSpec([2]), n=1,
                      example=np.zeros((1, 6), np.float32), warmup=True)
    acct = KVCacheAccountant(capacity_bytes=64, overcommit=1.0)
    rset.attach_accountant(acct)
    acct.register("r0", per_slot_bytes=64, slots=1)
    states = rset.states()
    assert states[0]["kv_resident_bytes"] == 0
    disp = ReplicaDispatcher(rset, start=False, clock=FakeClock())
    disp.submit(np.zeros((1, 6), np.float32))   # admissible while empty
    assert acct.try_admit("r0")
    acct.occupy("r0")
    assert rset.states()[0]["kv_resident_bytes"] == 64
    assert not rset.kv_admissible()
    with pytest.raises(QueueFull, match="kv_residency"):
        disp.submit(np.zeros((1, 6), np.float32))


# ------------------------------------------------------------- wedge + fault
def test_decode_wedge_flight_record(model, monkeypatch, tmp_path):
    """The ISSUE-11 flight-recorder satellite: a decode step stuck past
    the dispatch timeout triggers flight_record with the stuck
    sequences' trace_ids; their futures fail loud; the engine keeps
    serving the queue on a fresh carry."""
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "decode_wedge@1")
    clock = FakeClock()
    eng = _engine(model, slots=2, clock=clock, timeout_ms=100.0)
    stuck = [eng.submit(np.arange(3).astype(np.int32), max_new=6)
             for _ in range(2)]
    eng.poll()          # step 0 runs clean
    eng.poll()          # step 1 "never answers" (injected wedge)
    assert not any(f.done() for f in stuck)
    clock.advance(0.2)  # past the 100 ms dispatch timeout
    eng.poll()          # the scan trips the watchdog
    for f in stuck:
        assert f.done()
        with pytest.raises(DeadlineExceeded, match="wedged"):
            f.result(timeout=0)
    assert telemetry.value("serving.decode.wedges") == 1
    assert telemetry.value("flight.dumps", tag="decode_wedge") == 1
    arts = [p for p in os.listdir(tmp_path) if "decode_wedge" in p]
    assert len(arts) == 1
    payload = json.loads((tmp_path / arts[0]).read_text())
    assert payload["reason"] == "decode_wedge"
    assert set(payload["trace_ids"]) == {f.trace_id for f in stuck}
    assert payload["extra"]["stuck"] == 2
    # the engine survives: slots freed, fresh carry, queue keeps serving
    assert eng.live_slots == 0
    out = _run_all(eng, [eng.submit(np.arange(4).astype(np.int32),
                                    max_new=3)])[0]
    assert out.tolist() == _reference_greedy(model, np.arange(4), 3)


def test_deadline_expires_while_queued(model):
    clock = FakeClock()
    eng = _engine(model, slots=1, clock=clock)
    hog = eng.submit(np.arange(3).astype(np.int32), max_new=10)
    eng.poll()   # hog takes the only slot
    late = eng.submit(np.arange(4).astype(np.int32), max_new=2,
                      deadline_ms=50.0)
    clock.advance(0.1)   # deadline passes while queued behind the hog
    _run_all(eng, [hog])
    eng.poll()   # the freed slot's admission pass pops (and expires) late
    assert late.done()
    with pytest.raises(DeadlineExceeded, match="KV slot"):
        late.result(timeout=0)
    assert telemetry.value("serving.deadline_expired") == 1


def test_queue_bound_sheds(model):
    eng = _engine(model, slots=1, max_queue=2)
    futs = [eng.submit(np.arange(3).astype(np.int32), max_new=3)
            for _ in range(2)]
    with pytest.raises(QueueFull, match="queue_full"):
        eng.submit(np.arange(3).astype(np.int32), max_new=3)
    _run_all(eng, futs)


# ------------------------------------------------------------- threaded mode
def test_threaded_end_to_end(model):
    acct = KVCacheAccountant(overcommit=50.0)
    eng = _engine(model, slots=2, accountant=acct)
    eng.start()
    try:
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, VOCAB, size=rng.randint(3, 11))
                   .astype(np.int32) for _ in range(8)]
        results = [None] * len(prompts)

        def client(i):
            fut = eng.submit(prompts[i], max_new=3 + i % 4)
            results[i] = fut.result(timeout=30.0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        for i, (p, out) in enumerate(zip(prompts, results)):
            assert out is not None, "request %d hung" % i
            assert out.tolist() == _reference_greedy(model, p, 3 + i % 4)
        # the ledger balances under the submit/occupy race: admit() runs
        # under the admission lock BEFORE the loop thread can pop the
        # sequence, so no phantom queued count survives the run
        snap = acct.snapshot()["r0"]
        assert snap["live"] == 0 and snap["queued"] == 0, snap
    finally:
        eng.close(timeout=10.0)


def test_crash_barrier_fails_loud(model, monkeypatch):
    eng = _engine(model, slots=1)
    eng.start()
    try:
        monkeypatch.setattr(
            eng, "_harvest",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
        fut = eng.submit(np.arange(3).astype(np.int32), max_new=4)
        with pytest.raises(MXNetError, match="decode loop crashed"):
            fut.result(timeout=30.0)
        assert telemetry.value("serving.worker_crashes") == 1
        with pytest.raises(QueueFull, match="worker_crashed"):
            eng.submit(np.arange(3).astype(np.int32))
    finally:
        eng.close(timeout=5.0)


def test_threaded_injected_wedge_recovers(model, monkeypatch):
    """Threaded mode, injected wedge: the unresolved armed entry BLOCKS
    further steps (no clobbering — the wedge cannot be swallowed), the
    monitor trips it on the real clock, the stuck futures fail loud, and
    — because the loop thread kept cycling — probation clears and the
    engine keeps serving."""
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "decode_wedge@0")
    eng = _engine(model, slots=2, timeout_ms=100.0)
    eng.start()
    try:
        stuck = eng.submit(np.arange(3).astype(np.int32), max_new=6)
        with pytest.raises(DeadlineExceeded, match="wedged"):
            stuck.result(timeout=30.0)
        assert telemetry.value("serving.decode.wedges") == 1
        out = eng.submit(np.arange(4).astype(np.int32),
                         max_new=3).result(timeout=30.0)
        assert out.tolist() == _reference_greedy(model, np.arange(4), 3)
    finally:
        eng.close(timeout=10.0)


def test_wedge_probation_crashes_blocked_loop(model, monkeypatch):
    """A REAL wedge blocks the only loop thread inside the device call:
    after the trip, probation watches for loop progress for one more
    timeout window — none means blocked-forever, and the crash barrier
    fails the pending queue loud instead of stranding it
    (shed-never-hang)."""
    eng = _engine(model, slots=1, timeout_ms=100.0)
    block = threading.Event()
    real = eng._get_step_jit

    def blocked_get(b):
        jitted = real(b)

        def run(*args):
            block.wait(30.0)   # "the device call never returns"
            return jitted(*args)

        return run

    monkeypatch.setattr(eng, "_get_step_jit", blocked_get)
    eng.start()
    try:
        stuck = eng.submit(np.arange(3).astype(np.int32), max_new=6)
        queued = eng.submit(np.arange(4).astype(np.int32), max_new=3)
        with pytest.raises(DeadlineExceeded, match="wedged"):
            stuck.result(timeout=30.0)
        # probation expires with zero loop progress: the pending queue
        # fails loud and new submits shed
        with pytest.raises(MXNetError, match="decode loop crashed"):
            queued.result(timeout=30.0)
        assert telemetry.value("serving.worker_crashes") == 1
        with pytest.raises(QueueFull, match="worker_crashed"):
            eng.submit(np.arange(3).astype(np.int32))
    finally:
        block.set()
        eng.close(timeout=10.0)


def test_prefill_failure_completes_the_popped_future(model, monkeypatch):
    """A sequence popped from the queue whose prefill raises is in
    neither _pending nor _slots: its future must complete (loud) before
    the error propagates, and its accountant queued count must
    release — otherwise the crash barrier strands it forever."""
    acct = KVCacheAccountant(overcommit=10.0)
    eng = _engine(model, slots=1, accountant=acct)
    boom = {"on": True}
    real = eng._pred.predict_flat

    def flaky(*a, **k):
        if boom["on"]:
            raise RuntimeError("device burp")
        return real(*a, **k)

    monkeypatch.setattr(eng._pred, "predict_flat", flaky)
    fut = eng.submit(np.arange(3).astype(np.int32), max_new=3)
    with pytest.raises(RuntimeError, match="device burp"):
        eng.poll()
    assert fut.done()
    with pytest.raises(MXNetError, match="prefill failed"):
        fut.result(timeout=0)
    snap = acct.snapshot()["r0"]
    assert snap["queued"] == 0 and snap["live"] == 0, snap
    # poll mode has no crash barrier: once the device recovers, serving
    # continues
    boom["on"] = False
    out = _run_all(eng, [eng.submit(np.arange(4).astype(np.int32),
                                    max_new=2)])[0]
    assert out.tolist() == _reference_greedy(model, np.arange(4), 2)


def test_blocked_insert_dispatch_does_not_hold_the_lock(model, monkeypatch):
    """The insert jit dispatches OUTSIDE self._cond (same discipline as
    the step path): a dispatch blocked by a wedged device must leave
    submits and the wedge scan runnable instead of deadlocking the whole
    engine on the lock. (Generous timeout: the prefill wedge watchdog
    must NOT trip during this test — that path has its own test below.)"""
    eng = _engine(model, slots=2, timeout_ms=30000.0)
    block = threading.Event()
    real = eng._get_insert_jit

    def blocked_get(s):
        jitted = real(s)

        def run(*args):
            block.wait(30.0)
            return jitted(*args)

        return run

    monkeypatch.setattr(eng, "_get_insert_jit", blocked_get)
    eng.start()
    try:
        first = eng.submit(np.arange(3).astype(np.int32), max_new=2)
        time.sleep(0.1)   # the loop is now blocked inside the insert
        t0 = time.perf_counter()
        second = eng.submit(np.arange(4).astype(np.int32), max_new=2)
        elapsed = time.perf_counter() - t0
        assert elapsed < 1.0, "submit blocked behind the wedged dispatch"
        assert eng._scan_wedges(eng._clock()) is None  # scan runnable too
        # the popped-but-unregistered sequence is VISIBLE to drain: the
        # engine must not report empty while a prompt is mid-prefill
        assert eng.drain(timeout=0.2) is False
        block.set()
        for f in (first, second):
            assert len(f.result(timeout=30.0)) == 2
    finally:
        block.set()
        eng.close(timeout=10.0)


def test_prefill_wedge_trips_and_sheds(model, monkeypatch, tmp_path):
    """A wedge during the PREFILL/insert dispatch (not a step) is
    detected too: the prefill watchdog entry trips, the stuck prompt's
    future fails loud with a flight artifact, and — the loop thread
    being genuinely blocked — probation escalates to the crash barrier
    so the queue sheds instead of stranding."""
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    eng = _engine(model, slots=1, timeout_ms=100.0)
    block = threading.Event()
    real = eng._get_insert_jit

    def blocked_get(s):
        jitted = real(s)

        def run(*args):
            block.wait(30.0)   # "the device never answers"
            return jitted(*args)

        return run

    monkeypatch.setattr(eng, "_get_insert_jit", blocked_get)
    eng.start()
    try:
        stuck = eng.submit(np.arange(3).astype(np.int32), max_new=3)
        queued = eng.submit(np.arange(4).astype(np.int32), max_new=3)
        with pytest.raises(DeadlineExceeded, match="prefill dispatch"):
            stuck.result(timeout=30.0)
        # the future fails ATOMICALLY with the abandonment; the flight
        # dump (tmp+rename) follows on the monitor thread — wait for the
        # finalized artifact, not the in-progress .tmp
        arts = []
        for _ in range(200):
            arts = [p for p in os.listdir(tmp_path)
                    if "decode_wedge" in p and p.endswith(".json")]
            if arts:
                break
            time.sleep(0.02)
        assert telemetry.value("serving.decode.wedges") == 1
        assert len(arts) == 1
        payload = json.loads((tmp_path / arts[0]).read_text())
        assert payload["extra"]["kind"] == "prefill"
        assert stuck.trace_id in payload["trace_ids"]
        # probation: the blocked loop makes no progress -> crash barrier
        with pytest.raises(MXNetError, match="decode loop crashed"):
            queued.result(timeout=30.0)
        with pytest.raises(QueueFull, match="worker_crashed"):
            eng.submit(np.arange(3).astype(np.int32))
    finally:
        block.set()
        eng.close(timeout=10.0)


def test_int8_refresh_sticky_on_degenerate_reload():
    """A reload that zeroes a quantized weight keeps its int8 slot (unit
    grid — zeros stay exact): the executables' argument structure never
    changes, so refresh stays recompile-free even through degenerate
    weights."""
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8))
    net.initialize()
    pred = Predictor(net, BucketSpec([2]),
                     example=np.zeros((1, 6), np.float32), warmup=True,
                     int8=True)
    qdts0 = list(pred._param_qdtypes)
    weight = [p for p in pred._params if p.data()._data.ndim == 2][0]
    weight.set_data(mx.nd.zeros(weight.data().shape))
    pred.refresh_params()
    assert list(pred._param_qdtypes) == qdts0   # structure pinned
    out = pred.predict(np.ones((2, 6), np.float32)).asnumpy()
    # zero weight -> output is exactly the (untouched) bias
    bias = [p for p in pred._params if p.data()._data.ndim == 1][0]
    np.testing.assert_allclose(out, np.tile(bias.data().asnumpy(), (2, 1)),
                               atol=1e-6)
    assert telemetry.retrace_stats("serving.predict")["compiles"] \
        == len(BucketSpec([2]))


# ------------------------------------------------------------ bench smoke
def test_serve_bench_decode_smoke():
    """tools/serve_bench.py --mode decode, small: the DETERMINISTIC
    gates (token parity continuous vs restart, zero post-warmup
    compiles, zero in-loop d2h, int8 parity + KV bytes). The tokens/s
    speedup gate is wall-clock and belongs to the bench artifact, not
    tier-1."""
    rec = sb.run_decode(n_requests=12, slots=2, max_new=8, vocab=64,
                        dim=16, max_prompt=12, emit=lambda r: None)
    assert rec["continuous"]["compiles_post_warmup"] == 0
    assert rec["restart"]["tokens"] == rec["continuous"]["tokens"]
    assert rec["continuous"]["steps"] < rec["restart"]["steps"]
    assert rec["prefill_logits_rel_err"] < 0.05
    assert rec["step_logits_rel_err"] < 0.05
    assert rec["kv_bytes_ratio"] <= 0.55
    assert telemetry.value("serving.decode.d2h") == 0
