"""Streaming subsystem: resumable-state contract, tracker policy, service.

The load-bearing guarantee is the PR-3 state contract: a streaming tick of
T iterations must be *bit-identical* to the equivalent resumed
``deepca``/``depca`` call — same iterates, same resume-continuous
``comm_rounds``, same schedule indexing, same K+t increasing-rounds
continuation.  Everything else (drift policy, bucketing, padding,
prefetch lifecycle) layers on top of that identity.
"""
import math
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (ConsensusEngine, IterationDriver, PowerStep,
                        TopologySchedule, deepca, depca, erdos_renyi,
                        metrics, rebase_carry, synthetic_spiked,
                        top_k_eigvecs)
from repro.core.step import mean_basis
from repro.streaming import (AdmissionPolicy, DriftPolicy,
                             EigengapShiftStream, PCAService,
                             SampleArrivalStream, SlowRotationStream,
                             StreamingDeEPCA)

jax.config.update("jax_enable_x64", False)

#: Policy that never escalates/restarts — ticks are pure resumed windows.
PASSIVE = DriftPolicy(jump=math.inf, restart=math.inf, target=None,
                      max_escalations=0)


def _stream(**kw):
    args = dict(m=6, d=16, k=3, n_per_agent=20, seed=0, rate=0.06)
    args.update(kw)
    return SlowRotationStream(**args)


# ------------------------------------------------ resumable-state contract
@pytest.mark.parametrize("algorithm", ["deepca", "depca"])
def test_tick_bit_identical_to_resumed_call(algorithm):
    """Two ticks over drifting ops == call + resumed call, bitwise."""
    fn = deepca if algorithm == "deepca" else depca
    s = _stream()
    topo = erdos_renyi(6, p=0.6, seed=1)
    ops0, ops1 = s.ops_at(0), s.ops_at(1)
    U0, U1 = s.truth_at(0)[0], s.truth_at(1)[0]
    W0 = s.init_W0()
    T, K = 4, 4

    tr = StreamingDeEPCA(k=3, T_tick=T, K=K, algorithm=algorithm,
                         topology=topo, backend="stacked", W0=W0,
                         policy=PASSIVE)
    r0 = tr.tick(ops0, U0)
    r1 = tr.tick(ops1, U1)

    a = fn(ops0, topo, W0, k=3, T=T, K=K, U=U0, backend="stacked")
    # the second tick's Alg. 2 sign reference is the first tick's mean
    # basis (the tracker re-anchors it every tick)
    b = fn(ops1, topo, mean_basis(a.state[1]), k=3, T=T, K=K, U=U1,
           backend="stacked", state=a.state)
    # iterates and full resumable state
    np.testing.assert_array_equal(np.asarray(tr.W), np.asarray(b.W))
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(tr.state[i]),
                                      np.asarray(b.state[i]))
    np.testing.assert_array_equal(np.asarray(tr.state[3]),
                                  np.asarray(b.state[3]))
    # resume-continuous round accounting in the per-tick traces
    np.testing.assert_array_equal(np.asarray(r0.trace.comm_rounds),
                                  np.asarray(a.trace.comm_rounds))
    np.testing.assert_array_equal(np.asarray(r1.trace.comm_rounds),
                                  np.asarray(b.trace.comm_rounds))
    np.testing.assert_allclose(np.asarray(r1.trace.mean_tan_theta),
                               np.asarray(b.trace.mean_tan_theta),
                               rtol=1e-6, atol=1e-8)


def test_tick_continues_schedule_offset():
    """Dynamic-schedule ticks index topology_at by GLOBAL iteration."""
    s = _stream()
    sched = TopologySchedule.periodic_rewiring(6, p=0.6, seed=0, period=1)
    ops, U = s.ops_at(0), s.truth_at(0)[0]
    W0 = s.init_W0()
    T, K = 3, 4

    tr = StreamingDeEPCA(k=3, T_tick=T, K=K, schedule=sched,
                         backend="stacked", W0=W0, policy=PASSIVE)
    tr.tick(ops, U)
    tr.tick(ops, U)
    # one uninterrupted schedule-driven run over the same 2T window
    full = deepca(ops, None, W0, k=3, T=2 * T, K=K, U=U, backend="stacked",
                  schedule=sched)
    np.testing.assert_array_equal(np.asarray(tr.W), np.asarray(full.W))


def test_tick_continues_increasing_rounds():
    """DePCA K+t round schedule continues across streaming ticks."""
    s = _stream()
    topo = erdos_renyi(6, p=0.6, seed=2)
    ops, U = s.ops_at(0), s.truth_at(0)[0]
    W0 = s.init_W0()
    T, K = 3, 3

    tr = StreamingDeEPCA(k=3, T_tick=T, K=K, algorithm="depca",
                         increasing_consensus=True, topology=topo,
                         backend="stacked", W0=W0, policy=PASSIVE)
    r0 = tr.tick(ops, U)
    r1 = tr.tick(ops, U)
    full = depca(ops, topo, W0, k=3, T=2 * T, K=K, U=U, backend="stacked",
                 increasing_consensus=True)
    np.testing.assert_array_equal(np.asarray(tr.W), np.asarray(full.W))
    rounds = np.concatenate([np.asarray(r0.trace.comm_rounds),
                             np.asarray(r1.trace.comm_rounds)])
    np.testing.assert_array_equal(
        rounds, np.cumsum([K + t for t in range(2 * T)]).astype(np.float32))


def test_run_stream_is_sequenced_resumed_runs():
    """The driver's streaming substrate == manual resumed windows, and all
    ticks share ONE cached program."""
    s = _stream()
    topo = erdos_renyi(6, p=0.6, seed=1)
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", 4),
        engine=ConsensusEngine.for_algorithm("deepca", topo, K=4,
                                             backend="stacked"))
    ops_seq = [s.ops_at(t) for t in range(3)]
    W0 = s.init_W0()
    outs = list(driver.run_stream(ops_seq, W0, T=2))
    carry, t0 = None, 0
    for ops, run in zip(ops_seq, outs):
        ref = driver.run(ops, W0, T=2, t0=t0, carry=carry)
        np.testing.assert_array_equal(np.asarray(run.carry[1]),
                                      np.asarray(ref.carry[1]))
        carry, t0 = ref.carry, t0 + 2
    assert len(driver._programs) == 1       # one compiled program, N ticks


def test_tracker_state_is_deepca_resumable():
    """deepca(state=tracker.state) picks up where the tracker stopped."""
    s = _stream()
    topo = erdos_renyi(6, p=0.6, seed=1)
    W0 = s.init_W0()
    tr = StreamingDeEPCA(k=3, T_tick=4, K=4, topology=topo,
                         backend="stacked", W0=W0, policy=PASSIVE)
    tr.tick(s.ops_at(0))
    res = deepca(s.ops_at(0), topo, W0, k=3, T=4, K=4, backend="stacked",
                 state=tr.state)
    # continued round accounting: 4 + 4 iterations at K=4 rounds each
    assert float(res.trace.comm_rounds[-1]) == 32.0


# ----------------------------------------------------- drift policy behavior
def test_tracker_run_accepts_all_documented_tick_forms():
    s = _stream()
    topo = erdos_renyi(6, p=0.6, seed=1)
    tr = StreamingDeEPCA(k=3, T_tick=2, K=3, topology=topo,
                         backend="stacked", W0=s.init_W0(), policy=PASSIVE)
    reps = tr.run([s.tick(0),                      # StreamTick
                   s.ops_at(1),                    # bare StackedOperators
                   (s.ops_at(2),),                 # (ops,) 1-tuple
                   (s.ops_at(3), s.truth_at(3)[0])])   # (ops, U) pair
    assert len(reps) == 4 and reps[-1].tick == 3


def test_drift_flag_and_escalation_at_abrupt_shift():
    topo = erdos_renyi(6, p=0.5, seed=0)
    sh = EigengapShiftStream(m=6, d=16, k=3, n_per_agent=24, shift_every=3,
                             seed=0)
    tr = StreamingDeEPCA(k=3, T_tick=3, K=4, topology=topo,
                         backend="stacked", W0=sh.init_W0(),
                         policy=DriftPolicy(jump=4.0, restart=math.inf,
                                            max_escalations=2))
    reports = tr.run(sh.ticks(5))
    shift, quiet = reports[3], reports[2]
    assert shift.drift and not quiet.drift
    assert shift.escalations >= 1
    assert shift.iterations > quiet.iterations
    # escalation recovered accuracy after the jump
    assert shift.stat < shift.jump_stat


def test_restart_goes_through_fault_tolerance_rebase():
    topo = erdos_renyi(6, p=0.5, seed=0)
    sh = EigengapShiftStream(m=6, d=16, k=3, n_per_agent=24, shift_every=3,
                             seed=0)
    tr = StreamingDeEPCA(k=3, T_tick=3, K=4, topology=topo,
                         backend="stacked", W0=sh.init_W0(),
                         policy=DriftPolicy(jump=2.0, restart=2.0,
                                            max_escalations=2))
    reports = tr.run(sh.ticks(4))
    assert reports[3].restarted
    # the tracker keeps converging after the rebase
    assert reports[3].stat < reports[3].jump_stat


def test_rebase_carry_restores_tracking_invariant():
    """rebase_carry (the shared restart compute site) re-establishes
    mean(S) == mean(A_j W_j) exactly — for the streaming restart and for
    kill_agents alike."""
    from repro.runtime.fault_tolerance import kill_agents

    ops = synthetic_spiked(6, 16, 3, n_per_agent=20, seed=0)
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.standard_normal((6, 16, 3)), jnp.float32)
    S, G_prev = (jnp.asarray(rng.standard_normal((6, 16, 3)), jnp.float32)
                 for _ in range(2))
    carry = rebase_carry(ops, W)
    np.testing.assert_array_equal(np.asarray(carry[0]),
                                  np.asarray(ops.apply(W)))
    np.testing.assert_array_equal(np.asarray(carry[0]),
                                  np.asarray(carry[2]))
    # kill_agents with no deaths is exactly the streaming restart
    _, state = kill_agents(ops, (S, W, G_prev), [])
    for a, b in zip(state, carry):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- the streams
def test_streams_are_deterministic_and_constant_shape():
    for cls, kw in [(SlowRotationStream, dict(rate=0.05)),
                    (EigengapShiftStream, dict(shift_every=2)),
                    (SampleArrivalStream, dict(arrivals=5))]:
        a = cls(m=4, d=12, k=2, n_per_agent=10, seed=3, **kw)
        b = cls(m=4, d=12, k=2, n_per_agent=10, seed=3, **kw)
        for t in (0, 2):
            np.testing.assert_array_equal(np.asarray(a.ops_at(t).data),
                                          np.asarray(b.ops_at(t).data))
            assert a.ops_at(t).data.shape == (4, 10, 12)


@pytest.mark.parametrize("cls,kw", [
    (SlowRotationStream, dict(rate=0.05)),
    (EigengapShiftStream, dict(shift_every=2)),
    (SampleArrivalStream, dict(arrivals=5)),
], ids=lambda v: getattr(v, "__name__", ""))
def test_host_ticks_touch_no_jax_and_match_ticks(cls, kw, monkeypatch):
    """``host_ticks`` (what ingest threads iterate) draws in NumPy only;
    ``on_device`` then yields the very tick ``ticks`` does."""
    from repro.streaming import stream as stream_mod
    a = cls(m=4, d=12, k=2, n_per_agent=10, seed=3, **kw)
    b = cls(m=4, d=12, k=2, n_per_agent=10, seed=3, **kw)

    class NoJax:
        def __getattr__(self, name):
            raise AssertionError(f"host_ticks called jnp.{name}")

    def no_truth(*_):
        raise AssertionError("host_ticks computed the ground truth")

    with monkeypatch.context() as mp:
        mp.setattr(stream_mod, "jnp", NoJax())
        mp.setattr(stream_mod, "top_k_eigvecs", no_truth)
        hosts = list(a.host_ticks(3, t0=1))
    for host, want in zip(hosts, b.ticks(3, t0=1)):
        assert isinstance(host.data, np.ndarray)
        assert host.data.dtype == np.float32 and host.t == want.t
        got = a.on_device(host)
        np.testing.assert_array_equal(host.data, np.asarray(want.ops.data))
        np.testing.assert_array_equal(np.asarray(got.ops.data),
                                      np.asarray(want.ops.data))
        np.testing.assert_array_equal(np.asarray(got.U), np.asarray(want.U))


def test_sample_arrival_windows_overlap():
    """Tick t and t+1 share the bit-identical overlapping samples."""
    s = SampleArrivalStream(m=3, d=8, k=2, n_per_agent=8, arrivals=3, seed=1)
    w0, w1 = np.asarray(s.ops_at(0).data), np.asarray(s.ops_at(1).data)
    np.testing.assert_array_equal(w0[:, 3:], w1[:, :5])


def test_eigengap_shift_moves_the_subspace():
    sh = EigengapShiftStream(m=4, d=12, k=2, n_per_agent=24, shift_every=2,
                             seed=0)
    # across the boundary the top-k subspace jumps by a large angle
    assert float(metrics.sin_theta_k(sh.truth_at(1)[0],
                                     sh.truth_at(2)[0])) > 0.5
    # within a regime it only wiggles by sampling noise
    assert float(metrics.sin_theta_k(sh.truth_at(0)[0],
                                     sh.truth_at(1)[0])) < 0.3


def test_warm_start_beats_cold_restart_on_rounds():
    """The subsystem's reason to exist, at test scale: fewer comm rounds
    per tick to the same target when the tracker state is carried."""
    topo = erdos_renyi(6, p=0.5, seed=0)
    s = _stream(rate=0.04, n_per_agent=32)
    W0 = s.init_W0()
    target, chunk, T_max = 2e-2, 2, 20
    tr = StreamingDeEPCA(k=3, T_tick=chunk, K=4, topology=topo,
                         backend="stacked", W0=W0,
                         policy=DriftPolicy(target=target, escalate_T=chunk,
                                            max_escalations=T_max // chunk))
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", 4),
        engine=ConsensusEngine.for_algorithm("deepca", topo, K=4,
                                             backend="stacked"))
    warm_rounds, cold_rounds = [], []
    for tick in s.ticks(4):
        rep = tr.tick(tick.ops, tick.U)
        warm_rounds.append(rep.comm_rounds)
        carry, t = None, 0
        while t < T_max:
            run = driver.run(tick.ops, W0, T=chunk, t0=t, carry=carry)
            carry, t = run.carry, t + chunk
            if float(metrics.mean_tan_theta(tick.U, carry[1])) <= target:
                break
        cold_rounds.append(4.0 * t)
    # tick 0 is cold for both; from tick 1 on the warm start must win
    assert np.mean(warm_rounds[1:]) < np.mean(cold_rounds[1:])


# ------------------------------------------------------------- the service
def _request(d, n, k, seed):
    ops = _stream(d=d, n_per_agent=n, seed=seed).ops_at(0)
    rng = np.random.default_rng(seed)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    return ops, W0


def test_service_padded_results_match_direct_runs():
    topo = erdos_renyi(6, p=0.6, seed=0)
    T, K = 6, 4
    svc = PCAService(topo, T=T, K=K, backend="stacked",
                     policy=AdmissionPolicy(max_batch=4, pad_n=16, pad_k=4))
    reqs = [_request(16, n, k, seed=10 * i + n + k)
            for i, (n, k) in enumerate([(20, 2), (32, 4), (24, 3), (30, 2)])]
    ids = [svc.submit(ops, W0) for ops, W0 in reqs]
    svc.flush()
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", K),
        engine=ConsensusEngine.for_algorithm("deepca", topo, K=K,
                                             backend="stacked"))
    for rid, (ops, W0) in zip(ids, reqs):
        resp = svc.result(rid)
        k = W0.shape[1]
        assert resp.W.shape == (6, 16, k)
        ref = driver.run(ops, W0, T=T).carry[1]
        np.testing.assert_allclose(np.asarray(resp.W), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        # the padded answer is still the right subspace to fp accuracy
        U, _ = top_k_eigvecs(ops.mean_matrix(), k)
        got = float(metrics.tan_theta_k(
            U, jnp.linalg.qr(jnp.mean(resp.W, axis=0))[0]))
        want = float(metrics.tan_theta_k(
            U, jnp.linalg.qr(jnp.mean(ref, axis=0))[0]))
        assert abs(got - want) < 1e-3


def test_service_unpadded_request_is_bitwise_direct():
    """A request already on bucket boundaries takes the exact batched
    path: bit-equal to run_batch, which is bit-equal to run (test_driver)."""
    topo = erdos_renyi(6, p=0.6, seed=0)
    svc = PCAService(topo, T=5, K=4, backend="stacked",
                     policy=AdmissionPolicy(max_batch=1, pad_n=16, pad_k=2))
    ops, W0 = _request(16, 32, 2, seed=5)
    rid = svc.submit(ops, W0)       # max_batch=1 -> launched immediately
    resp = svc.result(rid)
    assert resp is not None and svc.stats["padded_requests"] == 0
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", 4),
        engine=ConsensusEngine.for_algorithm("deepca", topo, K=4,
                                             backend="stacked"))
    out = driver.run_batch([ops], W0[None], T=5)
    np.testing.assert_array_equal(np.asarray(resp.W), np.asarray(out.W[0]))


def test_service_bucketing_and_cache_accounting():
    topo = erdos_renyi(6, p=0.6, seed=0)
    svc = PCAService(topo, T=4, K=3, backend="stacked",
                     policy=AdmissionPolicy(max_batch=4, pad_n=16, pad_k=4))
    mix = [(20, 2), (24, 3), (18, 4), (36, 2), (40, 4), (20, 3)]
    reqs = [_request(16, n, k, seed=i) for i, (n, k) in enumerate(mix)]

    ids = [svc.submit(ops, W0) for ops, W0 in reqs]
    svc.flush()
    assert all(svc.result(i, pop=False) is not None for i in ids)
    first = dict(svc.stats)
    # n in {18..24} -> n_pad 32; {36, 40} -> 48; all k -> 4: TWO buckets
    assert first["batches"] == 2
    assert first["cold_launches"] == 2 and first["warm_launches"] == 0

    # the same ragged mix again: zero new programs, all launches warm
    ids = [svc.submit(ops, W0) for ops, W0 in reqs]
    svc.flush()
    assert all(svc.result(i) is not None for i in ids)
    assert svc.stats["cold_launches"] == first["cold_launches"]
    assert svc.stats["warm_launches"] == first["warm_launches"] + 2


def test_service_admission_policy():
    topo = erdos_renyi(6, p=0.6, seed=0)
    clock = {"now": 0.0}
    svc = PCAService(topo, T=3, K=3, backend="stacked",
                     policy=AdmissionPolicy(max_batch=2, max_wait=0.5,
                                            pad_n=16, pad_k=2),
                     clock=lambda: clock["now"])
    ops, W0 = _request(16, 16, 2, seed=0)
    rid = svc.submit(ops, W0)
    assert svc.result(rid, pop=False) is None       # waiting for batch
    assert svc.poll() == 0                          # max_wait not reached
    clock["now"] = 1.0
    assert svc.poll() == 1                          # force-launched
    assert svc.result(rid) is not None
    # a full bucket launches without poll
    r1 = svc.submit(ops, W0)
    r2 = svc.submit(*_request(16, 16, 2, seed=1))
    assert svc.result(r1) is not None and svc.result(r2) is not None
    assert svc.result(r1) is None                   # pop=True consumed it


def test_service_validation():
    topo = erdos_renyi(6, p=0.6, seed=0)
    svc = PCAService(topo, T=3, K=3, backend="stacked",
                     policy=AdmissionPolicy(pad_k=8))
    ops, W0 = _request(16, 16, 2, seed=0)
    with pytest.raises(ValueError, match="m="):
        bad = _stream(m=5, d=16).ops_at(0)
        svc.submit(bad, W0)
    small = _stream(d=10).ops_at(0)
    # k within pad_k of d is still servable: the pad clamps to d
    assert svc.bucket_of(small, 9)[4] == 10
    with pytest.raises(ValueError, match="exceeds d"):
        svc.bucket_of(small, 11)
    # and a clamped-k request round-trips through the service
    svc2 = PCAService(topo, T=3, K=3, backend="stacked",
                      policy=AdmissionPolicy(max_batch=1, pad_k=8))
    rng = np.random.default_rng(0)
    W9 = jnp.asarray(np.linalg.qr(rng.standard_normal((10, 9)))[0],
                     jnp.float32)
    resp = svc2.result(svc2.submit(small, W9))
    assert resp is not None and resp.W.shape == (6, 10, 9)


# ------------------------------------------------------ prefetch lifecycle
def test_prefetch_iterator_lifecycle():
    from repro.data.synthetic import PrefetchIterator

    # full-queue exhaustion must still deliver the done sentinel
    it = PrefetchIterator(iter(range(10)), depth=2)
    assert list(it) == list(range(10))
    it.close()

    # close() unblocks a worker parked on a full queue
    p = PrefetchIterator(iter(range(1000)), depth=1)
    assert next(p) == 0
    time.sleep(0.15)
    p.close()
    p._thread.join(timeout=2.0)
    assert not p._thread.is_alive()
    assert p._thread.daemon

    # context manager + post-close iteration
    with PrefetchIterator(iter(range(3)), depth=2) as q:
        assert next(q) == 0
    with pytest.raises(StopIteration):
        next(q)
    q.close()                                       # idempotent


def test_prefetch_iterator_surfaces_source_exception():
    from repro.data.synthetic import PrefetchIterator

    def bad():
        yield 1
        raise RuntimeError("boom")

    it = PrefetchIterator(bad(), depth=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="boom"):
        next(it)
    it.close()


def test_prefetch_close_wakes_parked_consumer():
    """close() from another thread must unblock a consumer waiting in
    __next__ on an empty queue (slow source)."""
    import threading

    from repro.data.synthetic import PrefetchIterator

    release = threading.Event()

    def slow_source():
        release.wait(timeout=30.0)
        yield 1

    it = PrefetchIterator(slow_source(), depth=1)
    outcome = {}

    def consume():
        try:
            outcome["item"] = next(it)
        except StopIteration:
            outcome["stopped"] = True

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    time.sleep(0.1)                 # consumer is parked in q.get()
    it.close()
    t.join(timeout=3.0)
    assert not t.is_alive()
    assert outcome.get("stopped")
    release.set()                   # let the source thread finish


def test_multistream_close_one_keeps_other_lanes_items():
    """Per-stream close() must not drop queued items of other streams —
    the failure mode of draining a shared queue."""
    from repro.data.synthetic import MultiStreamPrefetcher

    with MultiStreamPrefetcher({"a": iter(range(6)),
                                "b": iter(range(100, 106))},
                               depth=4) as mux:
        assert mux.get("a") == 0
        time.sleep(0.1)             # let both lanes fill their queues
        mux.close("b")
        assert mux.streams == ("a",)
        # every remaining "a" item survives b's close
        assert [mux.get("a") for _ in range(5)] == [1, 2, 3, 4, 5]
        with pytest.raises(StopIteration):
            mux.get("a")
        with pytest.raises(KeyError):
            mux.get("b")


def test_multistream_backpressure_is_per_tenant():
    """A slow consumer on one lane (its bounded queue stays full) must
    never block ingest or consumption on the rest."""
    import threading

    from repro.data.synthetic import MultiStreamPrefetcher

    pulled = {"fast": 0}

    def fast_source():
        for i in range(200):
            pulled["fast"] = i
            yield i

    mux = MultiStreamPrefetcher({"slow": iter(range(1000)),
                                 "fast": fast_source()}, depth=1)
    try:
        got = []

        def consume_fast():
            for _ in range(200):
                got.append(mux.get("fast"))

        t = threading.Thread(target=consume_fast, daemon=True)
        t.start()
        t.join(timeout=10.0)
        assert not t.is_alive(), "slow lane backpressure stalled fast lane"
        assert got == list(range(200))
        assert pulled["fast"] == 199    # ingest kept up with the consumer
    finally:
        mux.close()


def test_multistream_tick_covers_open_lanes_and_drops_exhausted():
    from repro.data.synthetic import MultiStreamPrefetcher

    mux = MultiStreamPrefetcher({"a": iter(range(3)), "b": iter(range(1))},
                                depth=2)
    try:
        assert mux.tick() == {"a": 0, "b": 0}
        # b exhausts: closed and dropped, a unaffected
        assert mux.tick() == {"a": 1}
        assert mux.streams == ("a",)
        # admission mid-flight; duplicate names refused
        mux.add("c", iter(range(5)))
        with pytest.raises(ValueError, match="already open"):
            mux.add("c", iter(range(5)))
        assert mux.tick() == {"a": 2, "c": 0}
        assert mux.tick() == {"c": 1}
        assert mux.streams == ("c",)
    finally:
        mux.close()
    assert mux.streams == ()
    mux.close()                     # idempotent


# ------------------------------------------------------------ block_n knob
def test_block_n_env_override(monkeypatch):
    from repro.kernels.fastmix import DEFAULT_BLOCK_N, default_block_n

    topo = erdos_renyi(6, p=0.6, seed=0)
    # PR 5: engines no longer resolve block_n at construction — None defers
    # to the kernels, which resolve env > autotune cache > default at trace
    # time (so a tuned cache reaches engines built before it existed).
    assert ConsensusEngine(topo, K=3).block_n is None
    assert default_block_n() == DEFAULT_BLOCK_N
    monkeypatch.setenv("REPRO_FASTMIX_BLOCK_N", "256")
    assert default_block_n() == 256            # env wins over cache/default
    assert ConsensusEngine(topo, K=3, block_n=64).block_n == 64
    monkeypatch.setenv("REPRO_FASTMIX_BLOCK_N", "nope")
    with pytest.raises(ValueError, match="positive integer"):
        default_block_n()
    monkeypatch.setenv("REPRO_FASTMIX_BLOCK_N", "-8")
    with pytest.raises(ValueError, match="positive integer"):
        default_block_n()


def test_block_n_values_agree_with_reference():
    """Any tile width gives the same gossip result (interpret-mode kernel
    vs the stacked bit-reference, fp32 tolerance)."""
    topo = erdos_renyi(8, p=0.5, seed=3)
    rng = np.random.default_rng(0)
    S = jnp.asarray(rng.standard_normal((8, 40, 4)), jnp.float32)
    ref = ConsensusEngine(topo, K=5, backend="stacked").mix(S)
    for bn in (128, 256):
        out = ConsensusEngine(topo, K=5, backend="pallas", interpret=True,
                              block_n=bn).mix(S)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
