"""TrackerFleet: vmapped multi-tenant tracking vs solo trackers.

The load-bearing contract is solo equivalence: a tenant's per-tick carry
(and therefore its subspace estimate) must be *bit-identical* to a solo
:class:`StreamingDeEPCA` fed the same zero-row-padded operators, with
every drift decision (drift / restart / escalation count) coinciding —
including when the tenant's restart or escalation runs as a masked
in-batch select while other tenants ride along as no-ops.  On top of
that: the slot-pool admission contract (evict -> join lands in the freed
slot and reproduces a fresh tracker exactly) and the bucketing contract
(a 10-shape tenant mix collapses onto two compiled window programs, cold
only on first touch).
"""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ConsensusEngine, IterationDriver, PowerStep, \
    erdos_renyi, synthetic_spiked
from repro.core.operators import StackedOperators
from repro.streaming import (DriftPolicy, EigengapShiftStream,
                             SlowRotationStream, StreamingDeEPCA,
                             TrackerFleet, scatter_carry, select_carry)

jax.config.update("jax_enable_x64", False)

PASSIVE = DriftPolicy(jump=math.inf, restart=math.inf, target=None,
                      max_escalations=0)


def _pad(ops, n_pad):
    n = ops.data.shape[1]
    if n == n_pad:
        return ops
    return StackedOperators(
        data=jnp.pad(ops.data, ((0, 0), (0, n_pad - n), (0, 0))))


def _assert_state_equal(fleet, tid, solo):
    """Full resume-tuple equality: every carry slot AND the offset."""
    fs, ss = fleet.tenant_state(tid), solo.state
    assert len(fs) == len(ss)
    for a, b in zip(fs, ss):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- carry primitives
def test_select_carry_masks_per_slot():
    old = (jnp.zeros((4, 2, 3)), jnp.zeros((4, 2, 3)))
    new = (jnp.ones((4, 2, 3)), 2 * jnp.ones((4, 2, 3)))
    mask = jnp.asarray([True, False, True, False])
    out = select_carry(mask, new, old)
    np.testing.assert_array_equal(np.asarray(out[0][:, 0, 0]),
                                  [1.0, 0.0, 1.0, 0.0])
    np.testing.assert_array_equal(np.asarray(out[1][:, 0, 0]),
                                  [2.0, 0.0, 2.0, 0.0])


def test_scatter_carry_writes_one_slot():
    carry = (jnp.zeros((3, 2, 2)),)
    out = scatter_carry(carry, 1, (jnp.ones((2, 2)),))
    np.testing.assert_array_equal(np.asarray(out[0][0]), np.zeros((2, 2)))
    np.testing.assert_array_equal(np.asarray(out[0][1]), np.ones((2, 2)))
    np.testing.assert_array_equal(np.asarray(out[0][2]), np.zeros((2, 2)))


# -------------------------------------------- driver carry-resume substrate
def test_run_batch_carry_resume_bitwise():
    """One T=4 batched window == T=2 + resumed T=2, bitwise (the fleet's
    window substrate)."""
    m = 6
    topo = erdos_renyi(m, p=0.6, seed=1)
    eng = ConsensusEngine.for_algorithm("deepca", topo, K=3,
                                        backend="stacked")
    driver = IterationDriver(step=PowerStep.for_algorithm("deepca", 3),
                             engine=eng)
    rng = np.random.default_rng(0)
    arrs, W0s = [], []
    for b in range(3):
        ops = synthetic_spiked(m, 16, 3, n_per_agent=20, seed=b)
        arrs.append(ops.data)
        W0s.append(np.linalg.qr(rng.standard_normal((16, 3)))[0])
    ops_b = StackedOperators(data=jnp.stack(arrs))
    W0 = jnp.asarray(np.stack(W0s), jnp.float32)

    full = driver.run_batch(ops_b, W0, T=4)
    half = driver.run_batch(ops_b, W0, T=2)
    resumed = driver.run_batch(ops_b, W0, T=2, carry=half.carries)
    for a, b in zip(full.carries, resumed.carries):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_batch_carry_rejects_wrong_leading_axis():
    m = 6
    topo = erdos_renyi(m, p=0.6, seed=1)
    eng = ConsensusEngine.for_algorithm("deepca", topo, K=3,
                                        backend="stacked")
    driver = IterationDriver(step=PowerStep.for_algorithm("deepca", 3),
                             engine=eng)
    ops = synthetic_spiked(m, 16, 3, n_per_agent=20, seed=0)
    ops_b = StackedOperators(data=jnp.stack([ops.data, ops.data]))
    W0 = jnp.stack([jnp.eye(16, 3)] * 2)
    out = driver.run_batch(ops_b, W0, T=1)
    bad = tuple(c[0] for c in out.carries)          # no leading B axis
    with pytest.raises(ValueError, match="leading problem axis"):
        driver.run_batch(ops_b, W0, T=1, carry=bad)


# --------------------------------------------------------- solo equivalence
def test_fleet_passive_ticks_bit_identical_to_solo():
    """Mixed-shape fleet, passive policy: every tenant's carry and offset
    match its solo tracker exactly, across 2 shape buckets."""
    m, d, k = 6, 16, 3
    topo = erdos_renyi(m, p=0.6, seed=1)
    streams = {"a": SlowRotationStream(m=m, d=d, k=k, n_per_agent=20,
                                       seed=0, rate=0.06),
               "b": SlowRotationStream(m=m, d=d, k=k, n_per_agent=36,
                                       seed=1, rate=0.06),
               "c": SlowRotationStream(m=m, d=d, k=k, n_per_agent=24,
                                       seed=2, rate=0.06)}
    fleet = TrackerFleet(k=k, T_tick=3, K=4, topology=topo,
                         backend="stacked", policy=PASSIVE, slots=2)
    solos = {}
    n_pads = {}
    for tid, s in streams.items():
        fleet.join(tid, s.init_W0(), n=s.n_per_agent)
        n_pads[tid] = fleet.bucket_of(d, k, s.n_per_agent)[3]
        solos[tid] = StreamingDeEPCA(k=k, T_tick=3, K=4, topology=topo,
                                     backend="stacked", W0=s.init_W0(),
                                     policy=PASSIVE)
    assert fleet.bucket_of(d, k, 20) == fleet.bucket_of(d, k, 24)
    assert fleet.bucket_of(d, k, 20) != fleet.bucket_of(d, k, 36)

    for t in range(3):
        items = {tid: s.tick(t) for tid, s in streams.items()}
        rep = fleet.tick(items)
        for tid, item in items.items():
            sr = solos[tid].tick(_pad(item.ops, n_pads[tid]), item.U)
            fr = rep.tenants[tid]
            assert (fr.drift, fr.restarted, fr.escalations) == \
                (sr.drift, sr.restarted, sr.escalations)
            assert fr.iterations == sr.iterations
            _assert_state_equal(fleet, tid, solos[tid])
    assert fleet.program_count == 2
    assert fleet.stats["cold_launches"] == 2


def test_escalation_mask_bit_identical_to_solo():
    """One tenant escalates (truth supplied, unreachable target) while its
    bucket-mate rides the escalation windows as a masked no-op — both stay
    bit-identical to their solo trackers."""
    m, d, k = 6, 16, 3
    topo = erdos_renyi(m, p=0.6, seed=2)
    pol = DriftPolicy(jump=math.inf, restart=math.inf, target=1e-12,
                      max_escalations=2)
    hot = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=3,
                             rate=0.2)
    quiet = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=4,
                               rate=0.0)
    fleet = TrackerFleet(k=k, T_tick=2, K=3, topology=topo,
                         backend="stacked", policy=pol, slots=4)
    fleet.join("hot", hot.init_W0(), n=20)
    fleet.join("quiet", quiet.init_W0(), n=20)
    n_pad = fleet.bucket_of(d, k, 20)[3]
    solo_hot = StreamingDeEPCA(k=k, T_tick=2, K=3, topology=topo,
                               backend="stacked", W0=hot.init_W0(),
                               policy=pol)
    solo_quiet = StreamingDeEPCA(k=k, T_tick=2, K=3, topology=topo,
                                 backend="stacked", W0=quiet.init_W0(),
                                 policy=pol)

    for t in range(3):
        ht, qt = hot.tick(t), quiet.tick(t)
        # truth only for "hot": the target applies to it alone, so the
        # escalation mask is genuinely partial over the bucket
        rep = fleet.tick({"hot": (ht.ops, ht.U), "quiet": qt.ops})
        sh = solo_hot.tick(_pad(ht.ops, n_pad), ht.U)
        sq = solo_quiet.tick(_pad(qt.ops, n_pad))
        assert rep.tenants["hot"].escalations == sh.escalations == 2
        assert rep.tenants["quiet"].escalations == sq.escalations == 0
        _assert_state_equal(fleet, "hot", solo_hot)
        _assert_state_equal(fleet, "quiet", solo_quiet)


def test_restart_mask_bit_identical_to_solo():
    """Hair-trigger restart threshold: every tick >= 1 rebases through the
    masked restart pass (vmapped rebase_carry + select + rerun) and must
    equal the solo tracker's restart path bitwise."""
    m, d, k = 6, 16, 3
    topo = erdos_renyi(m, p=0.6, seed=3)
    pol = DriftPolicy(jump=1e-9, restart=1e-9, target=None,
                      max_escalations=1)
    streams = {"a": SlowRotationStream(m=m, d=d, k=k, n_per_agent=20,
                                       seed=5, rate=0.1),
               "b": SlowRotationStream(m=m, d=d, k=k, n_per_agent=20,
                                       seed=6, rate=0.1)}
    fleet = TrackerFleet(k=k, T_tick=2, K=3, topology=topo,
                         backend="stacked", policy=pol, slots=4)
    solos = {}
    for tid, s in streams.items():
        fleet.join(tid, s.init_W0(), n=20)
        solos[tid] = StreamingDeEPCA(k=k, T_tick=2, K=3, topology=topo,
                                     backend="stacked", W0=s.init_W0(),
                                     policy=pol)
    n_pad = fleet.bucket_of(d, k, 20)[3]

    saw_restart = False
    for t in range(3):
        items = {tid: s.tick(t) for tid, s in streams.items()}
        rep = fleet.tick(items)
        for tid, item in items.items():
            sr = solos[tid].tick(_pad(item.ops, n_pad), item.U)
            fr = rep.tenants[tid]
            assert (fr.drift, fr.restarted, fr.escalations) == \
                (sr.drift, sr.restarted, sr.escalations)
            saw_restart |= fr.restarted
            _assert_state_equal(fleet, tid, solos[tid])
    assert saw_restart, "restart path was never exercised"
    assert fleet.stats["restarts"] > 0


def test_decisions_match_solo_on_eigengap_shift():
    """Abrupt-shift stream with a moderate policy: whatever decisions the
    solo tracker takes, the fleet takes the same ones (and stays bitwise
    on the carry)."""
    m, d, k = 6, 20, 3
    topo = erdos_renyi(m, p=0.6, seed=4)
    pol = DriftPolicy(jump=3.0, restart=1e6, target=None,
                      max_escalations=1)
    s = EigengapShiftStream(m=m, d=d, k=k, n_per_agent=24, seed=7,
                            shift_every=3, gap_shift=0.8)
    fleet = TrackerFleet(k=k, T_tick=3, K=4, topology=topo,
                         backend="stacked", policy=pol, slots=2)
    fleet.join("t", s.init_W0(), n=24)
    n_pad = fleet.bucket_of(d, k, 24)[3]
    solo = StreamingDeEPCA(k=k, T_tick=3, K=4, topology=topo,
                           backend="stacked", W0=s.init_W0(), policy=pol)
    drifts = []
    for t in range(6):
        item = s.tick(t)
        rep = fleet.tick({"t": item})
        sr = solo.tick(_pad(item.ops, n_pad), item.U)
        fr = rep.tenants["t"]
        assert (fr.drift, fr.restarted, fr.escalations) == \
            (sr.drift, sr.restarted, sr.escalations)
        drifts.append(fr.drift)
        _assert_state_equal(fleet, "t", solo)
    assert any(drifts), "shift stream never tripped the drift flag"


# -------------------------------------------------------- membership churn
def test_evict_join_reuses_slot_and_reproduces_fresh_tracker():
    """leave() + join() lands in the vacated slot and the joiner's first
    tick is bit-identical to a brand-new solo tracker's."""
    m, d, k = 6, 16, 3
    topo = erdos_renyi(m, p=0.6, seed=5)
    sa = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=8,
                            rate=0.05)
    sb = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=9,
                            rate=0.05)
    fleet = TrackerFleet(k=k, T_tick=3, K=4, topology=topo,
                         backend="stacked", policy=PASSIVE, slots=2)
    fleet.join("a", sa.init_W0(), n=20)
    slot_b = fleet.join("b", sb.init_W0(), n=20)
    n_pad = fleet.bucket_of(d, k, 20)[3]
    for t in range(2):
        fleet.tick({"a": sa.tick(t), "b": sb.tick(t)})
    programs_before = fleet.program_count

    fleet.leave("b")
    sc = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=10,
                            rate=0.05)
    assert fleet.join("c", sc.init_W0(), n=20) == slot_b
    item = sc.tick(0)
    fleet.tick({"a": sa.tick(2), "c": item})

    fresh = StreamingDeEPCA(k=k, T_tick=3, K=4, topology=topo,
                            backend="stacked", W0=sc.init_W0(),
                            policy=PASSIVE)
    fresh.tick(_pad(item.ops, n_pad), item.U)
    _assert_state_equal(fleet, "c", fresh)
    # membership churn retraced nothing
    assert fleet.program_count == programs_before
    assert fleet.stats["joins"] == 3 and fleet.stats["leaves"] == 1


def test_join_pool_growth_is_one_cold_compile():
    """Joining past the slot-pool capacity doubles the pool: exactly one
    new program shape, counted cold once, then warm."""
    m, d, k = 6, 16, 3
    topo = erdos_renyi(m, p=0.6, seed=6)
    streams = [SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=i,
                                  rate=0.05) for i in range(3)]
    fleet = TrackerFleet(k=k, T_tick=2, K=3, topology=topo,
                         backend="stacked", policy=PASSIVE, slots=2)
    fleet.join("t0", streams[0].init_W0(), n=20)
    fleet.join("t1", streams[1].init_W0(), n=20)
    fleet.tick({"t0": streams[0].tick(0), "t1": streams[1].tick(0)})
    assert fleet.program_count == 1

    fleet.join("t2", streams[2].init_W0(), n=20)     # pool 2 -> 4
    rep = fleet.tick({f"t{i}": streams[i].tick(1) for i in range(3)})
    assert rep.cold_launches == 1 and fleet.program_count == 2
    rep = fleet.tick({f"t{i}": streams[i].tick(2) for i in range(3)})
    assert rep.cold_launches == 0


def test_ten_shape_mix_two_programs():
    """The acceptance pin: 10 distinct per-agent sample counts collapse
    onto <= 2 compiled window programs, cold only on the first tick."""
    m, d, k = 6, 16, 3
    topo = erdos_renyi(m, p=0.6, seed=7)
    ns = [40 + 2 * i for i in range(10)]             # 40..58 -> pads 48, 64
    streams = [SlowRotationStream(m=m, d=d, k=k, n_per_agent=n, seed=i,
                                  rate=0.05) for i, n in enumerate(ns)]
    fleet = TrackerFleet(k=k, T_tick=2, K=3, topology=topo,
                         backend="stacked", policy=PASSIVE, slots=8)
    for i, (s, n) in enumerate(zip(streams, ns)):
        fleet.join(f"t{i}", s.init_W0(), n=n)
    assert len({fleet.bucket_of(d, k, n) for n in ns}) == 2

    rep = fleet.tick({f"t{i}": s.tick(0) for i, s in enumerate(streams)})
    assert rep.cold_launches == 2
    rep = fleet.tick({f"t{i}": s.tick(1) for i, s in enumerate(streams)})
    assert rep.cold_launches == 0
    assert fleet.program_count == 2


def test_serve_fleet_workload_threaded_ingest():
    """``serve --workload pca-fleet`` in process: ticks drawn on the
    ingest lanes, two programs, no steady cold launch, the churn's joiner
    served, and BLAS threading given back afterwards."""
    from threadpoolctl import threadpool_info
    from repro.launch import serve

    def blas_threads():
        return [p["num_threads"] for p in threadpool_info()
                if p["user_api"] == "blas"]

    before = blas_threads()
    args = serve.build_parser().parse_args([
        "--workload", "pca-fleet", "--tenants", "10", "--m", "6",
        "--d", "16", "--k-top", "3", "--n-per-agent", "48", "--ticks", "4",
        "--rounds", "3"])
    out = serve.serve_pca_fleet(args)
    fleet = out["fleet"]
    assert fleet.program_count == 2 and out["steady_cold"] == 0
    assert "joiner" in fleet.tenants
    assert set(out["last"]) == set(fleet.tenants)
    assert len(out["steady_ms"]) == 3
    assert blas_threads() == before


# ------------------------------------------------------------ slot stacking
def _eager_stack(bkt, active, items):
    """The slot stack built one eager op at a time: each tenant's rows
    padded alone, free slots on the first active tenant's, slots without
    a truth on their ``Q_prev``."""
    kind, n_pad = bkt.key[0], bkt.key[3]
    payloads = {}
    for s, tid in active:
        ops, U = items[tid]
        payloads[s] = (ops.array if kind == "dense"
                       else _pad(ops, n_pad).data, U)
    filler = payloads[active[0][0]][0]
    arr = jnp.stack([payloads[s][0] if s in payloads else filler
                     for s in range(bkt.capacity)])
    U_b = jnp.stack([payloads[s][1] if s in payloads
                     and payloads[s][1] is not None else bkt.Q_prev[s]
                     for s in range(bkt.capacity)])
    return arr, U_b


@pytest.mark.parametrize("kind, ns, gone, truths", [
    ("data", (14, 10, 16, 12), (), ()),
    ("dense", (8, 8), (), ()),
    ("data", (14, 10, 12, 16, 10), ("t1", "t3"), ()),
    ("data", (12, 16, 10), ("t0",), ("t2",)),
    ("dense", (8, 8, 8), (), ("t0", "t2")),
], ids=["data-full", "dense-fillers", "data-fillers", "data-some-truths",
        "dense-some-truths"])
def test_stack_program_bit_equal_to_eager(kind, ns, gone, truths):
    """One ``stack_slots`` dispatch builds the bucket's operators and
    truth stack bit for bit as the per-slot eager construction did —
    ragged rows, free filler slots (a tenant that left mid-pool), and
    truths from some tenants only."""
    m, d, k = 4, 8, 2
    fleet = TrackerFleet(k=k, T_tick=2, K=2, slots=8, policy=PASSIVE,
                         topology=erdos_renyi(m, p=0.6, seed=3),
                         backend="stacked")
    items = {}
    for i, n in enumerate(ns):
        s = SlowRotationStream(m=m, d=d, k=k, n_per_agent=n, seed=i,
                               rate=0.1)
        tid, tick = f"t{i}", s.tick(0)
        ops = tick.ops
        if kind == "dense":
            ops = StackedOperators(dense=jnp.einsum(
                "mnd,mne->mde", ops.data, ops.data) / n)
        fleet.join(tid, s.init_W0(), n=n, kind=kind)
        items[tid] = (ops, tick.U if tid in truths else None)
    for tid in gone:
        fleet.leave(tid)
        del items[tid]
    (bkt,) = fleet._buckets.values()
    active = [(s, tid) for s, tid in enumerate(bkt.slots) if tid is not None]
    assert len(active) < bkt.capacity

    arr, U_b, has_U = fleet._stack(bkt, active, items)
    want_arr, want_U = _eager_stack(bkt, active, items)
    assert arr.dtype == want_arr.dtype and U_b.dtype == want_U.dtype
    np.testing.assert_array_equal(np.asarray(arr), np.asarray(want_arr))
    np.testing.assert_array_equal(np.asarray(U_b), np.asarray(want_U))
    assert has_U == {tid: tid in truths for _, tid in active}


def test_stack_cold_counts_pools_and_churn_compiles_nothing():
    """``stack_cold`` counts a bucket pool's first stack launch only, and
    churn compiles no program: a leave -> join into the freed slot, a
    leave with no rejoin, a join with other rows into the same bucket,
    and a tenant that starts giving a truth."""
    from repro.analysis.retrace import count_compiles
    m, d, k = 4, 8, 2
    fleet = TrackerFleet(k=k, T_tick=2, K=2, slots=4, policy=PASSIVE,
                         topology=erdos_renyi(m, p=0.6, seed=4),
                         backend="stacked")
    streams = {n: SlowRotationStream(m=m, d=d, k=k, n_per_agent=n,
                                     seed=n, rate=0.05)
               for n in (10, 12, 20)}
    rows = {"a": 10, "b": 12, "c": 20}         # buckets n_pad 16 and 32
    truthful = set()
    for tid, n in rows.items():
        fleet.join(tid, streams[n].init_W0(), n=n)
    t = iter(range(100))

    def tick():
        i, items = next(t), {}
        for tid in fleet.tenants:
            s = streams[rows[tid]].tick(i)
            items[tid] = (s.ops, s.U if tid in truthful else None)
        return fleet.tick(items).stack_cold

    assert [tick(), tick()] == [2, 0]
    with count_compiles() as compiles:
        fleet.leave("b")
        fleet.join("b", streams[12].init_W0(), n=12)     # the freed slot
        cold = [tick()]
        fleet.leave("a")                                 # no rejoin
        cold.append(tick())
        rows["a"] = 12                                   # other rows
        fleet.join("a", streams[12].init_W0(), n=12)
        cold.append(tick())
        truthful.add("a")                                # a first truth
        cold.append(tick())
        fleet.leave("c")                                 # bucket 2 idle
        cold.append(tick())
    assert cold == [0, 0, 0, 0, 0]
    assert compiles.count == 0, compiles.messages
    assert fleet.stats["stack_cold"] == 2
    assert fleet.program_count == 2                    # windows only


# ------------------------------------------------------------- guard rails
def test_tick_requires_exact_tenant_cover():
    m, d, k = 6, 16, 3
    topo = erdos_renyi(m, p=0.6, seed=8)
    s = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=0)
    fleet = TrackerFleet(k=k, T_tick=2, K=3, topology=topo,
                         backend="stacked", policy=PASSIVE)
    fleet.join("a", s.init_W0(), n=20)
    with pytest.raises(ValueError, match="exactly the active tenants"):
        fleet.tick({})
    with pytest.raises(ValueError, match="exactly the active tenants"):
        fleet.tick({"a": s.tick(0), "ghost": s.tick(0)})


def test_join_duplicate_and_unknown_leave():
    m, d, k = 6, 16, 3
    topo = erdos_renyi(m, p=0.6, seed=9)
    s = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=0)
    fleet = TrackerFleet(k=k, T_tick=2, K=3, topology=topo,
                         backend="stacked", policy=PASSIVE)
    fleet.join("a", s.init_W0(), n=20)
    with pytest.raises(ValueError, match="already joined"):
        fleet.join("a", s.init_W0(), n=20)
    with pytest.raises(KeyError):
        fleet.leave("nope")
