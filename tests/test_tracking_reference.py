"""Trackers against the plain reference, and Alg. 2's re-anchored sign
reference.

A tracker's sign reference is its start ``W0`` for the first window and
the mean basis of the state each later window resumes from.  With the
reference pinned to ``W0`` (Alg. 2 as written for one-shot solves) a
drifting subspace leaves the start behind: once a column's overlap with
``W0`` crosses zero the agents split on that column's sign, the tracked
mean column cancels and tan-theta jumps.  Here a solo tracker and a fleet
are carried far past such crossings with the re-anchored reference, and
fail with the pinned one.  The fleet is also replayed by
``reference_tracker.py``, a plain NumPy float64 tracker, and the
benchmark's device stream generator is held to the host stream's law.
"""
import importlib.util
import math
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import erdos_renyi
from repro.core.mixing import fastmix_eta
from repro.core.operators import StackedOperators
from repro.streaming import (DriftPolicy, SlowRotationStream,
                             StreamingDeEPCA, TrackerFleet)
from repro.streaming.stream import _rotation

from reference_tracker import (ReferenceTracker, erdos_renyi_adjacency,
                               fastmix_eta as ref_eta, mixing_matrix)

jax.config.update("jax_enable_x64", False)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the crossing witness: heterogeneous agents, two gossip rounds, and
#: 0.05 rad a tick for 64 ticks (3.2 rad, far past 90 degrees); seeds 1
#: and 3 split on a column's sign with the reference pinned to W0
M, D, K_TOP, RATE, TICKS = 6, 16, 3, 0.05, 64
#: a tracker that keeps its agents together reads under this at every
#: tick (re-anchored: at most 0.04 on either seed); a sign split reads
#: over it (pinned: 52 on seed 1, 0.86 on seed 3)
TAN_MAX = 0.2


def _witness(seed):
    return SlowRotationStream(m=M, d=D, k=K_TOP, n_per_agent=40, seed=seed,
                              rate=RATE, heterogeneity=1.0)


def _agent0_start(stream):
    X0 = np.asarray(stream.ops_at(0).data[0], np.float64)
    return jnp.asarray(np.linalg.svd(X0)[2][:K_TOP].T, jnp.float32)


def _worst_tan(U, W_stack):
    """Largest tan-theta of any agent's basis against ``U`` (host f64)."""
    U = np.asarray(U, np.float64)
    Q = np.linalg.qr(np.asarray(W_stack, np.float64))[0]
    UtQ = np.swapaxes(U, -1, -2) @ Q
    c = np.linalg.svd(UtQ, compute_uv=False).min(axis=-1)
    s = np.linalg.svd(Q - U @ UtQ, compute_uv=False).max(axis=-1)
    return float(np.max(s / c))


def _pin_fleet_reference(monkeypatch):
    """Every fleet window's sign reference is its tenant's start ``W0``."""
    join, window = TrackerFleet.join, TrackerFleet._window
    starts = {}

    def pinned_join(self, tid, W0, **kw):
        slot = join(self, tid, W0, **kw)
        starts[self._tenants[tid].bucket, slot] = jnp.asarray(W0)
        return slot

    def pinned_window(self, bkt, ops_b, carry, ref, *a, **kw):
        ref = jnp.stack([starts.get((bkt.key, s), ref[s])
                         for s in range(bkt.capacity)])
        return window(self, bkt, ops_b, carry, ref, *a, **kw)
    monkeypatch.setattr(TrackerFleet, "join", pinned_join)
    monkeypatch.setattr(TrackerFleet, "_window", pinned_window)


def _track(kind, streams, topo):
    """Worst agent's tan-theta per tenant over every tick."""
    worst = {s: 0.0 for s in streams}
    if kind == "solo":
        for seed, st in streams.items():
            tr = StreamingDeEPCA(k=K_TOP, T_tick=3, K=2, topology=topo,
                                 backend="stacked", W0=_agent0_start(st))
            for t in range(TICKS):
                item = st.tick(t)
                tr.tick(item.ops)
                worst[seed] = max(worst[seed], _worst_tan(item.U, tr.W))
        return worst
    fleet = TrackerFleet(k=K_TOP, T_tick=3, K=2, topology=topo,
                         backend="stacked", slots=2)
    for seed, st in streams.items():
        fleet.join(str(seed), _agent0_start(st), n=40)
    for t in range(TICKS):
        items = {seed: st.tick(t) for seed, st in streams.items()}
        fleet.tick({str(seed): it.ops for seed, it in items.items()})
        for seed, it in items.items():
            worst[seed] = max(worst[seed],
                              _worst_tan(it.U, fleet.tenant_W(str(seed))))
    return worst


@pytest.mark.parametrize("kind", ["solo", "fleet"])
@pytest.mark.parametrize("reference", ["reanchored", "pinned_W0"])
def test_sign_reference_far_past_the_start(kind, reference, monkeypatch):
    """Re-anchored: every tick stays under ``TAN_MAX``.  Pinned to W0 (the
    fault of a fixed Alg. 2 reference): each witness seed goes over it."""
    streams = {seed: _witness(seed) for seed in (1, 3)}
    topo = erdos_renyi(M, p=0.6, seed=1)
    if reference == "pinned_W0":
        monkeypatch.setattr(StreamingDeEPCA, "_reference",
                            lambda self: self.W0)
        _pin_fleet_reference(monkeypatch)
    # the drift is real: the truth ends far from where it started
    st = streams[1]
    U0, U1 = (np.asarray(st.truth_at(t)[0], np.float64) for t in (0, TICKS))
    cos = np.linalg.svd(U0.T @ U1, compute_uv=False).min()
    assert np.degrees(np.arccos(min(cos, 1.0))) > 60.0
    worst = _track(kind, streams, topo)
    if reference == "reanchored":
        assert max(worst.values()) < TAN_MAX, worst
    else:
        assert min(worst.values()) > TAN_MAX, worst


def test_fleet_bit_identical_to_solo_across_sign_crossings():
    """Every tick of the witness run: the fleet's carry equals the solo
    tracker's bitwise, though each window's reference is the mean basis
    computed by two different programs (jitted and vmapped, or eager)."""
    st = _witness(1)
    topo = erdos_renyi(M, p=0.6, seed=1)
    W0 = _agent0_start(st)
    fleet = TrackerFleet(k=K_TOP, T_tick=3, K=2, topology=topo,
                         backend="stacked", slots=2)
    fleet.join("t", W0, n=40)
    solo = StreamingDeEPCA(k=K_TOP, T_tick=3, K=2, topology=topo,
                           backend="stacked", W0=W0)
    n_pad = fleet.bucket_of(D, K_TOP, 40)[3]
    assert n_pad == 48
    for t in range(TICKS):
        item = st.tick(t)
        fr = fleet.tick({"t": item.ops}).tenants["t"]
        sr = solo.tick(StackedOperators(data=jnp.pad(
            item.ops.data, ((0, 0), (0, n_pad - 40), (0, 0)))))
        assert (fr.drift, fr.restarted, fr.escalations) == \
            (sr.drift, sr.restarted, sr.escalations)
        for a, b in zip(fleet.tenant_state("t"), solo.state):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- the plain reference
def test_reference_graph_and_weights_match_the_program():
    """The reference's written-out graph, weights and FastMix momentum are
    the program's, seeds past 32 bits included."""
    for m, p, seed in ((6, 0.6, 1), (16, 0.5, 2 ** 31 + 17), (50, 0.5, 3)):
        topo = erdos_renyi(m, p=p, seed=seed)
        L = mixing_matrix(erdos_renyi_adjacency(m, p, seed))
        np.testing.assert_allclose(L, topo.mixing, rtol=0, atol=1e-12)
        assert ref_eta(L) == pytest.approx(fastmix_eta(topo.lambda2),
                                           rel=1e-12)


def test_benchmark_copy_of_the_reference_is_this_one():
    """The benchmark may not import from ``tests/``: it keeps a copy."""
    here = os.path.join(ROOT, "tests", "reference_tracker.py")
    copy = os.path.join(ROOT, "bench", "reference_tracker.py")
    with open(here) as a, open(copy) as b:
        assert a.read() == b.read()


POLICIES = {
    "passive": DriftPolicy(jump=math.inf, restart=math.inf, target=None,
                           max_escalations=0),
    # every tick from the third on restarts and then escalates once
    "restart": DriftPolicy(jump=1e-9, restart=1e-9, max_escalations=1),
    # truth is given to tenant t0 only: it escalates twice a tick, its
    # bucket-mates ride the escalation windows as no-ops
    "escalate": DriftPolicy(jump=math.inf, restart=math.inf, target=1e-12,
                            max_escalations=2),
}


@pytest.mark.parametrize("policy", list(POLICIES))
def test_fleet_matches_reference_tracker(policy):
    """Three tenants in two buckets over six ticks, replayed by the plain
    float64 tracker with the restarts and escalations the fleet reported.

    Tolerance: the fleet runs in float32, the reference in float64, so
    each agent's basis differs by float32 rounding carried through the
    iterations; on the CPU the worst agent reads under 2e-6 in every
    case.  ``1e-4`` leaves that 50 times, and is far under what a wrong
    step, a wrong sign (a flipped column reads over 1) or a replay off by
    one window reads."""
    m, d, k = 6, 16, 3
    adj = erdos_renyi_adjacency(m, 0.6, 1)
    topo = erdos_renyi(m, p=0.6, seed=1)
    streams = {f"t{i}": SlowRotationStream(m=m, d=d, k=k,
                                           n_per_agent=20 + 6 * i,
                                           seed=10 + i, rate=0.1)
               for i in range(3)}
    fleet = TrackerFleet(k=k, T_tick=2, K=3, topology=topo,
                         backend="stacked", policy=POLICIES[policy], slots=4)
    refs = {}
    for tid, s in streams.items():
        fleet.join(tid, s.init_W0(), n=s.n_per_agent)
        refs[tid] = ReferenceTracker(adj, 3, np.asarray(s.init_W0()))
    seen = set()
    for t in range(6):
        items = {tid: s.tick(t) for tid, s in streams.items()}
        payload = {tid: ((it.ops, it.U) if tid == "t0" else it.ops)
                   for tid, it in items.items()}
        rep = fleet.tick(payload)
        for tid, it in items.items():
            r = rep.tenants[tid]
            seen.add((r.restarted, r.escalations))
            W = refs[tid].tick(np.asarray(it.ops.data), 2,
                               restarted=r.restarted,
                               escalations=r.escalations)
            got = np.asarray(fleet.tenant_W(tid))
            assert _worst_tan(W, got) < 1e-4, (t, tid)
            # the same columns with the same signs
            np.testing.assert_allclose(got, W, atol=1e-4)
    want = {"passive": (False, 0), "restart": (True, 1),
            "escalate": (False, 2)}[policy]
    assert want in seen, seen


def test_bfloat16_reference_reads_apart():
    """The control: the same replay in bfloat16 is far from float64, while
    float64 replays of one stream agree exactly."""
    m, d, k = 6, 16, 3
    adj = erdos_renyi_adjacency(m, 0.6, 1)
    s = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=4, rate=0.1)
    W0 = np.asarray(s.init_W0())
    a, b = (ReferenceTracker(adj, 3, W0) for _ in range(2))
    c = ReferenceTracker(adj, 3, W0, precision="bfloat16")
    for t in range(4):
        X = np.asarray(s.ops_at(t).data)
        Wa, Wb, Wc = a.tick(X, 2), b.tick(X, 2), c.tick(X, 2)
    np.testing.assert_array_equal(Wa, Wb)
    assert _worst_tan(Wa, Wc) > 1e-3


# ------------------------------------------- the device stream generator
def _generator():
    path = os.path.join(ROOT, "bench", "gen", "slow_rotation.py")
    spec = importlib.util.spec_from_file_location("bench_gen_slow_rotation",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _max_angle(A, B):
    c = np.linalg.svd(np.linalg.qr(A)[0].T @ np.linalg.qr(B)[0],
                      compute_uv=False).min()
    return float(np.arccos(min(c, 1.0)))


def test_device_stream_rotates_like_the_host_stream():
    """Per tick the top-k basis turns by at most the configured angle, and
    on average (six seeds, two ticks each) by the host stream's angle to
    within 10% (d=64, k=4: device 0.0291 rad, host 0.0305 at 0.05)."""
    gen = _generator()
    d, k, m, rate = 64, 4, 4, 0.05
    dev, host = [], []
    for seed in range(6):
        st = gen.setup(seed, tenants=1, m=m, d=d, k=k, heterogeneity=0.0)
        s = SlowRotationStream(m=m, d=d, k=k, rate=rate, seed=seed)
        for t in (0, 10):
            U, U1 = (np.asarray(gen.bases(st, tt, rate))[0, 0]
                     for tt in (t, t + 1))
            dev.append(_max_angle(U, U1))
            A, B = ((_rotation(d, rate * tt, seed + 7) @ s._U0)[:, :k]
                    for tt in (t, t + 1))
            host.append(_max_angle(A, B))
    assert max(dev) <= rate * (1 + 1e-3)
    assert np.mean(dev) == pytest.approx(np.mean(host), rel=0.10)


def test_device_stream_spectrum_matches_the_host_stream():
    """The empirical mean covariance of many rows: the same top-k
    eigenvalues (within 5%) and noise floor (within 3%) as the host's."""
    gen = _generator()
    d, k, m, n = 64, 4, 4, 4000
    st = gen.setup(1, tenants=2, m=m, d=d, k=k, heterogeneity=0.2)
    X = gen.tick(st, 3, ns=(n, n - 10), rate=0.05)
    assert [x.shape for x in X] == [(m, n, d), (m, n - 10, d)]
    assert X[0].dtype == jnp.float32
    host = SlowRotationStream(m=m, d=d, k=k, rate=0.05, seed=1,
                              n_per_agent=n, heterogeneity=0.2)

    def evals(Y):
        Y = np.asarray(Y, np.float64).reshape(-1, d)
        return np.sort(np.linalg.eigvalsh(Y.T @ Y / len(Y)))[::-1]

    ed, eh = evals(X[0]), evals(host.ops_at(3).data)
    np.testing.assert_allclose(ed[:k], eh[:k], rtol=0.05)
    assert np.median(ed) == pytest.approx(np.median(eh), rel=0.03)
