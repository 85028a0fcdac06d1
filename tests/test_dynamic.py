"""Dynamic topologies: TopologySchedule + DynamicConsensusEngine + e2e.

Covers the Remark-3 regime: time-varying graphs (dropout / rewiring),
fault-degraded graphs (agent death), the no-retrace traced-operand mixing
paths, resume round-accounting, and the degraded-mid-run convergence
acceptance scenario.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (ConsensusEngine, DynamicConsensusEngine,
                        StackedOperators, TopologySchedule, adjacency_of,
                        complete, deepca, depca, erdos_renyi, hypercube,
                        hypercube_structure, ring, ring_structure,
                        synthetic_spiked, top_k_eigvecs)
from repro.runtime import (AgentFailure, DisconnectedTopologyError,
                           deepca_with_failures, degrade_topology,
                           kill_agents)


# ------------------------------------------------------------- schedules
def test_constant_and_piecewise_schedules():
    a, b = ring(8), erdos_renyi(8, p=0.6, seed=1)
    const = TopologySchedule.constant(a)
    assert const.topology_at(0) is a and const.topology_at(99) is a
    pw = TopologySchedule.piecewise([(0, a), (5, b)])
    assert pw.topology_at(4) is a and pw.topology_at(5) is b
    assert pw.constant_m(0, 20) == 8
    with pytest.raises(ValueError):
        TopologySchedule.piecewise([(3, a)])          # no knot at 0
    with pytest.raises(ValueError):
        TopologySchedule.piecewise([(0, a), (0, b)])  # duplicate step


def test_edge_dropout_is_deterministic_connected_and_validated():
    base = erdos_renyi(10, p=0.5, seed=0)
    s1 = TopologySchedule.edge_dropout(base, 0.3, seed=2)
    s2 = TopologySchedule.edge_dropout(base, 0.3, seed=2)
    for t in range(6):
        t1, t2 = s1.topology_at(t), s2.topology_at(t)
        np.testing.assert_array_equal(t1.mixing, t2.mixing)  # reproducible
        assert t1.spectral_gap > 0.0                  # never disconnected
    # different steps draw different graphs (with overwhelming probability)
    assert any(not np.array_equal(s1.topology_at(0).mixing,
                                  s1.topology_at(t).mixing)
               for t in range(1, 6))
    # p=0 is the base graph itself
    assert TopologySchedule.edge_dropout(base, 0.0).topology_at(3) is base


def test_dropout_on_a_tree_falls_back_to_base():
    # a degraded ring is a line graph: dropping ANY edge disconnects it, so
    # every step must fall back to the (connected) base rather than gossip
    # on a non-contracting matrix
    line = degrade_topology(ring(8), [0])
    sched = TopologySchedule.edge_dropout(line, 0.4, seed=0, max_retries=5)
    for t in range(4):
        assert sched.topology_at(t) is line


def test_periodic_rewiring_phases():
    sched = TopologySchedule.periodic_rewiring(8, p=0.6, seed=0, period=3)
    assert sched.topology_at(0).name == sched.topology_at(2).name
    assert sched.topology_at(3).name != sched.topology_at(0).name
    assert sched.constant_m(0, 10) == 8


def test_degraded_schedule_changes_m_and_blocks_scan_consumers():
    base = erdos_renyi(12, p=0.6, seed=3)
    sched = TopologySchedule.degraded(base, {4: [1, 5], 8: [0]})
    assert sched.topology_at(0).m == 12
    assert sched.topology_at(4).m == 10
    assert sched.topology_at(8).m == 9
    assert sched.constant_m(0, 4) == 12      # pre-failure window is fine
    with pytest.raises(ValueError):
        sched.constant_m(0, 10)              # spans a failure boundary


def test_adjacency_roundtrip():
    topo = erdos_renyi(9, p=0.6, seed=7)
    from repro.core import from_adjacency
    rebuilt = from_adjacency("rt", adjacency_of(topo))
    np.testing.assert_allclose(rebuilt.mixing, topo.mixing, atol=1e-12)


# ------------------------------------------- structured-lowering matching
def test_structure_checks_reject_degraded_graphs():
    assert ring_structure(ring(8)) is not None
    assert hypercube_structure(hypercube(8))
    # dropping an edge breaks the structural match -> dense fallback
    dropped = TopologySchedule.edge_dropout(hypercube(8), 0.3, seed=1)
    for t in range(5):
        tp = dropped.topology_at(t)
        if tp is not hypercube(8) and tp.name != "hypercube8":
            assert not hypercube_structure(tp)
    assert ring_structure(erdos_renyi(8, p=0.6, seed=0)) is None
    assert not hypercube_structure(complete(8))


# ------------------------------------------------- dynamic engine parity
def test_dynamic_engine_matches_per_step_static():
    """Traced (L, eta) / engine_at mixing == a fresh static engine per step
    (stacked+pallas)."""
    base = erdos_renyi(8, p=0.5, seed=0)
    sched = TopologySchedule.edge_dropout(base, 0.25, seed=4)
    rng = np.random.default_rng(0)
    S = jnp.asarray(rng.standard_normal((8, 16, 3)), jnp.float32)
    dyn_s = DynamicConsensusEngine(schedule=sched, K=6, backend="stacked")
    dyn_p = DynamicConsensusEngine(schedule=sched, K=6, backend="pallas",
                                   interpret=True)
    Ls, etas = dyn_s.operands(0, 5)
    for t in range(5):
        ref = ConsensusEngine(sched.topology_at(t), K=6,
                              backend="stacked").mix(S)
        for dyn, tol in ((dyn_s, 1e-5), (dyn_p, 2e-4)):
            got_tr = dyn.engine_at(0).mix(S, mixing=(Ls[t], etas[t]))
            got_ea = dyn.engine_at(t).mix(S)
            assert float(jnp.max(jnp.abs(got_tr - ref))) < tol, t
            assert float(jnp.max(jnp.abs(got_ea - ref))) < tol, t
        # mean preservation holds per-step under the schedule (Prop. 1)
        np.testing.assert_allclose(
            np.mean(np.asarray(dyn_s.engine_at(0).mix(
                S, mixing=(Ls[t], etas[t]))), axis=0),
            np.mean(np.asarray(S), axis=0), atol=1e-4)


def test_deepca_constant_schedule_equals_static():
    ops = synthetic_spiked(8, 16, 2, n_per_agent=24, seed=0)
    U, _ = top_k_eigvecs(ops.mean_matrix(), 2)
    rng = np.random.default_rng(3)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((16, 2)))[0],
                     jnp.float32)
    topo = erdos_renyi(8, p=0.6, seed=2)
    r_static = deepca(ops, topo, W0, k=2, T=12, K=5, U=U, backend="stacked")
    r_dyn = deepca(ops, None, W0, k=2, T=12, K=5, U=U, backend="stacked",
                   schedule=TopologySchedule.constant(topo))
    np.testing.assert_allclose(np.asarray(r_dyn.W), np.asarray(r_static.W),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(r_dyn.trace.comm_rounds),
                               np.asarray(r_static.trace.comm_rounds))


def test_deepca_converges_under_rewiring_and_dropout():
    ops = synthetic_spiked(10, 20, 3, n_per_agent=40, seed=0)
    U, _ = top_k_eigvecs(ops.mean_matrix(), 3)
    rng = np.random.default_rng(1)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((20, 3)))[0],
                     jnp.float32)
    for sched in (TopologySchedule.periodic_rewiring(10, p=0.5, seed=0),
                  TopologySchedule.edge_dropout(
                      erdos_renyi(10, p=0.6, seed=2), 0.2, seed=5)):
        res = deepca(ops, None, W0, k=3, T=60, K=6, U=U, schedule=sched)
        assert float(res.trace.mean_tan_theta[-1]) < 1e-3, sched.name


def test_trace_contraction_rate_tracks_schedule():
    base = erdos_renyi(8, p=0.5, seed=0)
    sched = TopologySchedule.edge_dropout(base, 0.3, seed=9)
    ops = synthetic_spiked(8, 12, 2, n_per_agent=16, seed=0)
    rng = np.random.default_rng(0)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((12, 2)))[0],
                     jnp.float32)
    res = deepca(ops, None, W0, k=2, T=6, K=4, schedule=sched)
    want = [sched.topology_at(t).fastmix_rate(4) for t in range(6)]
    np.testing.assert_allclose(np.asarray(res.trace.contraction_rate), want,
                               rtol=1e-5)
    # static runs carry the constant per-iteration rate too
    res_s = deepca(ops, base, W0, k=2, T=6, K=4)
    np.testing.assert_allclose(np.asarray(res_s.trace.contraction_rate),
                               np.full(6, base.fastmix_rate(4)), rtol=1e-5)
    # depca exposes it as well
    res_d = depca(ops, base, W0, k=2, T=4, K=3)
    np.testing.assert_allclose(np.asarray(res_d.trace.contraction_rate),
                               np.full(4, base.fastmix_rate(3)), rtol=1e-5)


def test_depca_accepts_schedule():
    ops = synthetic_spiked(8, 12, 2, n_per_agent=24, seed=0)
    U, _ = top_k_eigvecs(ops.mean_matrix(), 2)
    rng = np.random.default_rng(2)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((12, 2)))[0],
                     jnp.float32)
    topo = erdos_renyi(8, p=0.6, seed=1)
    r_dyn = depca(ops, None, W0, k=2, T=8, K=4, U=U,
                  schedule=TopologySchedule.constant(topo), backend="stacked")
    r_static = depca(ops, topo, W0, k=2, T=8, K=4, U=U, backend="stacked")
    np.testing.assert_allclose(np.asarray(r_dyn.W), np.asarray(r_static.W),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------------------- resume round accounting
def test_split_run_trace_equals_single_run():
    """Regression: resumed runs must continue (not restart) comm_rounds."""
    ops = synthetic_spiked(10, 20, 3, n_per_agent=32, seed=0)
    U, _ = top_k_eigvecs(ops.mean_matrix(), 3)
    rng = np.random.default_rng(1)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((20, 3)))[0],
                     jnp.float32)
    topo = erdos_renyi(10, p=0.5, seed=2)
    full = deepca(ops, topo, W0, k=3, T=10, K=5, U=U, backend="stacked")
    a = deepca(ops, topo, W0, k=3, T=4, K=5, U=U, backend="stacked")
    b = deepca(ops, topo, W0, k=3, T=6, K=5, U=U, backend="stacked",
               state=a.state)
    rounds = np.concatenate([np.asarray(a.trace.comm_rounds),
                             np.asarray(b.trace.comm_rounds)])
    np.testing.assert_array_equal(rounds, np.asarray(full.trace.comm_rounds))
    tan = np.concatenate([np.asarray(a.trace.mean_tan_theta),
                          np.asarray(b.trace.mean_tan_theta)])
    np.testing.assert_allclose(tan, np.asarray(full.trace.mean_tan_theta),
                               rtol=1e-4, atol=1e-6)
    # legacy 3-tuple states still resume (with a zero offset)
    legacy = deepca(ops, topo, W0, k=3, T=6, K=5, U=U, backend="stacked",
                    state=a.state[:3])
    np.testing.assert_allclose(np.asarray(legacy.W), np.asarray(b.W),
                               rtol=1e-5, atol=1e-6)


def test_resumed_schedule_continues_at_global_step():
    """A resumed run indexes the schedule by GLOBAL iteration, not 0."""
    ops = synthetic_spiked(8, 12, 2, n_per_agent=24, seed=0)
    rng = np.random.default_rng(0)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((12, 2)))[0],
                     jnp.float32)
    sched = TopologySchedule.periodic_rewiring(8, p=0.6, seed=0, period=1)
    full = deepca(ops, None, W0, k=2, T=8, K=4, schedule=sched,
                  backend="stacked")
    a = deepca(ops, None, W0, k=2, T=3, K=4, schedule=sched,
               backend="stacked")
    b = deepca(ops, None, W0, k=2, T=5, K=4, schedule=sched,
               backend="stacked", state=a.state)
    np.testing.assert_allclose(np.asarray(b.W), np.asarray(full.W),
                               rtol=1e-5, atol=1e-6)


# -------------------------------------------------- fault-degraded e2e
def test_kill_agents_restarts_tracker_on_survivors():
    ops = synthetic_spiked(8, 12, 2, n_per_agent=16, seed=0)
    rng = np.random.default_rng(0)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((12, 2)))[0],
                     jnp.float32)
    res = deepca(ops, erdos_renyi(8, p=0.6, seed=0), W0, k=2, T=5, K=4)
    ops2, state2 = kill_agents(ops, res.state, [1, 6])
    assert ops2.m == 6 and state2[0].shape[0] == 6
    # Lemma 2 invariant restored exactly on the survivor population
    S, _, G_prev = state2[0], state2[1], state2[2]
    np.testing.assert_allclose(np.mean(np.asarray(S), axis=0),
                               np.mean(np.asarray(G_prev), axis=0),
                               atol=1e-6)


@pytest.mark.slow
def test_degraded_midrun_deepca_reaches_high_precision(tmp_path):
    """Acceptance: 2 dead agents on er(16) mid-run; tan_theta < 1e-6."""
    jax.config.update("jax_enable_x64", True)
    try:
        ops32 = synthetic_spiked(16, 24, 3, n_per_agent=48, seed=0)
        ops = StackedOperators(
            data=jnp.asarray(np.asarray(ops32.data), jnp.float64))
        rng = np.random.default_rng(1)
        W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((24, 3)))[0],
                         jnp.float64)
        topo = erdos_renyi(16, p=0.5, seed=3)
        out = deepca_with_failures(
            ops, topo, W0, k=3, T=120, K=8,
            failures=[AgentFailure(at_iter=40, dead=[2, 11])],
            backend="stacked", ckpt_dir=str(tmp_path / "ck"))
        res = out["result"]
        assert out["survivors"] == 14
        assert out["topology"].m == 14
        final = float(res.trace.mean_tan_theta[-1])
        assert final < 1e-6, f"degraded run stalled at tan={final}"
        # round accounting is continuous across the failure boundary
        np.testing.assert_array_equal(
            np.asarray(res.trace.comm_rounds),
            np.arange(41, 121, dtype=np.float32) * 8.0)
        # checkpoints were written at segment boundaries
        assert any(n.startswith("step_") for n in os.listdir(tmp_path / "ck"))
    finally:
        jax.config.update("jax_enable_x64", False)


# ------------------------------------------------- shard_map leg (slow)
_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (ConsensusEngine, DynamicConsensusEngine,
                            DistributedDeEPCA, StackedOperators,
                            TopologySchedule, deepca, erdos_renyi, ring,
                            synthetic_spiked, top_k_eigvecs)

    mesh = Mesh(np.asarray(jax.devices()), ("agents",))
    rng = np.random.default_rng(0)
    base = ring(8)
    sched = TopologySchedule.edge_dropout(base, 0.25, seed=7)

    # identical schedule: stacked and shard_map agree per step (acceptance)
    S = jnp.asarray(rng.standard_normal((8, 24, 3)), jnp.float32)
    dyn_ref = DynamicConsensusEngine(schedule=sched, K=6, backend="stacked")
    dyn_shm = DynamicConsensusEngine(schedule=sched, K=6,
                                     backend="shard_map", mesh=mesh)
    Ls, etas = dyn_ref.operands(0, 6)
    for t in range(6):
        ref = dyn_ref.engine_at(0).mix(S, mixing=(Ls[t], etas[t]))
        got_tr = dyn_shm.engine_at(0).mix(S, mixing=(Ls[t], etas[t]))
        got_ea = dyn_shm.engine_at(t).mix(S)
        e1 = float(jnp.max(jnp.abs(got_tr - ref)))
        e2 = float(jnp.max(jnp.abs(got_ea - ref)))
        assert e1 < 2e-4 and e2 < 2e-4, (t, e1, e2)
    print("OK schedule parity")

    # DistributedDeEPCA survives the mid-run topology swaps and matches the
    # stacked simulator fed the same schedule
    m, d, k = 8, 24, 3
    ops = synthetic_spiked(m, d, k, n_per_agent=32, seed=0)
    dense = jnp.einsum("mnd,mne->mde", ops.data, ops.data)
    ops_dense = StackedOperators(dense=dense)
    U, _ = top_k_eigvecs(ops_dense.mean_matrix(), k)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    ref = deepca(ops_dense, None, W0, k=k, T=12, K=6, U=U,
                 backend="stacked", schedule=sched)
    dd = DistributedDeEPCA(mesh, base, k=k, K=6, T=12)
    W, Sd = dd.run(dense, W0, schedule=sched)
    err = float(jnp.max(jnp.abs(W - ref.W)))
    assert err < 2e-3, err
    # intact-ring steps kept the structured lowering; degraded ones shared
    # ONE dense compiled step (the no-retrace contract)
    keys = sorted(k_[0] for k_ in dd._step_cache)
    assert "dense" in keys and "structured" in keys, keys
    print("OK distributed swap", err)
    print("ALLOK")
""")


@pytest.mark.slow
def test_time_varying_parity_with_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ALLOK" in out.stdout
