"""PowerStep/IterationDriver: driver-vs-legacy parity on every substrate.

The refactor's contract: the single PowerStep body run by the driver is
*bit-identical* to the pre-refactor loop bodies it replaced.  Each parity
test inlines the legacy iteration verbatim (frozen from the pre-refactor
``algorithms.py`` / ``gossip_shard.py``) and compares exactly — plus
batched-vs-loop parity for ``run_batch``, resume equivalence for both
algorithms, and fused-tracking-kernel tolerance.
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
                        IterationDriver, PowerStep, TopologySchedule, deepca,
                        depca, erdos_renyi, sign_adjust, synthetic_spiked,
                        top_k_eigvecs)
from repro.core.operators import gram_form_pays

jax.config.update("jax_enable_x64", False)


def _setup(m=8, d=16, k=2, seed=0):
    ops = synthetic_spiked(m, d, k, n_per_agent=24, seed=seed)
    U, _ = top_k_eigvecs(ops.mean_matrix(), k)
    rng = np.random.default_rng(seed + 3)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    return ops, U, W0


def _qr(S):
    # The legacy bodies below are frozen *wiring* (tracking arithmetic, mix
    # placement, sign adjust, resume/round accounting); orthonormalization
    # itself is a shared compute site that PR 5 swapped to CholeskyQR2
    # repo-wide, so the bit-parity contract is "legacy wiring + the shared
    # qr_orth" — using the site keeps these tests pinning exactly the
    # driver refactor, not the (intentionally changed) QR implementation.
    from repro.core.step import qr_orth
    return qr_orth(S)


def _apply(ops, T):
    # The apply is such a site too: a launch of T iterations evaluates
    # A_j W_j in the order gram_form_pays picks for its shapes, so the
    # legacy bodies apply the operator in that same form.
    n, d = ops.data.shape[1:]
    return (ops.with_gram() if gram_form_pays(n, d, T) else ops).apply


# ------------------------------------------------- substrate 1: static scan
def test_driver_matches_legacy_static_scan():
    ops, U, W0 = _setup()
    topo = erdos_renyi(8, p=0.6, seed=2)
    T, K = 12, 5
    eng = ConsensusEngine.for_algorithm("deepca", topo, K=K,
                                        backend="stacked")

    # legacy deepca scan body, verbatim
    W = jnp.broadcast_to(W0, (8,) + W0.shape).astype(jnp.float32)
    mix = eng.mix
    apply = _apply(ops, T)

    def legacy_step(carry, _):
        S, W, G_prev = carry
        G = apply(W)
        S_new = S + G - G_prev
        S_new = mix(S_new)
        W_new = sign_adjust(_qr(S_new), W0)
        return (S_new, W_new, G), (S_new, W_new)

    (S, Wl, Gp), (S_hist, W_hist) = jax.lax.scan(
        legacy_step, (W, W, W), None, length=T)

    res = deepca(ops, topo, W0, k=2, T=T, K=K, U=U, backend="stacked")
    np.testing.assert_array_equal(np.asarray(res.W), np.asarray(Wl))
    np.testing.assert_array_equal(np.asarray(res.state[0]), np.asarray(S))
    np.testing.assert_array_equal(np.asarray(res.state[2]), np.asarray(Gp))

    # legacy depca scan body, verbatim
    def legacy_depca_step(W_stack, _):
        G = apply(W_stack)
        G = eng.mix(G, rounds=K)
        W_new = sign_adjust(_qr(G), W0)
        return W_new, (G, W_new)

    Wd, _ = jax.lax.scan(legacy_depca_step, W, None, length=T)
    res_d = depca(ops, topo, W0, k=2, T=T, K=K, U=U, backend="stacked")
    np.testing.assert_array_equal(np.asarray(res_d.W), np.asarray(Wd))


# ------------------------------------------- substrate 2: traced-operand scan
def test_driver_matches_legacy_traced_scan():
    ops, U, W0 = _setup()
    sched = TopologySchedule.edge_dropout(erdos_renyi(8, p=0.6, seed=1),
                                          0.25, seed=4)
    T, K = 10, 5
    dyn = DynamicConsensusEngine.for_algorithm("deepca", sched, K=K,
                                               backend="stacked")
    Ls, etas = dyn.operands(0, T, dtype=jnp.float32)
    W = jnp.broadcast_to(W0, (8,) + W0.shape).astype(jnp.float32)
    apply = _apply(ops, T)

    def legacy_step(carry, xs):
        L_t, eta_t = xs
        S, W, G_prev = carry
        G = apply(W)
        S_new = S + G - G_prev
        S_new = dyn.mix_traced(S_new, L_t, eta_t)
        W_new = sign_adjust(_qr(S_new), W0)
        return (S_new, W_new, G), (S_new, W_new)

    (_, Wl, _), _ = jax.lax.scan(legacy_step, (W, W, W), (Ls, etas),
                                 length=T)
    res = deepca(ops, None, W0, k=2, T=T, K=K, U=U, backend="stacked",
                 schedule=sched)
    np.testing.assert_array_equal(np.asarray(res.W), np.asarray(Wl))


# -------------------------------------- substrate 3: unrolled (rounds vary)
def test_driver_matches_legacy_unrolled():
    ops, U, W0 = _setup()
    topo = erdos_renyi(8, p=0.6, seed=2)
    T, K = 6, 3
    eng = ConsensusEngine.for_algorithm("depca", topo, K=K,
                                        backend="stacked")

    # legacy increasing-consensus loop, verbatim
    W_stack = jnp.broadcast_to(W0, (8,) + W0.shape).astype(jnp.float32)
    apply = _apply(ops, T)
    for t in range(T):
        G = apply(W_stack)
        G = eng.mix(G, rounds=K + t)
        W_stack = sign_adjust(_qr(G), W0)
    res = depca(ops, topo, W0, k=2, T=T, K=K, U=U, backend="stacked",
                increasing_consensus=True)
    np.testing.assert_array_equal(np.asarray(res.W), np.asarray(W_stack))
    np.testing.assert_array_equal(
        np.asarray(res.trace.comm_rounds),
        np.cumsum([K + t for t in range(T)]).astype(np.float32))

    # legacy dynamic depca loop (unrolled, traced matrices), verbatim
    sched = TopologySchedule.periodic_rewiring(8, p=0.6, seed=0, period=2)
    dyn = DynamicConsensusEngine.for_algorithm("depca", sched, K=K,
                                               backend="stacked")
    W_stack = jnp.broadcast_to(W0, (8,) + W0.shape).astype(jnp.float32)
    apply = _apply(ops, T)
    for t in range(T):
        G = apply(W_stack)
        topo_t = dyn.topology_at(t)
        G = dyn.mix_traced(G, jnp.asarray(topo_t.mixing, jnp.float32),
                           dyn.eta_of(topo_t), rounds=K)
        W_stack = sign_adjust(_qr(G), W0)
    res_d = depca(ops, None, W0, k=2, T=T, K=K, U=U, backend="stacked",
                  schedule=sched)
    np.testing.assert_array_equal(np.asarray(res_d.W), np.asarray(W_stack))


# --------------------------------------------------- substrate 4: shard_map
_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import (ConsensusEngine, DistributedDeEPCA, ring,
                            erdos_renyi, sign_adjust, synthetic_spiked)
    from jax import shard_map

    m, d, k, T, K = 8, 24, 3, 10, 5
    ops = synthetic_spiked(m, d, k, n_per_agent=32, seed=0)
    dense = jnp.einsum("mnd,mne->mde", ops.data, ops.data)
    rng = np.random.default_rng(1)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    mesh = Mesh(np.array(jax.devices()).reshape(m), ("agents",))

    for topo in (ring(m), erdos_renyi(m, p=0.6, seed=4)):
        engine = ConsensusEngine.for_algorithm(
            "deepca", topo, K=K, backend="shard_map", mesh=mesh,
            axis="agents")

        # legacy structured shard_map step, verbatim (pre-refactor body)
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("agents"),) * 4 + (P(),),
            out_specs=(P("agents"),) * 3, check_vma=False)
        def _legacy(A, S, W, G_prev, W0):
            G = jnp.einsum("mde,mek->mdk", A, W)
            S_new = S + G - G_prev
            S_new = engine.local_mix(S_new, axis="agents")
            q, _ = jnp.linalg.qr(S_new[0])
            W_new = sign_adjust(q, W0)[None]
            return S_new, W_new, G

        legacy = jax.jit(_legacy)
        shard = NamedSharding(mesh, P("agents"))
        rep = NamedSharding(mesh, P())
        W = jax.device_put(jnp.broadcast_to(W0, (m, d, k)), shard)
        S = W; G_prev = W
        W0r = jax.device_put(W0, rep)
        A = jax.device_put(dense, shard)
        for _ in range(T):
            S, W, G_prev = legacy(A, S, W, G_prev, W0r)

        dd = DistributedDeEPCA(mesh, topo, k=k, K=K, T=T)
        Wd, Sd = dd.run(dense, W0)
        err = float(jnp.max(jnp.abs(Wd - W)))
        # the driver body applies the operator with Precision.HIGHEST (the
        # stacked simulator's setting); on CPU this is the same arithmetic
        assert err < 1e-6, (topo.name, err)
        print("OK", topo.name, err)
    print("ALLOK")
""")


@pytest.mark.slow
def test_driver_matches_legacy_shard_map():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ALLOK" in out.stdout


# ----------------------------------------------------------- batched serving
def test_run_batch_matches_python_loop():
    B, m, d, k, T, K = 4, 8, 16, 2, 8, 4
    topo = erdos_renyi(m, p=0.6, seed=2)
    problems = [synthetic_spiked(m, d, k, n_per_agent=24, seed=s)
                for s in range(B)]
    rng = np.random.default_rng(0)
    W0 = jnp.stack([
        jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                    jnp.float32) for _ in range(B)])
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", K),
        engine=ConsensusEngine.for_algorithm("deepca", topo, K=K,
                                             backend="stacked"))
    out = driver.run_batch(problems, W0, T=T, with_history=True)
    assert out.W.shape == (B, m, d, k)
    assert out.W_hist.shape == (B, T, m, d, k)
    for b in range(B):
        ref = driver.run(problems[b], W0[b], T=T)
        np.testing.assert_array_equal(np.asarray(out.W[b]),
                                      np.asarray(ref.carry[1]))
        np.testing.assert_array_equal(np.asarray(out.S[b]),
                                      np.asarray(ref.carry[0]))
        np.testing.assert_array_equal(np.asarray(out.W_hist[b]),
                                      np.asarray(ref.W_hist))

    # dynamic schedules with per-problem offsets
    sched = TopologySchedule.periodic_rewiring(m, p=0.6, seed=0, period=2)
    dyn = DynamicConsensusEngine.for_algorithm("deepca", sched, K=K,
                                               backend="stacked")
    driver_d = IterationDriver(step=PowerStep.for_algorithm("deepca", K),
                               dynamic=dyn)
    offs = [0, 1, 2, 3]
    out_d = driver_d.run_batch(problems, W0, T=T, t0=offs)
    for b in range(B):
        ref = driver_d.run(problems[b], W0[b], T=T, t0=offs[b])
        np.testing.assert_array_equal(np.asarray(out_d.W[b]),
                                      np.asarray(ref.carry[1]))


@pytest.mark.parametrize("substrate", ["scan", "run_batch"])
def test_lower_last_launch_is_the_launched_program(substrate):
    """The lowering ``lower_last_launch`` returns, compiled and called on
    the launch's own arguments, reproduces the launch bit for bit."""
    ops, _, W0 = _setup()
    topo = erdos_renyi(8, p=0.6, seed=2)
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", 4),
        engine=ConsensusEngine.for_algorithm("deepca", topo, K=4,
                                             backend="stacked"))
    with pytest.raises(RuntimeError, match="no compiled program"):
        driver.lower_last_launch()
    if substrate == "scan":
        W = driver.run(ops, W0, T=5).carry[1]
        args = (ops.array, W0, driver.step.init_carry(ops, W0))
    else:
        W = driver.run_batch([ops], W0[None], T=5).W
        args = (ops.array[None], W0[None])
    carry = driver.lower_last_launch().compile()(*args)[0]
    np.testing.assert_array_equal(np.asarray(carry[1]), np.asarray(W))


def test_run_batch_validation():
    _, _, W0 = _setup()
    topo = erdos_renyi(8, p=0.6, seed=2)
    driver = IterationDriver(
        step=PowerStep.for_algorithm("depca", 4, increasing_consensus=True),
        engine=ConsensusEngine.for_algorithm("depca", topo, K=4,
                                             backend="stacked"))
    with pytest.raises(ValueError, match="increasing"):
        driver.run_batch([synthetic_spiked(8, 16, 2, seed=0)], W0, T=4)
    with pytest.raises(ValueError):
        IterationDriver(step=PowerStep.for_algorithm("deepca", 4))


# ------------------------------------------------------- resume equivalence
@pytest.mark.parametrize("algorithm", ["deepca", "depca"])
def test_resume_equivalence(algorithm):
    """T iterations == T/2 + resume T/2: identical trace and iterates."""
    fn = deepca if algorithm == "deepca" else depca
    ops, U, W0 = _setup(m=8, d=20, k=3, seed=1)
    topo = erdos_renyi(8, p=0.5, seed=2)
    T, K = 10, 5
    full = fn(ops, topo, W0, k=3, T=T, K=K, U=U, backend="stacked")
    a = fn(ops, topo, W0, k=3, T=T // 2, K=K, U=U, backend="stacked")
    b = fn(ops, topo, W0, k=3, T=T - T // 2, K=K, U=U, backend="stacked",
           state=a.state)
    np.testing.assert_array_equal(np.asarray(b.W), np.asarray(full.W))
    rounds = np.concatenate([np.asarray(a.trace.comm_rounds),
                             np.asarray(b.trace.comm_rounds)])
    np.testing.assert_array_equal(rounds, np.asarray(full.trace.comm_rounds))
    tan = np.concatenate([np.asarray(a.trace.mean_tan_theta),
                          np.asarray(b.trace.mean_tan_theta)])
    np.testing.assert_allclose(tan, np.asarray(full.trace.mean_tan_theta),
                               rtol=1e-5, atol=1e-7)


def test_resume_continues_increasing_rounds_and_schedule():
    """depca resume indexes the round schedule by GLOBAL iteration."""
    ops, U, W0 = _setup(m=8, d=16, k=2, seed=0)
    topo = erdos_renyi(8, p=0.6, seed=1)
    full = depca(ops, topo, W0, k=2, T=8, K=3, U=U, backend="stacked",
                 increasing_consensus=True)
    a = depca(ops, topo, W0, k=2, T=3, K=3, U=U, backend="stacked",
              increasing_consensus=True)
    b = depca(ops, topo, W0, k=2, T=5, K=3, U=U, backend="stacked",
              increasing_consensus=True, state=a.state)
    np.testing.assert_array_equal(np.asarray(b.W), np.asarray(full.W))
    rounds = np.concatenate([np.asarray(a.trace.comm_rounds),
                             np.asarray(b.trace.comm_rounds)])
    np.testing.assert_array_equal(rounds, np.asarray(full.trace.comm_rounds))

    # dynamic depca resume continues schedule indexing at the global step
    sched = TopologySchedule.periodic_rewiring(8, p=0.6, seed=0, period=1)
    full_s = depca(ops, None, W0, k=2, T=8, K=4, schedule=sched,
                   backend="stacked")
    a_s = depca(ops, None, W0, k=2, T=3, K=4, schedule=sched,
                backend="stacked")
    b_s = depca(ops, None, W0, k=2, T=5, K=4, schedule=sched,
                backend="stacked", state=a_s.state)
    np.testing.assert_array_equal(np.asarray(b_s.W), np.asarray(full_s.W))


# ------------------------------------------------ fused tracking kernel path
def test_fused_tracking_matches_unfused_reference():
    """mix_track on the pallas backend == track-then-mix stacked, f32 tol."""
    topo = erdos_renyi(12, p=0.5, seed=3)
    rng = np.random.default_rng(0)
    S, G, Gp = (jnp.asarray(rng.standard_normal((12, 24, 4)), jnp.float32)
                for _ in range(3))
    ref = ConsensusEngine(topo, K=6, backend="stacked").mix_track(S, G, Gp)
    kern = ConsensusEngine(topo, K=6, backend="pallas",
                           interpret=True).mix_track(S, G, Gp)
    poly = ConsensusEngine(topo, K=6, backend="pallas").mix_track(S, G, Gp)
    scale = float(jnp.max(jnp.abs(ref))) + 1.0
    np.testing.assert_allclose(np.asarray(kern), np.asarray(ref),
                               rtol=2e-5, atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(poly), np.asarray(ref),
                               rtol=2e-5, atol=2e-5 * scale)

    # the poly fallback is bit-for-bit the unfused composition
    from repro.kernels.fastmix import (fastmix_poly, fastmix_track_poly,
                                       tracking_update)
    L32 = jnp.asarray(topo.mixing, jnp.float32)
    from repro.core import fastmix_eta
    eta = fastmix_eta(topo.lambda2)
    fused = fastmix_track_poly(S, G, Gp, L32, eta, 6)
    unfused = fastmix_poly(tracking_update(S, G, Gp), L32, eta, 6)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))

    # the tracked mean rides through gossip untouched (Prop. 1 invariant)
    want_mean = np.mean(np.asarray(S + G - Gp), axis=0)
    for out in (ref, kern, poly):
        np.testing.assert_allclose(np.mean(np.asarray(out), axis=0),
                                   want_mean, atol=1e-4)


def test_deepca_pallas_backend_uses_fused_tracking_end_to_end():
    """deepca(backend='pallas') == deepca(backend='stacked') to fp32 tol."""
    ops, U, W0 = _setup()
    topo = erdos_renyi(8, p=0.6, seed=2)
    r_ref = deepca(ops, topo, W0, k=2, T=15, K=5, U=U, backend="stacked")
    r_fused = deepca(ops, topo, W0, k=2, T=15, K=5, U=U, backend="pallas")
    np.testing.assert_allclose(np.asarray(r_fused.W), np.asarray(r_ref.W),
                               rtol=2e-3, atol=2e-3)
