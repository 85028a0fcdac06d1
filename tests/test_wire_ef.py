"""Quantized-wire + error-feedback contract (PR-8).

Covers the wire quantizers' edge cases (int8 saturation/scale floor, fp8
no-inf saturation, zeros/subnormals, f64 parity), the CHOCO-style
difference-send (`ef_quantize`) convergence property, the engines' EF
calling convention (`ef=` required/rejected, tuple returns, fused-path
refusals), the accelerated/EF carry-slot contract, and the end-to-end
claim the bench rows quantify: an EF-quantized int8 wire tracks the fp32
envelope where a plain bf16 wire floors.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (ConsensusEngine, DynamicConsensusEngine, PowerStep,
                        TopologySchedule, deepca, erdos_renyi, rebase_carry,
                        synthetic_spiked, top_k_eigvecs)
from repro.core.step import split_state
from repro.kernels.fastmix import (EF_WIRE_DTYPES, WIRE_ITEMSIZE,
                                   ef_quantize, quantize_wire)

jax.config.update("jax_enable_x64", False)

FP8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)          # 448
FP8_MIN_SUBNORMAL = 2.0 ** -9


def _problem(m=8, d=16, k=2, seed=0):
    ops = synthetic_spiked(m, d, k, n_per_agent=24, seed=seed)
    U, _ = top_k_eigvecs(ops.mean_matrix(), k)
    rng = np.random.default_rng(seed + 3)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    return ops, U, W0


# ------------------------------------------------------ quantizer edge cases
def test_int8_quantize_saturates_and_inverts():
    """Symmetric per-agent scale: absmax maps to +-127 exactly, everything
    round-trips within half a step of the dynamic scale."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 32)) * 10.0, jnp.float32)
    q = quantize_wire(x, "int8")
    absmax = np.max(np.abs(np.asarray(x)), axis=1, keepdims=True)
    step = absmax / 127.0
    assert np.all(np.abs(np.asarray(q - x)) < step * 0.5 + 1e-7)
    # the per-agent extremum is reproduced exactly (hits the +-127 code)
    hit = np.max(np.abs(np.asarray(q)), axis=1, keepdims=True)
    np.testing.assert_allclose(hit, absmax, rtol=1e-6)


def test_int8_quantize_zero_and_subnormal_are_finite():
    """The scale floor at finfo.tiny keeps all-zero (and subnormal-scale)
    agents exact and NaN-free instead of dividing by zero."""
    x = jnp.zeros((3, 8), jnp.float32)
    np.testing.assert_array_equal(np.asarray(quantize_wire(x, "int8")), 0.0)
    tiny = jnp.full((2, 8), 1e-40, jnp.float32)     # subnormal in f32
    q = quantize_wire(tiny, "int8")
    assert np.all(np.isfinite(np.asarray(q)))


def test_int8_quantize_f64_parity():
    """The f64 path quantizes through the same 255-level grid: the f32 and
    f64 round-trips of the same values agree to f32 round-off."""
    rng = np.random.default_rng(1)
    vals = rng.standard_normal((4, 16))
    with jax.enable_x64(True):
        x64 = jnp.asarray(vals, jnp.float64)
        q64 = quantize_wire(x64, "int8")
        assert q64.dtype == jnp.float64
        q64 = np.asarray(q64)
    q32 = quantize_wire(jnp.asarray(vals, jnp.float32), "int8")
    assert q32.dtype == jnp.float32
    np.testing.assert_allclose(q64, np.asarray(q32), rtol=1e-5, atol=1e-6)


def test_fp8_quantize_saturates_no_nan():
    """e4m3fn has no inf: out-of-range values saturate at +-448 instead of
    round-tripping to NaN."""
    x = jnp.asarray([[-1e9, -448.0, -1.0, 0.0, 1.0, 448.0, 1e9]],
                    jnp.float32)
    q = np.asarray(quantize_wire(x, "fp8"))
    assert np.all(np.isfinite(q))
    np.testing.assert_array_equal(q[0, [0, -1]], [-FP8_MAX, FP8_MAX])
    np.testing.assert_array_equal(q[0, 3], 0.0)


def test_ef_quantize_replica_tracks_fixed_point():
    """Repeated difference-sends of a fixed iterate drive the replica to
    it geometrically — the EF property that kills the quantization floor."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((4, 24)), jnp.float32)
    for wire in EF_WIRE_DTYPES:
        h = jnp.zeros_like(x)
        errs = []
        for _ in range(12):
            h = ef_quantize(x, h, wire)
            errs.append(float(jnp.max(jnp.abs(x - h))))
        assert errs[-1] < 1e-3 * errs[0], (wire, errs)


def test_fp8_companding_transmits_sub_subnormal_innovations():
    """The cube-root companded fp8 send represents innovations far below
    e4m3fn's smallest subnormal (2^-9) — the un-companded wire would
    round these to zero and the replica would stop tracking."""
    delta = jnp.full((2, 8), FP8_MIN_SUBNORMAL / 100.0, jnp.float32)
    h = ef_quantize(delta, jnp.zeros_like(delta), "fp8")
    got = np.asarray(h)
    assert np.all(got > 0.0)
    np.testing.assert_allclose(got, np.asarray(delta), rtol=0.25)


# ------------------------------------------------------ engine EF contract
def test_engine_requires_and_rejects_ef():
    topo = erdos_renyi(6, p=0.8, seed=0)
    S = jnp.asarray(np.random.default_rng(0).standard_normal((6, 12, 2)),
                    jnp.float32)
    for wire in EF_WIRE_DTYPES:
        eng = ConsensusEngine(topo, K=3, backend="stacked", wire_dtype=wire)
        assert eng.ef_wire
        with pytest.raises(ValueError, match="error-feedback"):
            eng.mix(S)                          # dropped residual
        out, ef_out = eng.mix(S, ef=jnp.zeros_like(S))
        assert out.shape == S.shape and ef_out.shape == S.shape
    plain = ConsensusEngine(topo, K=3, backend="stacked")
    with pytest.raises(ValueError, match="EF wire modes"):
        plain.mix(S, ef=jnp.zeros_like(S))      # spurious residual


def test_engine_ef_mean_preserved():
    """The CHOCO combine `cur + (L - I) h` keeps the agent mean exact:
    quantization noise cannot bias the tracked mean (Lemma 2)."""
    topo = erdos_renyi(8, p=0.7, seed=1)
    rng = np.random.default_rng(3)
    S = jnp.asarray(rng.standard_normal((8, 20, 3)), jnp.float32)
    for wire in EF_WIRE_DTYPES:
        eng = ConsensusEngine(topo, K=5, backend="stacked", wire_dtype=wire)
        out, _ = eng.mix(S, ef=jnp.zeros_like(S))
        np.testing.assert_allclose(np.asarray(jnp.mean(out, axis=0)),
                                   np.asarray(jnp.mean(S, axis=0)),
                                   rtol=2e-4, atol=2e-5)


def test_ef_modes_refuse_apply_mix_fusion_and_shard_map():
    """Quantization is nonlinear: no P_K(L) collapse exists, so the fused
    apply+mix path must refuse EF modes rather than silently skip the
    wire; shard_map gossips at native precision and rejects wire modes."""
    topo = erdos_renyi(6, p=0.8, seed=0)
    eng = ConsensusEngine(topo, K=3, backend="stacked", wire_dtype="int8")
    S = jnp.zeros((6, 12, 2), jnp.float32)
    with pytest.raises(ValueError, match="apply_mix_track"):
        eng.apply_mix_track(S, S, S, lambda W: W)
    dyn = DynamicConsensusEngine(
        schedule=TopologySchedule.constant(topo), K=3, wire_dtype="fp8")
    with pytest.raises(ValueError, match="apply_mix_track"):
        dyn.engine_at(0).apply_mix_track(
            S, S, S, lambda W: W, mixing=(jnp.asarray(topo.mixing), 0.3))
    for wire in EF_WIRE_DTYPES:
        with pytest.raises(ValueError, match="shard_map"):
            ConsensusEngine(topo, K=3, backend="shard_map", wire_dtype=wire)


def test_pallas_backend_ef_matches_stacked_reference():
    """int8 has no in-kernel mirror (its per-agent scale is a cross-tile
    reduction): the pallas engine must fall through to the per-round
    reference bit-exactly.  fp8's interpret-mode kernel mirror agrees to
    fp32 round-off."""
    topo = erdos_renyi(8, p=0.7, seed=2)
    rng = np.random.default_rng(4)
    S = jnp.asarray(rng.standard_normal((8, 40, 4)), jnp.float32)
    ef0 = jnp.zeros_like(S)
    for wire, exact in (("int8", True), ("fp8", False)):
        ref, ref_ef = ConsensusEngine(
            topo, K=5, backend="stacked", wire_dtype=wire).mix(S, ef=ef0)
        out, out_ef = ConsensusEngine(
            topo, K=5, backend="pallas", interpret=True,
            wire_dtype=wire).mix(S, ef=ef0)
        tol = dict(rtol=0, atol=0) if exact else dict(rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol)
        np.testing.assert_allclose(np.asarray(out_ef), np.asarray(ref_ef),
                                   **tol)


def test_bytes_per_round_accounting():
    """Payload bytes are a deterministic function of (wire mode, d, k):
    4/2/1/1 B per element, +4 B per-agent scale for int8 only."""
    topo = erdos_renyi(4, p=0.9, seed=0)
    d, k = 10, 3
    want = {None: 120, "bf16": 60, "int8": 34, "fp8": 30}
    for wire, expect in want.items():
        eng = ConsensusEngine(topo, K=2, backend="stacked", wire_dtype=wire)
        assert eng.bytes_per_round(d, k) == expect, wire
    assert set(WIRE_ITEMSIZE) == {None, "bf16", "int8", "fp8"}


# ------------------------------------------------- carry-slot contract
def test_carry_slots_and_rebase_extras():
    ops, _, W0 = _problem()
    for accel, ef, n in ((False, False, 3), (True, False, 4),
                         (False, True, 4), (True, True, 5)):
        step = PowerStep.for_algorithm("deepca", 4, accelerated=accel,
                                       ef_wire=ef)
        assert step.carry_slots == n
        carry = rebase_carry(ops, jnp.broadcast_to(W0, (ops.m,) + W0.shape),
                             accelerated=accel, ef_wire=ef)
        assert len(carry) == n
        for extra in carry[3:]:     # momentum history / EF replica zeroed
            np.testing.assert_array_equal(np.asarray(extra), 0.0)
        inner, off = split_state(tuple(carry) + (jnp.zeros(2, jnp.int32),))
        assert len(inner) == n and off is not None


def test_accelerated_ef_deepca_state_roundtrip():
    """Accelerated + EF state rides the resumable-carry contract: T=8 in
    one call == 4+4 resumed, bitwise, with all 5 slots restored."""
    ops, U, W0 = _problem()
    topo = erdos_renyi(8, p=0.6, seed=2)
    kw = dict(k=2, K=4, U=U, backend="stacked", wire_dtype="int8",
              accelerated=True)
    full = deepca(ops, topo, W0, T=8, **kw)
    a = deepca(ops, topo, W0, T=4, **kw)
    b = deepca(ops, topo, W0, T=4, state=a.state, **kw)
    assert len(a.state) == 5 + 1        # 5 slots + trailing offset
    np.testing.assert_array_equal(np.asarray(full.W), np.asarray(b.W))
    for i in range(len(full.state)):
        np.testing.assert_array_equal(np.asarray(full.state[i]),
                                      np.asarray(b.state[i]))


def test_streaming_tick_bit_matches_resumed_accel_ef_run():
    """The PR-3 streaming contract extended to the PR-8 state: ticks with
    accelerated momentum + an int8-EF wire are bit-identical to the
    equivalent resumed deepca calls, 5-slot carry included."""
    import math
    from repro.core.step import mean_basis
    from repro.streaming import DriftPolicy, SlowRotationStream, \
        StreamingDeEPCA
    s = SlowRotationStream(m=6, d=16, k=3, n_per_agent=20, seed=0, rate=0.06)
    topo = erdos_renyi(6, p=0.6, seed=1)
    ops0, ops1 = s.ops_at(0), s.ops_at(1)
    U0, U1 = s.truth_at(0)[0], s.truth_at(1)[0]
    W0 = s.init_W0()
    passive = DriftPolicy(jump=math.inf, restart=math.inf, target=None,
                          max_escalations=0)
    tr = StreamingDeEPCA(k=3, T_tick=4, K=4, topology=topo,
                         backend="stacked", W0=W0, policy=passive,
                         accelerated=True, wire_dtype="int8")
    tr.tick(ops0, U0)
    tr.tick(ops1, U1)
    kw = dict(k=3, T=4, K=4, backend="stacked", accelerated=True,
              wire_dtype="int8")
    a = deepca(ops0, topo, W0, U=U0, **kw)
    # the second tick's Alg. 2 sign reference is the first tick's mean
    # basis (the tracker re-anchors it every tick)
    b = deepca(ops1, topo, mean_basis(a.state[1]), U=U1, state=a.state,
               **kw)
    np.testing.assert_array_equal(np.asarray(tr.W), np.asarray(b.W))
    assert len(tr.state) == len(b.state) == 5 + 1
    for i in range(len(b.state)):
        np.testing.assert_array_equal(np.asarray(tr.state[i]),
                                      np.asarray(b.state[i]))


# ---------------------------------------------- end-to-end accuracy claims
def test_ef_wire_breaks_bf16_floor():
    """On a spiked problem the plain bf16 wire floors orders of magnitude
    above fp32; the int8-EF wire (half bf16's bytes) tracks the fp32
    envelope."""
    ops, U, W0 = _problem(m=8, d=16, k=2, seed=0)
    topo = erdos_renyi(8, p=0.6, seed=2)
    kw = dict(k=2, T=25, K=6, U=U, backend="stacked")
    fp32 = float(deepca(ops, topo, W0, **kw).trace.mean_tan_theta[-1])
    bf16 = float(deepca(ops, topo, W0, wire_dtype="bf16",
                        **kw).trace.mean_tan_theta[-1])
    int8 = float(deepca(ops, topo, W0, wire_dtype="int8",
                        **kw).trace.mean_tan_theta[-1])
    assert bf16 > 30.0 * fp32           # the plain-quantization floor
    assert int8 < 10.0 * fp32 + 1e-6    # EF restores the fp32 envelope
    assert int8 < bf16 / 10.0


def test_accelerated_ef_converges_like_accelerated_fp32():
    ops, U, W0 = _problem(m=8, d=16, k=2, seed=1)
    topo = erdos_renyi(8, p=0.6, seed=3)
    kw = dict(k=2, T=25, K=6, U=U, backend="stacked", accelerated=True)
    fp32 = float(deepca(ops, topo, W0, **kw).trace.mean_tan_theta[-1])
    for wire in EF_WIRE_DTYPES:
        ef = float(deepca(ops, topo, W0, wire_dtype=wire,
                          **kw).trace.mean_tan_theta[-1])
        assert ef < 10.0 * fp32 + 1e-4, (wire, ef, fp32)
