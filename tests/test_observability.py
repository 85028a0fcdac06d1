"""The observability layer (PR 9): in-graph convergence diagnostics, span
tracing with Perfetto export, and the live health monitor.

The load-bearing guarantees:

* diagnostics OFF (the default) is bit-identical to the pre-diagnostics
  driver — measuring is opt-in and the off path pays zero;
* diagnostics ON measures real convergence: the max-over-agents consensus
  residual contracts on a healthy run, and the measured observables ride
  every substrate (scan / unrolled / vmap batch) identically;
* the health monitor names real pathologies from the live event stream —
  a plain-bf16 wire pinned at its quantization floor, a thrashing drift
  policy (restart storm), cold-launch churn — without false-flagging a
  healthy fp32 run;
* span tracing exports valid Chrome-trace-event JSON (Perfetto-loadable)
  and costs nothing when no tracer is installed.
"""
import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.runtime import config, telemetry, tracing
from repro.runtime.diagnostics import (DiagnosticsSpec, ESCALATE_RULES,
                                       HealthMonitor, HealthRules,
                                       current_monitor, diag_vector,
                                       install_health_monitor,
                                       resolve_diagnostics)


@pytest.fixture(autouse=True)
def _clean_observability_state():
    yield
    telemetry.set_sink(None)
    tracing.set_tracer(None)
    os.environ.pop(config.ENV_DIAG, None)
    os.environ.pop(config.ENV_TRACE, None)


def _driver(m=8, d=16, k=2, K=4, seed=0, wire=None, accelerated=False,
            diagnostics=None):
    from repro.core import (ConsensusEngine, IterationDriver, PowerStep,
                            erdos_renyi, synthetic_spiked)
    topo = erdos_renyi(m, p=0.6, seed=seed)
    ops = synthetic_spiked(m, d, k, n_per_agent=16, seed=seed)
    rng = np.random.default_rng(seed)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    engine = ConsensusEngine.for_algorithm("deepca", topo, K=K,
                                           backend="stacked",
                                           wire_dtype=wire)
    step = PowerStep.for_algorithm(
        "deepca", K, ef_wire=engine.ef_wire, accelerated=accelerated,
        momentum=0.25 if accelerated else 0.0)
    driver = IterationDriver(step=step, engine=engine,
                             diagnostics=diagnostics)
    return driver, ops, W0


# ============================================================ spec parsing
def test_spec_parse_vocabulary():
    for off in (None, False, "", "0", "off", "none", "NULL", "no"):
        assert DiagnosticsSpec.parse(off) is None
    for on in (True, "1", "on", "TRUE", "all"):
        assert DiagnosticsSpec.parse(on) == DiagnosticsSpec()
    spec = DiagnosticsSpec.parse("consensus, movement")
    assert spec == DiagnosticsSpec(consensus=True, movement=True,
                                   ef_residual=False, momentum=False)
    assert DiagnosticsSpec.parse(spec) is spec
    with pytest.raises(ValueError, match="unknown: wat"):
        DiagnosticsSpec.parse("consensus,wat")


def test_spec_names_gate_on_step_capabilities():
    spec = DiagnosticsSpec()
    plain, _, _ = _driver()
    full, _, _ = _driver(wire="int8", accelerated=True)
    assert spec.names(plain.step) == ("consensus", "movement")
    assert spec.names(full.step) == ("consensus", "movement",
                                     "ef_residual", "momentum")


def test_resolve_diagnostics_env_precedence(monkeypatch):
    assert resolve_diagnostics(None) is None          # no env, no request
    monkeypatch.setenv(config.ENV_DIAG, "consensus")
    assert resolve_diagnostics(None) == DiagnosticsSpec(
        consensus=True, movement=False, ef_residual=False, momentum=False)
    assert resolve_diagnostics(False) is None         # False beats env
    assert resolve_diagnostics("on") == DiagnosticsSpec()


def test_env_knobs_validate(monkeypatch):
    monkeypatch.setenv(config.ENV_DIAG, "bogus_observable")
    with pytest.raises(ValueError, match="REPRO_DIAG"):
        config.get_config()
    monkeypatch.setenv(config.ENV_DIAG, "consensus,momentum")
    monkeypatch.setenv(config.ENV_TRACE, "chrome:/tmp/t.json")
    cfg = config.get_config()
    assert cfg.diag == "consensus,momentum"
    assert cfg.trace == "chrome:/tmp/t.json"
    monkeypatch.setenv(config.ENV_TRACE, "chrome:")
    with pytest.raises(ValueError, match="REPRO_TRACE"):
        config.get_config()


# ========================================================== bit-identity
@pytest.mark.parametrize("wire,accelerated,substrate", [
    (None, False, "scan"),
    (None, False, "unrolled"),
    ("int8", True, "scan"),
])
def test_diag_off_is_bit_identical(wire, accelerated, substrate):
    """The diagnostics-off program is the pre-diagnostics program: same
    carry bits, same history bits.  Diag-on runs a *different* cached
    program whose primary outputs still match bit-for-bit (the measured
    reductions are read-only observers)."""
    base, ops, W0 = _driver(wire=wire, accelerated=accelerated)
    on = dataclasses.replace(base, diagnostics="on")
    r_off = base.run(ops, W0, T=5, substrate=substrate)
    r_on = on.run(ops, W0, T=5, substrate=substrate)
    assert r_off.diag is None and r_off.diag_names == ()
    assert r_on.diag is not None
    for a, b in zip(r_off.carry, r_on.carry):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(r_off.W_hist),
                                  np.asarray(r_on.W_hist))


def test_diag_matches_across_substrates():
    """scan and unrolled execute the same per-step measurement."""
    driver, ops, W0 = _driver(diagnostics="on")
    d_scan = np.asarray(driver.run(ops, W0, T=5, substrate="scan").diag)
    d_unrl = np.asarray(driver.run(ops, W0, T=5, substrate="unrolled").diag)
    np.testing.assert_allclose(d_scan, d_unrl, rtol=1e-6, atol=1e-7)


# ============================================== measured convergence
def test_healthy_run_consensus_residual_contracts():
    """The tentpole's measured claim: on a healthy fp32 run the
    max-over-agents consensus residual ``max_i ||S_i - mean S||_F``
    contracts by orders of magnitude, and movement decays with it."""
    driver, ops, W0 = _driver(diagnostics="on")
    run = driver.run(ops, W0, T=20)
    assert run.diag_names == ("consensus", "movement")
    diag = np.asarray(run.diag)
    consensus, movement = diag[:, 0], diag[:, 1]
    assert consensus[-1] < 1e-4 * consensus[0]
    assert movement[-1] < 1e-4 * movement[0]
    # and the tail keeps contracting (not merely small at the end)
    assert consensus[-1] < 0.9 * consensus[-5]


def test_ef_and_momentum_observables_measure_their_terms():
    driver, ops, W0 = _driver(wire="int8", accelerated=True,
                              diagnostics="on")
    run = driver.run(ops, W0, T=8)
    assert run.diag_names == ("consensus", "movement", "ef_residual",
                              "momentum")
    diag = np.asarray(run.diag)
    ef, mom = diag[:, 2], diag[:, 3]
    assert np.all(ef > 0)               # the int8 wire leaves a residual
    assert ef[-1] < 2 * ef[3]           # ...which stays bounded (EF works)
    assert mom[0] == 0.0                # W_prev starts zeroed
    # afterwards: beta * max_i ||W_prev_i||_F = 0.25 * sqrt(k) exactly
    np.testing.assert_allclose(mom[1:], 0.25 * math.sqrt(W0.shape[1]),
                               rtol=1e-5)


def test_diag_events_emitted_alongside_iterations():
    T = 4
    driver, ops, W0 = _driver(diagnostics="on")
    with telemetry.capture() as rec:
        driver.run(ops, W0, T=T)
    diags = rec.of("diag")
    assert len(diags) == T == len(rec.of("iteration"))
    assert [ev["t"] for ev in diags] == list(range(T))
    for ev in diags:
        assert ev["source"] == "driver.run" and ev["substrate"] == "scan"
        assert ev["floor"] == driver.quantization_floor()
        assert ev["consensus"] > 0 and ev["movement"] > 0
    run = driver.run(ops, W0, T=T)      # values match DriverRun.diag
    np.testing.assert_allclose(
        [ev["consensus"] for ev in diags],
        np.asarray(run.diag)[:, 0], rtol=1e-6)


def test_run_batch_diag_events_reduce_max_over_problems():
    from repro.core import synthetic_problem_batch
    B, m, d, k, T = 3, 8, 16, 2, 4
    driver, _, _ = _driver(m=m, d=d, k=k, diagnostics="on")
    problems, W0 = synthetic_problem_batch(B, m, d, k, n_per_agent=16,
                                           seed=0)
    with telemetry.capture() as rec:
        out = driver.run_batch(problems, W0, T=T)
    assert out.diag.shape == (B, T, 2)
    diags = rec.of("diag")
    assert len(diags) == T
    worst = np.asarray(out.diag).max(axis=0)      # the worst problem
    np.testing.assert_allclose([ev["consensus"] for ev in diags],
                               worst[:, 0], rtol=1e-6)
    assert all(ev["batch"] == B and ev["source"] == "driver.run_batch"
               for ev in diags)


def test_diagnostics_rejected_on_shard_map_substrate():
    from jax.sharding import Mesh
    from repro.core import ConsensusEngine, IterationDriver, PowerStep, ring
    m = jax.device_count()
    eng = ConsensusEngine(topology=ring(max(m, 2)), K=2, backend="stacked")
    driver = IterationDriver(step=PowerStep(track=True, rounds=2),
                             engine=eng, diagnostics="on")
    mesh = Mesh(np.array(jax.devices()), ("agents",))
    with pytest.raises(ValueError, match="shard_map"):
        driver.sharded_step_fn(mesh, "agents", eng)
    with pytest.raises(ValueError, match="shard_map"):
        driver.sharded_dense_step_fn(mesh, "agents")


# ======================================================== health monitor
def _mon(rules=None):
    rec = telemetry.RecordingSink()
    return HealthMonitor(rec, rules), rec


def test_monitor_forwards_and_interleaves_health_after_evidence():
    mon, rec = _mon(HealthRules(stall_window=2, stall_abs_floor=0.0,
                                stall_rel_floor=0.0))
    mon.emit("diag", {"source": "x", "t": 0, "movement": 0.5})
    mon.emit("diag", {"source": "x", "t": 1, "movement": 0.5})
    names = [name for name, _ in rec.events]
    assert names == ["diag", "diag", "health"]    # diagnosis follows proof
    assert mon.diagnoses[0]["rule"] == "stalled-movement"
    assert rec.of("health")[0]["movement"] == 0.5


def test_stalled_movement_fires_on_plateau_not_on_decay():
    floor = 2.0 ** -8                              # a bf16 wire's floor
    mon, _ = _mon()
    win = HealthRules().stall_window
    for t in range(win):                           # healthy geometric decay
        mon.emit("diag", {"source": "ok", "t": t, "floor": floor,
                          "movement": 1.0 * 0.4 ** t})
    assert mon.diagnoses == []
    for t in range(win):                           # plateau above the floor
        mon.emit("diag", {"source": "sick", "t": t, "floor": floor,
                          "movement": 2e-3})
    assert [d["rule"] for d in mon.diagnoses] == ["stalled-movement"]
    assert "quantization floor" in mon.diagnoses[0]["message"]
    assert mon.diagnoses[0]["source"] == "sick"


def test_stall_floor_suppresses_converged_noise():
    """Sub-floor jitter on a *converged* run is not a stall."""
    mon, _ = _mon()
    for t in range(HealthRules().stall_window):
        mon.emit("diag", {"source": "x", "t": t, "floor": 0.0,
                          "movement": 5e-6})       # below stall_abs_floor
    assert mon.diagnoses == []


def test_contraction_collapse_fires_with_analytical_bound_attached():
    mon, rec = _mon()
    mon.emit("iteration", {"source": "x", "t": 0, "rate": 0.42})
    rules = HealthRules()
    for t in range(rules.collapse_window + 1):
        mon.emit("diag", {"source": "x", "t": t, "floor": 2.0 ** -8,
                          "consensus": 0.11})      # ratio 1.0, above floor
    assert [d["rule"] for d in mon.diagnoses] == ["contraction-collapse"]
    assert mon.diagnoses[0]["bound"] == 0.42
    assert rec.of("health")[0]["measured_ratio"] == pytest.approx(1.0)


def test_contraction_collapse_streak_resets_on_real_contraction():
    mon, _ = _mon()
    c = 1.0
    for t in range(12):
        c *= 1.01 if t % 3 else 0.5   # contracts every third iteration
        mon.emit("diag", {"source": "x", "t": t, "floor": 0.0,
                          "consensus": c})
    assert mon.diagnoses == []


def test_restart_storm_fires_on_burst_not_on_sparse_restarts():
    mon, _ = _mon()
    for tick in (0, 20, 40):                       # sparse: healthy policy
        mon.emit("stream.restart", {"tick": tick, "jump_stat": 1.0})
    assert mon.diagnoses == []
    for tick in (41, 43, 45):                      # burst within the window
        mon.emit("stream.restart", {"tick": tick, "jump_stat": 1.0})
    assert [d["rule"] for d in mon.diagnoses] == ["restart-storm"]


def test_cold_launch_churn_fires_on_cold_fraction():
    mon, _ = _mon()
    for _ in range(12):                            # warm steady state: fine
        mon.emit("service.launch", {"bucket": "b", "warm": True})
    assert mon.diagnoses == []
    for _ in range(12):
        mon.emit("launch", {"source": "driver.run", "warm": False})
    assert [d["rule"] for d in mon.diagnoses] == ["cold-launch-churn"]
    assert mon.diagnoses[0]["frac"] > HealthRules().churn_cold_frac


def test_cooldown_prevents_diagnosis_floods():
    mon, _ = _mon(HealthRules(stall_window=2, stall_abs_floor=0.0,
                              stall_rel_floor=0.0, cooldown=50))
    for t in range(30):                            # persistent condition
        mon.emit("diag", {"source": "x", "t": t, "movement": 0.5})
    assert len(mon.diagnoses) == 1                 # one diagnosis, no flood


def test_finalize_summary_and_tracker_bookmarks():
    mon, rec = _mon(HealthRules(stall_window=2, stall_abs_floor=0.0,
                                stall_rel_floor=0.0))
    mark = mon.mark()
    assert mon.new_diagnoses(mark) == []
    mon.emit("diag", {"source": "x", "t": 0, "movement": 0.5})
    mon.emit("diag", {"source": "x", "t": 1, "movement": 0.5})
    fresh = mon.new_diagnoses(mark)
    assert [d["rule"] for d in fresh] == ["stalled-movement"]
    assert fresh[0]["rule"] in ESCALATE_RULES
    out = mon.finalize()
    assert len(out) == 1
    summary = rec.of("health")[-1]
    assert summary["rule"] == "summary" and summary["ok"] is False
    assert summary["diagnoses"] == 1 and summary["n_stalled_movement"] == 1


def test_install_health_monitor_wraps_current_sink_idempotently():
    rec = telemetry.RecordingSink()
    telemetry.set_sink(rec)
    assert current_monitor() is None
    mon = install_health_monitor()
    assert current_monitor() is mon and mon.inner is rec
    assert install_health_monitor() is mon         # no double wrap
    telemetry.emit("launch", warm=True)            # flows through to inner
    assert rec.of("launch") == [{"warm": True}]


# =============================================== end-to-end pathologies
def test_bf16_floor_stall_is_flagged_healthy_fp32_is_not():
    """The committed bf16 pathology: a plain (no-EF) bf16 wire pins the
    measured consensus residual at its quantization floor — the monitor
    must name it, and must NOT flag the identical fp32 run."""
    for wire, expect in ((None, []), ("bf16", ["contraction-collapse"])):
        driver, ops, W0 = _driver(wire=wire, diagnostics="on")
        rec = telemetry.RecordingSink()
        mon = HealthMonitor(rec)
        prev = telemetry.set_sink(mon)
        try:
            driver.run(ops, W0, T=30)
        finally:
            telemetry.set_sink(prev)
        rules = sorted({d["rule"] for d in mon.diagnoses})
        assert rules == expect, (wire, mon.diagnoses)
        if wire == "bf16":
            ev = rec.of("health")[0]
            # stuck at the measured floor, against a contracting bound
            assert ev["consensus"] > 0.1 * driver.quantization_floor()
            assert ev["bound"] is not None and ev["bound"] < 1.0


def test_streaming_restart_storm_is_flagged_live():
    """A hair-trigger drift policy over a fast-rotating stream restarts
    every few ticks; the monitor names the thrash from the live stream."""
    from repro.core.topology import ring
    from repro.streaming import (DriftPolicy, SlowRotationStream,
                                 StreamingDeEPCA)
    s = SlowRotationStream(m=6, d=16, k=3, n_per_agent=20, seed=0, rate=0.5)
    pol = DriftPolicy(jump=0.25, restart=0.5, floor=1e-9,
                      max_escalations=0)
    tr = StreamingDeEPCA(k=3, T_tick=2, K=3, topology=ring(6),
                         backend="stacked", W0=s.init_W0(), policy=pol)
    rec = telemetry.RecordingSink()
    mon = HealthMonitor(rec)
    prev = telemetry.set_sink(mon)
    try:
        for t in s.ticks(8):
            tr.tick(t.ops, t.U)
    finally:
        telemetry.set_sink(prev)
    assert sum(1 for r in tr.reports if r.restarted) >= 3
    assert "restart-storm" in {d["rule"] for d in mon.diagnoses}
    # the health event interleaves into the same stream as the evidence
    names = [name for name, _ in rec.events]
    assert names.index("health") > names.index("stream.restart")


def test_tracker_escalates_on_fresh_health_diagnosis():
    """ESCALATE_RULES diagnoses raised during a tick's first window are
    treated as drift: the tracker spends an extra window even though the
    drift statistic itself is quiet (jump threshold = inf)."""
    from repro.core.topology import ring
    from repro.streaming import (DriftPolicy, SlowRotationStream,
                                 StreamingDeEPCA)
    s = SlowRotationStream(m=6, d=16, k=3, n_per_agent=20, seed=0,
                           rate=0.01)
    pol = DriftPolicy(jump=math.inf, restart=math.inf, max_escalations=2)
    tr = StreamingDeEPCA(k=3, T_tick=2, K=3, topology=ring(6),
                         backend="stacked", W0=s.init_W0(), policy=pol,
                         diagnostics="on")
    # hair-trigger rules: any positive movement counts as a stall
    trigger = HealthRules(stall_window=2, stall_abs_floor=0.0,
                          stall_rel_floor=0.0, stall_drop=0.0, cooldown=0)
    mon = HealthMonitor(telemetry.NullSink(), trigger)
    prev = telemetry.set_sink(mon)
    try:
        r = tr.tick(s.ops_at(0))
    finally:
        telemetry.set_sink(prev)
    assert mon.diagnoses                          # the rule really fired
    assert r.drift is True and r.escalations == 1
    # without a monitor installed the same tick is quiet
    tr2 = StreamingDeEPCA(k=3, T_tick=2, K=3, topology=ring(6),
                          backend="stacked", W0=s.init_W0(), policy=pol,
                          diagnostics="on")
    r2 = tr2.tick(s.ops_at(0))
    assert r2.drift is False and r2.escalations == 0


# ============================================================== tracing
def test_span_is_noop_without_tracer():
    with telemetry.capture() as rec:
        with tracing.span("free", T=1):
            pass
    assert rec.events == []                       # not even a span event


def test_chrome_tracer_nested_spans_and_perfetto_export(tmp_path):
    path = str(tmp_path / "traces" / "t.json")    # parent dir auto-created
    tracer = tracing.ChromeTracer(path)
    tracing.set_tracer(tracer)
    try:
        with telemetry.capture() as rec:
            with tracing.span("outer", workload="pca"):
                with tracing.span("inner", T=3):
                    pass
    finally:
        tracing.set_tracer(None)
    assert len(tracer) == 2
    saved = tracer.save()
    with open(saved) as fh:
        doc = json.load(fh)
    assert doc["displayTimeUnit"] == "ms"
    events = {e["name"]: e for e in doc["traceEvents"]}
    assert set(events) == {"outer", "inner"}
    for e in events.values():                     # Chrome trace-event shape
        assert e["ph"] == "X" and e["cat"] == "repro"
        assert isinstance(e["ts"], int) and e["dur"] >= 1
        assert e["pid"] == os.getpid()
    assert events["outer"]["args"] == {"workload": "pca"}
    # the inner span nests inside the outer one on the timeline
    assert events["outer"]["ts"] <= events["inner"]["ts"]
    assert (events["inner"]["ts"] + events["inner"]["dur"]
            <= events["outer"]["ts"] + events["outer"]["dur"] + 1)
    # spans mirror into telemetry (inner exits first) with nesting depth
    spans = rec.of("span")
    assert [(s["name"], s["depth"]) for s in spans] == [("inner", 1),
                                                        ("outer", 0)]
    # telemetry carries the raw duration (an empty block can round to 0);
    # only the Chrome export clamps dur to >= 1 for Perfetto rendering
    assert all(s["dur_us"] >= 0 for s in spans)


def test_driver_spans_cover_run_and_launch_with_warm_flag(tmp_path):
    driver, ops, W0 = _driver()
    tracer = tracing.ChromeTracer(str(tmp_path / "d.json"))
    tracing.set_tracer(tracer)
    try:
        driver.run(ops, W0, T=3)
        driver.run(ops, W0, T=3)
    finally:
        tracing.set_tracer(None)
    by_name = {}
    for e in json.loads(open(tracer.save()).read())["traceEvents"]:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["driver.run"]) == 2
    launches = by_name["driver.launch"]
    assert [e["args"]["warm"] for e in launches] == [False, True]
    assert all(e["args"]["T"] == 3 for e in launches)


def test_profile_stages_spans(tmp_path):
    driver, ops, W0 = _driver()
    tracer = tracing.ChromeTracer(str(tmp_path / "p.json"))
    tracing.set_tracer(tracer)
    try:
        stages = driver.profile_stages(ops, W0, iters=2)
    finally:
        tracing.set_tracer(None)
    names = {e["name"] for e in
             json.loads(open(tracer.save()).read())["traceEvents"]}
    assert {"driver.profile_stages", "profile.apply", "profile.mix",
            "profile.orth"} <= names
    assert set(stages) == {"apply", "mix", "orth"}


def test_tracer_from_spec_vocabulary(tmp_path):
    for off in (None, "", "off", "none", "0", "false"):
        assert tracing.tracer_from_spec(off) is None
    t = tracing.tracer_from_spec(f"chrome:{tmp_path / 'a.json'}")
    assert isinstance(t, tracing.ChromeTracer) and not t.jax_annotations
    t2 = tracing.tracer_from_spec(f"chrome+jax:{tmp_path / 'b.json'}")
    assert isinstance(t2, tracing.ChromeTracer) and t2.jax_annotations
    assert isinstance(tracing.tracer_from_spec("jax"), tracing.JaxTracer)
    with pytest.raises(ValueError, match="needs a file path"):
        tracing.tracer_from_spec("chrome:")
    with pytest.raises(ValueError, match="unknown trace spec"):
        tracing.tracer_from_spec("zipkin:wat")


def test_jax_annotation_spans_still_record(tmp_path):
    """chrome+jax wraps spans in jax.profiler annotations; recording must
    survive whether or not the profiler cooperates."""
    tracer = tracing.ChromeTracer(str(tmp_path / "j.json"),
                                  jax_annotations=True)
    tracing.set_tracer(tracer)
    try:
        with tracing.span("annotated", x=1):
            pass
    finally:
        tracing.set_tracer(None)
    assert len(tracer) == 1


def test_jax_spans_pass_attributes_to_trace_annotation(monkeypatch):
    """Under a ``jax`` tracer a span's attributes reach the profiler's
    annotation (made JSON-scalar), so ids land in the device trace."""
    opened = []

    class Annotation:
        def __init__(self, name, **kwargs):
            opened.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tracing.set_tracer(tracing.tracer_from_spec("jax"))
    with tracing.span("service.launch", launch=3, warm=True,
                      bucket=("data", 4)):
        pass
    assert opened == [("service.launch",
                       {"launch": 3, "warm": True,
                        "bucket": "('data', 4)"})]


def _span_tree(events):
    """Rebuild the span tree from ``span`` telemetry events, which arrive
    as spans close (children first), each with its nesting depth."""
    open_children = {}
    for e in events:
        node = (e["name"],
                {k: v for k, v in e.items()
                 if k not in ("event", "name", "dur_us", "depth")},
                open_children.pop(e["depth"] + 1, []))
        open_children.setdefault(e["depth"], []).append(node)
    return open_children.pop(0, [])


def _service(clock):
    from repro.core import erdos_renyi
    from repro.streaming import AdmissionPolicy, PCAService
    return PCAService(erdos_renyi(4, p=0.6, seed=0), T=2, K=2,
                      backend="stacked", clock=clock,
                      policy=AdmissionPolicy(max_batch=2, max_wait=0.5,
                                             pad_n=16, pad_k=4))


def _service_request(seed, m=4, n=10, d=8, k=3):
    from repro.core.operators import StackedOperators
    rng = np.random.default_rng(seed)
    ops = StackedOperators(data=jnp.asarray(rng.standard_normal((m, n, d)),
                                            jnp.float32))
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    return ops, W0


def test_service_spans_nest_launch_work_under_submit_and_poll(tmp_path):
    """A full bucket's launch nests in the submit that filled it, a forced
    launch in the poll; each launch covers the pads, completions and
    stacks before the dispatch, with the request and launch ids."""
    clock = {"now": 0.0}
    svc = _service(lambda: clock["now"])
    tracing.set_tracer(tracing.ChromeTracer(str(tmp_path / "s.json")))
    with telemetry.capture() as rec:
        r0 = svc.submit(*_service_request(0))
        r1 = svc.submit(*_service_request(1))     # fills the bucket
        r2 = svc.submit(*_service_request(2))
        clock["now"] = 1.0
        assert svc.poll() == 1                    # max_wait forces it
    assert all(svc.result(r) is not None for r in (r0, r1, r2))
    bucket = str(svc.bucket_of(_service_request(0)[0], 3))

    def launch(lid, requests, batch_padded):
        # each batch shape launches once, so the service counts both
        # launches cold; the driver's ``warm`` is a hit in its per-T
        # program cache, so its second launch is warm
        work = [child for r in requests for child in (
            ("service.pad", {"request": r}, []),
            ("service.complement", {"request": r}, []))]
        return ("service.launch",
                {"launch": lid, "batch": len(requests),
                 "batch_padded": batch_padded, "warm": False,
                 "bucket": bucket},
                work + [("service.stack", {}, []),
                        ("driver.stack", {}, []),
                        ("driver.launch",
                         {"substrate": "vmap", "T": 2, "warm": lid > 0,
                          "form": "gram"},
                         [])])

    assert _span_tree(rec.of("span")) == [
        ("service.submit", {"request": r0}, []),
        ("service.submit", {"request": r1},
         [launch(0, [r0, r1], 2)]),
        ("service.submit", {"request": r2}, []),
        ("service.poll", {}, [launch(1, [r2], 1)]),
    ]


def test_service_records_no_spans_without_tracer(monkeypatch):
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: opened.append(a))
    clock = {"now": 0.0}
    svc = _service(lambda: clock["now"])
    with telemetry.capture() as rec:
        svc.submit(*_service_request(0))
        svc.submit(*_service_request(1))
        svc.submit(*_service_request(2))
        clock["now"] = 1.0
        svc.poll()
    assert rec.of("span") == [] and opened == []
    # the launch events still carry their attributes for a live sink
    assert [e["batch"] for e in rec.of("service.launch")] == [2, 1]


def test_run_batch_launch_names_the_apply_form(tmp_path):
    """``driver.launch`` and the ``launch`` event say which evaluation
    order the apply took: ``gram`` for a data batch whose rows are tall
    against the launch's iterations, ``data`` for a fleet-like one, and
    nothing for a dense batch."""
    from repro.core import (ConsensusEngine, IterationDriver, PowerStep,
                            erdos_renyi)
    from repro.core.operators import StackedOperators, data_gram
    m, k = 6, 3
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", 2),
        engine=ConsensusEngine.for_algorithm(
            "deepca", erdos_renyi(m, p=0.6, seed=0), K=2, backend="stacked"))
    rng = np.random.default_rng(0)

    def batch(n, d):
        X = jnp.asarray(rng.standard_normal((2, m, n, d)), jnp.float32)
        W0 = jnp.stack([jnp.eye(d, k)] * 2)
        return X, W0

    w8a_like, fleet_like = batch(64, 16), batch(8, 64)
    tracer = tracing.ChromeTracer(str(tmp_path / "f.json"))
    tracing.set_tracer(tracer)
    try:
        with telemetry.capture() as rec:
            driver.run_batch(StackedOperators(data=w8a_like[0]),
                             w8a_like[1], T=12)
            driver.run_batch(StackedOperators(data=fleet_like[0]),
                             fleet_like[1], T=3)
            driver.run_batch(StackedOperators(
                dense=jax.vmap(data_gram)(w8a_like[0])), w8a_like[1], T=12)
    finally:
        tracing.set_tracer(None)
    launches = [e["args"] for e in json.loads(open(tracer.save()).read())[
        "traceEvents"] if e["name"] == "driver.launch"]
    assert [a.get("form") for a in launches] == ["gram", "data", None]
    assert [(e["kind"], e.get("form")) for e in rec.of("launch")] == [
        ("data", "gram"), ("data", "data"), ("dense", None)]


def test_run_batch_program_names_the_step_stages():
    """The served program's ops carry the apply, mix and orth scopes in
    their metadata, so a device profile splits the step by stage."""
    from repro.core.step import SCOPE_APPLY, SCOPE_MIX, SCOPE_ORTH
    svc = _service(lambda: 0.0)
    svc.submit(*_service_request(0))
    svc.submit(*_service_request(1))
    text = svc.driver.lower_last_launch().as_text(debug_info=True)
    for scope in (SCOPE_APPLY, SCOPE_MIX, SCOPE_ORTH):
        assert scope in text, scope


def _fleet_ticks(policy, ticks):
    """Two tenants in one bucket of four slots over ``ticks`` ticks."""
    from repro.core import erdos_renyi
    from repro.streaming import SlowRotationStream, TrackerFleet
    streams = {f"t{i}": SlowRotationStream(m=4, d=8, k=2, n_per_agent=10,
                                           seed=i, rate=0.1)
               for i in range(2)}
    fleet = TrackerFleet(k=2, T_tick=2, K=2, slots=4, policy=policy,
                         topology=erdos_renyi(4, p=0.6, seed=0),
                         backend="stacked")
    for tid, s in streams.items():
        fleet.join(tid, s.init_W0(), n=10)
    return fleet, [fleet.tick({tid: s.tick(t).ops
                               for tid, s in streams.items()})
                   for t in range(ticks)]


def test_fleet_spans_and_counters_name_every_pass(tmp_path):
    """Under ``fleet.tick`` each bucket's span holds the operator stacking
    (one stack program, cold on the first tick), one ``fleet.window`` per
    pass with its kind, and the ``fleet.stats`` read after each; the
    tick's report and event count the restarts, escalations, the
    slot-windows run and kept, and the stack's cold launches."""
    from repro.streaming import DriftPolicy
    # the third tick restarts both tenants and escalates them once
    policy = DriftPolicy(jump=1e-9, restart=1e-9, max_escalations=1)
    tracing.set_tracer(tracing.ChromeTracer(str(tmp_path / "f.json")))
    try:
        with telemetry.capture() as rec:
            fleet, reports = _fleet_ticks(policy, 3)
    finally:
        tracing.set_tracer(None)
    bucket = str(fleet.bucket_of(8, 2, 10))

    def window(kind, warm):
        return ("fleet.window", {"kind": kind, "T": 2},
                [("driver.stack", {}, []),
                 ("driver.launch", {"substrate": "vmap", "T": 2,
                                    "warm": warm, "form": "gram"}, [])])

    def base(warm):
        stack = {"stack_cold": int(not warm)}
        return [("fleet.stack", stack, []), window("base", warm),
                ("fleet.stats", {}, [])]

    restart = base(True) + [
        window("restart", True), ("fleet.stats", {}, []),
        window("escalation", True), ("fleet.stats", {}, [])]
    ticks = [t for t in _span_tree(rec.of("span")) if t[0] == "fleet.tick"]
    assert [t[1] for t in ticks] == [{"tick": t, "tenants": 2}
                                     for t in range(3)]
    buckets = [t[2] for t in ticks]
    head = ("fleet.bucket", {"bucket": bucket, "capacity": 4, "active": 2})
    assert buckets == [[head + (base(False),)], [head + (base(True),)],
                       [head + (restart,)]]
    keys = ("windows", "restarts", "escalations", "slot_windows",
            "useful_slot_windows", "stack_cold")
    counts = [(1, 0, 0, 4, 2, 1), (1, 0, 0, 4, 2, 0), (3, 2, 2, 12, 6, 0)]
    assert [tuple(getattr(r, k) for k in keys) for r in reports] == counts
    assert [tuple(e[k] for k in keys)
            for e in rec.of("fleet.tick")] == counts


def test_fleet_records_no_spans_without_tracer(monkeypatch):
    from repro.streaming import DriftPolicy
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: opened.append(a))
    with telemetry.capture() as rec:
        _fleet_ticks(DriftPolicy(), 2)
    assert rec.of("span") == [] and opened == []
    assert [e["slot_windows"] for e in rec.of("fleet.tick")] == [4, 4]
