"""RuntimeConfig + telemetry (repro.runtime.config / .telemetry) and the
bench_diff regression gate.

The PR-7 contract surface: env-var precedence (explicit override > env >
default), live env re-reads, override() restore on every exit path,
configure()'s append-not-clobber XLA_FLAGS handling, JSON-serializable
describe() provenance, the telemetry sink vocabulary end to end through
IterationDriver, JSONL round-trips, and bench_diff's per-metric-class
regression rules.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.runtime import config, telemetry
from repro.runtime.config import configure, get_config, override

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "benchmarks")))
import bench_diff  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_runtime_state():
    """Snapshot/restore the env surface configure() writes through, and
    guarantee no telemetry sink or override layer leaks across tests."""
    names = config.ENV_VARS + ("XLA_FLAGS",)
    saved = {name: os.environ.get(name) for name in names}
    yield
    for name, val in saved.items():
        if val is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = val
    telemetry.set_sink(None)
    assert not config._overrides, "override() layer leaked"


# ======================================================== config precedence
def test_env_reads_are_live_and_override_wins(monkeypatch):
    monkeypatch.delenv(config.ENV_QR_IMPL, raising=False)
    assert get_config().qr_impl is None
    monkeypatch.setenv(config.ENV_QR_IMPL, "householder")
    assert get_config().qr_impl == "householder"      # no process restart
    with override(qr_impl="cholqr2") as cfg:
        assert cfg.qr_impl == "cholqr2"
        assert get_config().qr_impl == "cholqr2"      # explicit beats env
        with override(qr_impl=None):                  # None masks to unset
            assert get_config().qr_impl is None
        assert get_config().qr_impl == "cholqr2"      # inner layer popped
    assert get_config().qr_impl == "householder"      # env visible again


def test_override_restores_on_exception(monkeypatch):
    monkeypatch.delenv(config.ENV_FASTMIX_BLOCK_N, raising=False)
    with pytest.raises(RuntimeError, match="boom"):
        with override(fastmix_block_n=64):
            assert get_config().fastmix_block_n == 64
            raise RuntimeError("boom")
    assert get_config().fastmix_block_n is None


def test_override_validates_before_installing():
    with pytest.raises(TypeError, match="unknown RuntimeConfig field"):
        with override(frobnicate=1):
            pass
    with pytest.raises(ValueError, match="positive integer"):
        with override(fastmix_block_n=0):
            pass
    assert not config._overrides


@pytest.mark.parametrize("env,raw,match", [
    (config.ENV_QR_IMPL, "nonsense", "REPRO_QR_IMPL"),
    (config.ENV_FASTMIX_BLOCK_N, "-3", "positive integer"),
    (config.ENV_FASTMIX_BLOCK_N, "wide", "positive integer"),
    (config.ENV_AUTOTUNE, "maybe", "boolean"),
])
def test_invalid_env_value_raises_naming_the_variable(monkeypatch, env, raw,
                                                      match):
    monkeypatch.setenv(env, raw)
    with pytest.raises(ValueError, match=match):
        get_config()


# ========================================================= configure / jax
def test_configure_writes_knobs_to_env():
    cfg = configure(fastmix_block_n=256, autotune=True)
    assert os.environ[config.ENV_FASTMIX_BLOCK_N] == "256"
    assert os.environ[config.ENV_AUTOTUNE] == "1"
    assert cfg.fastmix_block_n == 256 and cfg.autotune is True
    # None leaves a knob untouched rather than unsetting it
    assert configure().fastmix_block_n == 256


def test_configure_installs_telemetry_sink(tmp_path):
    path = str(tmp_path / "t.jsonl")
    configure(telemetry=f"jsonl:{path}")
    assert telemetry.enabled()
    assert isinstance(telemetry.get_sink(), telemetry.JsonlSink)
    configure(telemetry="null")
    assert not telemetry.enabled()


def test_set_host_device_count_appends_never_clobbers(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_enable_fast_math=false")
    config.set_host_device_count(4)
    flags = os.environ["XLA_FLAGS"]
    assert "--xla_cpu_enable_fast_math=false" in flags      # preserved
    assert "--xla_force_host_platform_device_count=4" in flags
    # an existing device-count flag wins outright: later calls no-op
    config.set_host_device_count(8)
    assert "device_count=8" not in os.environ["XLA_FLAGS"]
    with pytest.raises(ValueError, match="positive"):
        config.set_host_device_count(0)


# the compile cache is only ever turned on in child processes: the test
# process itself keeps jax's default (off)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_SCRIPT = """
import jax, jax.numpy as jnp
from jax import monitoring
hits = []
monitoring.register_event_listener(
    lambda name, **kw: hits.append(name)
    if name == "/jax/compilation_cache/cache_hits" else None)
from repro.runtime import config
print("cache_dir", config.enable_compile_cache())
if {solve}:
    from repro.core import deepca, erdos_renyi
    from repro.core.operators import synthetic_spiked
    ops = synthetic_spiked(4, 16, 2, n_per_agent=8, seed=0)
    W0 = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(0), (16, 2)))[0]
    jax.block_until_ready(deepca(ops, erdos_renyi(4, p=0.5, seed=0), W0,
                                 k=2, T=3, K=2).W)
print("jax_dir", jax.config.jax_compilation_cache_dir)
print("hits", len(hits))
"""


def _cache_child(solve: bool, **env):
    import subprocess
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.path.join(_ROOT, "src"))
    full.pop(config.JAX_CACHE_ENV, None)
    full.update(env)
    out = subprocess.run([sys.executable, "-c",
                          _CACHE_SCRIPT.format(solve=solve)],
                         env=full, capture_output=True, text=True,
                         timeout=300, check=True).stdout
    return dict(line.split(" ", 1) for line in out.splitlines()
                if line.split(" ", 1)[0] in ("cache_dir", "jax_dir", "hits"))


def test_compile_cache_env_dir_is_hit_by_a_second_process(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, an entry point's second run in a
    fresh process reads what the first wrote there, and nothing lands in
    the checkout's own ``.jax_cache/``."""
    repo_cache = os.path.join(_ROOT, ".jax_cache")
    before = (sorted(os.listdir(repo_cache)) if os.path.isdir(repo_cache)
              else None)
    env = {config.JAX_CACHE_ENV: str(tmp_path / "jc"),
           # cache every program, however fast it compiles
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    first = _cache_child(True, **env)
    second = _cache_child(True, **env)
    assert first["cache_dir"] == second["cache_dir"] == str(tmp_path / "jc")
    assert first["hits"] == "0"
    assert os.listdir(tmp_path / "jc")
    assert int(second["hits"]) > 0
    after = (sorted(os.listdir(repo_cache)) if os.path.isdir(repo_cache)
             else None)
    assert after == before


def test_compile_cache_defaults_to_checkout_root():
    """Unset, the cache goes to ``.jax_cache/`` at the checkout root,
    resolved from the package path (nothing is compiled here)."""
    got = _cache_child(False)
    assert got["cache_dir"] == got["jax_dir"] == os.path.join(_ROOT,
                                                              ".jax_cache")


def test_describe_is_json_serializable_provenance(monkeypatch):
    monkeypatch.setenv(config.ENV_QR_IMPL, "cholqr2")
    d = config.describe()
    assert d["qr_impl"] == "cholqr2"
    assert d["env"][config.ENV_QR_IMPL] == "cholqr2"
    assert "xla_flags" in d
    # jax is imported in this process, so backend provenance is present
    assert d["jax"]["backend"] == jax.default_backend()
    assert d["jax"]["device_count"] == jax.device_count()
    json.dumps(d)


# ================================================================ telemetry
def test_null_sink_is_the_free_default():
    telemetry.set_sink(None)
    assert not telemetry.enabled()
    telemetry.emit("iteration", t=0)        # swallowed, no error


def test_capture_scopes_a_recording_sink():
    with telemetry.capture() as rec:
        assert telemetry.enabled()
        telemetry.emit("iteration", t=0, rate=0.5)
    assert rec.of("iteration") == [{"t": 0, "rate": 0.5}]
    assert not telemetry.enabled()          # previous sink restored


@pytest.mark.parametrize("spec", [None, "", "null", "none", "off", "NULL"])
def test_sink_spec_null_variants(spec):
    assert isinstance(telemetry.sink_from_spec(spec), telemetry.NullSink)


def test_sink_spec_log_and_jsonl(tmp_path):
    assert isinstance(telemetry.sink_from_spec("log"), telemetry.LoggingSink)
    sink = telemetry.sink_from_spec(f"jsonl:{tmp_path / 'x.jsonl'}")
    assert isinstance(sink, telemetry.JsonlSink)
    with pytest.raises(ValueError, match="needs a path"):
        telemetry.sink_from_spec("jsonl:")
    with pytest.raises(ValueError, match="unknown telemetry sink"):
        telemetry.sink_from_spec("bogus")


def test_jsonl_sink_round_trip(tmp_path):
    path = str(tmp_path / "events" / "t.jsonl")   # parent dir auto-created
    sink = telemetry.JsonlSink(path)
    prev = telemetry.set_sink(sink)
    try:
        telemetry.emit("iteration", t=0, rate=np.float32(0.25),
                       rounds=jnp.asarray(6))
        telemetry.emit("launch", warm=True, substrate="scan")
    finally:
        telemetry.set_sink(prev)
        sink.close()
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["event"] for r in recs] == ["iteration", "launch"]
    assert [r["seq"] for r in recs] == [0, 1]
    assert all("ts" in r for r in recs)
    assert recs[0]["rate"] == 0.25 and recs[0]["rounds"] == 6
    assert recs[1]["warm"] is True and recs[1]["substrate"] == "scan"


def test_jsonl_sink_buffered_mode_flushes_in_batches(tmp_path):
    path = str(tmp_path / "buf.jsonl")
    sink = telemetry.JsonlSink(path, flush_every=3)
    prev = telemetry.set_sink(sink)
    try:
        telemetry.emit("iteration", t=0)
        telemetry.emit("iteration", t=1)
        # below the flush threshold: nothing durable yet
        assert os.path.getsize(path) == 0
        telemetry.emit("iteration", t=2)        # third event flushes a batch
        with open(path) as f:
            assert len(f.readlines()) == 3
        telemetry.emit("iteration", t=3)        # buffered again
        with open(path) as f:
            assert len(f.readlines()) == 3
    finally:
        telemetry.set_sink(prev)
        sink.close()                    # documented: close() always flushes
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["t"] for r in recs] == [0, 1, 2, 3]
    assert [r["seq"] for r in recs] == [0, 1, 2, 3]


def test_sink_spec_jsonl_buffer(tmp_path):
    sink = telemetry.sink_from_spec(f"jsonl+buffer:{tmp_path / 'b.jsonl'}")
    assert isinstance(sink, telemetry.JsonlSink)
    assert sink.flush_every == telemetry.JsonlSink.BUFFERED_FLUSH_EVERY
    with pytest.raises(ValueError, match="needs a path"):
        telemetry.sink_from_spec("jsonl+buffer:")


def test_callback_sink_survives_raising_callback_then_disables():
    delivered = []
    calls = {"n": 0}

    def hook(event, fields):
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("hook broke")
        delivered.append(fields)

    sink = telemetry.CallbackSink(hook, max_failures=3)
    prev = telemetry.set_sink(sink)
    try:
        telemetry.emit("iteration", t=0)
        telemetry.emit("iteration", t=1)
        for t in (2, 3):            # failures 1-2: caught, sink stays live
            telemetry.emit("iteration", t=t)
        assert sink.active and sink.failures == 2
        with pytest.warns(RuntimeWarning, match="disabling CallbackSink"):
            telemetry.emit("iteration", t=4)    # failure 3: deactivates
        assert not sink.active
        telemetry.emit("iteration", t=5)        # dead hook costs nothing
    finally:
        telemetry.set_sink(prev)
    assert [f["t"] for f in delivered] == [0, 1]
    assert calls["n"] == 5          # the t=5 emit never reached the hook


# ================================================= driver instrumentation
def _driver(m=8, d=16, k=2, K=4, seed=0):
    from repro.core import (ConsensusEngine, IterationDriver, PowerStep,
                            erdos_renyi, synthetic_spiked)
    topo = erdos_renyi(m, p=0.6, seed=seed)
    ops = synthetic_spiked(m, d, k, n_per_agent=16, seed=seed)
    rng = np.random.default_rng(seed)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", K),
        engine=ConsensusEngine.for_algorithm("deepca", topo, K=K,
                                             backend="stacked"))
    return driver, ops, W0


def test_driver_run_emits_launch_and_iteration_events():
    T = 5
    driver, ops, W0 = _driver()
    with telemetry.capture() as rec:
        driver.run(ops, W0, T=T)
        driver.run(ops, W0, T=T)            # same (T, kind): cached program
    launches = rec.of("launch")
    assert [ev["warm"] for ev in launches] == [False, True]
    assert all(ev["source"] == "driver.run" and ev["T"] == T
               for ev in launches)
    iters = rec.of("iteration")
    assert len(iters) == 2 * T
    assert [ev["t"] for ev in iters[:T]] == list(range(T))
    assert all(ev["source"] == "driver.run" for ev in iters)
    # cumulative gossip rounds strictly increase within a window; the
    # contraction bound is a (0, 1) rate
    rounds = [ev["rounds"] for ev in iters[:T]]
    assert rounds == sorted(rounds) and rounds[0] >= 1
    assert all(0.0 < ev["rate"] < 1.0 for ev in iters)


def test_driver_run_batch_emits_batched_events():
    from repro.core import synthetic_problem_batch
    B, m, d, k, T = 3, 8, 16, 2, 4
    driver, _, _ = _driver(m=m, d=d, k=k)
    problems, W0 = synthetic_problem_batch(B, m, d, k, n_per_agent=16,
                                           seed=0)
    with telemetry.capture() as rec:
        driver.run_batch(problems, W0, T=T)
    launches = rec.of("launch")
    assert len(launches) == 1
    assert launches[0]["source"] == "driver.run_batch"
    assert launches[0]["substrate"] == "vmap" and launches[0]["warm"] is False
    iters = rec.of("iteration")
    assert len(iters) == T
    assert all(ev["batch"] == B and ev["source"] == "driver.run_batch"
               for ev in iters)


def test_run_batch_event_ordering_and_monotone_rounds():
    from repro.core import synthetic_problem_batch
    B, m, d, k, T = 2, 8, 16, 2, 4
    driver, _, _ = _driver(m=m, d=d, k=k)
    problems, W0 = synthetic_problem_batch(B, m, d, k, n_per_agent=16,
                                           seed=0)
    with telemetry.capture() as rec:
        driver.run_batch(problems, W0, T=T)
        driver.run_batch(problems, W0, T=T)
    # each window's launch event precedes its iteration block, in order
    # (other events — e.g. autotune on the cold launch — may interleave)
    names = [name for name, _ in rec.events
             if name in ("launch", "iteration")]
    assert names == (["launch"] + ["iteration"] * T) * 2
    iters = rec.of("iteration")
    for w in range(2):
        window = iters[w * T:(w + 1) * T]
        assert [ev["t"] for ev in window] == list(range(T))
        rounds = [ev["rounds"] for ev in window]
        assert rounds == sorted(rounds) and rounds[0] >= 1


def test_tracker_telemetry_across_resumed_windows():
    """Streaming ticks are resumed windows: the global iteration index
    continues, per-window cumulative rounds restart, and the
    ``bytes_on_wire`` deltas add up to the tracker's total wire cost."""
    import math
    from repro.core.topology import ring
    from repro.streaming import (DriftPolicy, SlowRotationStream,
                                 StreamingDeEPCA)
    m, d, k, T_tick, ticks = 6, 16, 3, 2, 3
    s = SlowRotationStream(m=m, d=d, k=k, n_per_agent=20, seed=0, rate=0.02)
    passive = DriftPolicy(jump=math.inf, restart=math.inf,
                          max_escalations=0)
    tr = StreamingDeEPCA(k=k, T_tick=T_tick, K=3, topology=ring(m),
                         backend="stacked", W0=s.init_W0(), policy=passive,
                         wire_dtype="bf16")
    with telemetry.capture() as rec:
        for t in range(ticks):
            tr.tick(s.ops_at(t))
    iters = rec.of("iteration")
    assert len(iters) == ticks * T_tick
    # global iteration index is resume-continuous across windows
    assert [ev["t"] for ev in iters] == list(range(ticks * T_tick))
    for w in range(ticks):
        rounds = [ev["rounds"] for ev in iters[w * T_tick:(w + 1) * T_tick]]
        assert rounds == sorted(rounds) and rounds[0] >= 1
    # the per-iteration bytes_on_wire deltas, summed across every resumed
    # window, reproduce total_rounds x the engine's per-round cost model
    bpr = tr.driver.engine.bytes_per_round(d, k)
    total = sum(ev["bytes_on_wire"] for ev in iters)
    assert total == int(round(tr.reports[-1].total_rounds)) * bpr
    # each tick's stream.tick summary lands after its iteration block
    names = [name for name, _ in rec.events]
    assert names.count("stream.tick") == ticks
    assert names.index("stream.tick") > names.index("iteration")
    assert [f["tick"] for n, f in rec.events if n == "stream.tick"] \
        == list(range(ticks))


# ================================================================ bench_diff
def _payload(rows, **meta):
    out = {"bench": "kernels", "device": "cpu", "quick": False, "rows": rows}
    out.update(meta)
    return out


def test_bench_diff_identical_payloads_pass():
    a = _payload([{"name": "r", "us": 100.0, "parity": 1e-9, "tol": 5e-5,
                   "ok": True}])
    rep = bench_diff.diff(a, a)
    assert rep["ok"] and rep["compared"] == 1
    assert not rep["regressions"] and not rep["warnings"]


def test_bench_diff_wallclock_is_loose_ratio():
    base = _payload([{"name": "r", "us": 100.0}])
    assert bench_diff.diff(base, _payload([{"name": "r", "us": 200.0}]))["ok"]
    bad = bench_diff.diff(base, _payload([{"name": "r", "us": 300.0}]))
    assert not bad["ok"] and "us" in bad["regressions"][0]
    fast = bench_diff.diff(base, _payload([{"name": "r", "us": 10.0}]))
    assert fast["ok"] and fast["improvements"]


def test_bench_diff_accuracy_has_absolute_floor():
    base = _payload([{"name": "r", "final_tan": 1e-10}])
    # big *ratio* jump under the 1e-6 floor: numerically still perfect
    assert bench_diff.diff(
        base, _payload([{"name": "r", "final_tan": 1e-7}]))["ok"]
    broken = bench_diff.diff(
        base, _payload([{"name": "r", "final_tan": 1e-2}]))
    assert not broken["ok"] and "final_tan" in broken["regressions"][0]


def test_bench_diff_ok_flip_and_tol_loosening_regress():
    base = _payload([{"name": "r", "us": 1.0, "ok": True, "tol": 5e-6,
                      "orth": 1e-7}])
    flipped = bench_diff.diff(
        base, _payload([{"name": "r", "us": 1.0, "ok": False, "tol": 5e-6,
                         "orth": 1e-7}]))
    assert not flipped["ok"] and "ok True -> False" in \
        flipped["regressions"][0]
    loosened = bench_diff.diff(
        base, _payload([{"name": "r", "us": 1.0, "ok": True, "tol": 1e-3,
                         "orth": 1e-7}]))
    assert not loosened["ok"] and "tol loosened" in loosened["regressions"][0]


def test_bench_diff_rounds_must_match_exactly():
    base = _payload([{"name": "r", "us": 1.0, "rounds": 300.0}])
    drift = bench_diff.diff(
        base, _payload([{"name": "r", "us": 1.0, "rounds": 305.0}]))
    assert not drift["ok"] and "rounds" in drift["regressions"][0]


def test_bench_diff_missing_rows_warn_unless_required():
    base = _payload([{"name": "a", "us": 1.0}, {"name": "b", "us": 1.0}])
    cand = _payload([{"name": "a", "us": 1.0}])
    soft = bench_diff.diff(base, cand)
    assert soft["ok"] and any("missing" in w for w in soft["warnings"])
    hard = bench_diff.diff(base, cand, require_rows=True)
    assert not hard["ok"]


def test_bench_diff_empty_intersection_is_not_a_pass():
    rep = bench_diff.diff(_payload([{"name": "a", "us": 1.0}]),
                          _payload([{"name": "b", "us": 1.0}]))
    assert not rep["ok"] and "no comparable rows" in rep["regressions"][0]


def test_bench_diff_cli_exit_codes_and_report(tmp_path, capsys):
    base = tmp_path / "base.json"
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    report = tmp_path / "report.json"
    base.write_text(json.dumps(_payload(
        [{"name": "r", "us": 100.0, "ok": True}])))
    good.write_text(json.dumps(_payload(
        [{"name": "r", "us": 110.0, "ok": True}])))
    bad.write_text(json.dumps(_payload(
        [{"name": "r", "us": 100.0, "ok": False}])))
    assert bench_diff.main([str(base), str(good)]) == 0
    assert bench_diff.main([str(base), str(bad),
                            "--report", str(report)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out
    rep = json.loads(report.read_text())
    assert not rep["ok"] and rep["compared"] == 1
