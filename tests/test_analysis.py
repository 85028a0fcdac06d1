"""The static-analysis subsystem (repro.analysis).

Each pass must (a) run clean over the shipped tree and (b) demonstrably
fire on its committed fixture — a checker nobody has ever seen fail is
indistinguishable from one that checks nothing.  Satellite coverage: the
retrace regression tests pin the dynamic same-m topology swap and warm
streaming ticks to ZERO steady-state compiles.
"""
import glob
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import budget, deadcode, lint, registry, retrace, \
    tracecheck
from repro.analysis.report import PassResult, Violation
from repro.kernels import autotune

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")


def _fixture(name):
    return os.path.join(FIXTURES, name)


# ===================================================================== report
def test_violation_render_and_roundtrip():
    v = Violation("lint", "bare-assert", "a/b.py", 12, "boom")
    assert "a/b.py:12" in v.render() and "[bare-assert]" in v.render()
    r = PassResult(name="lint")
    assert r.ok
    r.add("x", "p", 1, "m")
    assert not r.ok and "FAIL" in r.render()
    d = r.to_dict()
    assert d["violations"][0]["code"] == "x" and not d["ok"]


# ======================================================================= lint
def test_lint_clean_on_repo_tree():
    r = lint.run()
    assert r.ok, r.render()
    # the registry's canonical definitions were all actually seen
    assert r.checked > 50


@pytest.mark.parametrize("fixture,code,needle", [
    ("dup_tracking_site.py", "duplicate-compute-site", "tracking"),
    ("direct_qr.py", "duplicate-compute-site", "qr"),
    ("bare_assert.py", "bare-assert", "assert"),
    ("host_sync.py", "host-sync", "item"),
    ("env_config.py", "env-config", "REPRO_"),
    ("diag_site.py", "duplicate-compute-site", "diag_vector"),
    ("fleet_dup.py", "duplicate-compute-site", "select_carry"),
    ("fleet_dup.py", "duplicate-compute-site", "scatter_carry"),
])
def test_lint_fires_on_fixture(fixture, code, needle):
    r = lint.run(files=[_fixture(fixture)])
    hits = [v for v in r.violations if v.code == code]
    assert hits, r.render()
    assert any(needle in v.message for v in hits), r.render()


def test_lint_flags_reserved_def_shadowing():
    r = lint.run(files=[_fixture("dup_tracking_site.py")])
    assert any("reserved seam function" in v.message
               for v in r.violations), r.render()


def test_lint_flags_wire_roundtrip_fixture():
    r = lint.run(files=[_fixture("direct_qr.py")])
    assert any("quantize-wire" in v.message
               for v in r.violations), r.render()


def test_env_config_lint_covers_every_access_shape():
    """The fixture exercises get/getenv/subscript-write/jax.config.update;
    each one must fire individually."""
    r = lint.run(files=[_fixture("env_config.py")])
    hits = [v for v in r.violations if v.code == "env-config"]
    assert len(hits) == 4, r.render()
    assert any("jax.config.update" in v.message for v in hits), r.render()
    assert any("os.environ[" in v.message for v in hits), r.render()


def test_env_config_lint_allows_the_config_owner():
    """repro/runtime/config.py is the registered owner — repo-mode lint
    over the real tree must stay clean (the refactor's no-backslide
    guarantee, also the ISSUE-7 acceptance grep)."""
    r = lint.run()
    assert not [v for v in r.violations if v.code == "env-config"], \
        r.render()


def test_lint_missing_definition_guard(tmp_path):
    """Pointing the repo-mode linter at an empty tree reports registry rot
    (the registered compute-site definitions are gone)."""
    (tmp_path / "empty.py").write_text("x = 1\n")
    r = lint.run(src_root=str(tmp_path))
    assert any(v.code == "missing-definition" for v in r.violations)


# ================================================================= tracecheck
def test_tracecheck_clean_on_core_entry_points():
    r = tracecheck.run(names=["deepca[scan,stacked]",
                              "engine.mix_track[pallas]",
                              "engine.mix_track[pallas,wire]",
                              "mixing.fastmix_wire"])
    assert r.ok, r.render()
    assert r.checked == 4


def test_tracecheck_f64_audit_fires_on_narrowing():
    with jax.enable_x64(True):
        def leaky(x):
            y = x.astype(jnp.float32)          # the silent fidelity killer
            return (y @ y.T).astype(x.dtype)
        bad = tracecheck.check_f64(leaky, jnp.ones((4, 4), jnp.float64))
    assert bad and any("float32" in b for b in bad)


def test_tracecheck_wire_audit_fires_on_bf16_accumulation():
    def wire_bad(x):
        q = x.astype(jnp.bfloat16)
        return (q @ q.T).astype(x.dtype)       # bf16 x bf16 -> bf16 acc
    bad = tracecheck.check_wire(wire_bad, jnp.ones((8, 8), jnp.float32))
    assert any("accumulates bf16" in b for b in bad)


def test_tracecheck_wire_audit_fires_on_noop_wire_flag():
    bad = tracecheck.check_wire(lambda x: x * 2.0,
                                jnp.ones((4,), jnp.float32))
    assert any("no-op" in b for b in bad)


def test_tracecheck_wire_audit_accepts_fp32_accumulation():
    from repro.kernels.fastmix import quantize_wire

    def wire_ok(x):
        q = quantize_wire(x)                   # bf16 round-trip, fp32 acc
        return q @ q.T
    bad = tracecheck.check_wire(wire_ok, jnp.ones((8, 8), jnp.float32))
    assert not bad, bad


def test_tracecheck_walks_into_scan_and_pallas():
    """The jaxpr walker must see inside lax.scan bodies."""
    with jax.enable_x64(True):
        def leaky_scan(x):
            def body(c, _):
                return (c.astype(jnp.float32).astype(x.dtype) + 1.0), None
            out, _ = jax.lax.scan(body, x, None, length=2)
            return out
        bad = tracecheck.check_f64(leaky_scan, jnp.ones((4,), jnp.float64))
    assert bad, "narrowing inside a scan body went unnoticed"


# ==================================================================== retrace
def test_count_compiles_counts_fresh_jits():
    """The violation fixture: a fresh jit wrapper per call always
    recompiles — the harness must see it."""
    x = jnp.ones((8, 8), jnp.float32)
    with retrace.count_compiles() as c:
        for i in range(2):
            jax.jit(lambda v, i=i: v * (i + 2))(x).block_until_ready()
    assert c.count >= 2, c.messages


def test_count_compiles_zero_on_warm_jit():
    f = jax.jit(lambda v: v * 2)
    x = jnp.ones((8, 8), jnp.float32)
    f(x).block_until_ready()
    with retrace.count_compiles() as c:
        f(x).block_until_ready()
    assert c.count == 0, c.messages


def test_retrace_dynamic_same_m_topology_swap_is_zero_compiles():
    """Regression pin: DynamicConsensusEngine takes the graph as a traced
    operand — swapping ring -> Erdos-Renyi at the same m reuses the
    compiled program exactly."""
    contract = next(c for c in retrace.CONTRACTS
                    if c.name == "dynamic-same-m-swap")
    count, messages = retrace.measure(contract)
    assert count == 0, messages


def test_retrace_streaming_warm_ticks_zero_compiles():
    """Regression pin: StreamingDeEPCA warm ticks are pure resumed windows
    on one compiled program — tick 3..5 must not re-enter XLA."""
    contract = next(c for c in retrace.CONTRACTS
                    if c.name == "streaming-warm-ticks")
    count, messages = retrace.measure(contract)
    assert count == 0, messages


def test_retrace_driver_run_warm_zero_compiles():
    contract = next(c for c in retrace.CONTRACTS
                    if c.name == "driver-run-warm")
    count, messages = retrace.measure(contract)
    assert count == 0, messages


def test_retrace_fleet_warm_zero_compiles():
    """Regression pin: fleet membership churn (leave + re-join), in-batch
    restarts and escalation windows are slot scatters and masked selects
    on warm programs — steady-state fleet ticks must not re-enter XLA."""
    contract = next(c for c in retrace.CONTRACTS
                    if c.name == "fleet-warm")
    count, messages = retrace.measure(contract)
    assert count == 0, messages


def test_retrace_diag_run_warm_zero_compiles():
    """Regression pin: warm driver.run repeats with in-graph diagnostics ON
    stay on one cached scan program — measuring must not cost steady-state
    compiles."""
    contract = next(c for c in retrace.CONTRACTS
                    if c.name == "diag-run-warm")
    count, messages = retrace.measure(contract)
    assert count == 0, messages


# ===================================================================== budget
def test_budget_clean_on_repo_defaults():
    r = budget.run()
    assert r.ok, r.render()
    assert r.checked >= len(registry.REPRESENTATIVE_SHAPES)


def test_budget_fires_on_overbudget_cache_entry(tmp_path):
    cache = tmp_path / "autotune.json"
    cache.write_text(json.dumps({"version": 1, "entries": {
        "fastmix/tpu_v4/16x131072/float32": {"block_n": 131072, "us": 1.0},
    }}))
    r = budget.run(cache_path=str(cache))
    assert any(v.code == "vmem-cache" for v in r.violations), r.render()


def test_budget_skips_impl_pin_entries(tmp_path):
    cache = tmp_path / "autotune.json"
    cache.write_text(json.dumps({"version": 1, "entries": {
        "cholqr/cpu/64x8/float32": {"householder": 1},
    }}))
    r = budget.run(cache_path=str(cache))
    assert r.ok and any("no tile params" in s for s in r.skipped)


def test_budget_default_block_config_within_budget():
    used, cap = budget.check_config("fastmix", (16, 1024 * 16))
    assert used <= cap
    used, cap = budget.check_config(
        "fastmix", (16, 1024 * 16), {"block_n": 65536})
    assert used > cap


def test_apply_track_default_tiles_shrink_at_large_m():
    from repro.kernels.fastmix import (apply_track_default_tiles,
                                       apply_track_vmem_words)
    # bench-tuned defaults survive at the bench grid...
    assert apply_track_default_tiles(16, 1024, 16) == (64, 256)
    # ...and shrink to fit at the large-m corner the checker caught
    bd, be = apply_track_default_tiles(64, 4096, 32)
    assert (bd, be) != (64, 256)
    words = apply_track_vmem_words(64, 4096, 32, bd, be)
    assert words * 4 <= registry.vmem_budget(autotune.device_kind())


def test_budget_rejects_old_apply_track_tiles_at_w8a_shape():
    """Regression pin: the (32, 128) tiles the old model approved at the
    paper's w8a shape need 18.12 MiB on a v5e (the compiler refuses them);
    counting the resident output blocks rejects them, and the resolved
    defaults fit."""
    from repro.kernels.fastmix import apply_track_default_tiles
    used, cap = budget.check_config(
        "apply_track", (50, 300, 5), {"block_d": 32, "block_e": 128},
        device="tpu_v5_lite")
    assert used > cap
    bd, be = apply_track_default_tiles(50, 300, 5)
    used, cap = budget.check_config(
        "apply_track", (50, 300, 5), {"block_d": bd, "block_e": be},
        device="tpu_v5_lite")
    assert used <= cap


def test_vmem_budget_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no VMEM budget"):
        registry.vmem_budget("tpu_v99")


# =================================================================== deadcode
def test_deadcode_clean_on_repo():
    r = deadcode.run()
    assert r.ok, r.render()
    rep = deadcode.analyze()
    # the quarantine list matches reality: every entry is genuinely
    # non-runtime, and the paper surface is reachable
    assert "repro.core.algorithms" in rep["runtime"]
    assert "repro.analysis.lint" in rep["runtime"]
    assert not rep["stale_quarantine"]


def test_deadcode_flags_orphan_module(tmp_path):
    src = tmp_path / "src"
    (src / "repro").mkdir(parents=True)
    (src / "repro" / "__init__.py").write_text("")
    (src / "repro" / "orphan_mod.py").write_text("x = 1\n")
    r = deadcode.run(src_root=str(src), repo_root=str(tmp_path))
    assert any(v.code == "orphan-module" and "orphan_mod" in v.path
               for v in r.violations), r.render()


def test_deadcode_sees_dynamic_config_registry():
    """importlib.import_module(f"repro.configs.{...}") keeps the arch
    configs runtime-reachable."""
    rep = deadcode.analyze()
    assert "repro.configs.smollm_135m" in rep["runtime"]


# ======================================================================== CLI
def test_cli_lint_budget_deadcode_exit_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--lint", "--budget",
         "--deadcode", "--json"],
        capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": os.pathsep.join(
                 [registry.SRC_ROOT, os.environ.get("PYTHONPATH", "")])},
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] and len(payload["passes"]) == 3


def test_cli_nonzero_exit_on_violation(tmp_path, monkeypatch):
    """End to end: a fixture violation flips the exit status."""
    from repro.analysis.__main__ import main
    import repro.analysis.__main__ as cli
    bad = PassResult(name="lint")
    bad.add("bare-assert", "x.py", 1, "boom")
    monkeypatch.setattr(
        cli, "PASSES", (("lint", lambda: bad, "stub"),))
    assert main(["--lint"]) == 1
    good = PassResult(name="lint")
    monkeypatch.setattr(
        cli, "PASSES", (("lint", lambda: good, "stub"),))
    assert main(["--lint"]) == 0


def test_fixture_files_are_committed():
    """The proof-the-linter-fires fixtures must stay in the tree."""
    names = {os.path.basename(p)
             for p in glob.glob(os.path.join(FIXTURES, "*.py"))}
    assert {"dup_tracking_site.py", "direct_qr.py", "bare_assert.py",
            "host_sync.py", "env_config.py", "diag_site.py",
            "fleet_dup.py"} <= names
