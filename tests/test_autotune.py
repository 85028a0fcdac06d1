"""Persistent kernel autotuner (repro.kernels.autotune): cache semantics.

Covers the PR-5 satellite checklist: cache hit/miss, env-override
precedence over cached entries, corrupt/partial cache file recovery, and
per-device-kind keying — plus the kernel-facing ``block_* = None``
resolution paths (fastmix / qr_orth impl pinning).
"""
import json
import os

import jax.numpy as jnp
import pytest

from repro.kernels import autotune


@pytest.fixture
def cache(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    # the hot-path stat TTL would make same-test external writes invisible;
    # pin it to 0 so every lookup re-stats deterministically
    monkeypatch.setattr(autotune, "_STAT_TTL", 0.0)
    return path


# ------------------------------------------------------------- hit / miss
def test_no_cache_file_is_read_without_env(tmp_path, monkeypatch):
    """Without REPRO_AUTOTUNE_CACHE the built-in defaults decide: no file
    outside the checkout is read, and recording needs an explicit path."""
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    stray = tmp_path / "repro" / "autotune.json"
    stray.parent.mkdir()
    stray.write_text(json.dumps({"version": 1, "entries": {
        autotune.cache_key("fastmix", (16, 8192), jnp.float32):
            {"block_n": 1024}}}))
    assert autotune.default_cache_path() is None
    assert autotune.lookup("fastmix", "block_n", (16, 8192),
                           jnp.float32) is None
    with pytest.raises(ValueError, match=autotune.CACHE_ENV):
        autotune.record("fastmix", (16, 8192), jnp.float32,
                        {"block_n": 512})


def test_lookup_miss_returns_none(cache):
    assert autotune.lookup("fastmix", "block_n", (16, 8192),
                           jnp.float32) is None


def test_record_then_lookup_hit(cache):
    key = autotune.record("fastmix", (16, 8192), jnp.float32,
                          {"block_n": 1024, "us": 41.2})
    assert key == autotune.cache_key("fastmix", (16, 8192), jnp.float32)
    assert autotune.lookup("fastmix", "block_n", (16, 8192),
                           jnp.float32) == 1024
    # same pow2 bucket -> same entry (8192 buckets with 8000)
    assert autotune.lookup("fastmix", "block_n", (16, 8000),
                           jnp.float32) == 1024
    # different bucket / dtype / kernel -> miss
    assert autotune.lookup("fastmix", "block_n", (16, 512),
                           jnp.float32) is None
    assert autotune.lookup("fastmix", "block_n", (16, 8192),
                           jnp.bfloat16) is None
    assert autotune.lookup("gram", "block_n", (16, 8192),
                           jnp.float32) is None


def test_record_merges_params(cache):
    autotune.record("gram", (512, 256), jnp.float32, {"block_d": 64})
    autotune.record("gram", (512, 256), jnp.float32, {"block_n": 256})
    assert autotune.lookup("gram", "block_d", (512, 256), jnp.float32) == 64
    assert autotune.lookup("gram", "block_n", (512, 256), jnp.float32) == 256


def test_resolve_default_on_miss(cache):
    assert autotune.resolve("gram", "block_d", (512, 256), jnp.float32,
                            default=128) == 128


# ----------------------------------------------- config-override precedence
def test_config_override_beats_cached_entry(cache, monkeypatch):
    from repro.runtime import config as runtime_config

    autotune.record("fastmix", (16, 8192), jnp.float32, {"block_n": 1024})
    monkeypatch.setenv("REPRO_FASTMIX_BLOCK_N", "256")
    block = runtime_config.get_config().fastmix_block_n
    assert block == 256
    assert autotune.resolve("fastmix", "block_n", (16, 8192), jnp.float32,
                            override=block, default=512) == 256
    monkeypatch.delenv("REPRO_FASTMIX_BLOCK_N")
    block = runtime_config.get_config().fastmix_block_n
    assert block is None
    assert autotune.resolve("fastmix", "block_n", (16, 8192), jnp.float32,
                            override=block, default=512) == 1024


def test_invalid_env_raises_not_silently_ignored(cache, monkeypatch):
    from repro.runtime import config as runtime_config

    monkeypatch.setenv("REPRO_FASTMIX_BLOCK_N", "not-a-number")
    with pytest.raises(ValueError, match="positive integer"):
        runtime_config.get_config()
    monkeypatch.setenv("REPRO_FASTMIX_BLOCK_N", "0")
    with pytest.raises(ValueError, match="positive integer"):
        runtime_config.get_config()


def test_fastmix_default_block_n_consults_cache(cache, monkeypatch):
    from repro.kernels.fastmix import DEFAULT_BLOCK_N, default_block_n
    shape = (16, 4096)
    assert default_block_n(shape) == DEFAULT_BLOCK_N          # miss
    autotune.record("fastmix", shape, jnp.float32, {"block_n": 640})
    assert default_block_n(shape) == 640                      # hit
    monkeypatch.setenv("REPRO_FASTMIX_BLOCK_N", "128")
    assert default_block_n(shape) == 128                      # env wins


# ------------------------------------------- corrupt / partial file recovery
def test_missing_file_is_empty_cache(cache):
    assert not os.path.exists(cache)
    assert autotune.lookup("fastmix", "block_n", (4, 4), jnp.float32) is None


def test_corrupt_json_degrades_to_empty(cache):
    with open(cache, "w") as f:
        f.write("{ this is not json !!")
    assert autotune.lookup("fastmix", "block_n", (4, 4), jnp.float32) is None
    # and recording over a corrupt file heals it
    autotune.record("fastmix", (4, 4), jnp.float32, {"block_n": 256})
    assert autotune.lookup("fastmix", "block_n", (4, 4), jnp.float32) == 256
    with open(cache) as f:
        assert json.load(f)["version"] == 1


def test_partially_valid_entries_are_salvaged(cache):
    good_key = autotune.cache_key("fastmix", (16, 8192), jnp.float32)
    doc = {"version": 1, "entries": {
        good_key: {"block_n": 768},
        "mangled": "not-a-dict",                 # malformed entry: dropped
        autotune.cache_key("gram", (512, 256), jnp.float32): {
            "block_d": "sixty-four"},            # malformed tunable: miss
    }}
    with open(cache, "w") as f:
        json.dump(doc, f)
    assert autotune.lookup("fastmix", "block_n", (16, 8192),
                           jnp.float32) == 768
    assert autotune.lookup("gram", "block_d", (512, 256),
                           jnp.float32) is None
    # bool is not a valid tunable either (bool is an int subclass)
    autotune.record("gram", (512, 256), jnp.float32, {"block_d": True})
    assert autotune.lookup("gram", "block_d", (512, 256),
                           jnp.float32) is None


def test_wrong_version_is_ignored(cache):
    with open(cache, "w") as f:
        json.dump({"version": 99, "entries": {
            autotune.cache_key("fastmix", (4, 4), jnp.float32): {
                "block_n": 256}}}, f)
    assert autotune.lookup("fastmix", "block_n", (4, 4), jnp.float32) is None


def test_cache_reload_after_external_write(cache):
    """The in-process memo invalidates on mtime change (fresh writes from a
    bench process are visible without restarting)."""
    autotune.record("fastmix", (4, 4), jnp.float32, {"block_n": 256})
    assert autotune.lookup("fastmix", "block_n", (4, 4), jnp.float32) == 256
    doc = {"version": 1, "entries": {
        autotune.cache_key("fastmix", (4, 4), jnp.float32): {
            "block_n": 512}}}
    with open(cache, "w") as f:
        json.dump(doc, f)
    os.utime(cache, ns=(1, 1))       # force a distinct mtime
    assert autotune.lookup("fastmix", "block_n", (4, 4), jnp.float32) == 512


# ------------------------------------------------------ device-kind keying
def test_per_device_kind_keying(cache):
    shape, dt = (16, 8192), jnp.float32
    autotune.record("fastmix", shape, dt, {"block_n": 512},
                    device="tpu_v5e")
    autotune.record("fastmix", shape, dt, {"block_n": 1024},
                    device="tpu_v4")
    assert autotune.lookup("fastmix", "block_n", shape, dt,
                           device="tpu_v5e") == 512
    assert autotune.lookup("fastmix", "block_n", shape, dt,
                           device="tpu_v4") == 1024
    # the host's own device kind is a distinct namespace
    assert autotune.lookup("fastmix", "block_n", shape, dt) is None
    autotune.record("fastmix", shape, dt, {"block_n": 256})
    assert autotune.lookup("fastmix", "block_n", shape, dt) == 256
    assert autotune.device_kind() != ""


# ----------------------------------------------------------- measure_best
def test_measure_best_records_winner(cache):
    calls = []

    def run(candidate):
        if candidate == 13:
            raise ValueError("invalid on this host")
        calls.append(candidate)

    best = autotune.measure_best("gram", "block_d", (512, 256), jnp.float32,
                                 [13, 64, 128], run, reps=1)
    assert best in (64, 128)
    assert autotune.lookup("gram", "block_d", (512, 256),
                           jnp.float32) == best
    with pytest.raises(ValueError, match="no candidate"):
        autotune.measure_best("gram", "block_d", (1, 1), jnp.float32, [13],
                              run, reps=1)


# ----------------------------------------------- qr impl pinning via cache
def test_qr_orth_honours_cached_householder_pin(cache, monkeypatch):
    import numpy as np
    import jax
    from repro.kernels import cholqr

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((4, 32, 3)), jnp.float32)
    q_default = cholqr.qr_orth(X)
    autotune.record("cholqr", (32, 3), jnp.float32, {"householder": 1})
    q_pinned = cholqr.qr_orth(X)
    np.testing.assert_array_equal(np.asarray(q_pinned),
                                  np.asarray(jnp.linalg.qr(X)[0]))
    # env still wins over the pin
    monkeypatch.setenv(cholqr.QR_IMPL_ENV, "cholqr2")
    np.testing.assert_array_equal(np.asarray(cholqr.qr_orth(X)),
                                  np.asarray(q_default))
    monkeypatch.setenv(cholqr.QR_IMPL_ENV, "nonsense")
    with pytest.raises(ValueError, match="REPRO_QR_IMPL"):
        cholqr.qr_orth(X)
    del jax  # silence unused-import lint paths
