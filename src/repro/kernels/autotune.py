"""Persistent kernel autotuner: a JSON cache of winning block sizes.

PR 4 tuned exactly one knob (``REPRO_FASTMIX_BLOCK_N``) for exactly one
kernel.  This module generalises that into a tiny persistent autotuner
shared by every Pallas kernel in the repo (``fastmix``, ``gram``,
``power_matmul``, ``cholqr``, ``apply_track``): a JSON file maps

    <kernel>/<device kind>/<padded shape bucket>/<dtype>  ->  {param: value}

and each kernel consults it through its ``block_* = None`` defaults, so a
tuned machine transparently runs tuned tile sizes with **zero code or env
changes**.  Resolution precedence (checked per lookup, in order):

1. an explicit integer argument at the call site (never touched here);
2. the kernel's config override (e.g. ``RuntimeConfig.fastmix_block_n``,
   fed by ``REPRO_FASTMIX_BLOCK_N`` through
   :mod:`repro.runtime.config`) — the one-flag experiment workflow keeps
   working and always wins;
3. a cache entry for (kernel, device kind, shape bucket, dtype), read
   only when ``REPRO_AUTOTUNE_CACHE`` names a file — without it nothing
   outside the checkout decides what a kernel compiles;
4. the kernel's built-in default.

The cache is *populated* offline by the benchmark sweeps
(``benchmarks/bench_mixing.py --block-n --record`` /
``benchmarks/bench_kernels.py --record``) through :func:`measure_best`, or
on first use when ``REPRO_AUTOTUNE=1`` opts into in-process measurement.
Lookups never measure anything by default — library calls stay cheap and
deterministic.  Env parsing lives in :mod:`repro.runtime.config`; this
module only sees pre-validated values.

File format (``version`` guards future migrations)::

    {"version": 1,
     "entries": {"fastmix/cpu/16x8192/float32": {"block_n": 512,
                                                 "us": 41.2}}}

Robustness: a missing, corrupt, or partially-valid cache file never raises
— unreadable JSON degrades to an empty cache, malformed individual entries
are skipped while valid ones are kept (tested in tests/test_autotune.py).
Writes are atomic (tmp + ``os.replace``) so a crashed bench cannot corrupt
a good cache.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Callable, Dict, Iterable, Optional

from repro.runtime import telemetry
from repro.runtime import config as runtime_config

#: Env var overriding the cache file location (owned by runtime.config).
CACHE_ENV = runtime_config.ENV_AUTOTUNE_CACHE

#: Env var enabling measure-on-first-use (off by default: library calls
#: never time-sweep unless the user opts in; owned by runtime.config).
AUTOTUNE_ENV = runtime_config.ENV_AUTOTUNE

_VERSION = 1

# in-process memo of parsed cache files:
#   path -> (mtime_ns or None, entries, last_stat_monotonic)
_MEMO: Dict[str, tuple] = {}

#: How long (seconds) a memoized cache file is trusted before re-stat'ing.
#: Lookups sit on eager per-round hot paths (engines resolve
#: ``block_n=None`` on every non-jitted ``mix()`` call), so the stat round
#: is amortised; in-process :func:`record` invalidates immediately, and an
#: *external* writer (a bench process tuning while a server runs) becomes
#: visible within a second.  Tests pin this to 0 for determinism.
_STAT_TTL = 1.0


def default_cache_path() -> Optional[str]:
    """``RuntimeConfig.autotune_cache`` (i.e. ``$REPRO_AUTOTUNE_CACHE``),
    or ``None``: without it no cache file is read, so what a kernel
    compiles is built from the repository's own defaults alone."""
    return runtime_config.get_config().autotune_cache


_DEVICE_KIND: Optional[str] = None


def device_kind() -> str:
    """Cache-key device identifier: the accelerator kind, else the platform.

    ``device_kind`` distinguishes TPU generations (``TPU v4`` vs ``TPU
    v5e`` want different tile widths); on CPU hosts it degrades to the
    platform name so cross-machine CPU caches at least bucket together.
    Memoized for the process lifetime — ``jax.devices()`` costs tens of
    microseconds per call and the device set cannot change under us, while
    :func:`resolve` sits on eager per-round hot paths (engines resolve
    ``block_n=None`` at every non-jitted ``mix()`` call).
    """
    global _DEVICE_KIND
    if _DEVICE_KIND is None:
        import jax
        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", "") or dev.platform
        _DEVICE_KIND = str(kind).strip().replace(" ", "_").lower()
    return _DEVICE_KIND


def _next_pow2(x: int) -> int:
    x = max(int(x), 1)
    return 1 << (x - 1).bit_length()


def shape_bucket(shape: Iterable[int]) -> str:
    """Pad each dim up to a power of two: one cache entry serves the whole
    bucket of nearby shapes (a tuned tile width is insensitive to the last
    few rows)."""
    return "x".join(str(_next_pow2(s)) for s in shape)


def cache_key(kernel: str, shape: Iterable[int], dtype,
              device: Optional[str] = None) -> str:
    import jax.numpy as jnp
    dev = device if device is not None else device_kind()
    return f"{kernel}/{dev}/{shape_bucket(shape)}/{jnp.dtype(dtype).name}"


# ----------------------------------------------------------------- file IO
def _load_entries(path: str) -> Dict[str, dict]:
    """Parse the cache file; never raises.

    Corrupt JSON -> empty cache.  A valid JSON document with malformed
    pieces (wrong version, ``entries`` not a dict, non-dict entry values,
    non-int tunables) keeps every salvageable entry and drops the rest.
    """
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(doc, dict) or doc.get("version") != _VERSION:
        return {}
    raw = doc.get("entries")
    if not isinstance(raw, dict):
        return {}
    out: Dict[str, dict] = {}
    for key, val in raw.items():
        if isinstance(key, str) and isinstance(val, dict):
            out[key] = val
    return out


def _entries(path: Optional[str] = None) -> Dict[str, dict]:
    p = path if path is not None else default_cache_path()
    if p is None:
        return {}
    now = time.monotonic()
    memo = _MEMO.get(p)
    if memo is not None and now - memo[2] < _STAT_TTL:
        return memo[1]
    try:
        mtime = os.stat(p).st_mtime_ns
    except OSError:
        mtime = None
    if memo is not None and memo[0] == mtime:
        _MEMO[p] = (mtime, memo[1], now)
        return memo[1]
    entries = _load_entries(p) if mtime is not None else {}
    _MEMO[p] = (mtime, entries, now)
    return entries


def record(kernel: str, shape: Iterable[int], dtype, params: dict, *,
           device: Optional[str] = None, path: Optional[str] = None) -> str:
    """Merge ``params`` (plus optional metadata like ``us``) into the cache
    entry for (kernel, device, bucket, dtype); atomic write.  Returns the
    cache key written."""
    p = path if path is not None else default_cache_path()
    if p is None:
        raise ValueError(
            f"no autotune cache file to record into: set {CACHE_ENV} or "
            "pass path=")
    key = cache_key(kernel, shape, dtype, device=device)
    entries = dict(_entries(p))
    merged = dict(entries.get(key, {}))
    merged.update(params)
    entries[key] = merged
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p) or ".",
                               prefix=".autotune-")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump({"version": _VERSION, "entries": entries}, f, indent=1,
                      sort_keys=True)
        os.replace(tmp, p)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _MEMO.pop(p, None)
    return key


def lookup(kernel: str, param: str, shape: Iterable[int], dtype, *,
           device: Optional[str] = None,
           path: Optional[str] = None) -> Optional[int]:
    """Cached tunable for (kernel, device, bucket, dtype), or None."""
    key = cache_key(kernel, shape, dtype, device=device)
    entry = _entries(path).get(key)
    val = None if entry is None else entry.get(param)
    if isinstance(val, bool) or not isinstance(val, int) or val <= 0:
        val = None         # malformed tunable: treat as a miss, not an error
    if telemetry.enabled():
        telemetry.emit("autotune", kernel=kernel, param=param, key=key,
                       hit=val is not None, value=val)
    return val


def resolve(kernel: str, param: str, shape: Iterable[int], dtype, *,
            default: int, override: Optional[int] = None,
            path: Optional[str] = None) -> int:
    """Full precedence chain: explicit override > cache entry > default.

    ``override`` is the pre-validated config value for this knob (e.g.
    ``RuntimeConfig.fastmix_block_n``) — env-string parsing happens in
    :mod:`repro.runtime.config`, where a set-but-invalid value raises
    (silently ignoring a typo'd override is how benchmark campaigns go
    wrong).
    """
    if override is not None:
        return int(override)
    cached = lookup(kernel, param, shape, dtype, path=path)
    if cached is not None:
        return cached
    return int(default)


def autotune_enabled() -> bool:
    """True when ``REPRO_AUTOTUNE`` opts into measure-on-first-use."""
    return runtime_config.get_config().autotune


def measure_best(kernel: str, param: str, shape: Iterable[int], dtype,
                 candidates: Iterable[int], run: Callable[[int], None], *,
                 reps: int = 3, path: Optional[str] = None,
                 device: Optional[str] = None) -> int:
    """Time ``run(candidate)`` for each candidate, record the winner, and
    return it.  This is the population entry point the bench sweeps (and
    the opt-in first-use path) share."""
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            run(cand)                       # compile / warm
            t0 = time.perf_counter()
            for _ in range(reps):
                run(cand)
            dt = (time.perf_counter() - t0) / reps
        except Exception:
            continue                        # candidate invalid on this host
        if dt < best_t:
            best, best_t = int(cand), dt
    if best is None:
        raise ValueError(f"no candidate for {kernel}.{param} survived "
                         f"measurement on this host")
    record(kernel, shape, dtype, {param: best, "us": round(best_t * 1e6, 1)},
           path=path, device=device)
    return best
