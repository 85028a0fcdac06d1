"""Typed runtime configuration: the single owner of the ``REPRO_*`` env surface.

Every knob the repo reads from the process environment is declared,
parsed and validated *here* — the rest of ``src/repro`` consumes the
frozen :class:`RuntimeConfig` snapshot returned by :func:`get_config` and
never touches ``os.environ`` directly (the ``env-config`` lint pass in
:mod:`repro.analysis.lint` enforces this).  Likewise all ``jax.config``
mutation (x64, platform, debug-nans, compile logging, the persistent
compile cache) goes through the
first-class setters below, in the style of bayespec's
``elisa/util/config.py`` and the olmax launch scripts.

Resolution precedence, checked per knob:

1. an explicit value — a :func:`configure` argument or an :func:`override`
   context (tests);
2. the environment variable;
3. downstream fallbacks the knob documents (e.g. the autotune cache for
   ``fastmix_block_n``, the ``householder`` pin for ``qr_impl``);
4. the built-in default.

:func:`get_config` re-reads the environment on every call (memoized on
the raw env-string tuple), so ``monkeypatch.setenv`` in tests and late
``os.environ`` edits in launch scripts take effect immediately; a
set-but-invalid value raises ``ValueError`` naming the variable (silently
ignoring a typo'd override is how benchmark campaigns go wrong).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import threading
from typing import Any, Dict, Iterator, List, Optional, Tuple

# ------------------------------------------------------------ env surface
#: QR orthonormalization override: 'cholqr2' | 'householder'.
ENV_QR_IMPL = "REPRO_QR_IMPL"
#: FastMix Pallas tile width override (positive int).
ENV_FASTMIX_BLOCK_N = "REPRO_FASTMIX_BLOCK_N"
#: Opt into autotune measure-on-first-use (boolean flag).
ENV_AUTOTUNE = "REPRO_AUTOTUNE"
#: Autotune cache file location (path).
ENV_AUTOTUNE_CACHE = "REPRO_AUTOTUNE_CACHE"
#: Default telemetry sink spec ('null' | 'log' | 'jsonl:PATH').
ENV_TELEMETRY = "REPRO_TELEMETRY"
#: Default gossip wire precision ('none'/'fp32' | 'bf16' | 'int8' | 'fp8').
ENV_WIRE_DTYPE = "REPRO_WIRE_DTYPE"
#: Accelerated (momentum) power iterations: 'off'/'0' | 'on'/'1' (default
#: momentum) | a float momentum value.
ENV_ACCEL = "REPRO_ACCEL"
#: In-graph convergence diagnostics: 'off'/'0' | 'on'/'1'/'all' | a
#: comma-list of observables (see :data:`DIAG_OBSERVABLES`).
ENV_DIAG = "REPRO_DIAG"
#: Span-tracing spec: 'off' | 'jax' | 'chrome:PATH' | 'chrome+jax:PATH'.
ENV_TRACE = "REPRO_TRACE"
#: TrackerFleet slot-pool capacity per bucket (positive int).
ENV_FLEET_SLOTS = "REPRO_FLEET_SLOTS"
#: TrackerFleet per-tick latency objective in milliseconds (positive float).
ENV_FLEET_SLO_MS = "REPRO_FLEET_SLO_MS"

#: Every env var this module owns, in field order of :class:`RuntimeConfig`.
ENV_VARS: Tuple[str, ...] = (ENV_QR_IMPL, ENV_FASTMIX_BLOCK_N, ENV_AUTOTUNE,
                             ENV_AUTOTUNE_CACHE, ENV_TELEMETRY,
                             ENV_WIRE_DTYPE, ENV_ACCEL, ENV_DIAG, ENV_TRACE,
                             ENV_FLEET_SLOTS, ENV_FLEET_SLO_MS)

QR_IMPLS = ("cholqr2", "householder")
WIRE_DTYPES = ("bf16", "int8", "fp8")
#: Observable names a ``REPRO_DIAG`` comma-list may select — the single
#: source of truth shared with :mod:`repro.runtime.diagnostics`.
DIAG_OBSERVABLES = ("consensus", "movement", "ef_residual", "momentum")
#: Momentum used when acceleration is requested as a bare flag.  The
#: optimum is problem-dependent (beta* ~ lambda_{k+1}^2 / 4 for the power
#: method); 0.25 is the spectrum-agnostic setting that is safe whenever
#: lambda_{k+1} <= 1 after normalization.
DEFAULT_MOMENTUM = 0.25

_XLA_FLAGS = "XLA_FLAGS"
_HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"

_TRUE = frozenset(("1", "true", "yes", "on"))
_FALSE = frozenset(("", "0", "false", "no", "off"))


# --------------------------------------------------------------- parsers
def _parse_qr_impl(raw: Optional[str]) -> Optional[str]:
    if raw is None or raw == "":
        return None
    impl = raw.strip().lower()
    if impl not in QR_IMPLS:
        raise ValueError(
            f"{ENV_QR_IMPL} must be 'cholqr2' or 'householder', got {raw!r}")
    return impl


def _parse_positive_int(raw: Optional[str], env: str) -> Optional[int]:
    if raw is None or raw == "":
        return None
    try:
        val = int(raw)
    except ValueError as e:
        raise ValueError(
            f"{env} must be a positive integer, got {raw!r}") from e
    if val <= 0:
        raise ValueError(f"{env} must be a positive integer, got {raw!r}")
    return val


def _parse_wire_dtype(raw: Optional[str]) -> Optional[str]:
    if raw is None:
        return None
    val = raw.strip().lower()
    if val in ("", "none", "fp32", "f32", "full"):
        return None
    if val not in WIRE_DTYPES:
        raise ValueError(
            f"{ENV_WIRE_DTYPE} must be one of none/fp32/{'/'.join(WIRE_DTYPES)}, "
            f"got {raw!r}")
    return val


def _parse_accel(raw: Optional[str]) -> Optional[float]:
    """``None`` = acceleration off; a float = the momentum to use."""
    if raw is None:
        return None
    val = raw.strip().lower()
    if val in _FALSE:
        return None
    if val in _TRUE:
        return DEFAULT_MOMENTUM
    try:
        beta = float(val)
    except ValueError as e:
        raise ValueError(
            f"{ENV_ACCEL} must be a boolean flag or a momentum in [0, 1), "
            f"got {raw!r}") from e
    if not 0.0 <= beta < 1.0:
        raise ValueError(
            f"{ENV_ACCEL} momentum must lie in [0, 1), got {raw!r}")
    return beta if beta > 0.0 else None


def _parse_diag(raw: Optional[str]) -> Optional[str]:
    """Normalized diagnostics spec: ``None`` = off, ``'on'`` = everything,
    else a validated comma-list of :data:`DIAG_OBSERVABLES`."""
    if raw is None:
        return None
    val = raw.strip().lower()
    if val in _FALSE:
        return None
    if val in _TRUE or val == "all":
        return "on"
    parts = tuple(p.strip() for p in val.split(",") if p.strip())
    bad = sorted(set(parts) - set(DIAG_OBSERVABLES))
    if bad or not parts:
        raise ValueError(
            f"{ENV_DIAG} must be a boolean flag or a comma-list of "
            f"{'/'.join(DIAG_OBSERVABLES)}, got {raw!r}")
    return ",".join(parts)


def _parse_trace(raw: Optional[str]) -> Optional[str]:
    """Validated span-tracing spec (kept as the spec string; the tracer
    itself is built lazily by :mod:`repro.runtime.tracing`)."""
    if raw is None:
        return None
    val = raw.strip()
    if val.lower() in _FALSE or val.lower() in ("none", "null"):
        return None
    if val.lower() == "jax":
        return "jax"
    for prefix in ("chrome:", "chrome+jax:"):
        if val.lower().startswith(prefix):
            if not val[len(prefix):]:
                raise ValueError(
                    f"{ENV_TRACE} spec {raw!r} needs a file path after "
                    f"'{prefix}'")
            return val
    raise ValueError(
        f"{ENV_TRACE} must be 'jax', 'chrome:PATH', 'chrome+jax:PATH' or "
        f"'off', got {raw!r}")


def _parse_positive_float(raw: Optional[str], env: str) -> Optional[float]:
    if raw is None or raw == "":
        return None
    try:
        val = float(raw)
    except ValueError as e:
        raise ValueError(
            f"{env} must be a positive number, got {raw!r}") from e
    if val <= 0:
        raise ValueError(f"{env} must be a positive number, got {raw!r}")
    return val


def _parse_bool(raw: Optional[str], env: str) -> bool:
    if raw is None:
        return False
    val = raw.strip().lower()
    if val in _TRUE:
        return True
    if val in _FALSE:
        return False
    raise ValueError(
        f"{env} must be a boolean flag (1/0/true/false/on/off), got {raw!r}")


# ---------------------------------------------------------------- config
@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Frozen snapshot of the runtime knob surface.

    ``None`` means "unset": the consumer falls through to its documented
    next precedence level (autotune cache, then built-in default).
    """

    #: QR site pin; ``None`` -> autotune ``householder`` pin -> cholqr2.
    qr_impl: Optional[str] = None
    #: FastMix tile width; ``None`` -> autotune cache -> kernel default.
    fastmix_block_n: Optional[int] = None
    #: Measure-on-first-use autotuning (library calls never time-sweep
    #: unless opted in).
    autotune: bool = False
    #: Autotune cache path; ``None`` -> no cache is read or written and
    #: every kernel runs its built-in tiles.
    autotune_cache: Optional[str] = None
    #: Default telemetry sink spec; ``None`` -> no sink installed.
    telemetry: Optional[str] = None
    #: Default gossip wire precision for engine construction through
    #: :func:`repro.core.algorithms.resolve_engines`; ``None`` -> fp32.
    wire_dtype: Optional[str] = None
    #: Default accelerated-power-iteration momentum (``None`` -> off); the
    #: value is the beta used when an entry point does not pass its own.
    accel: Optional[float] = None
    #: In-graph diagnostics spec (``None`` -> off, ``'on'``, or a
    #: comma-list) consumed by
    #: :func:`repro.runtime.diagnostics.resolve_diagnostics`.
    diag: Optional[str] = None
    #: Span-tracing spec (``None`` -> off) consumed by
    #: :func:`repro.runtime.tracing.tracer_from_spec`.
    trace: Optional[str] = None
    #: :class:`repro.streaming.fleet.TrackerFleet` slot-pool capacity per
    #: shape bucket; ``None`` -> the fleet's built-in default (8).
    fleet_slots: Optional[int] = None
    #: Fleet per-tick latency objective (milliseconds); ``None`` -> SLO
    #: accounting off.
    fleet_slo_ms: Optional[float] = None

    def describe(self) -> Dict[str, Any]:
        """JSON-serializable provenance snapshot: the resolved knobs, the
        raw ``REPRO_*`` environment, and (when jax is already imported)
        backend/device/x64 state.  Stamped into bench JSON so every
        committed snapshot records what produced it."""
        out: Dict[str, Any] = dataclasses.asdict(self)
        out["env"] = {name: os.environ[name] for name in ENV_VARS
                      if name in os.environ}
        out["xla_flags"] = os.environ.get(_XLA_FLAGS)
        if "jax" in sys.modules:
            import jax
            out["jax"] = {
                "version": jax.__version__,
                "x64": bool(jax.config.jax_enable_x64),
                "backend": jax.default_backend(),
                "device_count": jax.device_count(),
                "device_kind": getattr(jax.devices()[0], "device_kind", "")
                or jax.devices()[0].platform,
            }
        return out


_FIELDS = tuple(f.name for f in dataclasses.fields(RuntimeConfig))

_lock = threading.Lock()
_memo: Optional[Tuple[Tuple[Optional[str], ...], RuntimeConfig]] = None
_overrides: List[Dict[str, Any]] = []


def _env_snapshot() -> Tuple[Optional[str], ...]:
    return tuple(os.environ.get(name) for name in ENV_VARS)


def from_env() -> RuntimeConfig:
    """Parse the environment into a fresh :class:`RuntimeConfig`.

    Validation is eager across all knobs: one typo'd variable fails every
    consumer loudly rather than just the one that happens to read it.
    """
    (raw_qr, raw_block, raw_auto, raw_cache, raw_tel, raw_wire,
     raw_accel, raw_diag, raw_trace, raw_slots, raw_slo) = _env_snapshot()
    return RuntimeConfig(
        qr_impl=_parse_qr_impl(raw_qr),
        fastmix_block_n=_parse_positive_int(raw_block, ENV_FASTMIX_BLOCK_N),
        autotune=_parse_bool(raw_auto, ENV_AUTOTUNE),
        autotune_cache=raw_cache or None,
        telemetry=raw_tel or None,
        wire_dtype=_parse_wire_dtype(raw_wire),
        accel=_parse_accel(raw_accel),
        diag=_parse_diag(raw_diag),
        trace=_parse_trace(raw_trace),
        fleet_slots=_parse_positive_int(raw_slots, ENV_FLEET_SLOTS),
        fleet_slo_ms=_parse_positive_float(raw_slo, ENV_FLEET_SLO_MS),
    )


def get_config() -> RuntimeConfig:
    """The active config: env snapshot with any :func:`override` layers
    applied on top (innermost wins)."""
    global _memo
    key = _env_snapshot()
    with _lock:
        if _memo is None or _memo[0] != key:
            _memo = (key, from_env())
        cfg = _memo[1]
        for layer in _overrides:
            cfg = dataclasses.replace(cfg, **layer)
    return cfg


def _validate_override(kwargs: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, value in kwargs.items():
        if name not in _FIELDS:
            raise TypeError(
                f"override(): unknown RuntimeConfig field {name!r} "
                f"(known: {', '.join(_FIELDS)})")
        if value is None:
            out[name] = None
        elif name == "qr_impl":
            out[name] = _parse_qr_impl(str(value))
        elif name == "fastmix_block_n":
            out[name] = _parse_positive_int(str(value), ENV_FASTMIX_BLOCK_N)
        elif name == "autotune":
            out[name] = bool(value)
        elif name == "wire_dtype":
            out[name] = _parse_wire_dtype(str(value))
        elif name == "accel":
            out[name] = _parse_accel(str(value))
        elif name == "diag":
            out[name] = _parse_diag("on" if value is True else str(value))
        elif name == "trace":
            out[name] = _parse_trace(str(value))
        elif name == "fleet_slots":
            out[name] = _parse_positive_int(str(value), ENV_FLEET_SLOTS)
        elif name == "fleet_slo_ms":
            out[name] = _parse_positive_float(str(value), ENV_FLEET_SLO_MS)
        else:
            out[name] = str(value)
    return out


@contextlib.contextmanager
def override(**kwargs: Any) -> Iterator[RuntimeConfig]:
    """Explicit-value layer masking the environment (tests, experiments).

    Every kwarg passed is an explicit override — including ``None``,
    which masks a set env var back to "unset".  Layers nest (innermost
    wins) and are restored on exit, including on exceptions.
    """
    layer = _validate_override(kwargs)
    with _lock:
        _overrides.append(layer)
    try:
        yield get_config()
    finally:
        with _lock:
            _overrides.remove(layer)


# -------------------------------------------------- process / jax setup
def enable_x64(enable: bool = True) -> None:
    """Toggle double-precision jax arithmetic (``jax_enable_x64``)."""
    import jax
    jax.config.update("jax_enable_x64", bool(enable))


def set_platform(platform: str = "cpu") -> None:
    """Pin the jax platform (``cpu`` / ``gpu`` / ``tpu``).

    Must run before the jax backend initializes; sets ``JAX_PLATFORMS``
    for subprocesses too.
    """
    os.environ["JAX_PLATFORMS"] = platform
    import jax
    jax.config.update("jax_platforms", platform)


#: JAX's own variable naming its persistent compilation cache directory.
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Called by entry points (``serve``, ``chip_smoke.py``), never at
    import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
    it and nothing is set here.  Otherwise the cache lives in
    ``.jax_cache/`` at the checkout root, resolved from this file's path
    (``<root>/src/repro/runtime/config.py``): the directory is part of
    each entry's key, so it is never built from a temporary name, a pid
    or the time.
    """
    configured = os.environ.get(JAX_CACHE_ENV)
    if configured:
        return configured
    import jax
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_host_device_count(n: int) -> None:
    """Request ``n`` fake host devices by *appending*
    ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``.

    Existing user flags are preserved, and a user-set device-count flag
    wins outright — this call never clobbers it (the bug this replaces:
    ``launch/dryrun.py`` used to overwrite ``XLA_FLAGS`` wholesale at
    import time).  Must run before the jax backend initializes.
    """
    if int(n) <= 0:
        raise ValueError(f"host device count must be positive, got {n!r}")
    flags = os.environ.get(_XLA_FLAGS, "")
    if _HOST_DEVICE_FLAG in flags:
        return
    flag = f"{_HOST_DEVICE_FLAG}={int(n)}"
    os.environ[_XLA_FLAGS] = f"{flags} {flag}".strip()


def set_debug_nans(enable: bool = True) -> None:
    """Toggle ``jax_debug_nans`` (fail fast on NaN production)."""
    import jax
    jax.config.update("jax_debug_nans", bool(enable))


@contextlib.contextmanager
def log_compiles(enable: bool = True) -> Iterator[None]:
    """Scoped ``jax_log_compiles`` toggle, restored on exit.  The analysis
    retrace harness counts compilations through this."""
    import jax
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", bool(enable))
    try:
        yield
    finally:
        jax.config.update("jax_log_compiles", prev)


def configure(*,
              x64: Optional[bool] = None,
              platform: Optional[str] = None,
              host_device_count: Optional[int] = None,
              debug_nans: Optional[bool] = None,
              qr_impl: Optional[str] = None,
              fastmix_block_n: Optional[int] = None,
              autotune: Optional[bool] = None,
              autotune_cache: Optional[str] = None,
              telemetry: Optional[str] = None,
              wire_dtype: Optional[str] = None,
              accel: Optional[Any] = None,
              diag: Optional[Any] = None,
              trace: Optional[str] = None,
              fleet_slots: Optional[int] = None,
              fleet_slo_ms: Optional[float] = None) -> RuntimeConfig:
    """One-call process setup: x64 / platform / fake-device-count as
    first-class arguments, plus persistent ``REPRO_*`` knob assignment.

    Knob values are written to ``os.environ`` (the process's single
    source of truth) so subprocesses inherit them; ``None`` leaves a knob
    untouched.  A ``telemetry`` spec (or an inherited ``REPRO_TELEMETRY``)
    installs the corresponding sink.  Returns the resulting config.
    """
    if host_device_count is not None:
        set_host_device_count(host_device_count)
    if platform is not None:
        set_platform(platform)
    if x64 is not None:
        enable_x64(x64)
    if debug_nans is not None:
        set_debug_nans(debug_nans)
    knobs = ((ENV_QR_IMPL, qr_impl),
             (ENV_FASTMIX_BLOCK_N, fastmix_block_n),
             (ENV_AUTOTUNE, autotune),
             (ENV_AUTOTUNE_CACHE, autotune_cache),
             (ENV_TELEMETRY, telemetry),
             (ENV_WIRE_DTYPE, wire_dtype),
             (ENV_ACCEL, accel),
             (ENV_DIAG, diag),
             (ENV_TRACE, trace),
             (ENV_FLEET_SLOTS, fleet_slots),
             (ENV_FLEET_SLO_MS, fleet_slo_ms))
    for env, val in knobs:
        if val is not None:
            if isinstance(val, bool):
                os.environ[env] = "1" if val else "0"
            else:
                os.environ[env] = str(val)
    cfg = get_config()          # validates; raises on a bad assignment
    if telemetry is not None:
        from . import telemetry as _telemetry
        _telemetry.set_sink(_telemetry.sink_from_spec(cfg.telemetry))
    return cfg


def describe() -> Dict[str, Any]:
    """Module-level shorthand for ``get_config().describe()``."""
    return get_config().describe()
