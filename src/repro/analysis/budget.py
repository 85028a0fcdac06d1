"""Static VMEM-footprint models for every Pallas kernel in the repo.

A Pallas kernel whose resident blocks outgrow VMEM (~16 MiB/core — see
``/opt/skills/guides`` and each kernel's docstring) fails at *compile*
time on real hardware, but the CPU interpret-mode CI never notices: tile
configs are data, not code, so a bad autotune-cache entry or an
over-ambitious default ships silently.  This pass recomputes each
kernel's per-grid-step working set from its block shapes — the same
arithmetic the kernel docstrings quote, with a 2x double-buffering
factor on streamed blocks — and fails any configuration exceeding
``VMEM_SAFETY`` x the per-device-kind budget
(:data:`repro.analysis.registry.VMEM_BUDGET_BYTES`).

Two sweeps:

* built-in defaults over :data:`registry.REPRESENTATIVE_SHAPES` — the
  shipped configuration must fit everywhere;
* every entry in the persistent autotune cache
  (:mod:`repro.kernels.autotune`) — keys carry the device kind and the
  shape bucket, so a tuned ``block_n`` recorded on one machine is
  checked against *that machine's* budget.

All models take the **padded** tile dims (the kernels' own ``_round_up``
rules), so e.g. a (8, 256, 4) problem is costed at the (128-padded)
tiles it actually allocates.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

from . import registry
from .report import PassResult

_WORD = 4        # kernels accumulate in fp32


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@dataclasses.dataclass(frozen=True)
class KernelModel:
    """VMEM model for one kernel.

    ``params`` maps tunable names to built-in defaults; ``vmem_bytes``
    takes the kernel-facing shape tuple plus a resolved param dict.
    ``shapes_for`` adapts an ``(m, d, k)`` representative problem to this
    kernel's shape convention (None -> not swept over representatives).
    """

    name: str
    params: Dict[str, int]
    vmem_bytes: Callable[[Tuple[int, ...], Dict[str, int]], int]
    shapes_for: Optional[Callable] = None
    #: shape -> built-in params, for kernels whose defaults are
    #: shape-aware (mirrors the kernel's own resolution)
    defaults_for: Optional[Callable] = None


# ------------------------------------------------------------ per-kernel math
def _fastmix_bytes(shape, p, *, tracked: bool) -> int:
    m, n = shape[0], shape[1]
    mp = _round_up(m, 128)
    bn = _round_up(min(p["block_n"], n), 128)
    # L resident + (1 or 3) streamed input tiles and 1 output tile, double
    # buffered, + prev/cur/sent working copies resident across the K rounds
    in_tiles = 3 if tracked else 1
    words = mp * mp + (2 * in_tiles + 2 + 3) * mp * bn
    return words * _WORD


def _apply_track_bytes(shape, p) -> int:
    """The kernel's own model (lazy import: jax)."""
    from repro.kernels.fastmix import apply_track_vmem_words
    return apply_track_vmem_words(*shape, p["block_d"], p["block_e"]) * _WORD


def _gram_bytes(shape, p) -> int:
    n, d = shape[-2], shape[-1]
    bd = _round_up(min(p["block_d"], max(d, 1)), 8)
    bn = _round_up(min(p["block_n"], max(n, 1)), 8)
    # two streamed (bn, bd) panels double buffered + resident (bd, bd) out
    words = 4 * bn * bd + bd * bd
    return words * _WORD


def _cholqr_bytes(shape, p) -> int:
    """CholeskyQR2's ``gram_tall`` kernel on ``(n, d, k)`` factors: the
    kernel's own model (lazy import: jax)."""
    from repro.kernels.gram import gram_tall_vmem_words
    return gram_tall_vmem_words(*shape[1:], p["block_f"], p["block_d"]) * _WORD


def _power_matmul_bytes(shape, p) -> int:
    d, k = shape[0], shape[-1]
    kp = max(128, _round_up(k, 128))
    bm = _round_up(min(p["block_m"], d), 8)
    bk = _round_up(min(p["block_k"], d), 8)
    # streamed A tile + resident W panel and output block (dbuf on stream)
    words = 2 * bm * bk + 2 * bk * kp + bm * kp
    return words * _WORD


def _flash_bytes(shape, p) -> int:
    sq, skv, hd = shape
    bq = min(p["block_q"], max(8, sq))
    bkv = min(p["block_kv"], max(8, skv))
    # q/out blocks + double-buffered k,v panels + (bq, bkv) score tile
    # and its softmax working copy
    words = 3 * bq * hd + 4 * bkv * hd + 2 * bq * bkv
    return words * _WORD


def _cholqr_defaults(shape):
    """The kernel's own shape-aware tiles (lazy import: jax)."""
    from repro.kernels.gram import gram_tall_tiles
    bf, bd = gram_tall_tiles(*shape)
    return {"block_f": bf, "block_d": bd}


def _apply_track_defaults(shape):
    """The kernel's own shape-aware default tiles (lazy import: jax)."""
    from repro.kernels.fastmix import apply_track_default_tiles
    bd, be = apply_track_default_tiles(*shape)
    return {"block_d": bd, "block_e": be}


def _rep_fastmix(m, d, k):
    return (m, d * k)


def _rep_apply_track(m, d, k):
    return (m, d, k)


def _rep_gram(m, d, k):
    return (64 * m, d)       # (n, d) raw-data panel


def _rep_cholqr(m, d, k):
    return (m, d, k)          # one factor per agent


def _rep_power_matmul(m, d, k):
    return (d, k)


def _rep_flash(m, d, k):
    return (d, d, 128)


KERNEL_MODELS: Dict[str, KernelModel] = {
    "fastmix": KernelModel(
        "fastmix", {"block_n": 512},
        lambda s, p: _fastmix_bytes(s, p, tracked=False), _rep_fastmix),
    "fastmix_track": KernelModel(
        "fastmix_track", {"block_n": 512},
        lambda s, p: _fastmix_bytes(s, p, tracked=True), _rep_fastmix),
    "apply_track": KernelModel(
        "apply_track", {"block_d": 64, "block_e": 256},
        _apply_track_bytes, _rep_apply_track,
        defaults_for=_apply_track_defaults),
    "gram": KernelModel(
        "gram", {"block_d": 128, "block_n": 512}, _gram_bytes, _rep_gram),
    "cholqr": KernelModel(
        # the tiles of the served (400, 300, 8) stack; other shapes take
        # the kernel's own (defaults_for)
        "cholqr", {"block_f": 100, "block_d": 300}, _cholqr_bytes,
        _rep_cholqr, defaults_for=_cholqr_defaults),
    "power_matmul": KernelModel(
        "power_matmul", {"block_m": 512, "block_k": 512},
        _power_matmul_bytes, _rep_power_matmul),
    "flash_attention": KernelModel(
        "flash_attention", {"block_q": 128, "block_kv": 128}, _flash_bytes,
        _rep_flash),
}

#: autotune params with no effect on the VMEM model (impl pins, timings)
_NON_TILE_PARAMS = {"householder", "us"}


def check_config(kernel: str, shape: Sequence[int],
                 params: Optional[Dict[str, int]] = None,
                 device: Optional[str] = None) -> Tuple[int, int]:
    """Returns ``(vmem_bytes, budget_bytes)`` for one configuration, against
    the budget of ``device`` (default: the device kind in use)."""
    if device is None:
        from repro.kernels.autotune import device_kind
        device = device_kind()
    model = KERNEL_MODELS[kernel]
    p = dict(model.params)
    for key, val in (params or {}).items():
        if key in model.params:
            p[key] = int(val)
    budget = registry.vmem_budget(device)     # capacity x VMEM_SAFETY
    return model.vmem_bytes(tuple(int(s) for s in shape), p), budget


def _parse_cache_key(key: str):
    """``kernel/device/bucket/dtype`` -> (kernel, device, shape tuple)."""
    parts = key.split("/")
    if len(parts) != 4:
        return None
    kernel, device, bucket, _ = parts
    try:
        shape = tuple(int(x) for x in bucket.split("x")) if bucket else ()
    except ValueError:
        return None
    return kernel, device, shape


def run(cache_path: Optional[str] = None) -> PassResult:
    """Sweep built-in defaults + the autotune cache against the budgets."""
    from repro.kernels import autotune

    result = PassResult(name="budget")

    # ---- shipped defaults must fit every representative problem ---------
    for m, d, k in registry.REPRESENTATIVE_SHAPES:
        for model in KERNEL_MODELS.values():
            if model.shapes_for is None:
                continue
            shape = model.shapes_for(m, d, k)
            defaults = (model.defaults_for(shape)
                        if model.defaults_for else None)
            used, cap = check_config(model.name, shape, defaults)
            result.checked += 1
            if used > cap:
                result.add(
                    "vmem-default", f"{model.name}{tuple(shape)}", 0,
                    f"built-in tiles need {used / 2**20:.1f} MiB VMEM, "
                    f"budget {cap / 2**20:.1f} MiB (problem m={m}, d={d}, "
                    f"k={k})")

    # ---- every recorded autotune entry against its device's budget ------
    entries = autotune._entries(cache_path)
    for key, params in sorted(entries.items()):
        parsed = _parse_cache_key(key)
        if parsed is None:
            result.add("cache-key", key, 0,
                       "unparseable autotune cache key")
            continue
        kernel, device, shape = parsed
        model = KERNEL_MODELS.get(kernel)
        if model is None:
            result.skipped.append(f"no VMEM model for cached kernel {key!r}")
            continue
        tile_params = {k_: v for k_, v in params.items()
                       if k_ in model.params}
        if not tile_params or not shape:
            # impl pins ("householder": 1) and timing-only entries
            result.skipped.append(f"no tile params in cache entry {key!r}")
            continue
        if device not in registry.VMEM_BUDGET_BYTES:
            result.add("vmem-device", key, 0,
                       f"no VMEM budget for device kind {device!r}")
            continue
        used, cap = check_config(kernel, shape, tile_params, device)
        result.checked += 1
        if used > cap:
            result.add(
                "vmem-cache", key, 0,
                f"recorded tiles {tile_params} need {used / 2**20:.1f} MiB "
                f"VMEM on {device!r}, budget {cap / 2**20:.1f} MiB")
    return result
