"""The contract registry: every machine-checked invariant, declared once.

This module is pure data (no jax import) — the single place a contributor
touches to:

* register a new **compute site** (e.g. a second legitimate home for the
  tracking arithmetic) by adding its ``(file, function)`` to the matching
  :class:`ComputeSite.allowed` set;
* widen or narrow the **bare-assert ban** scope (:data:`ASSERT_QUARANTINE`);
* widen the **env-config ownership** set (:data:`ENV_CONFIG_ALLOWED`) —
  who may touch ``REPRO_*`` env vars / mutate ``jax.config``;
* quarantine a seed module the **deadcode** pass flags
  (:data:`DEADCODE_QUARANTINE`) instead of deleting it;
* adjust the **VMEM budget** (:data:`VMEM_BUDGET_BYTES`) or the
  representative shape grid the budget pass sweeps.

The passes in :mod:`.lint`, :mod:`.tracecheck`, :mod:`.retrace`,
:mod:`.budget` and :mod:`.deadcode` all read their ground truth from here,
so the registry *is* the contract surface later PRs (async gossip,
int8/fp8 wire) must extend rather than bypass.
"""
from __future__ import annotations

import dataclasses
import os
from typing import FrozenSet, Tuple

#: Absolute path of the ``src`` directory the AST passes scan.
SRC_ROOT = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def src_path(rel: str) -> str:
    """Absolute path of a registry-relative source file."""
    return os.path.join(SRC_ROOT, rel)


# --------------------------------------------------------------------------
# Single-compute-site registry (lint pass)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ComputeSite:
    """One paper-level operation that must have exactly one home.

    Attributes:
      name: contract id, used in violation messages.
      pattern: which AST matcher in :mod:`.lint` recognises the operation
        (``tracking`` / ``linalg-qr`` / ``wire-roundtrip`` / ``def``).
      definition: ``(relpath, function)`` of the canonical definition; the
        lint pass fails if it disappears (registry rot guard).
      allowed: every ``(relpath, function)`` where the pattern may occur.
        In-kernel mirrors (VMEM-tile arithmetic that cannot call a jnp
        helper) are registered here explicitly.
      doc: why the contract exists — rendered in violation messages so a
        failing build teaches the fix.
    """

    name: str
    pattern: str
    definition: Tuple[str, str]
    allowed: FrozenSet[Tuple[str, str]]
    doc: str


COMPUTE_SITES: Tuple[ComputeSite, ...] = (
    ComputeSite(
        name="tracking-update",
        pattern="tracking",
        definition=("repro/kernels/fastmix.py", "tracking_update"),
        allowed=frozenset({
            ("repro/kernels/fastmix.py", "tracking_update"),
            # in-kernel mirrors: the combine runs on VMEM-resident tiles
            # inside the fused launches and cannot call out to jnp helpers
            ("repro/kernels/fastmix.py", "_fastmix_track_kernel"),
            ("repro/kernels/fastmix.py", "_fastmix_track_ef_kernel"),
            ("repro/kernels/fastmix.py", "_apply_track_kernel"),
        }),
        doc="Eqn. (3.1) subspace tracking `S + G - G_prev` must route "
            "through repro.kernels.fastmix.tracking_update (or its "
            "registered in-kernel mirrors)",
    ),
    ComputeSite(
        name="qr-orth",
        pattern="linalg-qr",
        definition=("repro/core/step.py", "qr_orth"),
        allowed=frozenset({
            # the registered Householder fallbacks behind the qr_orth seam
            ("repro/kernels/cholqr.py", "cholqr2"),
            ("repro/kernels/cholqr.py", "qr_orth"),
        }),
        doc="Eqn. (3.3) orthonormalization must route through "
            "repro.core.step.qr_orth (which owns the CholeskyQR2/"
            "Householder implementation swap); direct jnp.linalg.qr "
            "bypasses the REPRO_QR_IMPL / autotune-cache contract",
    ),
    ComputeSite(
        name="quantize-wire",
        pattern="wire-roundtrip",
        definition=("repro/kernels/fastmix.py", "quantize_wire"),
        allowed=frozenset({
            ("repro/kernels/fastmix.py", "quantize_wire"),
            ("repro/kernels/fastmix.py", "ef_quantize"),
            # in-kernel mirrors of the wire send rounding
            ("repro/kernels/fastmix.py", "_rounds"),
            ("repro/kernels/fastmix.py", "_rounds_ef"),
            ("repro/kernels/fastmix.py", "_apply_track_kernel"),
        }),
        doc="wire rounding (bf16/int8/fp8) must route through "
            "repro.kernels.fastmix.quantize_wire (or its registered "
            "in-kernel mirrors) so every wire path shares one rounding "
            "rule and the fp32-accumulation contract stays checkable",
    ),
    ComputeSite(
        name="ef-transmit",
        pattern="def",
        definition=("repro/compression/ef.py", "ef_transmit"),
        allowed=frozenset({
            ("repro/compression/ef.py", "ef_transmit"),
            # the gossip hot loop inlines the same identity on VMEM tiles
            ("repro/kernels/fastmix.py", "ef_quantize"),
            ("repro/kernels/fastmix.py", "_rounds_ef"),
            ("repro/kernels/fastmix.py", "_fastmix_ef_kernel"),
            ("repro/kernels/fastmix.py", "_fastmix_track_ef_kernel"),
        }),
        doc="error-feedback transmit (y = x + err; sent = Q(y); "
            "err' = (y - sent) * decay) must route through "
            "repro.compression.ef.ef_transmit or the registered gossip "
            "mirrors (fastmix.ef_quantize and the fused kernels) so the "
            "residual update rule has one auditable definition",
    ),
    ComputeSite(
        name="rebase-carry",
        pattern="def",
        definition=("repro/core/step.py", "rebase_carry"),
        allowed=frozenset({
            ("repro/core/step.py", "rebase_carry"),
        }),
        doc="the tracker-restart rebase (S := G_prev := A W) must have "
            "exactly one definition, repro.core.step.rebase_carry, shared "
            "by fault tolerance and the streaming tracker",
    ),
    ComputeSite(
        name="fleet-select-carry",
        pattern="def",
        definition=("repro/streaming/fleet.py", "select_carry"),
        allowed=frozenset({
            ("repro/streaming/fleet.py", "select_carry"),
        }),
        doc="the fleet's masked per-slot carry update (the branchless "
            "restart/escalation select over the batched tracker state) "
            "must have exactly one definition, "
            "repro.streaming.fleet.select_carry — a second mask rule "
            "forks which tenants a drift pass actually touches",
    ),
    ComputeSite(
        name="fleet-scatter-carry",
        pattern="def",
        definition=("repro/streaming/fleet.py", "scatter_carry"),
        allowed=frozenset({
            ("repro/streaming/fleet.py", "scatter_carry"),
        }),
        doc="the fleet's slot admission scatter (join/evict writes into "
            "the batched carry) must have exactly one definition, "
            "repro.streaming.fleet.scatter_carry; restart arithmetic "
            "itself stays home in repro.core.step.rebase_carry — the "
            "fleet adds no second home for it",
    ),
    ComputeSite(
        name="diag-observables",
        pattern="def",
        definition=("repro/runtime/diagnostics.py", "diag_vector"),
        allowed=frozenset({
            ("repro/runtime/diagnostics.py", "diag_vector"),
        }),
        doc="the in-graph diagnostic reductions (max-over-agents consensus "
            "residual, sign-aligned movement, EF residual norm, momentum "
            "magnitude) must have exactly one definition, "
            "repro.runtime.diagnostics.diag_vector — every driver "
            "substrate measures through PowerStep.measure so the observable "
            "semantics (and the diag-off bit-identity guarantee) cannot "
            "fork per call site",
    ),
)

#: Function names whose *re-definition* outside the registered files is a
#: duplicate-compute-site violation even when the body's arithmetic evades
#: the pattern matchers (shadowing the seam is as bad as bypassing it).
RESERVED_DEFS = {
    "tracking_update": ("repro/kernels/fastmix.py",),
    "quantize_wire": ("repro/kernels/fastmix.py",),
    "ef_quantize": ("repro/kernels/fastmix.py",),
    "ef_transmit": ("repro/compression/ef.py",),
    "rebase_carry": ("repro/core/step.py",),
    "diag_vector": ("repro/runtime/diagnostics.py",),
    "select_carry": ("repro/streaming/fleet.py",),
    "scatter_carry": ("repro/streaming/fleet.py",),
    "qr_orth": ("repro/core/step.py", "repro/kernels/cholqr.py"),
    # kernels/ops.py holds the public delegating wrapper (same seam)
    "cholqr2": ("repro/kernels/cholqr.py", "repro/kernels/ops.py"),
}


# --------------------------------------------------------------------------
# Bare-assert ban scope (lint pass)
# --------------------------------------------------------------------------
#: Dotted-module prefixes *exempt* from the bare-assert ban.  These are the
#: quarantined LM-training scaffold modules from the seed (see
#: DEADCODE_QUARANTINE): they are exercised by tier-1 tests but sit outside
#: the decentralized-PCA library surface, so `-O` stripping their asserts
#: cannot silently corrupt a PCA run.  Everything else under src/ must
#: raise (`validate_*`-style) instead of asserting.
ASSERT_QUARANTINE: Tuple[str, ...] = (
    "repro.models",
    "repro.configs",
    "repro.optim",
    "repro.roofline",
    "repro.launch.train",
    "repro.launch.dryrun",
    "repro.launch.mesh",
    "repro.launch.sharding",
    "repro.launch.specs",
    "repro.launch.steps",
)


# --------------------------------------------------------------------------
# Env/config ownership (env-config lint pass)
# --------------------------------------------------------------------------
#: Files (src-relative, "/"-separated) allowed to read/write ``REPRO_*``
#: environment variables and mutate ``jax.config``.  Exactly one entry by
#: design: :mod:`repro.runtime.config` is the typed owner of the whole
#: knob surface (parsing, validation, precedence); every other module
#: consumes ``get_config()`` / ``configure()``.  Widening this set is a
#: reviewed decision, not a convenience.
ENV_CONFIG_ALLOWED: FrozenSet[str] = frozenset({
    "repro/runtime/config.py",
})


# --------------------------------------------------------------------------
# Deadcode reachability (deadcode pass)
# --------------------------------------------------------------------------
#: The public entry-point modules reachability is computed from: the
#: paper-facing algorithm surface, the serving/streaming front ends, the
#: distributed runtime, and this analysis package itself.
ENTRY_POINTS: Tuple[str, ...] = (
    "repro.core",                    # deepca/depca + engines + driver
    "repro.streaming",               # StreamingDeEPCA + PCAService
    "repro.compression",             # DeEPCA-PowerSGD gradient compression
    "repro.runtime.fault_tolerance",
    "repro.checkpoint",
    "repro.launch.serve",            # python -m repro.launch.serve
    "repro.analysis",                # python -m repro.analysis
)

#: Modules the deadcode pass may find unreachable from ENTRY_POINTS but
#: which are deliberately KEPT: the LM-training scaffold the repo grew
#: from.  They are tier-1-test-covered (tests import them directly) and
#: `launch.serve --workload lm` reaches the model stack lazily, so they
#: stay, quarantined, until a PR replaces their tests.  A quarantined
#: module that becomes runtime-reachable again is reported as a *stale*
#: quarantine entry so the list cannot rot.
DEADCODE_QUARANTINE: Tuple[str, ...] = (
    "repro.launch.train",
    "repro.launch.dryrun",
    "repro.launch.mesh",
    "repro.launch.sharding",
    "repro.launch.specs",
    "repro.roofline.analysis",
)


# --------------------------------------------------------------------------
# VMEM budgets (budget pass)
# --------------------------------------------------------------------------
#: Per-device-kind VMEM capacity in bytes.  Keys match
#: :func:`repro.kernels.autotune.device_kind` strings (lower-case,
#: underscore-separated); a kind that is not listed is an error, not a
#: default.  ~16 MiB/core is the v4/v5e scoped-VMEM figure from the Pallas
#: guide; CPU interpret-mode runs are held to the same budget so a
#: CPU-tuned cache cannot pin a config that OOMs the day the job lands on
#: a TPU.
VMEM_BUDGET_BYTES = {
    "cpu": 16 * 1024 * 1024,
    "tpu_v3": 16 * 1024 * 1024,
    "tpu_v4": 16 * 1024 * 1024,
    "tpu_v5_lite": 16 * 1024 * 1024,
    "tpu_v5p": 16 * 1024 * 1024,
}

#: Fraction of VMEM a single kernel's working set may claim.  Headroom
#: covers what the footprint model cannot see: compiler-managed scratch,
#: semaphores, and the second copy of any buffer Mosaic chooses to
#: double-buffer beyond the ones the model already doubles.
VMEM_SAFETY = 0.9

#: Representative (m, d, k) problem shapes the budget pass sweeps the
#: *built-in* block defaults over — the shipped bench grid plus the largest
#: shape any test/bench touches.  Autotune-cache entries are additionally
#: checked at their own recorded bucket shapes.
REPRESENTATIVE_SHAPES: Tuple[Tuple[int, int, int], ...] = (
    (8, 256, 4),
    (16, 512, 8),
    (16, 1024, 16),
    (16, 2048, 32),
    (64, 4096, 32),
    (50, 300, 5),            # the paper's w8a split (Section 5)
)


def vmem_budget(device: str) -> int:
    """Usable VMEM bytes for a device kind (capacity x safety factor)."""
    if device not in VMEM_BUDGET_BYTES:
        raise ValueError(
            f"no VMEM budget for device kind {device!r}; add it to "
            f"VMEM_BUDGET_BYTES (known: {', '.join(VMEM_BUDGET_BYTES)})")
    return int(VMEM_BUDGET_BYTES[device] * VMEM_SAFETY)
