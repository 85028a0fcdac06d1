"""ConsensusEngine: one gossip subsystem, three pluggable backends.

Before this module, FastMix existed in three divergent forms — the stacked
einsum loop (:mod:`repro.core.mixing`), the ``shard_map`` collectives
(:mod:`repro.core.gossip_shard`), and the K-unrolled local loop
(:func:`repro.core.gossip_shard.fastmix_local`) — each hand-wired into its
caller.  The engine puts them behind one object that the step/driver layer
(:class:`repro.core.step.PowerStep` via
:class:`repro.core.driver.IterationDriver` — which
:func:`repro.core.algorithms.deepca`/:func:`~repro.core.algorithms.depca`
and :class:`repro.core.gossip_shard.DistributedDeEPCA` wrap) and
:func:`repro.launch.steps.make_train_step_compressed` consume, and is
the seam later scaling work (async gossip, time-varying topologies,
multi-mesh) plugs into.  The ``mix_track`` family
(:meth:`ConsensusEngine.mix_track`, :meth:`ConsensusEngine.local_mix_track`)
additionally fuses the DeEPCA subspace-tracking combine into the gossip
call — on the ``pallas`` backend inside the kernel launch itself.

Backends
--------
``stacked``
    Per-round dense mixing ``einsum('ij,j...->i...')`` on the agent-major
    array.  The bit-reference all other backends are property-tested
    against.
``pallas``
    Fused execution: **one** launch runs all K Chebyshev rounds.  On TPU
    (or with ``interpret=True`` anywhere) this is the Pallas kernel
    :func:`repro.kernels.fastmix.fastmix_fused`, which keeps both iterate
    buffers resident in VMEM across rounds instead of making K HBM
    round-trips.  On hosts where the kernel cannot compile it lowers to the
    algebraically fused :func:`repro.kernels.fastmix.fastmix_poly`
    (``S_out = P_K(L) S`` — one pass over the iterate).
``shard_map``
    Device-distributed gossip: agents live on devices along a named mesh
    axis; ring/hypercube topologies lower to ``collective_permute``
    (nearest-neighbour ICI traffic only), dense ones to one ``all_gather``
    per round.

Backend-selection rules (``backend="auto"``)
--------------------------------------------
* TPU default backend  -> ``pallas`` (the fused kernel is the hot path);
* anything else        -> ``stacked`` (the reference path; the fused
  fallback changes fp round-off, so off-TPU it is opt-in).
* ``shard_map`` is **never** auto-selected: it requires a mesh whose
  ``axis`` has exactly ``topology.m`` devices.  Pass it explicitly (or a
  ``mesh``) when you have one.

Variants
--------
``fastmix``  Chebyshev-accelerated gossip (Prop. 1; the DeEPCA default).
``naive``    plain gossip ``S <- L S`` (the DePCA / Xiao-Boyd baseline);
             internally just ``eta = 0``, so every backend supports it.
:meth:`ConsensusEngine.for_algorithm` encodes the deepca/depca mapping.

Dynamic topologies
------------------
Remark 3 of the paper: FastMix only needs the graph to be connected at each
round, not fixed.  :class:`DynamicConsensusEngine` runs gossip over a
:class:`~repro.core.schedule.TopologySchedule` (step -> topology) without
retracing the hot path:

* ``stacked`` / ``pallas`` consume the mixing matrix ``L`` and momentum
  ``eta`` as **traced operands** (the ``mixing=(L, eta)`` argument of
  :meth:`ConsensusEngine.mix` and its siblings) — the jit cache is keyed
  on shape, so any same-``m`` graph swap reuses the compiled computation.
  ``deepca(schedule=...)`` stacks the per-step ``(T, m, m)`` matrices and
  scans over them.
* ``shard_map`` keeps the ``collective_permute`` lowering only while the
  mixing matrix *structurally* matches a ring/hypercube
  (:func:`repro.core.gossip_shard.ring_structure` /
  :func:`~repro.core.gossip_shard.hypercube_structure` verify the actual
  matrix, not the name); any degraded/rewired graph falls back to the dense
  ``all_gather`` round, whose ``(L, eta)`` ride along as replicated operands
  so dense-to-dense swaps never retrace.  Structured graphs get one compiled
  step each (cached per topology name).
* agent-death degradation changes ``m`` and therefore cannot be expressed as
  an in-scan swap; it is handled segment-wise by
  :func:`repro.runtime.fault_tolerance.deepca_with_failures` (degrade ->
  compact state -> resume), with the same engines underneath.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .mixing import fastmix, fastmix_eta, fastmix_wire, fastmix_wire_ef, \
    naive_mix
from .step import SCOPE_APPLY, SCOPE_MIX
from .topology import Topology

BACKENDS = ("auto", "stacked", "pallas", "shard_map")
VARIANTS = ("fastmix", "naive")
WIRE_DTYPES = (None, "bf16", "int8", "fp8")
#: Wire modes coarse enough to need error feedback: the engines' ``mix`` /
#: ``mix_track`` take and return a per-agent ``ef`` residual for these
#: (``PowerStep(ef_wire=True)`` carries it in the iteration state), so the
#: quantization bias telescopes away instead of flooring the error.
EF_WIRE_DTYPES = ("int8", "fp8")

#: Default mesh-axis name for the shard_map backend.
AXIS = "agents"

#: Relative per-send rounding floor of each wire mode — the unit roundoff
#: of one quantized gossip send (fp32 eps, bf16's 8 mantissa bits, int8's
#: half-step at a per-agent symmetric scale, fp8-e4m3's unit roundoff).
#: This is the magnitude scale where a *plain* quantized wire stops making
#: progress; EF wires (:data:`EF_WIRE_DTYPES`) telescope the bias below
#: it.  Consumed by the engines' :meth:`~ConsensusEngine
#: .quantization_floor`, stamped on ``diag`` telemetry events, and used
#: by the health monitor's stalled-movement rule to judge whether a
#: measured plateau sits at the wire's precision floor.
WIRE_QUANT_FLOOR = {
    None: 2.0 ** -23,
    "bf16": 2.0 ** -8,
    "int8": 2.0 ** -8,
    "fp8": 2.0 ** -4,
}


def resolve_backend(backend: str) -> str:
    """Apply the module-level selection rules; returns a concrete backend."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "stacked"


def _variant_eta(variant: str, lambda2: float) -> float:
    """Chebyshev momentum; 0.0 degenerates every backend to naive gossip."""
    return 0.0 if variant == "naive" else fastmix_eta(lambda2)


def _resolve_mesh(mesh, m: int, axis: str):
    """The shard_map backends' mesh: the caller's, or all host devices."""
    if mesh is not None:
        return mesh
    from jax.sharding import Mesh
    import numpy as np
    devs = jax.devices()
    if len(devs) != m:
        raise ValueError(
            f"shard_map backend needs a mesh with {m} devices along "
            f"{axis!r}; have {len(devs)} devices and no mesh was supplied")
    return Mesh(np.asarray(devs), (axis,))


def _use_pallas_kernel(interpret: Optional[bool]) -> bool:
    """True when the pallas backend runs the real kernel (TPU) or the
    interpret-mode kernel (tests); False -> the algebraic fallback."""
    return interpret is True or jax.default_backend() == "tpu"


def _check_ef(wire_dtype: Optional[str], ef) -> bool:
    """Validate the caller's ``ef`` residual against the wire mode.

    Returns True when the call must run the error-feedback path (an EF
    wire mode with a residual supplied); raises on the two mismatches so
    a dropped or spurious residual fails loudly instead of silently
    changing convergence behaviour.
    """
    if wire_dtype in EF_WIRE_DTYPES:
        if ef is None:
            raise ValueError(
                f"wire_dtype {wire_dtype!r} carries an error-feedback "
                "residual; pass ef= (zeros_like the iterate on the first "
                "call / after a restart)")
        return True
    if ef is not None:
        raise ValueError(
            f"ef= is only meaningful for the EF wire modes "
            f"{EF_WIRE_DTYPES}; this engine's wire_dtype is {wire_dtype!r}")
    return False


def _fused_dtype(S: jax.Array):
    """The fused paths accumulate in fp32; f64 iterates stay f64."""
    return jnp.float64 if S.dtype == jnp.float64 else jnp.float32


def _fused_track_mix(S: jax.Array, G: jax.Array, G_prev: jax.Array,
                     L: jax.Array, eta, rounds: int, *,
                     interpret: Optional[bool], block_n: Optional[int],
                     wire: bool = False) -> jax.Array:
    """Fused tracking+gossip dispatch (pallas backend, any ``(L, eta)``).

    Same dtype/precision contract as :func:`_fused_mix`; the subspace-
    tracking combine rides inside the fused launch so the tracked iterate
    never round-trips through HBM.
    """
    from repro.kernels import fastmix as _fm
    if S.dtype == jnp.float64:
        x = _fm.tracking_update(S, G, G_prev)
        L64 = L.astype(jnp.float64)
        if wire:
            return fastmix_wire(x, L64, eta, rounds)
        return _fm.fastmix_poly(x, L64, eta, rounds)
    L32 = L.astype(jnp.float32)
    if _use_pallas_kernel(interpret):
        out = _fm.fastmix_track_fused(S, G, G_prev, L32, eta, rounds,
                                      block_n=block_n,
                                      interpret=interpret is True,
                                      wire_bf16=wire)
        return out.astype(S.dtype)
    x = _fm.tracking_update(S, G, G_prev)
    if wire:        # quantization is nonlinear: no P_K(L) collapse exists
        return fastmix_wire(x.astype(jnp.float32), L32, eta,
                            rounds).astype(S.dtype)
    return _fm.fastmix_poly(x.astype(jnp.float32), L32, eta,
                            rounds).astype(S.dtype)


def _fused_mix(S: jax.Array, L: jax.Array, eta, rounds: int, *,
               interpret: Optional[bool], block_n: Optional[int],
               wire: bool = False) -> jax.Array:
    """Fused-backend dispatch for the engine's own or a traced ``(L, eta)``.

    fp32 accumulation in both fused paths; cast back so the engine
    preserves the caller's dtype like the stacked reference does.
    Exception: f64 iterates (x64 workloads chasing <1e-8 targets) must not
    round-trip through fp32, so they take the polynomial path in full f64 —
    still fused, no precision cliff.  ``wire`` (bf16 wire mode) forces the
    per-round path off-TPU: quantized sends cannot be collapsed into
    ``P_K(L)``.
    """
    from repro.kernels import fastmix as _fm
    if S.dtype == jnp.float64:
        L64 = L.astype(jnp.float64)
        if wire:
            return fastmix_wire(S, L64, eta, rounds)
        return _fm.fastmix_poly(S, L64, eta, rounds)
    L32 = L.astype(jnp.float32)
    if _use_pallas_kernel(interpret):
        out = _fm.fastmix_fused(S, L32, eta, rounds, block_n=block_n,
                                interpret=interpret is True, wire_bf16=wire)
        return out.astype(S.dtype)
    if wire:
        return fastmix_wire(S.astype(jnp.float32), L32, eta,
                            rounds).astype(S.dtype)
    return _fm.fastmix_poly(S, L32, eta, rounds).astype(S.dtype)


def _fused_mix_ef(S: jax.Array, ef: jax.Array, L: jax.Array, eta,
                  rounds: int, *, interpret: Optional[bool],
                  block_n: Optional[int], wire: str):
    """EF-wire counterpart of :func:`_fused_mix` -> ``(S_out, ef_out)``.

    Quantized sends can never collapse into ``P_K(L)``, so there is no
    polynomial fallback: fp8 (scale-free, elementwise) runs the true
    in-kernel EF mirror :func:`repro.kernels.fastmix.fastmix_ef_fused`
    when the kernel fires; int8's per-agent scale is a cross-tile
    reduction the column-tiled kernel cannot see, so it (and every
    off-kernel/f64 case) runs the per-round stacked reference.
    """
    from repro.kernels import fastmix as _fm
    if S.dtype == jnp.float64:
        return fastmix_wire_ef(S, ef, L.astype(jnp.float64), eta, rounds,
                               wire_dtype=wire)
    L32 = L.astype(jnp.float32)
    if wire == "fp8" and _use_pallas_kernel(interpret):
        out, ef_out = _fm.fastmix_ef_fused(S, ef, L32, eta, rounds,
                                           wire=wire, block_n=block_n,
                                           interpret=interpret is True)
    else:
        out, ef_out = fastmix_wire_ef(
            S.astype(jnp.float32), ef.astype(jnp.float32), L32, eta,
            rounds, wire_dtype=wire)
    return out.astype(S.dtype), ef_out.astype(S.dtype)


def _fused_track_mix_ef(S: jax.Array, G: jax.Array, G_prev: jax.Array,
                        ef: jax.Array, L: jax.Array, eta, rounds: int, *,
                        interpret: Optional[bool], block_n: Optional[int],
                        wire: str):
    """EF-wire counterpart of :func:`_fused_track_mix` -> ``(S_out, ef_out)``.

    Same dispatch rules as :func:`_fused_mix_ef`; the fp8 kernel runs the
    subspace-tracking combine in-register ahead of the EF rounds.
    """
    from repro.kernels import fastmix as _fm
    if S.dtype == jnp.float64:
        x = _fm.tracking_update(S, G, G_prev)
        return fastmix_wire_ef(x, ef, L.astype(jnp.float64), eta, rounds,
                               wire_dtype=wire)
    L32 = L.astype(jnp.float32)
    if wire == "fp8" and _use_pallas_kernel(interpret):
        out, ef_out = _fm.fastmix_track_ef_fused(
            S, G, G_prev, ef, L32, eta, rounds, wire=wire,
            block_n=block_n, interpret=interpret is True)
    else:
        x = _fm.tracking_update(S, G, G_prev)
        out, ef_out = fastmix_wire_ef(
            x.astype(jnp.float32), ef.astype(jnp.float32), L32, eta,
            rounds, wire_dtype=wire)
    return out.astype(S.dtype), ef_out.astype(S.dtype)


@dataclasses.dataclass(frozen=True)
class ConsensusEngine:
    """Gossip consensus over a fixed topology with a pluggable backend.

    Attributes:
      topology: gossip graph; its mixing matrix drives every backend.
      K: default number of gossip rounds per :meth:`mix` call.
      backend: gossip backend; ``"auto"`` is resolved to a concrete choice
        at construction, so after ``__init__`` this always reads
        ``stacked``/``pallas``/``shard_map``.
      variant: ``"fastmix"`` (Chebyshev momentum) or ``"naive"`` (eta=0).
      mesh: optional ``jax.sharding.Mesh`` for the shard_map backend; when
        absent one is built on demand from ``jax.devices()`` (which must
        then have exactly ``topology.m`` devices).
      axis: mesh-axis name the shard_map backend gossips along.
      interpret: Pallas interpret-mode override for the pallas backend —
        ``None``/``False`` pick the real kernel on TPU and the fused
        polynomial fallback elsewhere; ``True`` forces the kernel in
        interpret mode on any host (used by the cross-backend parity
        tests).
      block_n: column-tile width of the fused kernel launches; ``None``
        (default, recommended) defers to the kernels, which resolve it at
        trace time through ``RuntimeConfig.fastmix_block_n``
        (``REPRO_FASTMIX_BLOCK_N`` via :mod:`repro.runtime.config`) and
        then the persistent autotune cache (:mod:`repro.kernels.autotune`)
        keyed on (device kind, shape bucket, dtype) — so a tuned machine
        runs tuned tiles with no engine change.
      wire_dtype: gossip **wire** precision — ``None`` (full precision),
        ``"bf16"``, ``"int8"`` or ``"fp8"``: each round's *sent* iterate
        is quantized (bf16 halves wire bytes; int8/fp8 quarter them) while
        the tracking combine, the Chebyshev recursion state and the QR all
        keep accumulating in fp32 (f64 stays f64).  The sub-bf16 modes are
        **error-feedback** wires (:data:`EF_WIRE_DTYPES`): :meth:`mix` /
        :meth:`mix_track` then take and return a per-agent ``ef`` residual
        (``PowerStep(ef_wire=True)`` carries it in the iteration state) so
        the coarse quantizer's bias telescopes away instead of flooring
        tan-theta like a plain low-precision wire would.  Supported on the
        ``stacked`` and ``pallas`` backends; per-round quantization cannot
        collapse into ``P_K(L)``, so the off-TPU pallas fallback runs the
        per-round wire loop (fp8 gets a true in-kernel EF mirror, int8's
        per-agent scale is a cross-tile reduction so it always runs the
        stacked reference).
    """

    topology: Topology
    K: int
    backend: str = "auto"
    variant: str = "fastmix"
    mesh: Optional[object] = None
    axis: str = AXIS
    interpret: Optional[bool] = None
    block_n: Optional[int] = None
    wire_dtype: Optional[str] = None
    # per-(rounds, traced) cache of jitted shard_map mix fns (jax's dispatch
    # cache is keyed on function identity, so rebuilding the closure per
    # call would re-trace every time)
    _sharded_mix_cache: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)
    # per-dtype cache of the device-resident mixing matrix, so eager hot
    # loops don't re-upload the (m, m) array on every mix() call
    _L_cache: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)

    def __post_init__(self):
        if self.variant not in VARIANTS:
            raise ValueError(
                f"variant must be one of {VARIANTS}, got {self.variant!r}")
        object.__setattr__(self, "backend", resolve_backend(self.backend))
        if self.wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"wire_dtype must be one of {WIRE_DTYPES}, got "
                f"{self.wire_dtype!r}")
        if self.wire_dtype is not None and self.backend == "shard_map":
            raise ValueError(
                "wire_dtype is not supported on the shard_map backend "
                "(collective rounds run at the mesh's native precision); "
                "use stacked or pallas")

    # ------------------------------------------------------------- scalars
    @property
    def eta(self) -> float:
        """Chebyshev momentum; 0.0 degenerates every backend to naive gossip."""
        return _variant_eta(self.variant, self.topology.lambda2)

    @property
    def mixing_matrix(self) -> jax.Array:
        return self._L(jnp.float32)

    def _L(self, dtype) -> jax.Array:
        key = jnp.dtype(dtype).name
        arr = self._L_cache.get(key)
        if arr is None:
            # materialise eagerly even when first touched inside a trace
            # (e.g. under run_batch's jit+vmap): caching a tracer here would
            # leak it into every later mix() call on this engine
            with jax.ensure_compile_time_eval():
                arr = jnp.asarray(self.topology.mixing, dtype=dtype)
            self._L_cache[key] = arr
        return arr

    def contraction_rate(self, rounds: Optional[int] = None) -> float:
        """Prop. 1 bound for this variant after ``rounds`` gossip rounds."""
        r = self.K if rounds is None else rounds
        if self.variant == "naive":
            return self.topology.naive_rate(r)
        return self.topology.fastmix_rate(r)

    @property
    def ef_wire(self) -> bool:
        """True when this engine's wire mode carries an EF residual."""
        return self.wire_dtype in EF_WIRE_DTYPES

    def bytes_per_round(self, d: int, k: int) -> int:
        """Wire bytes ONE agent sends per gossip round for a ``(d, k)``
        iterate.

        Full precision sends fp32 (4 B/entry), ``bf16`` 2, ``int8``/
        ``fp8`` 1; int8 additionally ships one fp32 per-agent scale per
        round.  Exact for the stacked/pallas backends (shard_map rejects
        wire modes and gossips at native mesh precision).
        """
        from repro.kernels.fastmix import WIRE_ITEMSIZE
        n = int(d) * int(k) * WIRE_ITEMSIZE[self.wire_dtype]
        if self.wire_dtype == "int8":
            n += 4
        return n

    def quantization_floor(self) -> float:
        """This wire mode's relative per-send rounding floor
        (:data:`WIRE_QUANT_FLOOR`) — the diag-event / health-rule yardstick
        for "is this plateau the wire's fault"."""
        return WIRE_QUANT_FLOOR[self.wire_dtype]

    # ------------------------------------------------- stacked-form mixing
    def _mixing(self, mixing, dtype):
        """``(L, eta)`` in ``dtype``: the engine's own constants, or the
        traced ``mixing`` pair a schedule scans over (any same-``m`` graph
        then reuses the compiled program)."""
        if mixing is None:
            return self._L(dtype), self.eta
        L, eta = mixing
        return L.astype(dtype), eta

    def _check_agents(self, S: jax.Array, mixing):
        m = self.topology.m if mixing is None else mixing[0].shape[-1]
        if S.shape[0] != m:
            raise ValueError(
                f"leading (agent) axis {S.shape[0]} != topology m={m}")

    def mix(self, S: jax.Array, rounds: Optional[int] = None, *,
            ef: Optional[jax.Array] = None, mixing=None):
        """Mix stacked ``(m, ...)`` agent variables; preserves the mean.

        ``rounds`` overrides the engine default K (static per call — this
        is what DePCA's increasing-consensus schedule uses).  ``mixing``
        replaces the engine's own ``(L, eta)`` with a traced pair (one
        step of :meth:`DynamicConsensusEngine.operands`); on ``shard_map``
        it takes the dense ``all_gather`` round, the one lowering valid for
        every graph.  On EF wire modes (:data:`EF_WIRE_DTYPES`) the
        per-agent residual ``ef`` is required and the call returns
        ``(S_out, ef_out)``; otherwise it returns ``S_out`` alone.
        """
        r = self.K if rounds is None else int(rounds)
        ef_mode = _check_ef(self.wire_dtype, ef)
        if r <= 0:
            return (S, ef) if ef_mode else S
        self._check_agents(S, mixing)
        if self.backend == "shard_map":
            return self._mix_shard_map(S, r, mixing)
        if self.backend == "pallas":
            L, eta = self._mixing(mixing, _fused_dtype(S))
            if ef_mode:
                return _fused_mix_ef(S, ef, L, eta, r,
                                     interpret=self.interpret,
                                     block_n=self.block_n,
                                     wire=self.wire_dtype)
            return _fused_mix(S, L, eta, r, interpret=self.interpret,
                              block_n=self.block_n,
                              wire=self.wire_dtype is not None)
        L, eta = self._mixing(mixing, S.dtype)
        if ef_mode:
            return fastmix_wire_ef(S, ef, L, eta, r,
                                   wire_dtype=self.wire_dtype)
        if self.wire_dtype is not None:
            return fastmix_wire(S, L, eta, r)
        if self.variant == "naive":
            return naive_mix(S, L, r)
        return fastmix(S, L, eta, r)

    def mix_track(self, S: jax.Array, G: jax.Array, G_prev: jax.Array,
                  rounds: Optional[int] = None, *,
                  ef: Optional[jax.Array] = None, mixing=None):
        """Fused Eqns. (3.1)+(3.2): gossip the subspace-tracked iterate.

        Semantically ``mix(tracking_update(S, G, G_prev))`` on every
        backend; the ``pallas`` backend runs the combine inside the fused
        launch (one fewer HBM pass per power iteration), the others fall
        through to :meth:`mix` on the shared tracking compute site.
        ``mixing`` as in :meth:`mix`.  EF wire modes require ``ef`` and
        return ``(S_out, ef_out)``.
        """
        r = self.K if rounds is None else int(rounds)
        ef_mode = _check_ef(self.wire_dtype, ef)
        if self.backend == "pallas" and r > 0:
            self._check_agents(S, mixing)
            L, eta = self._mixing(mixing, _fused_dtype(S))
            if ef_mode:
                return _fused_track_mix_ef(
                    S, G, G_prev, ef, L, eta, r, interpret=self.interpret,
                    block_n=self.block_n, wire=self.wire_dtype)
            return _fused_track_mix(S, G, G_prev, L, eta, r,
                                    interpret=self.interpret,
                                    block_n=self.block_n,
                                    wire=self.wire_dtype is not None)
        from repro.kernels.fastmix import tracking_update
        return self.mix(tracking_update(S, G, G_prev), rounds=rounds, ef=ef,
                        mixing=mixing)

    def apply_mix_track(self, S: jax.Array, W: jax.Array, G_prev: jax.Array,
                        ops, rounds: Optional[int] = None, *, mixing=None):
        """The whole gossip half-iteration, fused: local apply + Eqn. (3.1)
        combine + Eqn. (3.2) gossip -> ``(S_new, G)``.

        On the ``pallas`` backend with *dense* operators the
        :func:`repro.kernels.fastmix.apply_track_fused` kernel computes
        ``G = A_j W_j`` tile-by-tile and feeds ``S + G - G_prev`` straight
        into the Chebyshev rounds — ``G`` is written to HBM exactly once
        (as the next ``G_prev``) instead of written-then-reread between two
        launches.  Everywhere else (Gram-form data operators, off-TPU
        hosts, f64, non-pallas backends) it is the bit-equal composition
        ``ops.apply`` + :meth:`mix_track` — which on the off-TPU pallas
        backend IS the poly fallback the acceptance test pins.  ``mixing``
        as in :meth:`mix`.
        """
        if self.ef_wire:
            raise ValueError(
                "apply_mix_track does not thread the EF residual; EF wire "
                f"modes {EF_WIRE_DTYPES} compose ops.apply with "
                "mix_track(..., ef=) instead (PowerStep does this "
                "automatically when ef_wire=True)")
        r = self.K if rounds is None else int(rounds)
        if (self.backend == "pallas" and r > 0 and ops.dense is not None
                and S.dtype != jnp.float64
                and _use_pallas_kernel(self.interpret)):
            self._check_agents(S, mixing)
            L, eta = self._mixing(mixing, jnp.float32)
            from repro.kernels.fastmix import apply_track_fused
            with jax.named_scope(SCOPE_MIX):
                S_new, G = apply_track_fused(
                    ops.dense, W, S, G_prev, L, eta, r,
                    interpret=self.interpret is True,
                    wire_bf16=self.wire_dtype is not None)
            return S_new.astype(S.dtype), G.astype(S.dtype)
        with jax.named_scope(SCOPE_APPLY):
            G = ops.apply(W)
        with jax.named_scope(SCOPE_MIX):
            return self.mix_track(S, G, G_prev, rounds=rounds,
                                  mixing=mixing), G

    def _mix_shard_map(self, S: jax.Array, rounds: int, mixing=None):
        # one jitted program per (rounds, traced?): the engine's structured
        # round (collective_permute or all_gather), or -- with a traced
        # (L, eta) -- the dense all_gather round, whose replicated operands
        # let every same-m graph share the program
        key = (rounds, mixing is not None)
        fn = self._sharded_mix_cache.get(key)
        if fn is None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            from .gossip_shard import _dense_round, fastmix_local
            mesh = _resolve_mesh(self.mesh, self.topology.m, self.axis)
            axis = self.axis
            if mixing is None:
                def body(x):
                    return self.local_mix(x, axis=axis, rounds=rounds)
                in_specs = P(axis)
            else:
                def body(x, Lrep, etarep):
                    return fastmix_local(
                        x, lambda y: _dense_round(y, Lrep, axis), etarep,
                        rounds)
                in_specs = (P(axis), P(), P())
            fn = jax.jit(shard_map(body, mesh=mesh, in_specs=in_specs,
                                   out_specs=P(axis), check_vma=False))
            self._sharded_mix_cache[key] = fn
        return fn(S) if mixing is None else fn(S, *mixing)

    # -------------------------------------------- in-shard_map local mixing
    def local_round_fn(self, axis: Optional[str] = None
                       ) -> Callable[[jax.Array], jax.Array]:
        """One gossip round for a local ``(1, d, k)`` slice (inside shard_map)."""
        from .gossip_shard import make_round_fn
        return make_round_fn(self.topology, axis or self.axis)

    def local_mix(self, x: jax.Array, axis: Optional[str] = None,
                  rounds: Optional[int] = None) -> jax.Array:
        """Full K-round gossip on a local slice; call *inside* shard_map."""
        from .gossip_shard import fastmix_local
        r = self.K if rounds is None else int(rounds)
        return fastmix_local(x, self.local_round_fn(axis), self.eta, r)

    def local_mix_track(self, S: jax.Array, G: jax.Array, G_prev: jax.Array,
                        axis: Optional[str] = None,
                        rounds: Optional[int] = None) -> jax.Array:
        """Tracked :meth:`local_mix` (shard_map body of the DeEPCA step).

        The combine stays on the shared tracking compute site; per-device
        slices are small enough that XLA fuses it into the first collective
        round's input.
        """
        from repro.kernels.fastmix import tracking_update
        return self.local_mix(tracking_update(S, G, G_prev), axis=axis,
                              rounds=rounds)

    # -------------------------------------------------------- construction
    @classmethod
    def for_algorithm(cls, algorithm: str, topology: Topology, K: int, *,
                      backend: str = "auto", accelerate: bool = True,
                      **kw) -> "ConsensusEngine":
        """The deepca/depca variant selector.

        ``deepca`` and ``depca`` both gossip with FastMix when
        ``accelerate`` (the paper's setting) and plain gossip otherwise;
        DePCA's increasing-consensus schedule is expressed through the
        per-call ``rounds`` override of :meth:`mix`.  Centralising the
        mapping here keeps every algorithm entry point on the same engine.
        """
        if algorithm not in ("deepca", "depca"):
            raise ValueError(f"unknown algorithm {algorithm!r}")
        variant = "fastmix" if accelerate else "naive"
        return cls(topology=topology, K=K, backend=backend, variant=variant,
                   **kw)


@dataclasses.dataclass(frozen=True)
class DynamicConsensusEngine:
    """Gossip over a time-varying topology, without retracing the hot path.

    Wraps a :class:`~repro.core.schedule.TopologySchedule` and adds only
    what a schedule adds to :class:`ConsensusEngine`: the step's topology
    (:meth:`topology_at`), its cached static engine (:meth:`engine_at`),
    and the window's mixing matrices and momenta as ``(T, m, m)`` /
    ``(T,)`` arrays (:meth:`operands`).  Gossip itself runs on the engines
    it builds: eagerly per step (``engine_at(t).mix(S)``), or with one
    step's ``(L, eta)`` as a traced pair (``engine_at(0).mix(S,
    mixing=(L, eta))``), which is what ``deepca(schedule=...)`` scans
    over.  Options, their checks and the wire accounting
    (``bytes_per_round``, ``quantization_floor``) are the built engines'.
    """

    schedule: object                    # TopologySchedule (duck-typed)
    K: int
    backend: str = "auto"
    variant: str = "fastmix"
    mesh: Optional[object] = None
    axis: str = AXIS
    interpret: Optional[bool] = None
    block_n: Optional[int] = None       # None -> kernels resolve (autotune)
    wire_dtype: Optional[str] = None    # see ConsensusEngine.wire_dtype
    _engines: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)

    def __post_init__(self):
        # the step-0 engine checks the options and resolves the backend
        object.__setattr__(self, "backend", self.engine_at(0).backend)

    # ---------------------------------------------------------- per-step
    def topology_at(self, t: int):
        return self.schedule.topology_at(t)

    def engine_at(self, t: int) -> ConsensusEngine:
        """The step's static engine (cached per topology *object*).

        Keyed by identity, not name: schedules memoize per step, so the key
        is stable, and a user schedule that reuses one name for different
        graphs can never be served a stale engine.
        """
        topo = self.schedule.topology_at(t)
        key = (topo.name, id(topo))
        eng = self._engines.get(key)
        if eng is None:
            eng = ConsensusEngine(
                topology=topo, K=self.K, backend=self.backend,
                variant=self.variant, mesh=self.mesh, axis=self.axis,
                interpret=self.interpret, block_n=self.block_n,
                wire_dtype=self.wire_dtype)
            self._engines[key] = eng
        return eng

    def eta_of(self, topology) -> float:
        return _variant_eta(self.variant, topology.lambda2)

    def contraction_rates(self, t0: int, T: int,
                          rounds: Optional[int] = None):
        """Per-iteration Prop. 1 contraction bounds over ``[t0, t0+T)``."""
        r = self.K if rounds is None else int(rounds)
        return self.schedule.contraction_rates(
            t0, T, r, accelerate=(self.variant == "fastmix"))

    # -------------------------------------------------- traced operands
    def operands(self, t0: int, T: int, dtype=jnp.float32):
        """``(Ls, etas)`` — ``(T, m, m)`` mixing stack + ``(T,)`` momenta.

        Validates the window has constant ``m`` (scan shapes are static).
        """
        self.schedule.constant_m(t0, T)
        topos = self.schedule.topologies(t0, T)
        import numpy as np
        Ls = jnp.asarray(np.stack([tp.mixing for tp in topos]), dtype=dtype)
        etas = jnp.asarray([self.eta_of(tp) for tp in topos], dtype=dtype)
        return Ls, etas

    @property
    def ef_wire(self) -> bool:
        """True when the built engines' wire mode carries an EF residual."""
        return self.engine_at(0).ef_wire

    # -------------------------------------------------------- construction
    @classmethod
    def for_algorithm(cls, algorithm: str, schedule, K: int, *,
                      backend: str = "auto", accelerate: bool = True,
                      **kw) -> "DynamicConsensusEngine":
        """Schedule-driven counterpart of :meth:`ConsensusEngine.for_algorithm`."""
        if algorithm not in ("deepca", "depca"):
            raise ValueError(f"unknown algorithm {algorithm!r}")
        variant = "fastmix" if accelerate else "naive"
        return cls(schedule=schedule, K=K, backend=backend, variant=variant,
                   **kw)
