"""PowerStep: the paper's Alg. 1 iteration body as data + one function.

Before this module the five-line step — local power ``A_j W_j``, subspace
tracking (Eqn. 3.1), gossip (Eqn. 3.2), local QR (Eqn. 3.3), sign-adjust
(Alg. 2) — was copy-pasted across every execution substrate (two scan
bodies in ``deepca``, three loop variants in ``depca``, both ``shard_map``
step builders, the fault-tolerant runtime).  A :class:`PowerStep` captures
the *algorithmic* degrees of freedom as data:

* ``track`` — DeEPCA's subspace tracking vs. the DePCA baseline's plain
  power step (``S^t = A_j W_j`` gossiped directly);
* ``rounds`` / ``increasing`` — gossip rounds per iteration, optionally
  growing with the (global) iteration index (DePCA's increasing-consensus
  schedule, Eqn. 3.12);
* ``name`` — the algorithm label carried into results.

and :meth:`PowerStep.__call__` is the ONE definition of the iteration body.
Substrates differ only in the ``mix`` and ``apply_fn`` callables they hand
it — a stacked ``ConsensusEngine.mix_track`` (on the engine's own graph, or
on a schedule's traced ``(L, eta)`` inside a scan), or an
``engine.local_mix_track`` on a ``(1, d, k)`` slice inside ``shard_map``.
The actual tracking arithmetic lives in
:func:`repro.kernels.fastmix.tracking_update` (shared with the fused
Pallas kernel), so the whole repo has exactly one tracking compute site.

:class:`repro.core.driver.IterationDriver` runs a step under each substrate;
:func:`repro.core.algorithms.deepca` / ``depca`` are thin wrappers over it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

#: ``(S, W, G_prev)`` plus the step-dependent optional slots, in order:
#: ``W_prev`` (momentum history, ``accelerated=True``) then ``ef`` (the
#: error-feedback wire residual, ``ef_wire=True``).  Use
#: :meth:`PowerStep.carry_slots` / :meth:`PowerStep.normalize_carry` rather
#: than assuming length 3.
Carry = Tuple[jax.Array, ...]

#: ``jax.named_scope`` names of the step's three stages.  Every device op
#: a stage lowers to carries its scope in its metadata path, so a profile
#: of any substrate splits device time into apply, mix and orth.
SCOPE_APPLY = "deepca.apply"
SCOPE_MIX = "deepca.mix"
SCOPE_ORTH = "deepca.orth"


def sign_adjust(W: jax.Array, W0: jax.Array) -> jax.Array:
    """Alg. 2: flip column signs of W so <W[:,i], W0[:,i]> >= 0."""
    s = jnp.sign(jnp.sum(W * W0, axis=-2, keepdims=True))
    s = jnp.where(s == 0, 1.0, s)
    return W * s


def qr_orth(S: jax.Array) -> jax.Array:
    """Eqn. (3.3): per-agent thin-QR orthonormalisation (batched over any
    leading axes — works on stacked ``(m, d, k)`` and local ``(1, d, k)``
    slices alike).

    THE single orthonormalization compute site: every substrate, the
    streaming tracker/service, and the serve CLI route through here, so
    the implementation swap below reaches all of them at once.  Since PR 5
    it routes through batched CholeskyQR2
    (:func:`repro.kernels.cholqr.qr_orth` — Gram → Cholesky → small-matrix
    solve, twice, with a shifted-rescue pass for ill-conditioned factors),
    which replaces Householder panels with pure batched matmul work.  Up
    to column signs the result matches ``jnp.linalg.qr`` to round-off, and
    every algorithm call site applies Alg. 2 ``sign_adjust`` right after,
    which absorbs exactly that ambiguity.  ``REPRO_QR_IMPL=householder``
    (or a recorded autotune-cache winner) restores the LAPACK path
    per-process or per shape bucket.
    """
    from repro.kernels.cholqr import qr_orth as _impl
    return _impl(S)


def rebase_carry(ops, W: jax.Array, *, accelerated: bool = False,
                 ef_wire: bool = False) -> Carry:
    """Tracker restart: ``S := G_prev := A_j W_j`` on the *current* operators.

    Re-establishes Lemma 2's ``mean(S) == mean(G)`` invariant for the
    population/operators in force right now, keeping the warm ``W``.  This
    is the ONE definition of the subspace-tracker restart, shared by the
    fault-tolerance runtime (:func:`repro.runtime.fault_tolerance.kill_agents`
    restarts on the survivor population after an agent death) and the
    streaming tracker (:class:`repro.streaming.tracker.StreamingDeEPCA`
    restarts on abrupt data drift) — carrying the old ``S``/``G_prev``
    across either discontinuity would freeze the stale mean mismatch into a
    permanent bias floor.

    ``accelerated``/``ef_wire`` append the matching extra slots *zeroed*:
    the momentum history ``W_prev`` references the pre-discontinuity
    population and the EF residual compensates sends that never happened on
    the new graph — both are stale noise after a restart, so the first
    post-restart step degrades to a plain, uncompensated power step.
    """
    G0 = ops.apply(W)
    carry: Carry = (G0, W, G0)
    if accelerated:
        carry = carry + (jnp.zeros_like(G0),)
    if ef_wire:
        carry = carry + (jnp.zeros_like(G0),)
    return carry


def split_state(state) -> Tuple[Carry, Optional[jax.Array]]:
    """Split a resumable state ``(carry..., offset?)`` into its parts.

    The resumable-state contract appends a shape-``(2,)`` int32
    ``[comm_rounds, iters]`` offset after the carry slots; since the carry
    itself is variable-length (momentum / EF extras), the offset is
    identified structurally as the *trailing* 1-D length-2 integer array
    rather than positionally.  Returns ``(carry_tuple, offset_or_None)``.
    """
    import numpy as np
    state = tuple(state)
    last = state[-1] if state else None
    if last is not None and getattr(last, "ndim", None) == 1 \
            and tuple(last.shape) == (2,) \
            and np.issubdtype(last.dtype, np.integer):
        return state[:-1], last
    return state, None


@dataclasses.dataclass(frozen=True)
class PowerStep:
    """Alg. 1 / DePCA iteration body as data.

    Attributes:
      track: run the subspace-tracking update (DeEPCA) or gossip the raw
        power step (DePCA baseline).
      rounds: base gossip rounds K per power iteration.
      increasing: iteration ``t`` (global, resume-aware) gossips with
        ``rounds + t`` rounds instead of ``rounds`` (DePCA's practical fix
        for its consensus floor; forces the unrolled substrate).
      accelerated: momentum-accelerated power iterations — the QR input
        becomes ``S_new - momentum * W_prev`` (the previous *orthonormal*
        iterate, carried in an extra ``W_prev`` slot).  Momentum acts
        purely on the local orthonormalization input, so the gossiped
        tracking variable — and Lemma 2's ``mean(S) == mean(G)``
        invariant — is untouched and no extra bytes hit the wire.
      momentum: the momentum coefficient beta; the noisy-power-method
        optimum is ``lambda_{k+1}^2 / 4``.  Ignored unless ``accelerated``.
      ef_wire: carry a per-agent error-feedback residual (extra ``ef``
        slot) for the engine's quantized wire modes (``wire_dtype=
        "int8"|"fp8"``); the residual telescopes the quantization bias away
        across iterations instead of flooring accuracy at the wire
        precision.  The step only *routes* the slot — the EF arithmetic
        lives at the :func:`repro.kernels.fastmix.ef_quantize` site inside
        the engine's mix.
      name: algorithm label (``"DeEPCA"`` / ``"DePCA"``).
    """

    track: bool
    rounds: int
    increasing: bool = False
    accelerated: bool = False
    momentum: float = 0.0
    ef_wire: bool = False
    name: str = "DeEPCA"

    @classmethod
    def for_algorithm(cls, algorithm: str, K: int,
                      increasing_consensus: bool = False,
                      accelerated: bool = False, momentum: float = 0.0,
                      ef_wire: bool = False) -> "PowerStep":
        """The deepca/depca step selector (mirror of the engine selectors)."""
        if algorithm == "deepca":
            if increasing_consensus:
                raise ValueError("deepca does not use increasing consensus "
                                 "(K is eps-independent — Thm. 1)")
            return cls(track=True, rounds=K, accelerated=accelerated,
                       momentum=momentum, ef_wire=ef_wire, name="DeEPCA")
        if algorithm == "depca":
            return cls(track=False, rounds=K,
                       increasing=increasing_consensus,
                       accelerated=accelerated, momentum=momentum,
                       ef_wire=ef_wire, name="DePCA")
        raise ValueError(f"unknown algorithm {algorithm!r}")

    def rounds_at(self, t: int) -> int:
        """Gossip rounds for (global) iteration ``t``."""
        return self.rounds + t if self.increasing else self.rounds

    @property
    def carry_slots(self) -> int:
        """Number of arrays in this step's carry: 3 base slots plus
        ``W_prev`` (accelerated) plus ``ef`` (EF wire), in that order."""
        return 3 + int(self.accelerated) + int(self.ef_wire)

    def normalize_carry(self, carry: Carry) -> Carry:
        """Coerce a resumed carry to this step's slot layout.

        A legacy 3-slot ``(S, W, G_prev)`` resumed into an accelerated/EF
        step gets its extra slots synthesized as zeros (the first resumed
        iteration degrades to a plain power step, exactly like a restart);
        a carry already at ``carry_slots`` passes through.  Anything else
        is ambiguous — slots are positional — and raises.
        """
        carry = tuple(carry)
        if len(carry) == self.carry_slots:
            return carry
        if len(carry) == 3:
            zeros = jnp.zeros_like(carry[0])
            return carry + (zeros,) * (self.carry_slots - 3)
        raise ValueError(
            f"cannot resume a {len(carry)}-slot carry into a step with "
            f"carry_slots={self.carry_slots} (accelerated="
            f"{self.accelerated}, ef_wire={self.ef_wire}); slot layout is "
            "positional — rebuild the state with matching step flags")

    def init_carry(self, ops, W0: jax.Array, dtype=None) -> Carry:
        """Alg. 1 line 2: ``S^0 = G^0 = W^0`` on every agent.

        The carry is uniform across variants — DePCA simply never reads the
        ``S``/``G_prev`` slots — so resume state, checkpointing and the
        driver's substrates all share one shape.  Accelerated / EF-wire
        steps append their extra slots zeroed (no momentum history, no
        residual yet).
        """
        dt = dtype if dtype is not None else jnp.result_type(W0.dtype,
                                                             ops.dtype)
        W = jnp.broadcast_to(W0, (ops.m,) + W0.shape).astype(dt)
        return self.normalize_carry((W, W, W))

    def __call__(self, carry: Carry,
                 mix: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
                 W0: jax.Array,
                 apply_fn: Callable[[jax.Array], jax.Array],
                 apply_mix: Optional[Callable] = None
                 ) -> Tuple[Carry, Tuple[jax.Array, jax.Array]]:
        """One power iteration — the single definition of the Alg. 1 body.

        Args:
          carry: ``(S, W, G_prev[, W_prev][, ef])`` agent-stacked (or
            local-slice) state, per :meth:`carry_slots`.
          mix: consensus callable ``(S, G, G_prev) -> S_new`` — or, for
            ``ef_wire`` steps, ``(S, G, G_prev, ef) -> (S_new, ef_new)``;
            owns both the tracking-or-not decision's arithmetic (via the
            engine's ``mix_track`` family for ``track=True``) and the
            gossip rounds.
          W0: the common initialisation, for Alg. 2 sign adjustment.
          apply_fn: the local power step ``W -> A_j W_j``.
          apply_mix: optional fused half-iteration ``(S, W, G_prev) ->
            (S_new, G)`` (the engine's ``apply_mix_track`` family) that
            subsumes ``apply_fn`` + ``mix`` in one call — on the pallas
            backend with dense operators, one kernel launch.  Only
            meaningful for tracking steps; when absent (or ``track=False``)
            the classic two-call composition runs, bit-identically.
        Returns:
          ``(new_carry, (S_new, W_new))`` — scan-body shaped.
        """
        carry = tuple(carry)
        S, W, G_prev = carry[:3]
        extras = carry[3:]
        W_prev = extras[0] if self.accelerated else None
        ef = extras[-1] if self.ef_wire else None
        if apply_mix is not None and self.track and ef is None:
            # the engine names its own stages (apply, or one fused kernel
            # under mix)
            S_new, G = apply_mix(S, W, G_prev)    # fused Eqns. apply+(3.1)+(3.2)
        else:
            with jax.named_scope(SCOPE_APPLY):
                G = apply_fn(W)               # A_j W_j^t   (local compute)
            with jax.named_scope(SCOPE_MIX):
                if ef is None:
                    S_new = mix(S, G, G_prev)   # Eqns. (3.1)+(3.2) in mix
                else:
                    S_new, ef = mix(S, G, G_prev, ef)   # + EF residual
        with jax.named_scope(SCOPE_ORTH):
            # Accelerated variant: momentum acts only on the QR *input* —
            # the carried S stays the gossiped iterate, so subspace
            # tracking and the consensus invariant are exactly the
            # unaccelerated ones.
            Y = S_new - self.momentum * W_prev if self.accelerated else S_new
            W_new = sign_adjust(qr_orth(Y), W0)   # Eqn. (3.3) + Alg. 2
        new_extras = ((W,) if self.accelerated else ()) \
            + ((ef,) if self.ef_wire else ())
        return (S_new, W_new, G) + new_extras, (S_new, W_new)

    def measure(self, spec, new_carry: Carry, old_carry: Carry) -> jax.Array:
        """In-graph diagnostics for one application of this step.

        Delegates to :func:`repro.runtime.diagnostics.diag_vector` (the
        registered compute site) with this step's slot layout — the step
        owns what ``carry[1]`` / ``carry[3]`` / ``carry[-1]`` mean, so the
        driver's scan bodies never hard-code it.  Returns the stacked fp32
        observable vector ordered as ``spec.names(self)``; pure jnp, safe
        inside any traced substrate.
        """
        from repro.runtime.diagnostics import diag_vector
        return diag_vector(spec, self, new_carry, old_carry)

    def make_mix(self, engine, rounds: int = None, mixing=None):
        """Stacked-form ``mix`` callable for one iteration on a
        :class:`~repro.core.consensus.ConsensusEngine`; ``mixing`` is one
        step's traced ``(L, eta)`` of a schedule, ``None`` for the engine's
        own graph.  For ``ef_wire`` steps the callable takes/returns the EF
        residual as well."""
        r = self.rounds if rounds is None else rounds
        if self.ef_wire:
            if self.track:
                return lambda S, G, G_prev, ef: engine.mix_track(
                    S, G, G_prev, rounds=r, ef=ef, mixing=mixing)
            return lambda S, G, G_prev, ef: engine.mix(G, rounds=r, ef=ef,
                                                       mixing=mixing)
        if self.track:
            return lambda S, G, G_prev: engine.mix_track(
                S, G, G_prev, rounds=r, mixing=mixing)
        return lambda S, G, G_prev: engine.mix(G, rounds=r, mixing=mixing)

    def make_apply_mix(self, engine, ops, rounds: int = None, mixing=None):
        """Fused ``apply_mix`` callable for one iteration (``mixing`` as in
        :meth:`make_mix`), or ``None`` for non-tracking steps (DePCA
        gossips the raw power step; there is nothing to fuse the apply
        *into*) and for EF-wire steps (the EF residual threads through the
        two-call composition; the dense apply→track→mix kernel has no EF
        mirror)."""
        if not self.track or self.ef_wire:
            return None
        r = self.rounds if rounds is None else rounds
        return lambda S, W, G_prev: engine.apply_mix_track(
            S, W, G_prev, ops, rounds=r, mixing=mixing)


def mean_basis(W: jax.Array) -> jax.Array:
    """Orthonormal basis of the agents' mean estimate ``mean_j W_j``, each
    column's sign set by Alg. 2 against that mean (not left to the QR's
    own convention).

    ``W`` is ``(..., m, d, k)``; the result ``(..., d, k)``.  Trackers use
    it as Alg. 2's sign reference of the next window: a drifting subspace
    leaves any fixed start behind (a column's overlap with it crosses
    zero, and agents then split on that column's sign), while the basis
    the window resumes from stays within one window's drift of every
    agent.
    """
    Wbar = jnp.mean(W, axis=-3)
    return sign_adjust(qr_orth(Wbar), Wbar)
