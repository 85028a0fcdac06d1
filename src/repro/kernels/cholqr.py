"""Batched CholeskyQR2 orthonormalization — the Eqn. (3.3) fast path.

Every DeEPCA power iteration ends with a per-agent thin QR of the gossiped
iterate (``core/step.qr_orth``).  ``jnp.linalg.qr`` runs Householder
panels — sequential LAPACK-shaped work that maps poorly onto the MXU, and
whose batched form loops a per-matrix custom call m times.  For tall-skinny
factors the Gram-based route is the classical fix (LightLDA-style shifted
CholeskyQR; Fukaya et al.'s CholeskyQR2):

    G = X^T X          (k x k Gram — on TPU the Pallas `gram_tall`
                        kernel, one call for the whole stack of factors)
    R = chol(G)^T      (upper-triangular k x k)
    Q = X R^{-1}       (one small-matrix multiply against the tall factor)

run **twice**: one pass loses ~cond(X)^2 digits of orthogonality, the
second pass (on the now well-conditioned Q1) restores machine round-off.
Everything is batched matmul + tiny unrolled k x k linear algebra — no
per-matrix LAPACK loop, no sequential panels — which is exactly the work
accelerators (and XLA's CPU backend) run at full tilt.  It costs ~8dk^2
flops vs Householder's ~4dk^2; the crossover where the regular BLAS3 shape
wins is measured per host by ``benchmarks/bench_kernels.py`` and recorded
in ``BENCH_kernels.json`` (large d·k^2 wins on CPU too; small factors are
overhead-bound and the autotune cache can pin those buckets back to
Householder — see :func:`qr_orth`).

The k x k Cholesky and triangular inverse are deliberately **pure XLA**
(unrolled over k): ``jnp.linalg.cholesky``/``inv`` lower to per-matrix
LAPACK custom calls on CPU whose dispatch loop dominates at small k — the
very cost this module exists to remove.

Robustness (the classical CholeskyQR failure is cond(X)^2 overflowing the
Gram's precision):

* pass 1 is screened per batch element — a non-finite/degenerate Cholesky
  factor or a blown-up Gram condition estimate flags the element;
* flagged elements redo pass 1 on a **shifted** Gram ``G + s I`` (shifted
  CholeskyQR: always positive-definite), and a third pass is appended via
  ``lax.cond`` so the shift's orthogonality loss is repaired (sCQR3) —
  un-flagged runs skip the branch entirely under scan/jit (only vmapped
  substrates pay a `select`);
* ``k > d`` factors (no Gram route) and k beyond the unroll budget fall
  back to ``jnp.linalg.qr``, as does the ``REPRO_QR_IMPL=householder``
  escape hatch.

Sign convention: Cholesky R has a positive diagonal, so Q's column signs
may differ from Householder's — every algorithm call site runs Alg. 2
``sign_adjust`` right after, which absorbs exactly this ambiguity
(property-tested vs ``jnp.linalg.qr`` in tests/test_hotpath.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.runtime import config as runtime_config

from . import autotune

#: Env var selecting the orthonormalization implementation repo-wide
#: (read by ``core/step.qr_orth`` through :func:`qr_orth` at trace time):
#: ``cholqr2`` (default) or ``householder`` (the pre-PR-5 jnp.linalg.qr).
#: Owned/validated by :mod:`repro.runtime.config`.
QR_IMPL_ENV = runtime_config.ENV_QR_IMPL

#: Condition-estimate threshold (vs 1/eps) above which pass 1 re-runs on a
#: shifted Gram.  At this margin the un-shifted pass-2 Gram is still
#: comfortably positive definite (``eps * (guard/eps) = guard`` deviation
#: from identity), for f32 and f64 alike.
_COND_GUARD = 0.05

#: Largest k the unrolled small-matrix routines are generated for; beyond
#: it (not a power-iteration regime) Householder QR is used instead.
MAX_UNROLL_K = 64


def _chol_small(G: jax.Array, pivot_floor=None) -> jax.Array:
    """Pure-XLA batched Cholesky of ``(..., k, k)``, unrolled over columns.

    Column-by-column Cholesky–Banachiewicz: k steps of batched vector ops,
    no LAPACK custom call.  Non-PSD inputs produce non-finite entries
    (sqrt of a negative pivot), which is exactly the failure screen
    :func:`cholqr2` keys off.  ``pivot_floor`` (per batch element) clamps
    pivots from below — used on the rescue passes so an exactly
    rank-deficient factor degrades to a finite (range-space-orthonormal)
    result instead of NaNs; for any full-rank input the clamp is a no-op
    bit-for-bit.
    """
    k = G.shape[-1]
    L = jnp.zeros_like(G)
    for j in range(k):
        pivot = G[..., j, j] - (
            jnp.einsum("...p,...p->...", L[..., j, :j], L[..., j, :j])
            if j else 0.0)
        if pivot_floor is not None:
            pivot = jnp.maximum(pivot, pivot_floor)
        ljj = jnp.sqrt(pivot)
        if j + 1 < k:
            below = G[..., j + 1:, j] - (
                jnp.einsum("...ip,...p->...i", L[..., j + 1:, :j],
                           L[..., j, :j]) if j else 0.0)
            col = jnp.concatenate([ljj[..., None],
                                   below / ljj[..., None]], axis=-1)
        else:
            col = ljj[..., None]
        L = L.at[..., j:, j].set(col)
    return L


def _tri_inv_lower(L: jax.Array) -> jax.Array:
    """Pure-XLA inverse of batched lower-triangular ``(..., k, k)``.

    Row-wise forward substitution — k steps, each one batched small
    matvec; numerically the standard stable trsm recurrence.
    """
    k = L.shape[-1]
    eye = jnp.eye(k, dtype=L.dtype)
    M = jnp.zeros_like(L)
    for i in range(k):
        row = eye[i] - (
            jnp.einsum("...p,...pj->...j", L[..., i, :i], M[..., :i, :])
            if i else 0.0)
        M = M.at[..., i, :].set(row / L[..., i, i, None])
    return M


def _gram_nk(X: jax.Array, *, use_kernel: bool, interpret: bool) -> jax.Array:
    """``X^T X`` over the last two axes: ``(..., d, k) -> (..., k, k)``.

    ``use_kernel`` routes through the Pallas ``gram_tall`` kernel (TPU, or
    interpret mode for the wiring tests), which tiles itself from the
    shape; otherwise a HIGHEST-precision einsum — one fused batched matmul.
    """
    if use_kernel:
        from .gram import gram_tall
        return gram_tall(X, interpret=interpret)
    return jnp.einsum("...dk,...dl->...kl", X, X,
                      precision=jax.lax.Precision.HIGHEST)


def _apply_rinv(X: jax.Array, L: jax.Array) -> jax.Array:
    """``X R^{-1}`` for ``R = L^T`` — one tall batched matmul against the
    k x k inverse (substitution-built, no LAPACK)."""
    Rinv = jnp.swapaxes(_tri_inv_lower(L), -1, -2)
    return jnp.einsum("...dk,...kl->...dl", X, Rinv,
                      precision=jax.lax.Precision.HIGHEST)


def gram_condition_estimate(G: jax.Array) -> jax.Array:
    """Cheap per-element lower bound on cond_2 of a PSD Gram matrix
    (``max(diag)/min(diag)`` never overestimates for PSD); the non-finite
    Cholesky screen catches what this underestimate misses."""
    diag = jnp.diagonal(G, axis1=-2, axis2=-1)
    dmax = jnp.max(jnp.abs(diag), axis=-1)
    dmin = jnp.min(jnp.abs(diag), axis=-1)
    return dmax / jnp.maximum(dmin, jnp.finfo(G.dtype).tiny)


def _pivot_floor(G: jax.Array) -> jax.Array:
    """Per-element relative pivot clamp ``eps * trace(G) / k``.

    A full-rank pivot sits far above it (``max`` is then a bit-exact
    pass-through); an exactly-deficient pivot clamps to it instead of
    going negative, so the factor stays finite (and the diagonal screen in
    :func:`cholqr2` still flags it — a clamped pivot is by construction
    below the ``k * eps * trace`` threshold).
    """
    k = G.shape[-1]
    eps = jnp.finfo(G.dtype).eps
    return eps * jnp.trace(G, axis1=-2, axis2=-1) / k


def _chol_pass(X: jax.Array, *, use_kernel: bool,
               interpret: bool) -> jax.Array:
    """One plain (unscreened) CholeskyQR pass ``X -> Q``."""
    G = _gram_nk(X, use_kernel=use_kernel, interpret=interpret)
    return _apply_rinv(X, _chol_small(G, pivot_floor=_pivot_floor(G)))


@functools.partial(jax.jit,
                   static_argnames=("use_kernel", "interpret"))
def cholqr2(X: jax.Array, *, use_kernel: Optional[bool] = None,
            interpret: Optional[bool] = None) -> jax.Array:
    """Batched CholeskyQR2: ``(..., d, k) -> (..., d, k)`` orthonormal Q.

    fp32/bf16 inputs accumulate in fp32; f64 stays f64 end to end (the
    x64 paper-fidelity runs chase 1e-12 targets and must not round-trip).
    Ill-conditioned batch elements are rescued with a shifted first pass
    plus a conditionally-executed third pass (see module docstring).

    Jitted at definition (config kwargs static): *eager* callers — the
    streaming tracker's per-tick drift statistic, metrics on small
    factors — hit one stable program-cache entry instead of re-tracing
    the ``lax.cond`` rescue branch (whose fresh branch closures defeat
    the eager dispatch cache) on every call.  Inside an outer jit the
    nested call is inlined as usual.
    """
    d, k = X.shape[-2], X.shape[-1]
    if k > d or k > MAX_UNROLL_K:      # no Gram route / unroll budget blown
        return jnp.linalg.qr(X)[0]
    it = interpret is True
    dt = jnp.float64 if X.dtype == jnp.float64 else jnp.float32
    if dt == jnp.float64:
        # the Pallas Gram kernel accumulates in fp32; f64 factors must not
        # round-trip through it ("f64 stays f64 end to end")
        use_kernel = False
    elif use_kernel is None:
        use_kernel = it or jax.default_backend() == "tpu"
    x = X.astype(dt)
    eps = float(jnp.finfo(dt).eps)

    # ---- pass 1, screened ------------------------------------------------
    G1 = _gram_nk(x, use_kernel=use_kernel, interpret=it)
    L1 = _chol_small(G1, pivot_floor=_pivot_floor(G1))
    diag = jnp.diagonal(L1, axis1=-2, axis2=-1)
    trace = jnp.trace(G1, axis1=-2, axis2=-1)
    bad = (~jnp.all(jnp.isfinite(L1), axis=(-2, -1))
           | (jnp.min(diag, axis=-1) ** 2 <= (k * eps) * trace)
           | (gram_condition_estimate(G1) > _COND_GUARD / eps))
    # Shifted Gram rescue: s >= 11(dk + k(k+1)) eps ||X||^2 (Fukaya et
    # al.); trace bounds ||X||^2 from above — overshifting only costs
    # orthogonality that the appended third pass restores.  The k x k
    # factorisation is cheap enough to compute unconditionally; only the
    # selection depends on the screen.
    shift = 11.0 * (d * k + k * (k + 1)) * eps * trace
    Gs = G1 + shift[..., None, None] * jnp.eye(k, dtype=dt)
    L1 = jnp.where(bad[..., None, None],
                   _chol_small(Gs, pivot_floor=_pivot_floor(Gs)), L1)
    Q = _apply_rinv(x, L1)

    # ---- pass 2 (always) + conditional shifted-rescue pass 3 -------------
    Q = _chol_pass(Q, use_kernel=use_kernel, interpret=it)
    Q = jax.lax.cond(
        jnp.any(bad),
        lambda q: _chol_pass(q, use_kernel=use_kernel, interpret=it),
        lambda q: q, Q)
    return Q


def qr_orth(S: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
    """Orthonormalization entry point ``core/step.qr_orth`` routes through.

    Implementation resolution (at trace time, like every config knob here):

    1. ``RuntimeConfig.qr_impl`` (``REPRO_QR_IMPL``: ``cholqr2`` /
       ``householder``, validated by :mod:`repro.runtime.config`) —
       explicit wins;
    2. the autotune cache: a recorded ``{"householder": 1}`` for this
       (device kind, ``(d, k)`` bucket, dtype) pins the bucket back to
       ``jnp.linalg.qr`` — ``bench_kernels.py --record`` measures and
       records the per-shape winner;
    3. default: CholeskyQR2.
    """
    impl = runtime_config.get_config().qr_impl
    if impl is None:
        hh = autotune.lookup("cholqr", "householder", S.shape[-2:], S.dtype)
        impl = "householder" if hh == 1 else "cholqr2"
    if impl == "householder":
        return jnp.linalg.qr(S)[0]
    return cholqr2(S, interpret=interpret)
