"""Device-side copy of ``repro.streaming.SlowRotationStream``'s law.

A tenant's rows at tick ``t`` are Gaussian with covariance
``U_j(t) diag(evals) U_j(t)^T`` on agent ``j``: the stream's spectrum
(``1 + gap * (k, ..., 1)`` on top, ``noise`` below), its basis rotated by
the Cayley rotation of angle ``rate * t`` along a fixed skew direction,
and each agent's basis a small rotation of the shared one (strength
``0.05 * heterogeneity``).  Drawn with ``jax.random`` on the device, a
whole fleet tick in one jitted program:

* the noise floor is isotropic, so only the top-k columns of each
  agent's basis shape the law: a row is ``sqrt(noise) * eta +
  zeta diag(sqrt(evals_k - noise)) U_jk(t)^T`` with ``eta``, ``zeta``
  standard normal, the same distribution the host draws through the
  full ``d x d`` basis;
* the skew direction is kept in its 2-plane form ``P blockdiag(omega_i
  J) P^T`` (``P`` a Haar draw, ``omega`` the spectrum of a drawn
  Gaussian skew matrix, scaled to spectral norm 1: the law of the host's
  ``(A - A^T) / 2`` normalised), so the Cayley rotation of angle
  ``theta`` turns plane ``i`` by ``2 atan(theta omega_i / 2)`` and
  rotating the agents' bases is one ``(d, d)`` product per tenant;
* each agent's top-k basis ``qr(U0_k + 0.05 * heterogeneity * E_j)`` is
  drawn once from ``(seed, tenant, agent)`` and taken into the planes'
  coordinates at set-up, where the host redraws it every tick.

Set-up holds, per tenant, ``P`` and ``omega`` and, per agent, its
``(d, k)`` basis in plane coordinates; a tick is one draw and two small
products.  ``P`` and ``omega`` are drawn in NumPy from ``(seed,
tenant)``: a QR and a symmetric eigensolve of ``d x d`` per tenant take
LAPACK milliseconds and the chip's solvers seconds.  The configuration's
``assumed`` block lists each difference from the host stream.
"""
from __future__ import annotations

import concurrent.futures
import functools
from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Stream(NamedTuple):
    """The fleet's drifting streams, as set up on the device."""

    P: jax.Array          # (tenants, d, d) plane bases: [u_1..u_h, v_1..v_h]
    omega: jax.Array      # (tenants, d / 2) plane rates, max 1
    D: jax.Array          # (tenants, m, d, k) agents' bases, P^T U_jk(0)
    key: jax.Array        # the ticks' draws fold the tick into this key


def _planes(seed: int, tenant: int, d: int):
    """One tenant's skew direction in 2-plane form, ``(P, omega)``."""
    rng = np.random.default_rng([int(seed), 2, int(tenant)])
    A = rng.standard_normal((d, d))
    S = 0.5 * (A - A.T)
    # S^T S has each plane's omega^2 twice: ascending, pairs are adjacent
    omega = np.sqrt(np.maximum(np.linalg.eigvalsh(S.T @ S)[1::2], 0.0))
    P = np.linalg.qr(rng.standard_normal((d, d)))[0]
    return P.astype(np.float32), (omega / omega[-1]).astype(np.float32)


def setup(seed: int, *, tenants: int, m: int, d: int, k: int,
          heterogeneity: float) -> Stream:
    """Every tenant's skew planes and agents' bases, from ``seed``."""
    if d % 2:
        raise ValueError(f"d must be even for the 2-plane form, got {d}")
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        planes = list(pool.map(lambda t: _planes(seed, t, d),
                               range(tenants)))
    key = jax.random.wrap_key_data(np.random.SeedSequence(
        [int(seed), 1]).generate_state(2).astype(np.uint32))
    return _bases(key, jnp.asarray(np.stack([p for p, _ in planes])),
                  jnp.asarray(np.stack([w for _, w in planes])),
                  m=m, k=k, heterogeneity=heterogeneity)


@functools.partial(jax.jit, static_argnames=("m", "k", "heterogeneity"))
def _bases(key, P, omega, *, m: int, k: int, heterogeneity: float) -> Stream:
    tenants, d, _ = P.shape
    k_u, k_e, k_t = jax.random.split(key, 3)
    U0 = jnp.linalg.qr(jax.random.normal(k_u, (tenants, d, k)))[0]
    E = jax.random.normal(k_e, (tenants, m, d, k))
    B = jnp.linalg.qr(U0[:, None] + 0.05 * heterogeneity * E)[0]
    D = jnp.einsum("tde,tmdk->tmek", P, B, precision=HIGHEST)
    return Stream(P=P, omega=omega, D=D, key=k_t)


def bases(stream: Stream, t, rate: float) -> jax.Array:
    """``(tenants, m, d, k)``: every agent's top-k basis at tick ``t``."""
    h = stream.omega.shape[-1]
    phi = 2.0 * jnp.arctan(0.5 * rate * t * stream.omega)   # (tenants, h)
    c = jnp.cos(phi)[:, None, :, None]
    s = jnp.sin(phi)[:, None, :, None]
    Du, Dv = stream.D[:, :, :h], stream.D[:, :, h:]
    rotated = jnp.concatenate([c * Du - s * Dv, s * Du + c * Dv], axis=2)
    return jnp.einsum("tde,tmek->tmdk", stream.P, rotated, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("ns", "rate", "gap", "noise"))
def tick(stream: Stream, t, *, ns: Sequence[int], rate: float,
         gap: float = 0.5, noise: float = 0.3):
    """Tick ``t``'s rows: a tuple with one ``(m, ns[i], d)`` float32 array
    per tenant ``i``."""
    tenants, m, d, k = stream.D.shape
    n = max(ns)
    U = bases(stream, t, rate)
    k_eta, k_zeta = jax.random.split(jax.random.fold_in(stream.key, t))
    top = 1.0 + gap * jnp.arange(k, 0, -1, dtype=jnp.float32)
    zeta = jax.random.normal(k_zeta, (tenants, m, n, k)) \
        * jnp.sqrt(top - noise)
    X = jnp.sqrt(noise) * jax.random.normal(k_eta, (tenants, m, n, d)) \
        + jnp.einsum("tmnk,tmdk->tmnd", zeta, U, precision=HIGHEST)
    return tuple(X[i, :, :ni] for i, ni in enumerate(ns))
