"""Device-side copy of ``repro.core.operators.libsvm_like``'s arithmetic.

The paper's w8a/a9a stand-in: rows with a shared spiked structure plus
sparse {0,1} activations whose per-column rate follows a power law, the
profile rolled with the agent index (a sequential split of real data
drifts through the file).  Same distribution as the host original, drawn
with ``jax.random`` on the device in one jitted program per problem, so a
pool of problems costs no host time and no host-to-device copy.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("m", "n", "d", "sparsity",
                                             "heterogeneity", "k"))
def problem(key, *, m: int, n: int, d: int, sparsity: float = 0.85,
            heterogeneity: float = 1.0, k: int = 5) -> jax.Array:
    """One ``(m, n, d)`` float32 problem."""
    k_u, k_z, k_s = jax.random.split(key, 3)
    U = jnp.linalg.qr(jax.random.normal(k_u, (d, d), jnp.float32))[0]
    evals = jnp.full((d,), 0.1, jnp.float32).at[:k].set(
        2.0 * 0.7 ** jnp.arange(k, dtype=jnp.float32)[::-1] + 1.0)
    col_p = 0.5 / (1.0 + jnp.arange(d, dtype=jnp.float32)) ** 0.6
    shifts = np.round(np.arange(m) * d / (2 * m)).astype(np.int32)
    # np.roll(col_p, s)[c] == col_p[(c - s) % d]
    p_j = col_p[(jnp.arange(d)[None, :] - shifts[:, None]) % d]
    z = jax.random.normal(k_z, (m, n, d), jnp.float32) * jnp.sqrt(evals)
    shared = jnp.einsum("mnd,ed->mne", z, U, precision=HIGHEST)
    sparse = (jax.random.uniform(k_s, (m, n, d))
              < p_j[:, None, :] * (1.0 - sparsity) * 4).astype(jnp.float32)
    return (shared + 1.5 * heterogeneity * sparse) / np.sqrt(n)


def pool(keys, *, m: int, n: int, d: int, **kw) -> list:
    """One problem per key, each its own device array (a request's
    operators are one problem, so no request slices a shared buffer)."""
    return [problem(key, m=m, n=n, d=d, **kw) for key in keys]
