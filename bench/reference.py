"""The plain reference: top-k subspaces in host float64 and the tan-theta
distance, as ``chip_smoke.py`` computes them.  Imports nothing of the
program."""
from __future__ import annotations

import numpy as np


def ref_basis(X, k: int) -> np.ndarray:
    """Top-k eigenvectors of the mean covariance ``(1/m) sum_j X_j^T X_j``
    of data ``(m, n, d)``, in host f64."""
    X = np.asarray(X, np.float64)
    m, n, d = X.shape
    flat = X.reshape(m * n, d)
    C = flat.T @ flat / m
    evals, evecs = np.linalg.eigh(C)
    return evecs[:, np.argsort(evals)[::-1][:k]]


def local_basis(X0, k: int) -> np.ndarray:
    """Top-k right singular vectors of one agent's ``(n, d)`` rows, its
    local basis (a data-derived start), as float32."""
    return np.linalg.svd(np.asarray(X0, np.float64),
                         full_matrices=False)[2][:k].T.astype(np.float32)


def tan_f64(U: np.ndarray, W) -> float:
    """tan theta_k between span(U) and span(W) in host f64 (Eqn. 2.2)."""
    Q = np.linalg.qr(np.asarray(W, np.float64))[0]
    c = np.linalg.svd(U.T @ Q, compute_uv=False).min()
    s = np.linalg.norm(Q - U @ (U.T @ Q), ord=2)
    return float(s / max(c, 1e-300))


def worst_agent_tan(U: np.ndarray, W_stack) -> float:
    """The largest tan-theta of any agent's ``(d, k)`` basis against ``U``
    (:func:`tan_f64` over the stack at once); ``inf`` for a basis that is
    not finite."""
    W_stack = np.asarray(W_stack)
    if not np.all(np.isfinite(W_stack)):
        return float("inf")
    Q = np.linalg.qr(np.asarray(W_stack, np.float64))[0]
    c = np.linalg.svd(U.T @ Q, compute_uv=False).min(axis=-1)
    s = np.linalg.svd(Q - U @ (U.T @ Q), compute_uv=False).max(axis=-1)
    return float(np.max(s / np.maximum(c, 1e-300)))


def bf16_basis(X, k: int):
    """The control: :func:`ref_basis` computed from bfloat16 data with
    bfloat16 products (float32 accumulation and eigensolver), the nearest
    precision below the configurations' float32.  Runs on the device."""
    import jax.numpy as jnp
    Xb = jnp.asarray(X).astype(jnp.bfloat16)
    m = Xb.shape[0]
    C = jnp.einsum("mnd,mne->de", Xb, Xb,
                   preferred_element_type=jnp.float32) / m
    evals, evecs = jnp.linalg.eigh(C)
    return np.asarray(evecs[:, ::-1][:, :k], np.float32)
