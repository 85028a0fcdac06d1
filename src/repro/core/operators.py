"""Local PSD operators ``A_j`` — explicit matrices or implicit Gram forms.

The paper stores ``A_j in R^{dxd}`` on agent j with ``A = (1/m) sum_j A_j``.
At LM scale materializing ``A_j`` is an O(d^2) memory blow-up, so we also
support the implicit Gram form ``A_j = X_j^T X_j`` (data ``X_j in R^{n x d}``)
where the power step is fused as ``X_j^T (X_j W)`` — two tall-skinny matmuls,
never forming d x d.  Section 5 of the paper (Eqn. 5.1) is exactly this Gram
construction.  Where a launch runs enough iterations over tall enough rows,
forming the Gram once costs less than reading the rows twice an iteration
(:func:`gram_form_pays`); a data operator then carries its formed Gram
beside its rows (:meth:`StackedOperators.with_gram`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# multiply-adds at HIGHEST (six bf16 MXU passes) a v5e does in the time it
# reads one float32 word: 197 TFLOP/s / 2 / 6 against 819 GB/s / 4
MACS_PER_WORD = 80


def gram_form_pays(n: int, d: int, T: int) -> bool:
    """True when a launch of ``T`` applies of ``A_j = X_j^T X_j`` costs
    less with the Gram formed than in data form, for ``(n, d)`` rows an
    agent.

    Both applies are bound by bytes, so the cost is the words read, with
    the formation's ``n*d*d`` multiply-adds counted at
    :data:`MACS_PER_WORD` a word: at ``n ~ d ~ 8192, T = 30`` forming costs
    more than the data form's whole launch.  The Grams must also be no larger
    than the rows (``d <= n``): the launch then holds at most twice its
    operands, and the Gram apply never does more multiply-adds than the
    data form's, whatever ``k``.  Shapes and ``T`` decide; there is no
    knob, and the device's free memory is not consulted.
    """
    # words a launch: data 2*T*n*d; Gram T*d*d + n*d + n*d*d/MACS_PER_WORD
    return d <= n and (T * d * d + n * d + n * d * d / MACS_PER_WORD
                       < 2 * T * n * d)


def data_gram(X: jax.Array) -> jax.Array:
    """``(m, n, d)`` rows -> ``(m, d, d)`` Grams ``X_j^T X_j`` (Eqn. 5.1)."""
    return jnp.einsum("mnd,mne->mde", X, X,
                      precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass(frozen=True)
class StackedOperators:
    """Agent-stacked local operators.

    Exactly one of ``dense`` (m, d, d) or ``data`` (m, n, d) is set.  A data
    operator may also carry ``gram`` (m, d, d), its rows' formed Grams
    (:meth:`with_gram`): :meth:`apply` then reads the Grams, not the rows.
    """

    dense: Optional[jax.Array] = None   # (m, d, d)
    data: Optional[jax.Array] = None    # (m, n, d) -> A_j = X_j^T X_j
    # (m, d, d) = data_gram(data), formed inside a launch by the driver.
    # Kept apart from ``dense`` only so the engine keeps the apply an XLA
    # product (not ``apply_track_fused``, which no benchmark reader
    # measures yet); to be folded into ``dense`` once one does.
    gram: Optional[jax.Array] = None

    def __post_init__(self):
        if (self.dense is None) == (self.data is None):
            raise ValueError("exactly one of dense/data must be given")
        if self.gram is not None and self.data is None:
            raise ValueError("gram is carried by data operators only")

    def with_gram(self) -> "StackedOperators":
        """This data operator with its Grams formed, so each :meth:`apply`
        reads ``d*d`` words an agent in place of ``2*n*d``."""
        if self.data is None:
            raise ValueError("with_gram needs a data operator")
        return dataclasses.replace(self, gram=data_gram(self.data))

    @property
    def array(self) -> jax.Array:
        """Whichever representation is set (the single source of truth)."""
        return self.dense if self.dense is not None else self.data

    @property
    def m(self) -> int:
        return self.array.shape[0]

    @property
    def d(self) -> int:
        return self.array.shape[-1]

    @property
    def dtype(self):
        """dtype :meth:`apply` promotes to (with a same-dtype operand)."""
        return self.array.dtype

    def apply(self, W: jax.Array) -> jax.Array:
        """Stacked power step: returns (m, d, k) with slice_j = A_j W_j."""
        A = self.dense if self.dense is not None else self.gram
        if A is not None:
            return jnp.einsum("mde,mek->mdk", A, W,
                              precision=jax.lax.Precision.HIGHEST)
        XW = jnp.einsum("mnd,mdk->mnk", self.data, W,
                        precision=jax.lax.Precision.HIGHEST)
        return jnp.einsum("mnd,mnk->mdk", self.data, XW,
                          precision=jax.lax.Precision.HIGHEST)

    def mean_matrix(self) -> jax.Array:
        """A = (1/m) sum_j A_j, materialized (reference / ground truth only)."""
        if self.dense is not None:
            return jnp.mean(self.dense, axis=0)
        return jnp.mean(data_gram(self.data), axis=0)

    def spectral_bound(self) -> float:
        """L with ||A_j||_2 <= L for all j (paper's Lemma 1 constant)."""
        if self.dense is not None:
            norms = jnp.linalg.norm(self.dense, ord=2, axis=(1, 2))
        else:
            norms = jax.vmap(lambda X: jnp.linalg.norm(X, ord=2) ** 2)(self.data)
        return float(jnp.max(norms))


def synthetic_spiked(m: int, d: int, k: int, *, n_per_agent: int = 64,
                     gap: float = 0.5, noise: float = 0.3, seed: int = 0,
                     heterogeneity: float = 1.0) -> StackedOperators:
    """Spiked-covariance data split across m agents (heterogeneous shards).

    Each agent draws ``n_per_agent`` samples from N(0, Sigma_j) where
    Sigma_j shares global top-k directions but has agent-specific rotations
    of strength ``heterogeneity`` in the tail — mimicking the paper's
    sequential (non-iid) libsvm split (Eqn. 5.1).
    """
    rng = np.random.default_rng(seed)
    Uglob = np.linalg.qr(rng.standard_normal((d, d)))[0]
    evals = np.ones(d) * noise
    evals[:k] = 1.0 + gap * np.arange(k, 0, -1)
    data = np.empty((m, n_per_agent, d), dtype=np.float64)
    for j in range(m):
        theta = heterogeneity * rng.standard_normal((d, d)) * 0.05
        Uj = np.linalg.qr(Uglob + theta)[0]
        z = rng.standard_normal((n_per_agent, d)) * np.sqrt(evals)
        data[j] = z @ Uj.T
    return StackedOperators(data=jnp.asarray(data, dtype=jnp.float32))


def synthetic_problem_batch(B: int, m: int, d: int, k: int, *,
                            n_per_agent: int = 64, seed: int = 0):
    """B independent spiked-covariance problems + per-problem inits.

    The shared setup of every batched-serving consumer
    (:meth:`repro.core.driver.IterationDriver.run_batch` benchmarks,
    ``launch.serve --workload pca``, the quickstart): returns
    ``(problems, W0)`` where ``problems`` is a list of B
    :class:`StackedOperators` (seeds strided so the problems differ) and
    ``W0`` is a ``(B, d, k)`` stack of orthonormal initialisations.
    """
    problems = [synthetic_spiked(m, d, k, n_per_agent=n_per_agent,
                                 seed=seed + 17 * b) for b in range(B)]
    rng = np.random.default_rng(seed)
    W0 = jnp.stack([
        jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                    jnp.float32) for _ in range(B)])
    return problems, W0


def libsvm_like(m: int, n: int, d: int, *, seed: int = 0,
                sparsity: float = 0.85, heterogeneity: float = 1.0,
                dtype=jnp.float32) -> StackedOperators:
    """Synthetic stand-in for the paper's w8a/a9a experiments.

    The container is offline, so instead of downloading libsvm files we draw
    sparse {0,1}-heavy feature vectors with a power-law column marginal (the
    statistical shape of w8a/a9a) and split them *sequentially* across agents
    exactly as Eqn. (5.1).  A sequential split of real data is heterogeneous
    (the feature distribution drifts through the file); we reproduce that by
    rotating each agent's column-activation profile with its index
    (``heterogeneity`` scales the drift) — with 0.0 the shards are i.i.d.
    and DePCA needs no consensus at all, hiding the paper's whole point.
    """
    rng = np.random.default_rng(seed)
    k = 5
    Uglob = np.linalg.qr(rng.standard_normal((d, d)))[0]
    evals = 0.1 * np.ones(d)
    evals[:k] = 2.0 * 0.7 ** np.arange(k)[::-1] + 1.0   # clean top-k gap
    col_p = 0.5 / (1.0 + np.arange(d)) ** 0.6           # power-law activation
    data = np.empty((m, n, d))
    for j in range(m):
        z = rng.standard_normal((n, d)) * np.sqrt(evals)
        shared = z @ Uglob.T                             # global structure
        shift = int(round(j * d / (2 * m)))
        pj = np.roll(col_p, shift)                       # per-agent drift
        sparse = (rng.random((n, d)) < pj * (1.0 - sparsity) * 4
                  ).astype(np.float64)
        data[j] = (shared + 1.5 * heterogeneity * sparse) / np.sqrt(n)
    return StackedOperators(data=jnp.asarray(data, dtype=dtype))


def top_k_eigvecs(A: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Ground-truth top-k eigenpairs of a symmetric matrix."""
    evals, evecs = jnp.linalg.eigh(A)
    order = jnp.argsort(evals)[::-1]
    return evecs[:, order[:k]], evals[order]
