"""A plain NumPy subspace tracker: DeEPCA over drifting data, written out.

The reference a tracker's answers are replayed against.  Per tick it runs
``T`` iterations of DeEPCA (arXiv:2102.03990, Alg. 1) on the tick's data
``X`` of shape ``(m, n, d)``, agent by agent, in float64:

* each agent's power step ``G_j = X_j^T (X_j W_j)``;
* subspace tracking (Eqn. 3.1) ``S_j <- S_j + G_j - G_prev_j``;
* ``K`` rounds of FastMix (Alg. 3) over the gossip graph,
  ``S^{r+1} = (1 + eta) L S^r - eta S^{r-1}`` with ``S^{-1} = S^0``,
  ``L = I - M / lambda_max(M)`` built from the graph Laplacian ``M`` and
  ``eta`` from ``L``'s second-largest eigenvalue ``lambda2`` (Section 5);
* a QR of each agent's ``S_j`` (Eqn. 3.3) and Alg. 2's sign adjustment:
  column ``i`` is flipped where its inner product with the reference's
  column ``i`` is negative.

Departures from Alg. 1 and Alg. 2, all of them a tracker's:

* the state ``(S, W, G_prev)`` is carried from one tick to the next and
  resumed on the new tick's data (a warm start; Alg. 1 starts once, from
  ``S = G_prev = W = W0``);
* a restart sets ``S = G_prev = X_j^T (X_j W_j)`` on the tick's data and
  keeps ``W``;
* the sign reference is ``W0`` for the first window only; every later
  window takes the mean basis of the state it resumes from (the QR of
  the agents' mean ``W``, each column signed against that mean), where
  Alg. 2 keeps ``W0`` throughout;
* the iterations a tick runs (a base window, a restart's rerun,
  escalation windows) are given by the caller, as a replay of what the
  program under test reported, not decided here.

No kernel, no batching over problems, nothing of the program under test:
NumPy only.  ``precision="bfloat16"`` is the control: every product's
operands rounded to bfloat16 and summed in float32, the state and the
QRs in float32.
"""
from __future__ import annotations

from typing import Optional

import numpy as np


def erdos_renyi_adjacency(m: int, p: float, seed: int) -> np.ndarray:
    """The ``(m, m)`` 0/1 adjacency of a connected Erdos-Renyi graph: each
    pair an edge with probability ``p`` from ``default_rng(seed)``; a draw
    that is not connected is redrawn from ``seed + 1``, ``seed + 2``, ..."""
    for attempt in range(1000):
        rng = np.random.default_rng(seed + attempt)
        upper = np.triu(rng.random((m, m)) < p, k=1).astype(np.float64)
        adj = upper + upper.T
        seen, todo = {0}, [0]
        while todo:
            i = todo.pop()
            for j in np.nonzero(adj[i])[0]:
                if int(j) not in seen:
                    seen.add(int(j))
                    todo.append(int(j))
        if len(seen) == m:
            return adj
    raise RuntimeError("no connected Erdos-Renyi graph in 1000 draws")


def mixing_matrix(adj: np.ndarray) -> np.ndarray:
    """``L = I - M / lambda_max(M)``, ``M`` the graph Laplacian."""
    M = np.diag(adj.sum(axis=1)) - adj
    return np.eye(len(adj)) - M / np.linalg.eigvalsh(M)[-1]


def fastmix_eta(L: np.ndarray) -> float:
    """Alg. 3's momentum from ``L``'s second-largest eigenvalue."""
    lam2 = np.linalg.eigvalsh(L)[-2]
    s = np.sqrt(1.0 - lam2 ** 2)
    return float((1.0 - s) / (1.0 + s))


class ReferenceTracker:
    """One tenant's tracker: ``m`` agents on the graph ``adj``, ``K``
    FastMix rounds per iteration, started from the ``(d, k)`` basis
    ``W0``."""

    def __init__(self, adj: np.ndarray, K: int, W0,
                 precision: str = "float64"):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"precision must be float64 or bfloat16, got "
                             f"{precision!r}")
        self.precision = precision
        self.dtype = np.float64 if precision == "float64" else np.float32
        self.L = mixing_matrix(np.asarray(adj, np.float64))
        self.eta = fastmix_eta(self.L)
        self.K = int(K)
        self.W0 = np.asarray(W0, self.dtype)
        self.state: Optional[tuple] = None      # (S, W, G_prev)

    # -------------------------------------------------------- arithmetic
    def _mm(self, a, b):
        """``a @ b`` in the tracker's precision."""
        if self.precision == "float64":
            return np.matmul(np.asarray(a, np.float64),
                             np.asarray(b, np.float64))
        from ml_dtypes import bfloat16
        return np.matmul(np.asarray(a).astype(bfloat16).astype(np.float32),
                         np.asarray(b).astype(bfloat16).astype(np.float32))

    def _apply(self, X, W):
        """``G_j = X_j^T (X_j W_j)`` for every agent."""
        XW = self._mm(X, W)
        return self._mm(np.swapaxes(X, -1, -2), XW)

    def _fastmix(self, S):
        m = S.shape[0]
        prev = cur = S.reshape(m, -1)
        for _ in range(self.K):
            nxt = (1.0 + self.eta) * self._mm(self.L, cur) - self.eta * prev
            prev, cur = cur, nxt.astype(self.dtype)
        return cur.reshape(S.shape)

    @staticmethod
    def _sign_adjust(W, ref):
        s = np.sign(np.sum(W * ref, axis=-2, keepdims=True))
        return W * np.where(s == 0, 1.0, s)

    def mean_basis(self) -> np.ndarray:
        """The QR of the agents' mean ``W``, columns signed against it."""
        Wbar = self.state[1].mean(axis=0)
        return self._sign_adjust(np.linalg.qr(Wbar)[0], Wbar)

    def reference(self) -> np.ndarray:
        """The next window's sign reference."""
        return self.W0 if self.state is None else self.mean_basis()

    # ------------------------------------------------------------ ticks
    def window(self, X, T: int) -> None:
        """``T`` iterations on the data ``X`` (``(m, n, d)``)."""
        X = np.asarray(X, self.dtype)
        ref = self.reference()
        if self.state is None:
            W = np.broadcast_to(self.W0, (X.shape[0],) + self.W0.shape)
            self.state = (W, W, W)
        S, W, G_prev = self.state
        for _ in range(int(T)):
            G = self._apply(X, W)
            S = self._fastmix(S + G - G_prev)
            W = self._sign_adjust(np.linalg.qr(S)[0], ref)
            G_prev = G
        self.state = (S, W, G_prev)

    def restart(self, X) -> None:
        """``S = G_prev = X_j^T (X_j W_j)`` on ``X``, ``W`` kept."""
        W = self.state[1]
        G = self._apply(np.asarray(X, self.dtype), W)
        self.state = (G, W, G)

    def tick(self, X, T: int, *, restarted: bool = False,
             escalations: int = 0, escalate_T: Optional[int] = None
             ) -> np.ndarray:
        """One tick as the program reported it: the base window, a
        restart and its rerun, then ``escalations`` windows of
        ``escalate_T`` iterations.  Returns the ``(m, d, k)`` estimate."""
        self.window(X, T)
        if restarted:
            self.restart(X)
            self.window(X, T)
        for _ in range(int(escalations)):
            self.window(X, escalate_T or T)
        return self.state[1]
