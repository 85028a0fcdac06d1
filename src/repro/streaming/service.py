"""Dynamic-batching PCA request front-end over the batched driver.

:meth:`repro.core.driver.IterationDriver.run_batch` serves B problems from
ONE compiled program — but only if the B problems share shapes.  Real
request traffic is ragged: every request brings its own sample count
``n``, component count ``k`` (and padded batches arrive in whatever size
the queue happens to hold).  This module closes that gap with classic
serving-system machinery:

* **shape bucketing** — requests are keyed by their *padded* problem shape
  (``n`` rounded up to ``pad_n``, ``k`` to ``pad_k``, batch size to a
  power of two up to ``max_batch``), so a whole ragged workload collapses
  onto a handful of compiled programs that live in the driver's
  ``run_batch`` cache.  Padding is mathematically exact where it must be:
  zero sample rows leave ``X^T X`` unchanged, and extra orthonormal
  ``W0`` columns ride along without touching the leading ``k`` (every
  stage of the iteration — local apply, tracking, gossip, thin QR, sign
  adjust — treats columns independently, and Householder QR's leading-k
  columns depend only on the leading-k input columns);
* **admission policy** — a bucket is launched when it holds ``max_batch``
  requests, or when its oldest request has waited ``max_wait`` seconds
  (:meth:`PCAService.poll`; the clock is injectable so tests and
  simulations are deterministic);
* **cache accounting** — every launch is classified warm/cold against the
  set of (bucket, batch-size) program signatures already executed, which
  is exactly jax's jit-cache key for the cached ``run_batch`` callable:
  after warm-up a well-bucketed workload serves with zero cold launches
  (the acceptance property ``benchmarks/bench_streaming.py`` measures).

The service is synchronous and single-owner by design (submit/poll/
result); wrap it in a thread with
:class:`repro.data.synthetic.PrefetchIterator` feeding the request stream
when you need an async ingest path (``launch/serve.py --workload
pca-stream`` does this).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.consensus import ConsensusEngine
from repro.core.driver import IterationDriver
from repro.core.operators import StackedOperators
from repro.core.step import PowerStep
from repro.core.topology import Topology
from repro.runtime import telemetry, tracing
from repro.runtime.diagnostics import resolve_diagnostics


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pow2_at_least(x: int, cap: int) -> int:
    b = 1
    while b < x and b < cap:
        b *= 2
    return min(b, cap)


@dataclasses.dataclass(frozen=True)
class AdmissionPolicy:
    """Dynamic-batching knobs.

    Attributes:
      max_batch: hard batch-size cap; a bucket launches eagerly at this
        size.  Batches are padded up to the next power of two (≤ this), so
        the number of compiled programs per bucket is log, not linear, in
        the batch sizes seen.
      max_wait: seconds the oldest request in a bucket may wait before
        :meth:`PCAService.poll` force-launches it (latency bound under
        trickle traffic).
      pad_n: sample-count granularity — request ``n`` is zero-row padded up
        to a multiple of this (exact: zero rows do not change ``X^T X``).
      pad_k: component-count granularity — ``W0`` is completed with
        orthonormal extra columns up to a multiple of this; the extra
        columns are computed and discarded.
    """

    max_batch: int = 8
    max_wait: float = 0.01
    pad_n: int = 16
    pad_k: int = 4


class PCAResponse(NamedTuple):
    """One served request."""

    request_id: int
    W: jax.Array                # (m, d, k) local estimates, unpadded
    batch_size: int             # logical requests in the launch
    bucket: tuple               # the shape bucket it rode in
    waited: float               # queue wait (submit -> launch), seconds


@dataclasses.dataclass
class _Pending:
    request_id: int
    ops: StackedOperators
    W0: jax.Array
    arrived: float


class PCAService:
    """Request-queue front-end: submit ragged PCA problems, get batched
    answers.

    The fleet (gossip graph, agent count ``m``, rounds ``K``, iteration
    budget ``T``) is fixed at construction — that is what makes one
    persistent driver (and therefore one program cache) serve every
    request.  Requests vary in ``n`` (samples per agent) and ``k``
    (components); ``d`` may also vary, at the cost of one bucket family
    per distinct ``d``.
    """

    def __init__(self, topology: Topology, *, T: int, K: int,
                 algorithm: str = "deepca", backend: str = "auto",
                 policy: AdmissionPolicy = AdmissionPolicy(),
                 clock=time.monotonic, seed: int = 0,
                 diagnostics: Optional[object] = None):
        self.policy = policy
        self.T = int(T)
        self.m = topology.m
        self._clock = clock
        self._seed = seed
        engine = ConsensusEngine.for_algorithm(algorithm, topology, K=K,
                                               backend=backend)
        self.driver = IterationDriver(
            step=PowerStep.for_algorithm(algorithm, K), engine=engine,
            diagnostics=resolve_diagnostics(diagnostics))
        self._buckets: Dict[tuple, List[_Pending]] = {}
        self._results: Dict[int, PCAResponse] = {}
        self._next_id = 0
        # serving stats: launches are warm iff their (bucket, B_pad)
        # program signature has executed before — jax's jit-cache key for
        # the driver's cached batch callable
        self._signatures: set = set()
        self.stats = {"requests": 0, "batches": 0, "cold_launches": 0,
                      "warm_launches": 0, "padded_requests": 0,
                      "served": 0}

    # ---------------------------------------------------------- bucketing
    def bucket_of(self, ops: StackedOperators, k: int) -> tuple:
        """The padded-shape bucket key a request lands in."""
        kind = "dense" if ops.dense is not None else "data"
        d = ops.d
        if k > d:
            raise ValueError(f"requested k={k} exceeds d={d}")
        n_pad = (_round_up(ops.data.shape[1], self.policy.pad_n)
                 if kind == "data" else d)
        # clamp the pad to d: extra orthonormal columns only exist up to a
        # full basis, and any legal request (k <= d) must be servable
        k_pad = min(_round_up(k, self.policy.pad_k), d)
        return (kind, self.m, d, n_pad, k_pad, self.T)

    def _pad_request(self, p: _Pending, bucket: tuple
                     ) -> Tuple[StackedOperators, jax.Array]:
        kind, _, d, n_pad, k_pad, _ = bucket
        ops, W0 = p.ops, p.W0
        padded = False
        if kind == "data" and ops.data.shape[1] != n_pad:
            with tracing.span("service.pad", request=p.request_id):
                ops = StackedOperators(data=jnp.pad(
                    ops.data,
                    ((0, 0), (0, n_pad - ops.data.shape[1]), (0, 0))))
            padded = True
        if W0.shape[1] != k_pad:
            with tracing.span("service.complement", request=p.request_id):
                W0 = jnp.concatenate(
                    [W0, self._complement(W0, k_pad - W0.shape[1])], axis=1)
            padded = True
        if padded:
            self.stats["padded_requests"] += 1
        return ops, W0

    def _complement(self, W0: jax.Array, extra: int) -> jax.Array:
        """``extra`` orthonormal columns orthogonal to ``span(W0)`` (the
        ride-along components a k-padded request computes and discards)."""
        d = W0.shape[0]
        rng = np.random.default_rng((self._seed, d, extra))
        G = jnp.asarray(rng.standard_normal((d, extra)), W0.dtype)
        G = G - W0 @ (W0.T @ G)
        from repro.core.step import qr_orth   # shared CholeskyQR2 fast path
        return qr_orth(G)

    # ------------------------------------------------------------- intake
    def submit(self, ops: StackedOperators, W0: jax.Array) -> int:
        """Enqueue one PCA request; returns its id.

        ``ops`` must be an ``m``-agent problem on this service's fleet;
        ``W0`` is the request's ``(d, k)`` orthonormal initialisation (its
        column count is the requested component count).
        """
        if ops.m != self.m:
            raise ValueError(
                f"request has m={ops.m} agents; this service's fleet is "
                f"m={self.m}")
        rid = self._next_id
        with tracing.span("service.submit", request=rid):
            key = self.bucket_of(ops, W0.shape[1])
            self._next_id += 1
            self._buckets.setdefault(key, []).append(
                _Pending(rid, ops, W0, self._clock()))
            self.stats["requests"] += 1
            if len(self._buckets[key]) >= self.policy.max_batch:
                self._launch(key)
        return rid

    def poll(self, now: Optional[float] = None) -> int:
        """Launch every bucket whose oldest request exceeded ``max_wait``;
        returns the number of launches."""
        now = self._clock() if now is None else now
        n = 0
        with tracing.span("service.poll"):
            for key in list(self._buckets):
                q = self._buckets[key]
                if q and now - q[0].arrived >= self.policy.max_wait:
                    self._launch(key)
                    n += 1
        return n

    def flush(self) -> int:
        """Launch every non-empty bucket (drain; end-of-stream)."""
        n = 0
        for key in list(self._buckets):
            if self._buckets[key]:
                self._launch(key)
                n += 1
        return n

    def result(self, request_id: int, pop: bool = True
               ) -> Optional[PCAResponse]:
        """The response for a request id, if its batch has run."""
        if pop:
            return self._results.pop(request_id, None)
        return self._results.get(request_id)

    # ------------------------------------------------------------- launch
    def _launch(self, key: tuple) -> None:
        q = self._buckets.pop(key, [])
        if not q:
            return
        now = self._clock()
        B = len(q)
        B_pad = _pow2_at_least(B, self.policy.max_batch)
        sig = (key, B_pad)
        warm = sig in self._signatures
        launch = self.stats["batches"]
        self.stats["warm_launches" if warm else "cold_launches"] += 1
        self._signatures.add(sig)
        self.stats["batches"] += 1
        if telemetry.enabled():
            telemetry.emit("service.launch", bucket=str(key), batch=B,
                           batch_padded=B_pad, warm=warm)
        attrs = (dict(launch=launch, batch=B, batch_padded=B_pad, warm=warm,
                      bucket=str(key)) if tracing.enabled() else {})
        with tracing.span("service.launch", **attrs):
            padded = [self._pad_request(p, key) for p in q]
            # pad the batch axis with copies of the first problem so every
            # launch in this bucket uses one of log2(max_batch) program
            # shapes
            while len(padded) < B_pad:
                padded.append(padded[0])
            problems = [ops for ops, _ in padded]
            with tracing.span("service.stack"):
                W0 = jnp.stack([w for _, w in padded])
            out = self.driver.run_batch(problems, W0, T=self.T)
        for b, p in enumerate(q):
            k = p.W0.shape[1]
            self._results[p.request_id] = PCAResponse(
                request_id=p.request_id, W=out.W[b][:, :, :k],
                batch_size=B, bucket=key, waited=now - p.arrived)
            self.stats["served"] += 1
