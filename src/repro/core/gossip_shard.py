"""Device-distributed DeEPCA: agents = devices along a named mesh axis.

This is the production runtime of the paper's algorithm.  Each device holds
its local operator shard ``A_j`` (or data ``X_j``) and its ``(d, k)`` iterate;
gossip lowers to `collective_permute` for structured topologies (ring /
hypercube — pure nearest-neighbour ICI traffic, *no all-reduce anywhere in
the algorithm*) or to one `all_gather` per round for an arbitrary dense
mixing matrix (the paper's Erdős–Rényi setting).

This module owns the *collective lowerings* — per-round gossip primitives
(:func:`make_round_fn`, :func:`fastmix_local`) and the structural topology
matchers — and the device-placement loop of :class:`DistributedDeEPCA`.
The iteration body itself is NOT defined here: the jitted per-step
programs come from
:meth:`repro.core.driver.IterationDriver.sharded_step_fn` /
:meth:`~repro.core.driver.IterationDriver.sharded_dense_step_fn`, which run
the single shared :class:`~repro.core.step.PowerStep` on the local
``(1, d, k)`` slices, so the distributed runtime executes literally the
same Alg. 1 body as the stacked simulator in :mod:`repro.core.algorithms`
(bit-equivalence property-tested in tests/test_distributed.py and
tests/test_driver.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .consensus import ConsensusEngine
from .driver import IterationDriver
from .step import PowerStep
from .topology import Topology

AXIS = "agents"


# ---------------------------------------------------------------------------
# single gossip rounds, executed *inside* shard_map (x has shape (1, d, k))
# ---------------------------------------------------------------------------

def _ring_round(x: jax.Array, m: int, axis: str, self_w: float, nb_w: float):
    fwd = jax.lax.ppermute(x, axis, [(i, (i + 1) % m) for i in range(m)])
    bwd = jax.lax.ppermute(x, axis, [(i, (i - 1) % m) for i in range(m)])
    return self_w * x + nb_w * (fwd + bwd)


def _hypercube_round(x: jax.Array, m: int, axis: str):
    bits = m.bit_length() - 1
    acc = 0.5 * x
    w = 1.0 / (2 * bits)
    for b in range(bits):
        acc = acc + w * jax.lax.ppermute(
            x, axis, [(i, i ^ (1 << b)) for i in range(m)])
    return acc


def _dense_round(x: jax.Array, L: jax.Array, axis: str):
    # x: (1, d, k) local slice; all_gather -> (m, d, k); weight with own row.
    # L must already be in the iterate dtype — do NOT down-cast here (x64
    # iterates would silently lose the stacked-reference parity).
    allx = jax.lax.all_gather(x, axis, axis=0, tiled=True)   # (m, d, k)
    row = L[jax.lax.axis_index(axis)].astype(x.dtype)        # (m,)
    return jnp.einsum("j,jdk->dk", row, allx)[None]


def ring_structure(topology: Topology) -> Optional[Tuple[float, float]]:
    """``(self_w, nb_w)`` if the mixing matrix IS a uniform ring, else None.

    The check is structural (against the actual matrix), not by name: a
    dropout- or fault-degraded graph that started life as ``ring{m}`` no
    longer matches, and the caller must fall back to the dense lowering.
    """
    Lm, m = topology.mixing, topology.m
    if m < 2:
        return None
    self_w, nb_w = float(Lm[0, 0]), float(Lm[0, 1])
    if nb_w <= 0.0:
        return None
    want = np.full((m, m), 0.0)
    np.fill_diagonal(want, self_w)
    for i in range(m):
        want[i, (i + 1) % m] = nb_w
        want[i, (i - 1) % m] = nb_w
    return (self_w, nb_w) if np.allclose(Lm, want, atol=1e-12) else None


def hypercube_structure(topology: Topology) -> bool:
    """True iff the mixing matrix is exactly the uniform hypercube lowering."""
    m = topology.m
    if m < 2 or (m & (m - 1)):
        return False
    bits = m.bit_length() - 1
    want = np.full((m, m), 0.0)
    np.fill_diagonal(want, 0.5)
    w = 1.0 / (2 * bits)
    for i in range(m):
        for b in range(bits):
            want[i, i ^ (1 << b)] = w
    return bool(np.allclose(topology.mixing, want, atol=1e-12))


def make_round_fn(topology: Topology, axis: str = AXIS
                  ) -> Callable[[jax.Array], jax.Array]:
    """One gossip round for a local (1, d, k) slice under shard_map.

    Lowering selection is *structural*: ``collective_permute`` shifts are
    used only when the mixing matrix provably has the ring / hypercube
    form; any other matrix — including degraded or rewired descendants of a
    structured graph — takes one ``all_gather`` per round with the exact
    dense weights.  The dense row weights are materialised in the iterate's
    dtype at trace time, so f64 runs keep full precision.
    """
    m = topology.m
    ring_w = ring_structure(topology)
    if ring_w is not None:
        self_w, nb_w = ring_w
        if m == 2:
            # fwd and bwd shifts deliver the SAME single neighbour (the
            # adjacency is edge-clamped), so use one permute or the
            # contribution is double-counted vs the mixing-matrix row
            return lambda x: self_w * x + nb_w * jax.lax.ppermute(
                x, axis, [(0, 1), (1, 0)])
        return lambda x: _ring_round(x, m, axis, self_w, nb_w)
    if hypercube_structure(topology):
        return lambda x: _hypercube_round(x, m, axis)
    Lnp = topology.mixing                       # keep the f64 source of truth
    return lambda x: _dense_round(x, jnp.asarray(Lnp, x.dtype), axis)


def fastmix_local(x: jax.Array, round_fn, eta: float, K: int) -> jax.Array:
    """Alg. 3 on a local slice (runs inside shard_map; K static)."""
    prev, cur = x, x
    for _ in range(K):   # K is small and static; unrolled collectives
        prev, cur = cur, (1.0 + eta) * round_fn(cur) - eta * prev
    return cur


# ---------------------------------------------------------------------------
# distributed DeEPCA driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DistributedDeEPCA:
    """DeEPCA where each mesh device along ``axis`` is one agent.

    This class is a thin consumer of the shared step/driver layer: the
    per-iteration jitted programs come from
    :meth:`IterationDriver.sharded_step_fn` (one
    :class:`~repro.core.step.PowerStep` body for every substrate), and
    gossip is delegated to a :class:`~repro.core.consensus.ConsensusEngine`
    (shard_map backend) so this runtime, the stacked simulator and the
    compressed trainer all share one consensus implementation; pass
    ``engine=`` to override (e.g. a ``variant="naive"`` baseline).  What
    remains here is device placement, the compiled-step cache and mid-run
    topology swapping.

    The runtime survives mid-run topology swaps: :meth:`swap_topology`
    replaces the gossip graph between iterations (same ``m`` — the mesh is
    fixed), and :meth:`run` accepts a
    :class:`~repro.core.schedule.TopologySchedule` to drive swaps per step.
    Graphs whose mixing matrix still has the ring/hypercube structure keep
    the ``collective_permute`` lowering (one jitted step per such graph);
    everything else shares ONE dense jitted step that takes the mixing
    matrix and FastMix momentum as replicated *operands*, so arbitrary
    rewiring never retraces.

    Usage::

        dd = DistributedDeEPCA(mesh, topology, k=8, K=6, T=30)
        W = dd.run(A_sharded, W0)     # A_sharded: (m, d, d) sharded on axis 0
        W = dd.run(A_sharded, W0, schedule=sched)   # time-varying gossip
    """

    mesh: Mesh
    topology: Topology
    k: int
    K: int
    T: int
    axis: str = AXIS
    engine: Optional[ConsensusEngine] = None
    # operator form of the A argument to run(): "dense" ((m, d, d)
    # matrices), "data" ((m, n, d) rows, implicit Gram), or "auto" (square
    # trailing block => dense — ambiguous when n == d, so declare it when
    # you know it)
    operator_kind: str = "auto"
    # momentum-accelerated power iterations: the W_prev history slot
    # shards along the agent axis like the rest of the carry (no extra
    # wire traffic — momentum is local arithmetic before the QR)
    accelerated: bool = False
    momentum: float = 0.0
    _step_cache: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False)

    def __post_init__(self):
        if self.mesh.shape[self.axis] != self.topology.m:
            raise ValueError(
                f"mesh axis {self.axis}={self.mesh.shape[self.axis]} must equal "
                f"topology size m={self.topology.m}")
        if self.engine is None:
            self.engine = ConsensusEngine.for_algorithm(
                "deepca", self.topology, K=self.K, backend="shard_map",
                mesh=self.mesh, axis=self.axis)

    def swap_topology(self, topology: Topology) -> None:
        """Replace the gossip graph between iterations (``m`` must match)."""
        if topology.m != self.mesh.shape[self.axis]:
            raise ValueError(
                f"cannot swap to {topology.name}: m={topology.m} != mesh "
                f"axis {self.axis}={self.mesh.shape[self.axis]}")
        # identity check by content, not name: a user schedule may reuse one
        # name for different graphs, and a stale engine would gossip with
        # the wrong eta/matrix
        if topology is self.topology or np.array_equal(
                topology.mixing, self.topology.mixing):
            return
        self.topology = topology
        self.engine = dataclasses.replace(self.engine, topology=topology)

    # -- per-iteration programs (built by the shared driver layer) --------
    def _step(self) -> PowerStep:
        return PowerStep.for_algorithm("deepca", self.K,
                                       accelerated=self.accelerated,
                                       momentum=self.momentum)

    def _driver(self) -> IterationDriver:
        """A driver over the CURRENT engine (cheap; steps are cached here)."""
        return IterationDriver(step=self._step(), engine=self.engine)

    def step_fn(self):
        """Jitted step for the CURRENT topology (structured lowering path)."""
        return self._driver().sharded_step_fn(
            self.mesh, self.axis, self.engine,
            operator_kind=self.operator_kind)

    def _dense_step_fn(self):
        """One jitted step shared by ALL dense-lowered topologies.

        ``L`` (replicated ``(m, m)``) and ``eta`` are traced operands:
        swapping to any other same-``m`` dense graph reuses the compiled
        step — the heart of the no-retrace contract for dynamic topologies.
        """
        return self._driver().sharded_dense_step_fn(
            self.mesh, self.axis, operator_kind=self.operator_kind)

    def _step_for(self, topology: Topology):
        """(step_fn, extra_operands) for one topology, cached by lowering."""
        structured = (ring_structure(topology) is not None
                      or hypercube_structure(topology))
        if structured:
            # keyed by object identity (schedules memoize per step), so two
            # same-named but different graphs never share a compiled step
            key = ("structured", topology.name, id(topology))
            self.swap_topology(topology)
            fn = self._step_cache.get(key)
            if fn is None:
                fn = self._step_cache[key] = self.step_fn()
            return fn, ()
        key = ("dense",)
        fn = self._step_cache.get(key)
        if fn is None:
            fn = self._step_cache[key] = self._dense_step_fn()
        self.swap_topology(topology)
        # default-dtype materialisation: f64 when x64 is enabled, f32
        # otherwise — matches the iterate dtype the dense round casts to
        L = jnp.asarray(topology.mixing)
        eta = jnp.asarray(self.engine.eta)
        return fn, (L, eta)

    def run(self, A: jax.Array, W0: jax.Array,
            schedule: Optional["TopologySchedule"] = None
            ) -> Tuple[jax.Array, jax.Array]:
        """Runs T power iterations; returns (W_stack, S_stack).

        Pass ``A`` as a host (NumPy) array to copy each agent's slice
        straight to its own device; a ``jax.Array`` on one device is
        resharded from there.
        """
        m, d = self.topology.m, W0.shape[0]
        shard = NamedSharding(self.mesh, P(self.axis))
        rep = NamedSharding(self.mesh, P())
        W_stack = jax.device_put(
            np.broadcast_to(np.asarray(W0), (m, d, self.k)), shard)
        # the step's full slot layout (zeroed W_prev for accelerated runs);
        # zeros_like keeps the agent-axis sharding of the seeded slots
        carry = self._step().normalize_carry((W_stack, W_stack, W_stack))
        W0 = jax.device_put(W0, rep)
        A = jax.device_put(A, shard)
        if schedule is None:
            step = self.step_fn()
            for _ in range(self.T):
                carry = step(A, *carry, W0)
            return carry[1], carry[0]
        if schedule.constant_m(0, self.T) != m:
            raise ValueError(
                f"schedule {schedule.name!r} has m != mesh size {m}")
        for t in range(self.T):
            step, extra = self._step_for(schedule.topology_at(t))
            carry = step(A, *carry, W0, *extra)
        return carry[1], carry[0]
