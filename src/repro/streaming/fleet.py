"""TrackerFleet: vmapped multi-tenant online tracking from ONE program.

:class:`~repro.streaming.tracker.StreamingDeEPCA` tracks one stream per
driver, so serving N concurrent drifting streams pays N Python tick loops
and N program launches per tick.  The fleet closes that gap by combining
the two serving substrates the repo already has:

* the **batched driver** — :meth:`~repro.core.driver.IterationDriver
  .run_batch` with the ``carry=`` resume axis vmaps B independent tracker
  carries ``(S, W, G_prev[, W_prev][, ef])`` through ONE compiled window
  program, and
* **shape bucketing** — :class:`~repro.streaming.service.PCAService`'s
  padded-shape buckets (``n`` zero-row padded up to ``pad_n``; exact, zero
  rows do not change ``X^T X``), so a ragged tenant mix collapses onto a
  handful of compiled window programs.

Per-tenant drift policy runs *inside the batch*: every slot rides every
window launch, and restart / escalation are ``lax.cond``-free masked
selects on the batched carry (:func:`select_carry`), so one hot tenant
re-runs its window while the settled tenants ride along as no-ops — the
launch count per tick is bounded by the pass structure (base window,
optional restart re-run, up to ``max_escalations`` escalation windows),
never by the tenant count.  Tenant admission/eviction is a **slot pool**
per bucket: join/leave scatters a fresh-tracker (or vacated) state into a
free slot (:func:`scatter_carry`) without changing the batch shape, and
each tick stacks the whole pool (:func:`stack_slots`), so fleet
membership churn causes ZERO retraces (pinned by the ``fleet-warm``
retrace contract).  Vacated slots keep riding as inert fillers on a copy
of an active tenant's operators — real, finite dynamics, so the
max-over-batch diagnostics reduction never sees garbage.

Solo-equivalence contract: a tenant's per-tick *carry* (and therefore its
subspace estimate) is **bit-identical** to a solo
:class:`StreamingDeEPCA` fed the same (padded) operators — the fleet
reuses the driver's vmap≡scan bit-equality.  Monitoring *statistics*
agree to floating-point rounding (the batched SVD/QR lowering differs
from the solo trace's by vmap axis), and the fleet mirrors the solo
tracker's decision arithmetic host-side (EWMA, floor, jump/restart
thresholds, cold-start tick skip) so drift decisions coincide whenever
thresholds are decisive; property-tested in ``tests/test_fleet.py``.
Alg. 2's sign reference is re-anchored at every window, as in the solo
tracker: a tenant's first window uses its start ``W0``, every later one
the sign-aligned mean basis of the carry it resumes from (a tick's base
window: the previous tick's answer), so a subspace that drifts far from
its start never splits the agents on a column's sign.  Two
solo behaviors intentionally do NOT carry over: the live health-monitor
escalation (a process-global signal that cannot be attributed to one
tenant inside a batch) and dynamic topology schedules (the fleet is a
static-engine substrate).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import metrics
from repro.core.algorithms import resolve_acceleration, resolve_engines
from repro.core.driver import IterationDriver
from repro.core.operators import StackedOperators
from repro.core.step import Carry, PowerStep, mean_basis, rebase_carry
from repro.core.topology import Topology
from repro.runtime import telemetry, tracing
from repro.runtime.config import get_config
from repro.runtime.diagnostics import resolve_diagnostics

from .service import _round_up
from .tracker import DriftPolicy


def select_carry(mask: jax.Array, new: Carry, old: Carry) -> Carry:
    """Masked per-slot carry update — THE fleet's branchless drift
    arithmetic (registered compute site).

    ``mask`` is a ``(B,)`` bool vector over the slot axis; slots where it
    is True take the freshly-computed window/restart state, the rest keep
    their previous state untouched — ``jnp.where`` on every carry slot, no
    ``lax.cond``, so the whole fleet shares one program regardless of
    which tenants escalated.
    """
    out = []
    for n, o in zip(new, old):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        out.append(jnp.where(m, n, o))
    return tuple(out)


def scatter_carry(carry: Carry, slot: int, values: Carry) -> Carry:
    """Scatter one tenant's state into a slot of the batched carry — THE
    fleet's admission arithmetic (registered compute site).

    Join = scatter a fresh-tracker state (``W0`` broadcast into all three
    base slots, extras zeroed — exactly :meth:`PowerStep.init_carry`);
    the batch shape never changes, so membership churn never retraces.
    """
    return tuple(c.at[slot].set(jnp.asarray(v).astype(c.dtype))
                 for c, v in zip(carry, values))


@functools.partial(jax.jit, static_argnames="n_pad")
def pad_rows(X: jax.Array, *, n_pad: int) -> jax.Array:
    """One tenant's ``(m, n, d)`` data rows zero-padded to ``n_pad`` (exact:
    zero rows do not change ``X^T X``); one compiled program per ``n``."""
    return jnp.pad(X, ((0, 0), (0, n_pad - X.shape[1]), (0, 0)))


@jax.jit
def stack_slots(arrs, Q_prev: jax.Array, Us, has_U: jax.Array):
    """A bucket's slot-stacked operators and truths in ONE program.

    ``arrs`` holds one operator array per slot, all of the bucket's
    shape (a free slot repeats its filler); ``Us`` one ``(d, k)`` truth
    per slot and ``has_U`` the ``(C,)`` mask of the slots whose tenant
    gave one, the rest taking their ``Q_prev``.  The arguments' count and
    shapes are the bucket's pool, not its membership: churn compiles
    nothing.
    """
    U = jnp.stack(Us)
    dt = jnp.result_type(Q_prev, U)
    return jnp.stack(arrs), jnp.where(has_U[:, None, None], U.astype(dt),
                                      Q_prev.astype(dt))


class TenantReport(NamedTuple):
    """Per-tenant outcome of one fleet tick (mirror of
    :class:`~repro.streaming.tracker.TickReport`, minus the trace)."""

    tenant: str
    tick: int                   # tenant-local tick index
    slot: int
    bucket: tuple
    iterations: int             # power iterations this tenant ran this tick
    comm_rounds: float
    total_rounds: float
    stat: float
    jump_stat: float
    drift: bool
    restarted: bool
    escalations: int
    latency_ms: float           # wall-clock of the tenant's bucket tick
    slo_ok: bool


class FleetTickReport(NamedTuple):
    """One fleet tick: every bucket's windows + every tenant's outcome."""

    tick: int
    tenants: Dict[str, TenantReport]
    windows: int                # batched window launches across buckets
    warm_launches: int
    cold_launches: int
    latency_ms: float           # wall-clock over all buckets this tick
    restarts: int               # tenants rebased this tick
    escalations: int            # tenant escalation windows this tick
    slot_windows: int           # slot-windows run: capacity per launch
    useful_slot_windows: int    # of them, those a tenant keeps
    stack_cold: int             # first stack launches of a bucket's pool


@dataclasses.dataclass
class _Tenant:
    tid: str
    bucket: tuple
    slot: int
    ticks: int = 0
    ewma: Optional[float] = None
    has_Q: bool = False         # Q_prev slot valid (False before 1st tick)
    rounds: float = 0.0
    iters: int = 0


@dataclasses.dataclass
class _Bucket:
    key: tuple                  # (kind, m, d, n_pad, k, T_tick)
    capacity: int
    carry: Carry                # each slot-stacked: (C, m, d, k)
    Q_prev: jax.Array           # (C, d, k) previous-tick mean bases (a
                                # joiner's W0): the tick's sign reference
    slots: List[Optional[str]]  # tenant id per slot, None = free
    no_U: jax.Array             # (d, k) zeros: a truthless slot's truth


class TrackerFleet:
    """Multi-tenant online tracker: N drifting streams, one program/bucket.

    The fleet (gossip graph, ``m``, ``K``, ``T_tick``, algorithm) is fixed
    at construction like :class:`~repro.streaming.service.PCAService`;
    tenants vary in ``(d, k, n)`` and land in padded-shape buckets.  Feed
    ticks with :meth:`tick` (one operators snapshot per active tenant per
    call); manage membership with :meth:`join` / :meth:`leave`.

    Args:
      slots: slot-pool capacity per bucket (rounded up to a power of two;
        defaults to ``REPRO_FLEET_SLOTS`` or 8).  A bucket that outgrows
        its pool doubles it — one cold compile, counted as such.
      slo_ms: per-tick latency objective; ``None`` (default
        ``REPRO_FLEET_SLO_MS``) disables SLO accounting.  Breaches are
        reported per tenant (``slo_ok``) and on the ``fleet.tenant``
        telemetry event — the fleet never throttles on them.
      pad_n: sample-count bucket granularity (as the service's
        ``AdmissionPolicy.pad_n``).  There is deliberately no ``pad_k``:
        CholeskyQR2 mixes columns through the Gram matrix, so k-padding
        would break the solo bit-identity contract.
    """

    def __init__(self, k: int, T_tick: int, K: int, *,
                 topology: Topology, algorithm: str = "deepca",
                 backend: str = "auto", accelerate: bool = True,
                 policy: DriftPolicy = DriftPolicy(),
                 slots: Optional[int] = None,
                 slo_ms: Optional[float] = None,
                 pad_n: int = 16,
                 accelerated: Optional[bool] = None,
                 momentum: Optional[float] = None,
                 wire_dtype: Optional[str] = None,
                 diagnostics: Optional[object] = None):
        cfg = get_config()
        self.k = int(k)
        self.T_tick = int(T_tick)
        self.policy = policy
        self.pad_n = int(pad_n)
        self.slo_ms = cfg.fleet_slo_ms if slo_ms is None else float(slo_ms)
        slots = cfg.fleet_slots if slots is None else slots
        self.default_slots = max(1, int(slots) if slots is not None else 8)
        dyn, eng = resolve_engines(
            algorithm, topology, K, accelerate=accelerate, backend=backend,
            schedule=None, wire_dtype=wire_dtype)
        if dyn is not None:
            raise ValueError(
                "TrackerFleet is a static-engine substrate (dynamic "
                "topology schedules cannot share one vmapped program "
                "across per-tenant schedule offsets)")
        acc, beta = resolve_acceleration(accelerated, momentum)
        step = PowerStep.for_algorithm(
            algorithm, K, accelerated=acc, momentum=beta,
            ef_wire=eng.ef_wire)
        self.driver = IterationDriver(
            step=step, engine=eng,
            diagnostics=resolve_diagnostics(diagnostics))
        self.m = topology.m
        self._tenants: Dict[str, _Tenant] = {}
        self._buckets: Dict[tuple, _Bucket] = {}
        self._ticks = 0
        # warm/cold accounting at the XLA level: jax's jit cache keys on
        # input *shapes* below the driver's python-level program cache, so
        # a launch is warm iff its (bucket, capacity, T) signature ran
        # before — mirrors PCAService._signatures
        self._signatures: set = set()
        self.stats = {"ticks": 0, "windows": 0, "warm_launches": 0,
                      "cold_launches": 0, "joins": 0, "leaves": 0,
                      "restarts": 0, "escalations": 0, "slo_breaches": 0,
                      "slot_windows": 0, "useful_slot_windows": 0,
                      "stack_cold": 0}
        # (bucket, capacity) pools whose stack program ran, apart from the
        # window programs' signatures
        self._stack_pools: set = set()
        self._rebase_cache: dict = {}

    # ---------------------------------------------------------- bucketing
    def bucket_of(self, d: int, k: int, n: Optional[int],
                  kind: str = "data") -> tuple:
        """The padded-shape bucket a ``(d, k, n)`` tenant lands in (the
        service's bucketing, minus k-padding — see the class docstring)."""
        if kind not in ("data", "dense"):
            raise ValueError(f"kind must be data/dense, got {kind!r}")
        if kind == "data":
            if n is None:
                raise ValueError("data-operator tenants need n (samples "
                                 "per agent) at join time")
            n_pad = _round_up(int(n), self.pad_n)
        else:
            n_pad = int(d)
        return (kind, self.m, int(d), n_pad, int(k), self.T_tick)

    # ---------------------------------------------------------- membership
    @property
    def tenants(self) -> Tuple[str, ...]:
        return tuple(self._tenants)

    @property
    def program_count(self) -> int:
        """Distinct compiled window-program shapes across the fleet's life
        (the ≤-programs number the tenant-mix acceptance criterion pins)."""
        return len(self._signatures)

    def join(self, tid: str, W0: jax.Array, *, n: Optional[int] = None,
             kind: str = "data") -> int:
        """Admit a tenant; returns its slot index.

        ``W0`` is the tenant's ``(d, k)`` orthonormal init; ``n`` its
        samples-per-agent (data operators).  The slot starts as a fresh
        tracker — ``W0`` broadcast into all three base carry slots, extras
        zeroed, ``W0`` the first tick's sign reference — so the tenant's
        first tick is bit-identical to a new solo tracker's.
        """
        if tid in self._tenants:
            raise ValueError(f"tenant {tid!r} already joined")
        W0 = jnp.asarray(W0)
        d, k = int(W0.shape[0]), int(W0.shape[1])
        key = self.bucket_of(d, k, n, kind)
        bkt = self._buckets.get(key)
        if bkt is None:
            bkt = self._make_bucket(key, W0)
            self._buckets[key] = bkt
        grew = False
        try:
            slot = bkt.slots.index(None)
        except ValueError:
            slot = bkt.capacity
            self._grow_bucket(bkt)
            grew = True
        bkt.slots[slot] = tid
        dt = bkt.carry[0].dtype
        W_b = jnp.broadcast_to(W0, (self.m,) + W0.shape).astype(dt)
        fresh = self.driver.step.normalize_carry((W_b, W_b, W_b))
        bkt.carry = scatter_carry(bkt.carry, slot, fresh)
        bkt.Q_prev = bkt.Q_prev.at[slot].set(W0.astype(bkt.Q_prev.dtype))
        self._tenants[tid] = _Tenant(tid=tid, bucket=key, slot=slot)
        self.stats["joins"] += 1
        telemetry.emit("fleet.join", tenant=tid, bucket=str(key), slot=slot,
                       grew=grew)
        return slot

    def leave(self, tid: str) -> None:
        """Evict a tenant: its slot is freed and rides on as an inert
        filler until the next join scatters over it."""
        t = self._tenants.pop(tid, None)
        if t is None:
            raise KeyError(f"unknown tenant {tid!r}")
        self._buckets[t.bucket].slots[t.slot] = None
        self.stats["leaves"] += 1
        telemetry.emit("fleet.leave", tenant=tid, bucket=str(t.bucket),
                       slot=t.slot)

    def _make_bucket(self, key: tuple, W0: jax.Array) -> _Bucket:
        C = 1
        while C < self.default_slots:
            C *= 2
        kind, m, d, n_pad, k, _ = key
        dt = jnp.result_type(W0.dtype, jnp.float32)
        zero = jnp.zeros((C, m, d, k), dt)
        carry = tuple(zero for _ in range(self.driver.step.carry_slots))
        Q = jnp.broadcast_to(W0.astype(dt), (C, d, k))
        return _Bucket(key=key, capacity=C, carry=carry, Q_prev=Q,
                       slots=[None] * C, no_U=jnp.zeros((d, k), dt))

    def _grow_bucket(self, bkt: _Bucket) -> None:
        # a full pool doubles: one cold compile at the new batch shape
        # (counted by the warm/cold signature accounting), never a retrace
        # of the old one
        C = bkt.capacity
        bkt.carry = tuple(jnp.concatenate([c, jnp.zeros_like(c)])
                          for c in bkt.carry)
        bkt.Q_prev = jnp.concatenate([bkt.Q_prev, bkt.Q_prev])
        bkt.slots.extend([None] * C)
        bkt.capacity = 2 * C

    # ------------------------------------------------------------- windows
    def _rebase_fn(self, kind: str):
        """Cached vmapped tracker restart — one :func:`rebase_carry` call
        per slot (the registered restart compute site; the fleet adds no
        second home for the arithmetic)."""
        fn = self._rebase_cache.get(kind)
        if fn is None:
            step = self.driver.step

            def one(arr, W):
                ops = (StackedOperators(dense=arr) if kind == "dense"
                       else StackedOperators(data=arr))
                return rebase_carry(ops, W, accelerated=step.accelerated,
                                    ef_wire=step.ef_wire)

            fn = self._rebase_cache[kind] = jax.jit(jax.vmap(one))
        return fn

    @staticmethod
    @jax.jit
    def _stats_fn(W_b: jax.Array, Q_prev_b: jax.Array, U_b: jax.Array):
        """Batched per-slot drift statistics (one jitted program, one
        host sync per window pass).

        Mirrors the solo tracker bit-for-bit: ``Q`` is
        ``mean_basis(W)`` (the tracker's ``_mean_basis``, the next tick's
        sign reference), ``move``
        the ground-truth-free answer-movement statistic
        ``tan_theta_k(Q_prev, Q)``, ``mtt`` the paper's mean tan-theta
        against the supplied truth basis.
        """
        Q = mean_basis(W_b)
        move = jax.vmap(metrics.tan_theta_k)(Q_prev_b, Q)
        mtt = jax.vmap(metrics.mean_tan_theta)(U_b, W_b)
        return Q, move, mtt

    def _window(self, bkt: _Bucket, ops_b: StackedOperators, carry: Carry,
                ref: jax.Array, T: Optional[int] = None, *,
                kind: str = "base", useful: int = 0
                ) -> Tuple[Carry, Optional[jax.Array]]:
        """One batched window over every slot of the bucket, each slot's
        Alg. 2 sign reference ``ref[slot]``; ``useful`` counts the slots
        whose result a tenant keeps (``kind``: base, restart or
        escalation)."""
        T = self.T_tick if T is None else int(T)
        sig = (bkt.key, bkt.capacity, T)
        warm = sig in self._signatures
        self._signatures.add(sig)
        self.stats["warm_launches" if warm else "cold_launches"] += 1
        self.stats["windows"] += 1
        self.stats["slot_windows"] += bkt.capacity
        self.stats["useful_slot_windows"] += useful
        self._tick_warm += int(warm)
        self._tick_cold += int(not warm)
        attrs = dict(kind=kind, T=T) if tracing.enabled() else {}
        with tracing.span("fleet.window", **attrs):
            out = self.driver.run_batch(ops_b, ref, T=T, carry=carry)
        return out.carries, out.diag

    # ---------------------------------------------------------------- tick
    def tick(self, items: Dict[str, object]) -> FleetTickReport:
        """Consume one fleet tick.

        ``items`` maps EVERY active tenant id to its tick payload: a
        :class:`StackedOperators`, an ``(ops, U)`` pair, or anything with
        ``.ops`` / ``.U`` attributes (a
        :class:`~repro.streaming.stream.StreamTick`).  Ground truth ``U``
        is optional per tenant and enables tan-theta monitoring plus
        ``policy.target`` escalation for that tenant alone.
        """
        missing = set(self._tenants) - set(items)
        extra = set(items) - set(self._tenants)
        if missing or extra:
            raise ValueError(
                f"fleet tick must cover exactly the active tenants; "
                f"missing={sorted(missing)} unknown={sorted(extra)}")
        self._tick_warm = self._tick_cold = 0
        before = dict(self.stats)
        reports: Dict[str, TenantReport] = {}
        tic_all = time.perf_counter()
        with tracing.span("fleet.tick", tick=self._ticks,
                          tenants=len(items)):
            for key, bkt in self._buckets.items():
                active = [(s, tid) for s, tid in enumerate(bkt.slots)
                          if tid is not None]
                if not active:
                    continue
                attrs = (dict(bucket=str(key), capacity=bkt.capacity,
                              active=len(active))
                         if tracing.enabled() else {})
                with tracing.span("fleet.bucket", **attrs):
                    self._tick_bucket(bkt, active, items, reports)
        latency_ms = (time.perf_counter() - tic_all) * 1e3
        delta = {k: self.stats[k] - before[k]
                 for k in ("windows", "restarts", "escalations",
                           "slot_windows", "useful_slot_windows",
                           "stack_cold")}
        report = FleetTickReport(
            tick=self._ticks, tenants=reports,
            warm_launches=self._tick_warm, cold_launches=self._tick_cold,
            latency_ms=latency_ms, **delta)
        telemetry.emit("fleet.tick", tick=self._ticks,
                       tenants=len(reports), warm=report.warm_launches,
                       cold=report.cold_launches,
                       latency_ms=latency_ms, **delta)
        self.stats["ticks"] += 1
        self._ticks += 1
        return report

    def _stack(self, bkt: _Bucket, active, items):
        """The bucket's slot-stacked operators ``(C, m, n_pad, d)`` and
        truth stack ``(C, d, k)``, plus which tenants gave a truth: each
        ragged tenant's rows padded by :func:`pad_rows`, then one
        :func:`stack_slots` dispatch over the whole pool.

        Active slots carry their tenant's zero-row-padded operators; free
        slots ride a copy of the first active tenant's (real, finite
        dynamics — the max-over-batch diagnostics reduction must never see
        a QR of zeros); slots without a truth carry ``Q_prev``'s.
        """
        kind, _, _, n_pad, _, _ = bkt.key
        pool = (bkt.key, bkt.capacity)
        cold = pool not in self._stack_pools
        self._stack_pools.add(pool)
        self.stats["stack_cold"] += int(cold)
        attrs = dict(stack_cold=int(cold)) if tracing.enabled() else {}
        with tracing.span("fleet.stack", **attrs):
            arrs = [None] * bkt.capacity
            Us = [bkt.no_U] * bkt.capacity
            has_U = np.zeros(bkt.capacity, bool)
            for s, tid in active:
                item = items[tid]
                if isinstance(item, StackedOperators):
                    ops, U = item, None
                elif hasattr(item, "ops"):
                    ops, U = item.ops, getattr(item, "U", None)
                else:
                    ops, U = item[0], (item[1] if len(item) > 1 else None)
                a = ops.array
                if kind == "data" and a.shape[1] != n_pad:
                    a = pad_rows(a, n_pad=n_pad)
                arrs[s] = a
                if U is not None:
                    Us[s], has_U[s] = U, True
            filler = arrs[active[0][0]]
            arr, U_b = stack_slots(
                tuple(filler if a is None else a for a in arrs),
                bkt.Q_prev, tuple(Us), has_U)
        return arr, U_b, {tid: bool(has_U[s]) for s, tid in active}

    def _tick_bucket(self, bkt: _Bucket, active, items,
                     reports: Dict[str, TenantReport]) -> None:
        pol = self.policy
        kind = bkt.key[0]
        tic = time.perf_counter()

        arr, U_b, has_U = self._stack(bkt, active, items)
        ops_b = (StackedOperators(dense=arr) if kind == "dense"
                 else StackedOperators(data=arr))

        def stats(carry):
            with tracing.span("fleet.stats"):
                Q, move, mtt = self._stats_fn(carry[1], bkt.Q_prev, U_b)
                return Q, np.asarray(move), np.asarray(mtt)

        def stat_of(tid, s, move_h, mtt_h):
            if has_U[tid]:
                return float(mtt_h[s])
            t = self._tenants[tid]
            return float(move_h[s]) if t.has_Q else 0.0

        def advance(tids, T):
            K = float(self.driver.step.rounds)
            for tid in tids:
                t = self._tenants[tid]
                t.iters += T
                t.rounds += T * K

        # -- pass 1: the base window, every slot rides.  Each window's
        # Alg. 2 sign reference is the mean basis of the carry it resumes
        # from: here the previous tick's (a joiner's W0), after it the
        # ``Q`` of the last stats read
        carry, diag = self._window(bkt, ops_b, bkt.carry, bkt.Q_prev,
                                   useful=len(active))
        advance([tid for _, tid in active], self.T_tick)
        Q, move_h, mtt_h = stats(carry)
        jump = {tid: stat_of(tid, s, move_h, mtt_h) for s, tid in active}
        stat = dict(jump)

        # -- drift decisions, mirroring the solo tracker host-side (the
        # health-monitor escalation has no per-tenant attribution inside a
        # batch and is deliberately absent — see the module docstring)
        drift, severe = {}, {}
        for s, tid in active:
            t = self._tenants[tid]
            base = max(t.ewma, pol.floor) if t.ewma is not None else None
            drift[tid] = base is not None and jump[tid] > pol.jump * base
            severe[tid] = base is not None and jump[tid] > pol.restart * base

        # -- restart pass: rebase the severe slots (the registered
        # rebase_carry site, vmapped) and re-run the window; settled
        # tenants ride as no-ops through the masked select
        restarted = {tid: False for _, tid in active}
        if any(severe.values()):
            mask = jnp.asarray([severe.get(tid, False)
                                for tid in bkt.slots], bool)
            rebased = self._rebase_fn(kind)(arr, carry[1])
            rerun, _ = self._window(
                bkt, ops_b, select_carry(mask, rebased, carry), Q,
                kind="restart", useful=sum(severe.values()))
            carry = select_carry(mask, rerun, carry)
            Q, move_h, mtt_h = stats(carry)
            hot = [tid for _, tid in active if severe[tid]]
            advance(hot, self.T_tick)
            for s, tid in active:
                if severe[tid]:
                    restarted[tid] = True
                    stat[tid] = stat_of(tid, s, move_h, mtt_h)
                    self.stats["restarts"] += 1
                    telemetry.emit("fleet.restart", tenant=tid,
                                   tick=self._ticks,
                                   jump_stat=jump[tid])
        post_restart = dict(stat)

        # -- escalation passes: adaptive extra windows for tenants whose
        # statistic still exceeds the target (or that drifted), everyone
        # else riding as a no-op — at most max_escalations batched
        # launches, never per-tenant ones
        esc_T = pol.escalate_T or self.T_tick
        esc = {tid: 0 for _, tid in active}
        while True:
            go = {}
            for _, tid in active:
                need = (pol.target is not None and has_U[tid]
                        and stat[tid] > pol.target)
                go[tid] = (esc[tid] < pol.max_escalations
                           and (need or (drift[tid] and esc[tid] == 0)))
            if not any(go.values()):
                break
            mask = jnp.asarray([go.get(tid, False)
                                for tid in bkt.slots], bool)
            rerun, _ = self._window(bkt, ops_b, carry, Q, esc_T,
                                    kind="escalation",
                                    useful=sum(go.values()))
            carry = select_carry(mask, rerun, carry)
            Q, move_h, mtt_h = stats(carry)
            advance([tid for tid, g in go.items() if g], esc_T)
            for s, tid in active:
                if go[tid]:
                    esc[tid] += 1
                    stat[tid] = stat_of(tid, s, move_h, mtt_h)
                    self.stats["escalations"] += 1

        bkt.carry = carry
        bkt.Q_prev = Q
        latency_ms = (time.perf_counter() - tic) * 1e3
        slo_ok = self.slo_ms is None or latency_ms <= self.slo_ms
        if not slo_ok:
            self.stats["slo_breaches"] += 1

        # masked fleet diagnostics: the max-over-ACTIVE-tenants observables
        # from the base window (run_batch's own diag event reduces over
        # every slot, fillers included)
        if diag is not None and self.driver.diagnostics is not None:
            from repro.runtime import diagnostics as diagnostics_lib
            names = self.driver.diagnostics.names(self.driver.step)
            rows = np.asarray(diag)[[s for s, _ in active]].max(axis=0)
            diagnostics_lib.emit_diag(
                "fleet.tick", 0, names, rows,
                floor=self.driver.quantization_floor(),
                batch=len(active))

        for s, tid in active:
            t = self._tenants[tid]
            # EWMA mirror of the solo tracker: skip the cold-start tick;
            # after a restart fold in the rerun window's tan-theta (the
            # new regime's level) when truth is available, else leave the
            # baseline untouched
            if t.ticks > 0:
                if restarted[tid]:
                    val = post_restart[tid] if has_U[tid] else None
                else:
                    val = jump[tid]
                if val is not None:
                    t.ewma = val if t.ewma is None else \
                        (1.0 - pol.alpha) * t.ewma + pol.alpha * val
            t.has_Q = True
            iters_tick = ((1 + int(restarted[tid])) * self.T_tick
                          + esc[tid] * esc_T)
            rep = TenantReport(
                tenant=tid, tick=t.ticks, slot=s, bucket=bkt.key,
                iterations=iters_tick,
                comm_rounds=iters_tick * float(self.driver.step.rounds),
                total_rounds=t.rounds, stat=stat[tid],
                jump_stat=jump[tid], drift=bool(drift[tid]),
                restarted=restarted[tid], escalations=esc[tid],
                latency_ms=latency_ms, slo_ok=slo_ok)
            reports[tid] = rep
            telemetry.emit("fleet.tenant", tenant=tid, tick=t.ticks,
                           bucket=str(bkt.key), slot=s,
                           stat=rep.stat, jump_stat=rep.jump_stat,
                           drift=rep.drift, restarted=rep.restarted,
                           escalations=rep.escalations,
                           iterations=rep.iterations,
                           latency_ms=latency_ms, slo_ok=slo_ok)
            t.ticks += 1

    # --------------------------------------------------------------- state
    def tenant_W(self, tid: str) -> jax.Array:
        """The tenant's current ``(m, d, k)`` stacked local estimates."""
        t = self._tenants[tid]
        return self._buckets[t.bucket].carry[1][t.slot]

    def tenant_state(self, tid: str) -> tuple:
        """The tenant's deepca-compatible resume tuple ``(S, W, G_prev[,
        W_prev][, ef], offset)`` — interchangeable with a solo
        :attr:`StreamingDeEPCA.state`."""
        t = self._tenants[tid]
        bkt = self._buckets[t.bucket]
        carry = tuple(c[t.slot] for c in bkt.carry)
        offset = jnp.asarray([int(round(t.rounds)), t.iters], jnp.int32)
        return (*carry, offset)
