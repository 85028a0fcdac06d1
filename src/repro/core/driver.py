"""IterationDriver: run a PowerStep under any execution substrate.

One driver owns the four ways the repo executes power iterations, all
sharing the single :class:`~repro.core.step.PowerStep` body:

``scan``
    Static-topology ``jax.lax.scan`` with a
    :class:`~repro.core.consensus.ConsensusEngine` (the stacked simulator's
    hot path; any gossip backend).
``traced_scan``
    Dynamic-schedule scan: the per-step mixing matrices and momenta enter
    as ``(T, m, m)`` / ``(T,)`` traced operands
    (:meth:`DynamicConsensusEngine.operands`), so graph swaps never
    retrace.
``unrolled``
    Python-unrolled loop for per-iteration *static* variation — DePCA's
    increasing-rounds schedule and eager schedule consumption (per-step
    graphs resolved statically, matrices still traced).
``shard_map``
    The device-distributed runtime: :meth:`sharded_step_fn` /
    :meth:`sharded_dense_step_fn` build the jitted per-iteration programs
    :class:`~repro.core.gossip_shard.DistributedDeEPCA` loops over (agents
    = devices along a named mesh axis).

``scan``, ``traced_scan`` and :meth:`IterationDriver.run_batch` launch one
program (:meth:`IterationDriver.program`): the same scan body, which
differs only in where ``(L, eta)`` come from -- a static engine closes
over its own graph, a schedule scans its per-step operands.

Every substrate that owns its operators statically (scan, traced scan,
unrolled, run_batch — and run_stream, which resumes windows through
``run``) hands the step the engine's fused ``apply_mix_track`` entry point
(PR 5): on the pallas backend with dense operators the local apply, the
Eqn. (3.1) combine and all K gossip rounds run in ONE kernel launch; on
every other path it is the bit-equal ``ops.apply`` + ``mix_track``
composition, so substrates never fork numerically.  The ``shard_map``
builders keep the explicit composition (the collective gossip rounds
cannot fuse with the local matmul launch).

On top of the unified step the driver adds **batched multi-problem
execution** (:meth:`run_batch`): a ``vmap``-over-problems axis so ONE
compiled program serves ``B`` independent ``(ops, W0, schedule-offset)``
PCA problems per launch — the serving substrate ``repro.launch.serve``'s
``--workload pca`` mode uses for heavy traffic — and **streaming
execution** (:meth:`run_stream`): resumed windows over a drifting operator
stream, one compiled program shared by every tick (the substrate under
:mod:`repro.streaming`'s online tracker and ``--workload pca-stream``).

Substrate selection (``substrate="auto"``)
------------------------------------------
* increasing rounds          -> ``unrolled`` (per-iteration round counts are
  static jit arguments);
* static engine              -> ``scan``;
* dynamic engine + tracking  -> ``traced_scan``;
* dynamic engine, no tracking-> ``unrolled`` (the DePCA schedule path).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.runtime import telemetry, tracing
from repro.runtime import diagnostics as diagnostics_lib

from .consensus import ConsensusEngine, DynamicConsensusEngine
from .operators import StackedOperators, gram_form_pays
from .step import SCOPE_APPLY, Carry, PowerStep

SUBSTRATES = ("auto", "scan", "traced_scan", "unrolled")


def local_apply(A: jax.Array, W: jax.Array,
                kind: str = "auto") -> jax.Array:
    """Local power step on a ``(1, ...)`` shard_map slice.

    ``kind`` declares the operator form: ``"dense"`` (``(1, d, d)`` matrix
    ``A_j``) or ``"data"`` (``(1, n, d)`` rows ``X_j``, applied in implicit
    Gram form).  ``"auto"`` falls back to the historical shape heuristic —
    square trailing block means dense — which MISREADS data operators with
    ``n == d``; callers that know the form (e.g.
    :class:`~repro.core.gossip_shard.DistributedDeEPCA` via
    ``operator_kind=``) should pass it explicitly.  Both forms route
    through :meth:`StackedOperators.apply`, so the distributed runtime and
    the stacked simulator share one local-compute definition.
    """
    if kind == "auto":
        kind = ("dense" if A.ndim == 3 and A.shape[-2] == A.shape[-1]
                else "data")
    if kind == "dense":
        return StackedOperators(dense=A).apply(W)
    if kind == "data":
        return StackedOperators(data=A).apply(W)
    raise ValueError(f"kind must be auto/dense/data, got {kind!r}")


class DriverRun(NamedTuple):
    """One driver execution window (T iterations of one problem)."""

    carry: Carry               # (S, W, G_prev[, W_prev][, ef]) final state
    S_hist: jax.Array          # (T, m, d, k) pre-QR iterates
    W_hist: jax.Array          # (T, m, d, k) per-iteration estimates
    rounds: np.ndarray         # (T,) cumulative gossip rounds (this window)
    rates: np.ndarray          # (T,) Prop. 1 contraction bound per iteration
    #: (T, n) measured in-graph observables (diagnostics on) or ``None``
    diag: Optional[jax.Array] = None
    #: column labels for ``diag`` — ``DiagnosticsSpec.names(step)``
    diag_names: Tuple[str, ...] = ()


class BatchRun(NamedTuple):
    """`run_batch` output: leading axis is the problem axis B."""

    S: jax.Array               # (B, m, d, k)
    W: jax.Array               # (B, m, d, k) final local estimates
    G_prev: jax.Array          # (B, m, d, k)
    S_hist: Optional[jax.Array] = None    # (B, T, m, d, k) when requested
    W_hist: Optional[jax.Array] = None
    extras: Tuple[jax.Array, ...] = ()    # (B, m, d, k) W_prev / ef slots
    diag: Optional[jax.Array] = None      # (B, T, n) measured observables
    diag_names: Tuple[str, ...] = ()

    @property
    def carries(self) -> Carry:
        return (self.S, self.W, self.G_prev) + tuple(self.extras)


@dataclasses.dataclass
class IterationDriver:
    """Runs a :class:`PowerStep` under every execution substrate.

    Exactly one of ``engine`` (static topology) / ``dynamic``
    (schedule-driven) must be set; the wrappers in
    :mod:`repro.core.algorithms` build both from their public arguments.

    ``diagnostics`` (a :class:`~repro.runtime.diagnostics.DiagnosticsSpec`,
    or anything its ``parse`` accepts) opts the compiled scans into
    stacking the measured in-graph observables per iteration — returned as
    ``DriverRun.diag`` / ``BatchRun.diag`` and emitted as ``diag``
    telemetry events.  Off (the default) leaves every program body and
    cache key exactly as before: bit-identical outputs, zero cost.
    """

    step: PowerStep
    engine: Optional[ConsensusEngine] = None
    dynamic: Optional[DynamicConsensusEngine] = None
    diagnostics: Optional[diagnostics_lib.DiagnosticsSpec] = None
    # one cache of jitted programs for every compiled launch (run's scan
    # and traced scan, run_batch cold or resumed): repeated launches on one
    # driver (serving, resumed windows) must not re-trace the T-step scan
    _programs: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False)
    # (jitted program, argument shapes) of the latest compiled launch
    _last_launch: Optional[tuple] = dataclasses.field(
        default=None, init=False, repr=False)

    def __post_init__(self):
        if (self.engine is None) == (self.dynamic is None):
            raise ValueError(
                "exactly one of engine (static) / dynamic (schedule) "
                "must be provided")
        if self.diagnostics is not None and not isinstance(
                self.diagnostics, diagnostics_lib.DiagnosticsSpec):
            self.diagnostics = diagnostics_lib.DiagnosticsSpec.parse(
                self.diagnostics)

    def _diag_names(self) -> Tuple[str, ...]:
        return (self.diagnostics.names(self.step)
                if self.diagnostics is not None else ())

    def lower_last_launch(self) -> "jax.stages.Lowered":
        """The lowering of the latest compiled launch (``scan``,
        ``traced_scan`` or :meth:`run_batch`) at the shapes and dtypes it
        ran with -- the program a serving path actually dispatched, e.g.
        to see which Pallas kernels it holds."""
        if self._last_launch is None:
            raise RuntimeError("no compiled program has been launched yet")
        fn, avals = self._last_launch
        return fn.lower(*avals)

    @property
    def _gossip(self) -> ConsensusEngine:
        """The engine every substrate gossips through: the static engine,
        or a schedule's step-0 engine, handed each step's ``(L, eta)``."""
        return self.engine if self.engine is not None \
            else self.dynamic.engine_at(0)

    def quantization_floor(self) -> float:
        """The engine's wire quantization floor (attached to diag events)."""
        return self._gossip.quantization_floor()

    # ------------------------------------------------------------ running
    def run(self, ops: StackedOperators, W0: jax.Array, *, T: int,
            t0: int = 0, carry: Optional[Carry] = None,
            substrate: str = "auto") -> DriverRun:
        """T power iterations starting at global iteration ``t0``.

        ``carry`` resumes from a previous window's :attr:`DriverRun.carry`
        (cast to the run dtype, like a fresh start); ``t0`` keeps schedule
        indexing and increasing-rounds accounting global across resumes.
        """
        if substrate not in SUBSTRATES:
            raise ValueError(
                f"substrate must be one of {SUBSTRATES}, got {substrate!r}")
        dt = jnp.result_type(W0.dtype, ops.dtype)
        if carry is None:
            carry = self.step.init_carry(ops, W0, dtype=dt)
        else:
            # accept a bare (S, W, G_prev) from an unaccelerated/plain-wire
            # producer; normalize_carry zero-fills the step's extra slots
            carry = self.step.normalize_carry(
                tuple(x.astype(dt) for x in carry))
        if self.dynamic is not None and \
                self.dynamic.schedule.constant_m(t0, T) != ops.m:
            raise ValueError(
                f"schedule agent count != ops.m={ops.m} over iterations "
                f"[{t0}, {t0 + T})")
        if substrate == "auto":
            if self.step.increasing:
                substrate = "unrolled"
            elif self.dynamic is None:
                substrate = "scan"
            else:
                substrate = "traced_scan" if self.step.track else "unrolled"
        if substrate == "scan" and self.engine is None:
            raise ValueError("substrate 'scan' needs a static engine")
        if substrate == "traced_scan" and self.dynamic is None:
            raise ValueError("substrate 'traced_scan' needs a dynamic engine")
        if substrate != "unrolled" and self.step.increasing:
            raise ValueError("increasing rounds require the unrolled "
                             "substrate (per-step static round counts)")
        with tracing.span("driver.run", substrate=substrate, T=int(T)):
            if substrate == "unrolled":
                out = self._run_unrolled(ops, W0, carry, T, t0, dt)
            else:
                out = self._run_scan(ops, W0, carry, T, t0, dt, substrate)
            # DriverRun already carries the paper's observables host-side
            # (cumulative gossip rounds, per-iteration contraction bound) —
            # stream them when a sink is installed.
            telemetry.emit_iterations(
                "driver.run", t0, out.rounds, out.rates, substrate=substrate,
                bytes_per_round=self.bytes_per_round(W0))
            if out.diag is not None and out.diag_names:
                diagnostics_lib.emit_diag(
                    "driver.run", t0, out.diag_names, out.diag,
                    floor=self.quantization_floor(), substrate=substrate)
        return out

    def bytes_per_round(self, W0: jax.Array) -> int:
        """Per-agent wire bytes per gossip round at this iterate shape
        (the engine's :meth:`~ConsensusEngine.bytes_per_round` at the
        ``(d, k)`` of ``W0``) — the cost model behind the telemetry
        ``bytes_on_wire`` field and the bench ``bytes_per_round`` rows."""
        d, k = int(W0.shape[-2]), int(W0.shape[-1])
        return self._gossip.bytes_per_round(d, k)

    # -------------------------------------------------- streaming substrate
    def run_stream(self, ticks, W0, *, T: int, t0: int = 0,
                   carry: Optional[Carry] = None, substrate: str = "auto"):
        """Streaming substrate: resumed T-iteration windows over an operator
        stream.

        ``ticks`` is any iterable of :class:`StackedOperators` — one entry
        per stream tick, each potentially a *different* problem (drifting
        data).  Every tick warm-starts from the previous tick's resumable
        ``(S, W, G_prev)`` carry with global-iteration accounting continued
        (``t0`` advances by ``T`` per tick), and yields that tick's
        :class:`DriverRun`.  Because the per-problem operators enter the
        cached jitted programs as *traced operands* (see :meth:`program`),
        every tick after the first reuses one compiled program — the
        property that makes warm-start online tracking cheap.

        Carrying the tracker state across an operator change is sound: at
        the end of a tick ``mean(S) == mean(G_prev)`` (Lemma 2), so the
        first tracked update against the *new* operators restores
        ``mean(S) == mean(A_new W)`` exactly — the subspace-tracking trick
        *is* the warm start.  Higher-level drift policy (escalation,
        tracker restart on abrupt change) lives in
        :class:`repro.streaming.tracker.StreamingDeEPCA`, which drives this
        loop tick-by-tick instead of consuming the generator.
        """
        for ops in ticks:
            run = self.run(ops, W0, T=T, t0=t0, carry=carry,
                           substrate=substrate)
            carry = run.carry
            t0 += T
            yield run

    # ------------------------------------------------------ stage profiling
    def profile_stages(self, ops: StackedOperators, W0: jax.Array, *,
                       iters: int = 5) -> dict:
        """Wall-clock the three stages of one power iteration separately —
        local ``apply`` (``A_j W_j``), gossip ``mix`` (Eqns. 3.1+3.2) and
        ``orth`` (Eqn. 3.3 QR + Alg. 2 sign adjust) — and emit one
        ``stage`` telemetry event per stage.

        Each stage runs as its own jitted program on representative
        operands from ``init_carry``: one untimed warm call, then
        best-of-``iters`` synchronized (``block_until_ready``) timings.
        The split is diagnostic — production steps run the *fused* path,
        so the sum of stages upper-bounds (not equals) the fused
        per-iteration cost; the ratio is what tells an operator whether a
        deployment is compute-, gossip- or QR-bound.  Returns
        ``{"apply": us, "mix": us, "orth": us}``.
        """
        import time
        from .step import qr_orth, sign_adjust

        step = self.step
        dt = jnp.result_type(W0.dtype, ops.dtype)
        carry = step.init_carry(ops, W0, dtype=dt)
        S, W, G_prev = carry[:3]
        mix = step.make_mix(self._gossip)
        W0_c = jnp.asarray(W0, dt)

        apply_j = jax.jit(lambda V: ops.apply(V))
        if step.ef_wire:
            ef0 = jnp.zeros_like(S)
            mix_j = jax.jit(lambda s, g, gp: mix(s, g, gp, ef0))
        else:
            mix_j = jax.jit(lambda s, g, gp: mix(s, g, gp))
        orth_j = jax.jit(lambda s: sign_adjust(qr_orth(s), W0_c))

        def best_us(fn, *args):
            jax.block_until_ready(fn(*args))     # warm (trace + compile)
            best = float("inf")
            for _ in range(max(1, int(iters))):
                tic = time.perf_counter()
                jax.block_until_ready(fn(*args))
                best = min(best, time.perf_counter() - tic)
            return best * 1e6

        G = apply_j(W)
        out = {}
        with tracing.span("driver.profile_stages", iters=int(iters)):
            with tracing.span("profile.apply"):
                out["apply"] = best_us(apply_j, W)
            with tracing.span("profile.mix"):
                out["mix"] = best_us(mix_j, S, G, G_prev)
            with tracing.span("profile.orth"):
                out["orth"] = best_us(orth_j, S)
        for stage, us in out.items():
            telemetry.emit("stage", source="driver.profile_stages",
                           stage=stage, us=us, iters=int(iters))
        return out

    @staticmethod
    def _apply_form(kind: str, arr: jax.Array, T: int) -> Optional[str]:
        """The apply's evaluation order for a launch of ``T`` iterations
        over ``arr`` (``(..., n, d)`` rows): ``"gram"`` where
        :func:`~repro.core.operators.gram_form_pays` holds, else
        ``"data"``; ``None`` for dense operators.  Every single-device
        launch (:meth:`run`'s substrates, :meth:`run_batch`, cold or
        resumed) decides here, so one operator and ``T`` give one form."""
        if kind == "dense":
            return None
        n, d = arr.shape[-2:]
        return "gram" if gram_form_pays(n, d, T) else "data"

    @staticmethod
    def _rebuild_ops(kind: str, form: Optional[str],
                     arr: jax.Array) -> StackedOperators:
        """Operators from a launch's operand; the Gram form forms each
        agent's Gram here, once per launch, never kept across launches."""
        if kind == "dense":
            return StackedOperators(dense=arr)
        ops = StackedOperators(data=arr)
        if form == "gram":
            with jax.named_scope(SCOPE_APPLY):
                ops = ops.with_gram()
        return ops

    @staticmethod
    def _form_attrs(form: Optional[str]) -> dict:
        return {} if form is None else {"form": form}

    def _rates(self, t0: int, T: int, rounds: int) -> np.ndarray:
        """Prop. 1 contraction bound of each iteration in ``[t0, t0 + T)``
        at ``rounds`` gossip rounds."""
        if self.dynamic is not None:
            return self.dynamic.contraction_rates(t0, T, rounds=rounds)
        return np.full(T, self.engine.contraction_rate(rounds),
                       dtype=np.float32)

    def program(self, T: int, kind: str, form: Optional[str], *,
                batched: bool = False, with_history: bool = True,
                resume: bool = True):
        """The jitted program of a compiled launch; returns ``(fn, warm)``.

        ``fn(arr, W0, xs, *carry)`` runs ``T`` iterations of one problem,
        or with ``batched`` of a ``vmap`` over a leading problem axis of
        every argument.  ``xs`` is ``None`` on a static engine, whose
        program closes over its own graph, and ``(Ls, etas)`` on a
        schedule (:meth:`DynamicConsensusEngine.operands`), scanned one
        step's ``(L, eta)`` at a time, so graph swaps never retrace.
        Without ``resume`` the program starts from ``init_carry`` and takes
        no carry.  It returns ``(carry, hists, diag)``: ``hists`` is
        ``(S_hist, W_hist)`` with ``with_history`` and ``diag`` the stacked
        observables with diagnostics on, each ``()`` otherwise.  ``form``
        (:meth:`_apply_form`) picks the apply's evaluation order.  The
        cache key holds every argument and the diagnostics spec, so
        toggling diagnostics never invalidates (or perturbs) the off path.
        """
        spec = self.diagnostics
        key = (T, kind, form, batched, with_history, resume, spec)
        fn = self._programs.get(key)
        if fn is not None:
            return fn, True
        step, eng = self.step, self._gossip

        def one(arr, W0, xs, *carry):
            ops = self._rebuild_ops(kind, form, arr)
            if not resume:
                carry = step.init_carry(ops, W0)

            def body(c, mixing):
                new_c, ys = step(
                    c, step.make_mix(eng, mixing=mixing), W0, ops.apply,
                    apply_mix=step.make_apply_mix(eng, ops, mixing=mixing))
                if spec is not None:
                    ys = ys + (step.measure(spec, new_c, c),)
                return new_c, ys

            carry, ys = jax.lax.scan(body, carry, xs, length=T)
            return (carry, ys[:2] if with_history else (),
                    ys[2] if spec is not None else ())

        # the lowered module is named after this function (jit_one_static,
        # ...), so each program keeps the name its device traces and its
        # compile-cache entries carry
        one.__name__ = ("scan_fn" if not batched else "one_static"
                        if self.dynamic is None else "one_dynamic")
        fn = self._programs[key] = jax.jit(jax.vmap(one) if batched else one)
        return fn, False

    def _launch(self, source: str, substrate: str, T: int, kind: str,
                arr: jax.Array, *args, **build):
        """The one compiled-launch path: the apply's form, the program
        (:meth:`program`), its ``launch`` event and ``driver.launch`` span;
        the argument shapes are kept for :meth:`lower_last_launch`."""
        form = self._apply_form(kind, arr, T)
        fn, warm = self.program(T, kind, form, **build)
        telemetry.emit("launch", source=source, substrate=substrate, T=T,
                       kind=kind, warm=warm, **self._form_attrs(form))
        attrs = {}
        if tracing.enabled():
            attrs = dict(substrate=substrate, T=int(T), warm=warm,
                         **self._form_attrs(form))
        args = (arr,) + args
        self._last_launch = (fn, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
        with tracing.span("driver.launch", **attrs):
            return fn(*args)

    def _run_scan(self, ops, W0, carry, T, t0, dt, substrate) -> DriverRun:
        """``scan`` and ``traced_scan``: one compiled launch of one problem."""
        xs = None if self.dynamic is None \
            else self.dynamic.operands(t0, T, dtype=dt)
        kind = "dense" if ops.dense is not None else "data"
        carry, (S_hist, W_hist), diag = self._launch(
            "driver.run", substrate, T, kind, ops.array, W0, xs, *carry)
        rounds = np.arange(1, T + 1, dtype=np.float32) * float(self.step.rounds)
        return DriverRun(carry, S_hist, W_hist, rounds,
                         self._rates(t0, T, self.step.rounds),
                         diag=diag if self.diagnostics is not None else None,
                         diag_names=self._diag_names())

    def _run_unrolled(self, ops, W0, carry, T, t0, dt) -> DriverRun:
        step, dyn, eng = self.step, self.dynamic, self._gossip
        spec = self.diagnostics
        S_hist, W_hist, rounds, rates, diag = [], [], [], [], []
        total = 0
        kind = "dense" if ops.dense is not None else "data"
        form = self._apply_form(kind, ops.array, T)
        with tracing.span("driver.launch", substrate="unrolled", T=int(T),
                          **self._form_attrs(form)):
            ops = self._rebuild_ops(kind, form, ops.array)
            for i in range(T):
                t = t0 + i
                r = step.rounds_at(t)
                total += r
                mixing = None
                if dyn is not None:
                    topo_t = dyn.topology_at(t)
                    mixing = (jnp.asarray(topo_t.mixing, dt),
                              dyn.eta_of(topo_t))
                rates.append(float(self._rates(t, 1, r)[0]))
                new_carry, (S_t, W_t) = step(
                    carry, step.make_mix(eng, rounds=r, mixing=mixing), W0,
                    ops.apply, apply_mix=step.make_apply_mix(
                        eng, ops, rounds=r, mixing=mixing))
                if spec is not None:
                    diag.append(step.measure(spec, new_carry, carry))
                carry = new_carry
                S_hist.append(S_t)
                W_hist.append(W_t)
                rounds.append(total)
        return DriverRun(carry, jnp.stack(S_hist), jnp.stack(W_hist),
                         np.asarray(rounds, dtype=np.float32),
                         np.asarray(rates, dtype=np.float32),
                         diag=jnp.stack(diag) if spec is not None else None,
                         diag_names=self._diag_names())

    # ----------------------------------------------- batched multi-problem
    def run_batch(self, ops_batch, W0, *, T: int,
                  t0: Optional[Sequence[int]] = None,
                  with_history: bool = False,
                  carry: Optional[Carry] = None) -> BatchRun:
        """One compiled program serving B independent PCA problems.

        The per-problem scan is ``vmap``-ped over a leading problem axis, so
        a serving process amortises compilation, dispatch and scheduling
        across every concurrent workload instead of running B sequential
        drivers — the batched substrate of the production serving story.
        The win is in the amortisation: one launch replaces B
        trace+dispatch round-trips (10-40x vs a driver per request on the
        CPU bench host, see ``bench_mixing.py --batched``); at
        compute-bound shapes on CPU a warm single driver's jitted-program
        cache can match it, and the batched program earns its keep on
        accelerators and under real request traffic.

        Args:
          ops_batch: list of B :class:`StackedOperators` (same kind and
            shapes), or one whose arrays carry a leading ``(B, m, ...)``
            problem axis.
          W0: ``(d, k)`` shared or ``(B, d, k)`` per-problem inits.
          t0: per-problem global iteration offsets (dynamic schedules index
            ``schedule.topology_at(t0_b + i)``; each problem may sit at a
            different point of the shared schedule).  Ignored for static
            engines.
          with_history: also return the ``(B, T, m, d, k)`` iterate
            histories (costly at scale; off for pure serving).
          carry: resume all B problems from a previous window's
            :attr:`BatchRun.carries` — a carry tuple whose every element
            has a leading ``(B, ...)`` problem axis.  This is the batched
            stream substrate: resumed windows over B concurrent drifting
            problems through ONE compiled program (what
            :class:`repro.streaming.fleet.TrackerFleet` ticks on).  Like
            :meth:`run`, a bare 3-slot ``(S, W, G_prev)`` is
            zero-extended to the step's slot layout and cast to the run
            dtype.  Resume and cold-start compile as sibling cache
            entries, so mixing them never retraces either.

        The gossip math runs in stacked/traced form (``shard_map`` cannot be
        vmapped over problems — devices are a physical axis); the tracking
        combine still routes through the shared compute site.

        Data operators take the apply's evaluation order that :meth:`run`
        takes at the same shapes and ``T`` (:meth:`_apply_form`): where
        :func:`~repro.core.operators.gram_form_pays` holds, each agent's
        Gram ``X_j^T X_j`` is formed once inside the launch at
        ``HIGHEST`` precision and never kept across launches.
        """
        if self._gossip.backend == "shard_map":
            raise ValueError(
                "run_batch cannot vmap the shard_map backend (devices are "
                "a physical axis); use stacked/pallas for batched serving")
        step = self.step
        if step.increasing:
            raise ValueError("increasing rounds cannot be batched "
                             "(round counts vary per problem step)")
        with tracing.span("driver.stack"):
            kind, arr = self._stack_problems(ops_batch)
        B = arr.shape[0]
        W0 = jnp.asarray(W0)
        if W0.ndim == 2:
            W0 = jnp.broadcast_to(W0, (B,) + W0.shape)
        dt = jnp.result_type(W0.dtype, arr.dtype)
        resume = carry is not None
        if resume:
            carry = step.normalize_carry(
                tuple(jnp.asarray(x).astype(dt) for x in carry))
            bad = [tuple(x.shape) for x in carry if x.shape[:1] != (B,)]
            if bad:
                raise ValueError(
                    f"resume carry needs a leading problem axis B={B} on "
                    f"every slot; got shapes {bad}")
        resumed: Tuple[jax.Array, ...] = tuple(carry) if resume else ()

        xs = None
        if self.dynamic is not None:
            offs = [0] * B if t0 is None else [int(x) for x in t0]
            if len(offs) != B:
                raise ValueError(f"t0 has {len(offs)} offsets for {B} "
                                 "problems")
            Ls, etas = zip(*(self.dynamic.operands(off, T, dtype=dt)
                             for off in offs))
            xs = (jnp.stack(Ls), jnp.stack(etas))
        carry, hists, dvals = self._launch(
            "driver.run_batch", "vmap", T, kind, arr, W0, xs, *resumed,
            batched=True, with_history=with_history, resume=resume)
        diag = dvals if self.diagnostics is not None else None
        S, W, G_prev = carry[:3]
        extras = tuple(carry[3:])
        names = self._diag_names()
        if telemetry.enabled():
            K = step.rounds
            rates = self._rates(offs[0] if xs is not None else 0, T, K)
            rounds = np.arange(1, T + 1, dtype=np.float32) * float(K)
            telemetry.emit_iterations(
                "driver.run_batch", 0, rounds, rates, batch=B,
                bytes_per_round=self.bytes_per_round(W0))
            if diag is not None and names:
                # one event stream for the batch: worst problem per
                # iteration/observable (max over the B axis)
                diagnostics_lib.emit_diag(
                    "driver.run_batch", 0, names,
                    np.asarray(diag).max(axis=0),
                    floor=self.quantization_floor(), batch=B)
        if with_history:
            return BatchRun(S, W, G_prev, S_hist=hists[0], W_hist=hists[1],
                            extras=extras, diag=diag, diag_names=names)
        return BatchRun(S, W, G_prev, extras=extras, diag=diag,
                        diag_names=names)

    @staticmethod
    def _stack_problems(ops_batch) -> Tuple[str, jax.Array]:
        """Normalise a problem batch to ``(kind, (B, m, ...) array)``."""
        if isinstance(ops_batch, StackedOperators):
            arr = ops_batch.array
            if arr.ndim != 4:
                raise ValueError(
                    "a StackedOperators batch needs a leading problem axis "
                    f"(B, m, ...); got shape {arr.shape}")
            return ("dense" if ops_batch.dense is not None else "data"), arr
        kinds = {("dense" if o.dense is not None else "data")
                 for o in ops_batch}
        if len(kinds) != 1:
            raise ValueError(f"mixed operator kinds in batch: {kinds}")
        kind = kinds.pop()
        return kind, jnp.stack([o.array for o in ops_batch])

    # --------------------------------------------------- shard_map builders
    def sharded_step_fn(self, mesh, axis: str, engine: ConsensusEngine,
                        operator_kind: str = "auto"):
        """Jitted distributed step for a *structured* topology lowering.

        Gossip goes through ``engine.local_mix_track`` (ring/hypercube
        ``collective_permute`` or dense ``all_gather``, chosen structurally
        by the engine's round fn); the body is the shared PowerStep on the
        per-device ``(1, d, k)`` slice.  The jitted step takes and returns
        ``step.carry_slots`` state arrays (the accelerated ``W_prev`` slot
        shards like the rest; EF wire modes are rejected — wire precision
        is a stacked/pallas feature).
        """
        import functools
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        step = self.step
        if step.ef_wire:
            raise ValueError(
                "EF wire modes are not supported on the shard_map "
                "substrate (the engine rejects wire_dtype there)")
        if self.diagnostics is not None:
            raise ValueError(
                "in-graph diagnostics are not supported on the shard_map "
                "substrate (observables are max-over-agents reductions; "
                "agents are a physical device axis there)")
        nslots = step.carry_slots
        spec_v = P(axis)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(axis),) + (spec_v,) * nslots + (P(),),
            out_specs=(spec_v,) * nslots,
            check_vma=False)
        def _step(A, *rest):
            carry, W0 = rest[:-1], rest[-1]

            def mix(S_, G_, Gp_):
                if step.track:
                    return engine.local_mix_track(S_, G_, Gp_, axis=axis)
                return engine.local_mix(G_, axis=axis)

            new_carry, _ = step(
                carry, mix, W0,
                lambda V: local_apply(A, V, kind=operator_kind))
            return new_carry

        return jax.jit(_step)

    def sharded_dense_step_fn(self, mesh, axis: str,
                              operator_kind: str = "auto"):
        """One jitted distributed step shared by ALL dense-lowered graphs.

        ``L`` (replicated ``(m, m)``) and ``eta`` are traced operands:
        swapping to any other same-``m`` dense graph reuses the compiled
        step — the no-retrace contract for dynamic topologies.
        """
        import functools
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.kernels.fastmix import tracking_update

        step = self.step
        if step.ef_wire:
            raise ValueError(
                "EF wire modes are not supported on the shard_map "
                "substrate (the engine rejects wire_dtype there)")
        if self.diagnostics is not None:
            raise ValueError(
                "in-graph diagnostics are not supported on the shard_map "
                "substrate (observables are max-over-agents reductions; "
                "agents are a physical device axis there)")
        K = step.rounds
        nslots = step.carry_slots
        spec_v = P(axis)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(axis),) + (spec_v,) * nslots + (P(), P(), P()),
            out_specs=(spec_v,) * nslots,
            check_vma=False)
        def _step(A, *rest):
            carry, (W0, L, eta) = rest[:-3], rest[-3:]
            from .gossip_shard import _dense_round, fastmix_local

            def mix(S_, G_, Gp_):
                x = tracking_update(S_, G_, Gp_) if step.track else G_
                return fastmix_local(
                    x, lambda y: _dense_round(y, L, axis), eta, K)

            new_carry, _ = step(
                carry, mix, W0,
                lambda V: local_apply(A, V, kind=operator_kind))
            return new_carry

        return jax.jit(_step)
