"""Compile-count harness: pins the repo's no-retrace contracts.

The per-iteration hot paths are designed so that steady-state serving
never re-enters XLA: dynamic-topology mixing takes the graph ``L`` as a
*traced* operand (same-m graph swaps reuse the compiled program),
``IterationDriver`` caches its jitted scan programs per ``(T, kind)``,
``run_batch`` buckets ragged requests onto warm shapes, and streaming
ticks ride one compiled window program.  A regression that turns any of
these into a static argument (or keys a cache on array *values*) is
invisible to correctness tests — everything still converges, just 100x
slower — so this pass counts actual XLA compilations.

Counting uses ``jax_log_compiles``: with the flag enabled, jax logs one
``"Finished XLA compilation ..."`` WARNING per compile on the
``jax._src.dispatch`` logger; :func:`count_compiles` attaches a handler
and tallies them.  Each :class:`RetraceContract` runs an uncounted
warm-up, then a counted steady-state phase whose compile count must not
exceed its budget (0 for every shipped contract).
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
from typing import Callable, Iterator, List, Optional, Sequence

from .report import PassResult

_COMPILE_LOGGER = "jax._src.dispatch"
_COMPILE_PREFIX = "Finished XLA compilation"


class _CompileHandler(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.messages: List[str] = []

    @property
    def count(self) -> int:
        return len(self.messages)

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith(_COMPILE_PREFIX):
            self.messages.append(msg)


@contextlib.contextmanager
def count_compiles() -> Iterator[_CompileHandler]:
    """Context manager counting XLA compilations inside the block.

    The ``jax_log_compiles`` toggle goes through
    :func:`repro.runtime.config.log_compiles` — ``jax.config`` mutation
    outside ``runtime/config.py`` is banned by the env-config lint pass.
    """
    from repro.runtime import config as runtime_config

    with runtime_config.log_compiles(True):
        logger = logging.getLogger(_COMPILE_LOGGER)
        prev_level = logger.level
        if logger.getEffectiveLevel() > logging.WARNING:
            logger.setLevel(logging.WARNING)
        handler = _CompileHandler()
        logger.addHandler(handler)
        try:
            yield handler
        finally:
            logger.removeHandler(handler)
            logger.setLevel(prev_level)


@dataclasses.dataclass(frozen=True)
class RetraceContract:
    """One no-retrace contract.

    ``build()`` returns ``(warmup, steady)`` thunks; ``warmup`` runs
    outside the counter (first-call compiles are expected), ``steady``
    runs inside it and may trigger at most ``budget`` compilations.
    """

    name: str
    build: Callable
    budget: int = 0
    doc: str = ""


# ---------------------------------------------------------------- contracts
def _mini_problem(m=6, d=16, k=3, seed=0):
    from repro.core.operators import synthetic_spiked
    import jax.numpy as jnp
    import numpy as np
    ops = synthetic_spiked(m, d, k, n_per_agent=20, seed=seed)
    rng = np.random.default_rng(seed + 1)
    W0 = jnp.asarray(np.linalg.qr(rng.standard_normal((d, k)))[0],
                     jnp.float32)
    return ops, W0


def _build_dynamic_swap():
    """Same-m topology swap through the traced dynamic mixer: the graph is
    a runtime operand, so ring -> Erdos-Renyi (same m) must reuse the
    compiled program."""
    import jax
    import jax.numpy as jnp
    from repro.core.consensus import DynamicConsensusEngine
    from repro.core.schedule import TopologySchedule
    from repro.core.topology import erdos_renyi, ring

    m = 6
    dyn = DynamicConsensusEngine(
        schedule=TopologySchedule.constant(ring(m)), K=3, backend="stacked")
    ops, W0 = _mini_problem(m=m)
    S = jnp.broadcast_to(W0, (m,) + W0.shape)
    G = ops.apply(S)
    eng = dyn.engine_at(0)
    fn = jax.jit(lambda S, G, Gp, L, eta: eng.mix_track(S, G, Gp,
                                                        mixing=(L, eta)))
    L_ring = jnp.asarray(ring(m).mixing, jnp.float32)
    L_er = jnp.asarray(erdos_renyi(m, p=0.6, seed=3).mixing, jnp.float32)

    def warmup():
        fn(S, G, S, L_ring, 0.5).block_until_ready()

    def steady():
        fn(S, G, S, L_er, 0.4).block_until_ready()
        fn(S, G, S, L_ring, 0.5).block_until_ready()

    return warmup, steady


def _build_driver_schedule_window():
    """Dynamic-schedule driver windows at different ``t0`` (different
    topologies in the scanned ``Ls``) share one traced_scan program."""
    from repro.core.algorithms import resolve_engines
    from repro.core.driver import IterationDriver
    from repro.core.schedule import TopologySchedule
    from repro.core.step import PowerStep
    from repro.core.topology import complete, ring

    m = 6
    sched = TopologySchedule.piecewise([(0, ring(m)), (2, complete(m))])
    dyn, _ = resolve_engines("deepca", None, 3, schedule=sched,
                             backend="stacked")
    driver = IterationDriver(step=PowerStep(track=True, rounds=3),
                             dynamic=dyn)
    ops, W0 = _mini_problem(m=m)

    def warmup():
        driver.run(ops, W0, T=2, t0=0)

    def steady():
        driver.run(ops, W0, T=2, t0=2)   # crosses the topology knot
        driver.run(ops, W0, T=2, t0=0)

    return warmup, steady


def _build_streaming_ticks():
    """Warm streaming ticks over a drifting stream are pure resumed
    windows on one compiled program — zero compiles after tick 1."""
    import math
    from repro.streaming import (DriftPolicy, SlowRotationStream,
                                 StreamingDeEPCA)
    from repro.core.topology import ring

    s = SlowRotationStream(m=6, d=16, k=3, n_per_agent=20, seed=0,
                           rate=0.05)
    passive = DriftPolicy(jump=math.inf, restart=math.inf, target=None,
                          max_escalations=0)
    tr = StreamingDeEPCA(k=3, T_tick=2, K=3, topology=ring(6),
                         backend="stacked", W0=s.init_W0(),
                         policy=passive)

    def warmup():
        tr.tick(s.ops_at(0))
        tr.tick(s.ops_at(1))

    def steady():
        for t in (2, 3, 4):
            tr.tick(s.ops_at(t))

    return warmup, steady


def _build_run_batch():
    """Warm ``run_batch`` over same-bucket problem batches reuses the
    vmapped program (fresh data, same shapes)."""
    import jax.numpy as jnp
    from repro.core.consensus import ConsensusEngine
    from repro.core.driver import IterationDriver
    from repro.core.step import PowerStep
    from repro.core.topology import ring

    eng = ConsensusEngine(topology=ring(6), K=3, backend="stacked")
    driver = IterationDriver(step=PowerStep(track=True, rounds=3),
                             engine=eng)
    ops0, W0 = _mini_problem(m=6, seed=0)
    ops1, _ = _mini_problem(m=6, seed=7)
    from repro.core.operators import StackedOperators
    arr0 = jnp.stack([ops0.array, ops1.array])
    arr1 = jnp.stack([ops1.array, ops0.array])
    W0b = jnp.stack([W0, W0])

    def run(arr):
        out = driver.run_batch(StackedOperators(data=arr), W0b, T=2)
        out.W.block_until_ready()

    return (lambda: run(arr0)), (lambda: run(arr1))


def _build_driver_run():
    """Warm ``driver.run`` repeats (same T/kind, fresh data) hit the
    per-driver program cache."""
    from repro.core.consensus import ConsensusEngine
    from repro.core.driver import IterationDriver
    from repro.core.step import PowerStep
    from repro.core.topology import ring

    eng = ConsensusEngine(topology=ring(6), K=3, backend="stacked")
    driver = IterationDriver(step=PowerStep(track=True, rounds=3),
                             engine=eng)
    ops0, W0 = _mini_problem(m=6, seed=0)
    ops1, _ = _mini_problem(m=6, seed=5)

    def warmup():
        driver.run(ops0, W0, T=3)

    def steady():
        driver.run(ops1, W0, T=3)
        driver.run(ops0, W0, T=3)

    return warmup, steady


def _build_diag_run():
    """Diagnostics-ON warm runs: the measured in-graph observables ride
    the same cached scan program (cache keyed on the DiagnosticsSpec, so
    diag-on and diag-off each compile once and then stay warm)."""
    from repro.core.consensus import ConsensusEngine
    from repro.core.driver import IterationDriver
    from repro.core.step import PowerStep
    from repro.core.topology import ring

    eng = ConsensusEngine(topology=ring(6), K=3, backend="stacked")
    driver = IterationDriver(step=PowerStep(track=True, rounds=3),
                             engine=eng, diagnostics="on")
    ops0, W0 = _mini_problem(m=6, seed=0)
    ops1, _ = _mini_problem(m=6, seed=5)

    def warmup():
        driver.run(ops0, W0, T=3)

    def steady():
        driver.run(ops1, W0, T=3)
        driver.run(ops0, W0, T=3)

    return warmup, steady


def _build_fleet_warm():
    """Warm fleet ticks — including tenant join/leave churn, in-batch
    restarts and escalation windows — are masked selects and slot
    scatters on compiled programs: ZERO steady-state compiles.

    The warm-up phase deliberately exercises every eager program the
    fleet can reach (both shape buckets' base windows, the vmapped
    restart rebase, the escalation window, the stats program, each
    bucket's slot-stack program, the row pad of each row count, and the
    join/leave scatter ops) so the counted phase proves the
    membership-churn-never-retraces contract, not first-call compiles.
    Steady churn must not recompile the stack program either: it takes
    the whole pool, free slots riding a filler, and a truth and its mask
    for every slot, so a leave with no rejoin, a join with other rows
    into the same bucket and a tenant that starts giving a truth keep its
    arguments' count and shapes.
    """
    from repro.streaming import (DriftPolicy, SlowRotationStream,
                                 TrackerFleet)
    from repro.core.topology import ring

    m = 6
    # hair-trigger policy: every tick restarts AND escalates, so the
    # masked drift passes compile during warm-up and must stay warm
    hot = DriftPolicy(jump=1e-9, restart=1e-9, target=1e-12,
                      max_escalations=1)
    fleet = TrackerFleet(k=3, T_tick=2, K=3, topology=ring(m),
                         backend="stacked", policy=hot, slots=2)
    sa = SlowRotationStream(m=m, d=16, k=3, n_per_agent=20, seed=0,
                            rate=0.05)
    sb = SlowRotationStream(m=m, d=16, k=3, n_per_agent=36, seed=1,
                            rate=0.05)          # second shape bucket
    sc = SlowRotationStream(m=m, d=16, k=3, n_per_agent=18, seed=2,
                            rate=0.05)          # a's bucket, other rows
    streams = {"a": sa, "b": sb, "b2": sb, "c": sc}
    truthless = {"c"}
    fleet.join("a", sa.init_W0(), n=20)
    fleet.join("b", sb.init_W0(), n=36)
    fleet.join("c", sc.init_W0(), n=18)

    def items(t):
        # whatever the current membership is, feed exactly those tenants
        ticks = {tid: streams[tid].tick(t) for tid in fleet.tenants}
        return {tid: tk.ops if tid in truthless else tk
                for tid, tk in ticks.items()}

    def warmup():
        fleet.tick(items(0))        # escalation programs
        fleet.tick(items(1))        # the drift baselines start
        fleet.tick(items(2))        # both buckets' restart programs
        fleet.leave("b")            # churn: evict ...
        fleet.join("b2", sb.init_W0(), n=36)   # ... re-admit same slot
        fleet.tick(items(3))

    def steady():
        fleet.leave("b2")
        fleet.join("b", sb.init_W0(), n=36)
        fleet.tick(items(4))
        fleet.leave("c")            # a leave with no rejoin
        fleet.tick(items(5))
        streams["c"] = sa           # a join with other rows, same bucket
        fleet.join("c", sa.init_W0(), n=20)
        fleet.tick(items(6))
        truthless.clear()           # c starts giving a truth
        fleet.tick(items(7))

    return warmup, steady


CONTRACTS = (
    RetraceContract("dynamic-same-m-swap", _build_dynamic_swap,
                    doc="graph L is a traced operand"),
    RetraceContract("driver-schedule-window", _build_driver_schedule_window,
                    doc="traced_scan cache keyed (T, kind), not on Ls"),
    RetraceContract("streaming-warm-ticks", _build_streaming_ticks,
                    doc="ticks resume one compiled window program"),
    RetraceContract("run-batch-warm-bucket", _build_run_batch,
                    doc="batch cache keyed (T, kind, ...), not on data"),
    RetraceContract("driver-run-warm", _build_driver_run,
                    doc="run cache keyed (T, kind)"),
    RetraceContract("diag-run-warm", _build_diag_run,
                    doc="diag observables ride the cached scan program "
                        "(cache keyed (T, kind, spec))"),
    RetraceContract("fleet-warm", _build_fleet_warm,
                    doc="fleet join/leave/restart/escalation are slot "
                        "scatters and masked selects on warm programs"),
)


def measure(contract: RetraceContract):
    """Run one contract; returns ``(count, messages)`` from the counted
    steady-state phase."""
    warmup, steady = contract.build()
    warmup()
    with count_compiles() as counter:
        steady()
    return counter.count, list(counter.messages)


def run(names: Optional[Sequence[str]] = None) -> PassResult:
    result = PassResult(name="retrace")
    for contract in CONTRACTS:
        if names is not None and contract.name not in names:
            continue
        try:
            count, messages = measure(contract)
        except Exception as e:
            result.add("harness-error", contract.name, 0,
                       f"contract failed to run: {type(e).__name__}: {e}")
            continue
        result.checked += 1
        if count > contract.budget:
            detail = "; ".join(m.split(" in ")[0] for m in messages[:3])
            result.add(
                "retrace", contract.name, 0,
                f"{count} XLA compilation(s) in steady state "
                f"(budget {contract.budget}; {contract.doc}): {detail}")
    return result
