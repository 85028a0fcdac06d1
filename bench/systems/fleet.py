"""Drifting streams tracked by ``TrackerFleet``, ticked in lock step.

Set-up draws the tenants' streams on the device (the configuration's
``data`` generator), builds the fleet as the deployment runs it (the
gossip graph, ``backend="auto"``, the drift policy, ``pad_n``) and joins
every tenant from its agent 0's local basis of tick 0.  It then runs the
first ticks: tick 0 compiles both buckets' window programs, tick 1 starts
the drift baselines, and tick 2, under a policy that restarts and
escalates every tenant, runs the restart and escalation passes, so no
program of the window compiles.

In the window tick ``t``'s rows are drawn on the device and waited for
(``bench.generate``), then ``fleet.tick`` consumes them (``bench.tick``)
and each bucket's answers, every tenant's ``(m, d, k)`` estimate, are
fetched to the host in bucket order (``bench.result``); tick ``t + 1``
starts once all are there: a closed loop in lock step.  A tenant-tick,
one tenant's tracked basis for one new window of rows, is a solve; its
latency runs from its tick's start to its bucket's answers on the host,
so the second bucket's tenants wait for the first's.  The window closes
at the last answer of the last tick it started, and the rate is the
solves over that span: a tick of some 64 solves is too coarse a unit to
count only those that end before ``seconds``.  A tracer the
system installs for the window counts the fleet's ``fleet.window`` spans
and times its ``fleet.stats`` host reads.

``correct`` is decided after the window, untimed, on ``check.tenants``
tenants drawn from the seed, at every tick of the window:
``tan_replay_worst`` is the worst agent's tan-theta of the fleet's
estimate against the plain float64 tracker (``reference_tracker.py``)
replayed from the tenant's join over the same rows, with the iterations
and restarts the fleet reported; ``tan_truth_worst`` the worst agent's
against the float64 top-k of the tick's empirical mean covariance.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

import jax
from jax.profiler import TraceAnnotation

import harness
import reference
import reference_tracker

#: the fleet's spans the window's tracer reads
SPANS = ("fleet.window", "fleet.stats", "fleet.stack", "fleet.bucket")


class State:
    pass


class SpanCounter:
    """A tracer for ``repro.runtime.tracing.set_tracer`` that keeps, per
    span name, its count and summed seconds; nothing else."""

    jax_annotations = False

    def __init__(self):
        self.spans: Dict[str, List[float]] = {}

    def record(self, name, ts_us, dur_us, tid, args) -> None:
        c = self.spans.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += dur_us * 1e-6


def tenant_rows(cfg) -> List[int]:
    """Rows per agent of each tenant: the serve CLI's mix of ten sizes,
    ``n_per_agent - 8`` in steps of 2 (``serve.fleet_tenant_mix``)."""
    n, k = int(cfg["n_per_agent"]), int(cfg["k"])
    return [max(k + 2, n - 8 + 2 * (i % 10)) for i in range(cfg["tenants"])]


def setup(ctx) -> State:
    from repro.core import erdos_renyi
    from repro.core.operators import StackedOperators
    from repro.streaming import DriftPolicy, TrackerFleet

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    st = State()
    st.seed = ctx.seed
    st.m, st.d, st.k = cfg["m"], cfg["d"], cfg["k"]
    st.K, st.T = cfg["K"], cfg["T_tick"]
    st.ns = tenant_rows(cfg)
    st.tids = [f"tenant{i:03d}" for i in range(cfg["tenants"])]
    st.Ops = StackedOperators
    t0 = time.perf_counter()
    gen = harness.generator(cfg["data"]["generator"])
    opts = {k: v for k, v in cfg["data"].items() if k != "generator"}
    stream = gen.setup(ctx.seed, tenants=len(st.tids),
                       m=st.m, d=st.d, k=st.k,
                       heterogeneity=cfg["heterogeneity"])
    st.draw = lambda t: gen.tick(stream, t, ns=tuple(st.ns),
                                 rate=cfg["rate"], **opts)
    X0 = jax.block_until_ready(st.draw(0))
    if tr["start"] != "agent0":
        raise ValueError(f"unknown start {tr['start']!r}")
    st.W0 = {tid: reference.local_basis(X[0], st.k)
             for tid, X in zip(st.tids, X0)}
    st.checked = sorted(np.random.default_rng([int(ctx.seed), 5]).choice(
        len(st.tids), size=min(int(cfg["check"]["tenants"]), len(st.tids)),
        replace=False).tolist())
    t1 = time.perf_counter()

    st.policy = DriftPolicy(**cfg["policy"])
    st.fleet = TrackerFleet(
        k=st.k, T_tick=st.T, K=st.K, backend="auto", policy=st.policy,
        topology=erdos_renyi(st.m, p=cfg["p"], seed=ctx.seed),
        pad_n=cfg["pad_n"])
    groups: Dict[tuple, List[int]] = {}
    for i, tid in enumerate(st.tids):
        st.fleet.join(tid, st.W0[tid], n=st.ns[i])
        key = st.fleet.bucket_of(st.d, st.k, st.ns[i])
        groups.setdefault(key, []).append(i)
    # buckets tick in the order their first tenant joined
    st.groups = list(groups.values())
    st.log = {i: [] for i in st.checked}     # every tick since the join
    st.t = 0
    _tick(st, X0)
    _tick(st)
    st.fleet.policy = DriftPolicy(jump=0.0, restart=0.0, max_escalations=1)
    _tick(st)
    st.fleet.policy = st.policy
    st.setup_parts = {"data_s": t1 - t0, "warm_s": time.perf_counter() - t1}
    return st


def _tick(st: State, X=None):
    """One fleet tick in lock step; returns its index, its report, its
    start, and each bucket's answers on the host with the time they got
    there."""
    if X is None:
        with TraceAnnotation("bench.generate"):
            X = jax.block_until_ready(st.draw(st.t))
    fleet = st.fleet
    start = time.perf_counter()
    with TraceAnnotation("bench.tick"):
        rep = fleet.tick({tid: st.Ops(data=x) for tid, x in zip(st.tids, X)})
    answers = []
    with TraceAnnotation("bench.result"):
        for group in st.groups:
            Ws = jax.device_get([fleet.tenant_W(st.tids[i]) for i in group])
            answers.append((time.perf_counter(), dict(zip(group, Ws))))
    t = st.t
    for i in st.checked:
        r = rep.tenants[st.tids[i]]
        st.log[i].append((t, r.restarted, r.escalations))
    st.t += 1
    return t, rep, start, answers


def window(ctx, st: State, seconds: float) -> harness.Window:
    from repro.runtime import tracing
    counter = SpanCounter()
    prev = tracing.set_tracer(counter)
    lat, answers = [], {}
    served = failed = iterations = ticks = 0
    cold = st.fleet.stats["cold_launches"]
    t_start = last = time.perf_counter()
    t_end = t_start + seconds
    first = st.t
    try:
        with TraceAnnotation("bench.window"):
            while time.perf_counter() < t_end:
                t, rep, start, got = _tick(st)
                ticks += 1
                for done, Ws in got:
                    for i in Ws:
                        r = rep.tenants.get(st.tids[i])
                        if r is None or not np.isfinite(r.stat):
                            failed += 1
                        else:
                            iterations += r.iterations
                        served += 1
                        last = max(last, done)
                        lat.append(done - start)
                        if i in st.log:
                            answers[i, t] = Ws[i]
    finally:
        tracing.set_tracer(prev)
    attempted = ticks * len(st.tids)
    spans = {k: v for k, v in counter.spans.items() if k in SPANS}
    print(f"[bench] window: {ticks} ticks from tick {first}, "
          f"{attempted} tenant-ticks, "
          f"{st.fleet.stats['cold_launches'] - cold} cold launches; per "
          "tick " + " ".join(f"{k}={v[0] / max(ticks, 1):.2f}x/"
                             f"{1e3 * v[1] / max(ticks, 1):.3f}ms"
                             for k, v in sorted(spans.items())),
          file=sys.stderr, flush=True)
    return harness.Window(
        attempted=attempted, failed=failed + attempted - served,
        e2e={"solves_per_s": served / (last - t_start),
             "solve_p95_ms": 1e3 * harness.quantile(lat, 0.95)},
        counters={"solves": served, "iterations": iterations,
                  "ticks": ticks, "spans": spans,
                  "shape": {"m": st.m, "d": st.d, "k": st.k, "K": st.K}},
        answers=answers)


def route(st: State) -> str:
    """The gossip backend and the Pallas kernels in the window program
    the fleet launched last, lowered at the shapes it ran with."""
    return harness.route(st.fleet.driver)


def release(st: State) -> None:
    """Free the program's state before the reference runs."""
    st.fleet = None


def _rows(st: State):
    """``(t, {tenant: (m, n, d) float64 rows})`` of the checked tenants,
    tick by tick from 0, drawn again on the device as the run drew them."""
    last = max(t for log in st.log.values() for t, _, _ in log)
    for t in range(last + 1):
        X = st.draw(t)
        yield t, {i: np.asarray(X[i], np.float64) for i in st.checked}


def _replays(ctx, st: State, precision: str) -> Dict[Tuple[int, int], np.ndarray]:
    """``{(tenant, tick): (m, d, k)}``: each checked tenant's reference
    tracker, in ``precision``, replayed over every tick since its join
    with the restarts and escalations the fleet reported."""
    from threadpoolctl import threadpool_limits
    cfg = ctx.cell.config
    adj = reference_tracker.erdos_renyi_adjacency(st.m, cfg["p"], ctx.seed)
    esc_T = cfg["policy"].get("escalate_T") or st.T
    trackers = {i: reference_tracker.ReferenceTracker(
        adj, st.K, st.W0[st.tids[i]], precision=precision)
        for i in st.checked}
    what = {i: dict((t, (r, e)) for t, r, e in st.log[i])
            for i in st.checked}
    out = {}
    with threadpool_limits(limits=1, user_api="blas"), \
            concurrent.futures.ThreadPoolExecutor(len(st.checked)) as pool:
        for t, rows in _rows(st):
            def one(i):
                r, e = what[i][t]
                W = trackers[i].tick(rows[i], st.T, restarted=r,
                                     escalations=e, escalate_T=esc_T)
                return i, np.array(W)
            for i, W in pool.map(one, st.checked):
                out[i, t] = W
    return out


def _truths(st: State, ticks) -> Dict[Tuple[int, int], np.ndarray]:
    """``{(tenant, tick): (d, k)}``: the float64 top-k of each checked
    tenant's empirical mean covariance at the given ticks."""
    from threadpoolctl import threadpool_limits
    out = {}
    want = set(ticks)
    with threadpool_limits(limits=1, user_api="blas"), \
            concurrent.futures.ThreadPoolExecutor(len(st.checked)) as pool:
        for t, rows in _rows(st):
            if t not in want:
                continue
            for i, U in pool.map(lambda i: (i, reference.ref_basis(
                    rows[i], st.k)), st.checked):
                out[i, t] = U
    return out


def _reference(ctx, st: State, win: harness.Window):
    """The f64 replays and truths at the window's ticks (computed once)."""
    if getattr(st, "ref", None) is None:
        replay = _replays(ctx, st, "float64")
        ticks = sorted({t for _, t in win.answers})
        st.ref = {key: (replay[key], U)
                  for key, U in _truths(st, ticks).items()}
    return st.ref


def _worst_agent_pairwise(A: np.ndarray, B: np.ndarray) -> float:
    """The largest tan-theta between agent ``j``'s bases in ``A`` and in
    ``B`` (both ``(m, d, k)``; :func:`reference.tan_f64` over the stack at
    once); ``inf`` where ``B`` is not finite."""
    if B.shape != A.shape or not np.all(np.isfinite(B)):
        return float("inf")
    U = np.linalg.qr(np.asarray(A, np.float64))[0]
    Q = np.linalg.qr(np.asarray(B, np.float64))[0]
    UtQ = np.swapaxes(U, -1, -2) @ Q
    c = np.linalg.svd(UtQ, compute_uv=False).min(axis=-1)
    s = np.linalg.svd(Q - U @ UtQ, compute_uv=False).max(axis=-1)
    return float(np.max(s / np.maximum(c, 1e-300)))


def answer_tans(ctx, st: State, win: harness.Window) -> List:
    """``(tenant, tick, replay tan, truth tan)`` of every checked answer."""
    ref = _reference(ctx, st, win)
    out = []
    for (i, t), W in sorted(win.answers.items()):
        Wr, U = ref[i, t]
        out.append((i, t, _worst_agent_pairwise(Wr, W),
                    reference.worst_agent_tan(U, W)))
    return out


def check(ctx, st: State, win: harness.Window) -> List[harness.Check]:
    """The worst agent over the checked answers, against the replay and
    against the truth."""
    lim = ctx.cell.config["check"]
    got = answer_tans(ctx, st, win)
    replay = max((r for _, _, r, _ in got), default=float("inf"))
    truth = max((u for _, _, _, u in got), default=float("inf"))
    return [harness.Check("tan_replay_worst", replay,
                          float(lim["tan_replay_worst"])),
            harness.Check("tan_truth_worst", truth,
                          float(lim["tan_truth_worst"]))]


def detail(ctx, st: State, win: harness.Window) -> Dict:
    """How the checked answers read: their count, the medians, and the
    five worst of each with their tenant and tick."""
    got = answer_tans(ctx, st, win)
    out = {"answers": len(got)}
    for j, name in ((2, "replay"), (3, "truth")):
        vals = np.array([g[j] for g in got])
        worst = sorted(got, key=lambda g: -g[j])[:5]
        out[name] = {"median": float(np.median(vals)) if len(got) else None,
                     "worst": [[int(g[0]), int(g[1]), float(g[j])]
                               for g in worst]}
    return out


def control(ctx, st: State, win: harness.Window) -> harness.Window:
    """The window with every checked answer replaced by the reference
    tracker's, replayed in bfloat16."""
    bf16 = _replays(ctx, st, "bfloat16")
    return dataclasses.replace(
        win, answers={key: bf16[key] for key in win.answers})
