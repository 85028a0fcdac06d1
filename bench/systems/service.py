"""One-shot PCA solves served through ``PCAService``, by closed-loop clients.

Set-up draws the configuration's pool of problems on the device, builds
the service as the deployment runs it (its admission policy, the gossip
graph, ``backend="auto"``), and warms the one batch shape the traffic
uses by running the loop itself for two rounds of every client.

In the window each of the traffic's ``clients`` submits a request, waits
for its answer to reach the host, and submits the next: a closed loop.
Request ``i`` asks for the pool's problem ``order[i % pool]`` (a seeded
permutation).  Its ``(d, k)`` start is, by the traffic's ``start``,
``agent0``: the top-k basis of the problem's agent 0's own rows (a client
that warm-starts from its local PCA), or ``random``: drawn from
``(seed, i)``.  The loop calls ``poll`` only while it waits, so a launch
is either full or forced by ``max_wait``, as the admission policy means
it.

``correct`` compares every answer of the window to a seeded sample of
the pool's problems (at most ``check.answers`` of them, drawn from the
seed), each against the host float64 eigenbasis of its own problem, by
the worst agent's tan-theta.  A problem's answers come from every batch
slot, so a fault confined to one slot shows.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import time
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

import harness
import reference


class State:
    pass


def setup(ctx) -> State:
    from repro.core import erdos_renyi
    from repro.streaming import AdmissionPolicy, PCAService

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    st = State()
    st.m, st.n, st.d, st.k = cfg["m"], cfg["n_per_agent"], cfg["d"], cfg["k"]
    st.T, st.K = cfg["T"], cfg["K"]
    st.clients = int(tr["clients"])
    st.seed = ctx.seed
    t0 = time.perf_counter()
    gen = harness.generator(cfg["data"]["generator"])
    opts = {k: v for k, v in cfg["data"].items() if k != "generator"}
    keys = [harness.seed_key(ctx.seed, 1, i) for i in range(cfg["pool"])]
    st.pool = jax.block_until_ready(
        gen.pool(keys, m=st.m, n=st.n, d=st.d, **opts))
    st.order = np.random.default_rng([int(ctx.seed), 3]).permutation(
        cfg["pool"])
    st.checked = set(np.random.default_rng([int(ctx.seed), 5]).choice(
        cfg["pool"], size=min(int(cfg["check"]["problems"]), cfg["pool"]),
        replace=False).tolist())
    st.start = tr["start"]
    if st.start == "agent0":
        st.starts = [jnp.asarray(reference.local_basis(X[0], st.k))
                     for X in st.pool]
    elif st.start != "random":
        raise ValueError(f"unknown start {st.start!r}")
    t1 = time.perf_counter()
    st.policy = AdmissionPolicy(**cfg.get("admission", {}))
    st.svc = PCAService(erdos_renyi(st.m, p=cfg["p"], seed=ctx.seed),
                        T=st.T, K=st.K, backend="auto", policy=st.policy)
    st.next_id = 0
    for _ in range(2):
        _drive(st, time.perf_counter(), record=None)
    st.setup_parts = {"data_s": t1 - t0, "warm_s": time.perf_counter() - t1}
    return st


def _drive(st: State, t_end: float, record) -> None:
    """The closed loop: every client keeps one request in flight until
    ``t_end``; then the requests in flight are drained."""
    from repro.core.operators import StackedOperators
    svc, wait = st.svc, st.policy.max_wait
    pending = collections.deque()

    def submit():
        i = st.next_id
        st.next_id += 1
        p = int(st.order[i % len(st.order)])
        with TraceAnnotation("bench.generate"):
            W0 = (st.starts[p] if st.start == "agent0" else
                  jnp.asarray(harness.random_basis(st.seed, i, st.d, st.k)))
        with TraceAnnotation("bench.submit"):
            t = time.perf_counter()
            rid = svc.submit(StackedOperators(data=st.pool[p]), W0)
        pending.append((rid, i, p, t))

    for _ in range(st.clients):
        submit()
    while pending:
        rid, i, p, t = pending[0]
        resp = svc.result(rid)
        if resp is None:
            # not launched: wait out the oldest request's admission
            # deadline, then let the policy launch what it holds
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, t + wait - time.perf_counter()))
            with TraceAnnotation("bench.poll"):
                svc.poll()
            continue
        with TraceAnnotation("bench.result"):
            W = np.asarray(resp.W)
        done = time.perf_counter()
        pending.popleft()
        if record is not None:
            record(i, p, W, t, done, resp.waited)
        if done < t_end:
            submit()


def window(ctx, st: State, seconds: float) -> harness.Window:
    answers: List = []
    lat, waited = [], []
    t_end = time.perf_counter() + seconds
    first = st.next_id

    def record(i, p, W, t, done, w):
        if not np.all(np.isfinite(W)):
            record.nonfinite += 1
        if p in st.checked:
            answers.append((i, p, W))
        record.served += 1
        lat.append(done - t)
        waited.append(w)
        if done <= t_end:
            record.in_window += 1
    record.in_window = record.served = record.nonfinite = 0

    cold = st.svc.stats["cold_launches"]
    with TraceAnnotation("bench.window"):
        _drive(st, t_end, record)
    attempted = st.next_id - first
    # every batch shape was warmed in set-up: nothing compiles in here
    print(f"[bench] window: {attempted} requests, "
          f"{st.svc.stats['cold_launches'] - cold} cold launches",
          file=sys.stderr, flush=True)
    failed = attempted - record.served + record.nonfinite
    return harness.Window(
        attempted=attempted, failed=failed,
        e2e={"solves_per_s": record.in_window / seconds,
             "solve_p95_ms": 1e3 * harness.quantile(lat, 0.95)},
        counters={"waited_s": waited, "solves": record.served,
                  "iterations": record.served * st.T,
                  "shape": {"m": st.m, "d": st.d, "k": st.k, "K": st.K}},
        answers=answers)


def route(st: State) -> str:
    """The gossip backend and the Pallas kernels in the program the
    service launched last, lowered at the shapes it ran with."""
    return harness.route(st.svc.driver)


def release(st: State) -> None:
    """Free the program's state before the reference runs."""
    st.svc = None


def sample(ctx, win: harness.Window) -> List:
    """The answers to the checked problems, at most ``check.answers`` of
    them, drawn from the seed."""
    size = int(ctx.cell.config["check"]["answers"])
    rng = np.random.default_rng([int(ctx.seed), 6])
    idx = rng.choice(len(win.answers), size=min(size, len(win.answers)),
                     replace=False)
    return [win.answers[j] for j in sorted(idx)]


def answer_tans(ctx, st: State, win: harness.Window) -> List:
    """``(request, problem, worst agent's tan-theta)`` of each sampled
    answer against its own problem's host float64 eigenbasis (computed
    once per problem)."""
    bases: Dict[int, np.ndarray] = st.__dict__.setdefault("bases", {})
    out = []
    for i, p, W in sample(ctx, win):
        if p not in bases:
            bases[p] = reference.ref_basis(np.asarray(st.pool[p]), st.k)
        tan = (reference.worst_agent_tan(bases[p], W)
               if W.shape == (st.m, st.d, st.k) else float("inf"))
        out.append((i, p, tan))
    return out


def check(ctx, st: State, win: harness.Window) -> List[harness.Check]:
    """Worst agent's tan-theta over the sampled answers."""
    limit = float(ctx.cell.config["check"]["tan_worst_agent"])
    tans = [t for _, _, t in answer_tans(ctx, st, win)]
    worst = max(tans) if tans else float("inf")
    return [harness.Check("tan_worst_agent", worst, limit)]


def detail(ctx, st: State, win: harness.Window) -> Dict:
    """How the sampled answers read: their count, the median, how many
    lie over the limit, and the five worst with their request and
    problem."""
    limit = float(ctx.cell.config["check"]["tan_worst_agent"])
    got = answer_tans(ctx, st, win)
    tans = np.array([t for _, _, t in got])
    worst = sorted(got, key=lambda a: -a[2])[:5]
    return {"answers": len(got),
            "median": float(np.median(tans)) if len(got) else None,
            "over_limit": int(np.sum(tans > limit)),
            "worst": [[int(i), int(p), float(t)] for i, p, t in worst]}


def control(ctx, st: State, win: harness.Window) -> harness.Window:
    """The window with every answer replaced by the control's
    (``reference.bf16_basis`` of its problem, on every agent)."""
    bases: Dict[int, np.ndarray] = {}
    answers = []
    for i, p, W in win.answers:
        if p not in bases:
            bases[p] = np.broadcast_to(
                reference.bf16_basis(st.pool[p], st.k), (st.m, st.d, st.k))
        answers.append((i, p, bases[p]))
    return dataclasses.replace(win, answers=answers)
