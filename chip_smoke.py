#!/usr/bin/env python3
"""Bring-up check: the served PCA path on one TPU chip, or four.

Run from the root of a checkout::

    python chip_smoke.py              # phases a-c on one chip
    python chip_smoke.py --chips 4    # the four-chip phase only

Everything runs in this one process, through the objects
``python -m repro.launch.serve`` builds (``IterationDriver.run_batch``,
``StreamingDeEPCA``, ``PCAService``, ``TrackerFleet``) with
``backend="auto"``, or through ``DistributedDeEPCA`` for four chips.
Data is generated from ``--seed``.

One chip:

a. paper solve: the w8a split of Section 5 (m=50 agents on an
   Erdos-Renyi p=0.5 graph, d=300, 995 rows per agent, k=5, K=8), fp32;
b. batched serving: B=8 problems at m=16, d=1024, k=16, 1024 rows per
   agent (512 MiB of data), T=30, K=6;
c. streaming and fleet at m=16, d=512, k=8: a ``StreamingDeEPCA``
   tracker, a ragged mix of 24 requests through ``PCAService`` (512 +- 8
   rows per agent, K=12, T=30), and two 64-tenant ``TrackerFleet`` legs
   with the serve CLI's mixed sample counts (56-74 rows per agent), both
   fed by the CLI's threaded ingest: ``serve_pca_fleet`` itself, as
   ``serve --workload pca-fleet`` runs it (random starts), and a leg
   whose tenants start from their agent 0's local basis, both gated on
   accuracy.

Four chips (``--chips 4``): ``DistributedDeEPCA`` with one agent per chip
on a ring (``collective_permute``) and on a complete graph
(``all_gather``), data-form operators at d=1024 with 4096 rows per agent,
against ``deepca(..., backend="stacked")`` on one chip.

Each phase prints its shapes, the first call's wall time (compilation
included) and a warm call's wall time -- smoke timings, not benchmark
numbers --, ``peak_bytes_in_use`` of the process so far, the gossip
route that ran (``kernel``: the program the driver launched last, lowered
at the shapes it ran with, holds the Pallas tracking+gossip kernel
``_fastmix_track_kernel``; ``reference``: it does not), every Pallas
kernel in that program, and its accuracy against a host NumPy f64
eigendecomposition of the mean covariance.  Every fp32 phase fails
unless its engine resolved to ``pallas`` and the gossip kernel ran.
Nothing is caught: any failure ends the run with a traceback and a
non-zero exit code.  The last line of standard output is the JSON
verdict.  Without a TPU the script exits non-zero before printing any
result.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# tan-theta limits.  Each is the CPU rehearsal's value at the phase's own
# shapes and seed (JAX_PLATFORMS=cpu, where backend="auto" resolves to the
# stacked reference) times ten, rounded up to one significant digit.
# Against the host f64 reference they bound the worst agent; the
# agreement limits compare the mean bases of two runs.  With K=6 and
# heterogeneous agents the worst agent is still held back by consensus
# error after T iterations while the mean basis is much closer; the
# limits say what these solves reach, not what DeEPCA can reach.
#: a: m=50, d=300, n=995, k=5, K=8, T=40 (CPU rehearsal: 1.971e-6)
PAPER_TAN_MAX = 2e-5
#: a: pallas against stacked on the same chip (CPU, stacked twice:
#: 3.082e-8)
PAPER_AGREE_MAX = 4e-7
#: b: worst of B=8 problems, m=16, d=1024, n=1024, k=16, K=6, T=30
#: (CPU rehearsal, each problem alone: 4.129e-4)
BATCH_TAN_MAX = 5e-3
#: c: tracker, last of 6 ticks of T_tick=3 under 0.03 rad/tick drift
#: (CPU rehearsal: 2.137e-2)
TRACKER_TAN_MAX = 0.3
#: c: PCAService, 24 one-shot solves, K=12, T=30 (CPU rehearsal: 1.026e-5)
SERVICE_TAN_MAX = 2e-4
#: c: worst fleet tenant, last of 6 ticks of T_tick=3, tenants started
#: from their agent 0's local basis (CPU rehearsal: 3.292e-2)
FLEET_TAN_MAX = 0.4
#: c: worst tenant of the serve CLI's fleet, last of 6 ticks, tenants
#: started from random bases at heterogeneity 1.0, one joining mid-run:
#: the chip's reading (0.646, the joiner, seed 3000000109) times three,
#: not the CPU's, since four tenants at these shapes there read
#: 0.29-1.01 and 64 spread wider; with Alg. 2's reference pinned to W0
#: this leg read 5.971 on the chip
FLEET_CLI_TAN_MAX = 2.0
#: the Pallas tracking+gossip kernel every fp32 phase must run
GOSSIP_KERNEL = "_fastmix_track_kernel"
#: four chips: distributed against stacked (4 virtual CPU devices:
#: ring 6.248e-7, complete 4.794e-7)
DIST_AGREE_MAX = 7e-6
#: four chips: worst agent against the f64 reference (4 virtual CPU
#: devices: ring 3.397e-6, complete 3.574e-7)
DIST_TAN_MAX = 4e-5


# ----------------------------------------------------------------- helpers
def require_tpu():
    """The chip's devices; exits non-zero when JAX finds no TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX reports platform "
                 f"{devs[0].platform!r}); nothing was run")
    return devs


def on_tpu() -> bool:
    """False only where a phase is rehearsed on the CPU by calling its
    ``phase_*`` function directly (the limits above come from such runs);
    ``main`` never runs a phase without a TPU."""
    import jax
    return jax.default_backend() == "tpu"


def ref_basis(X, k: int) -> np.ndarray:
    """Top-k eigenvectors of the mean covariance ``(1/m) sum_j X_j^T X_j``
    of data ``(m, n, d)``, in host f64."""
    X = np.asarray(X, np.float64)
    m, n, d = X.shape
    C = X.reshape(m * n, d).T @ X.reshape(m * n, d) / m
    evals, evecs = np.linalg.eigh(C)
    return evecs[:, np.argsort(evals)[::-1][:k]]


def tan_f64(U: np.ndarray, W) -> float:
    """tan theta_k between span(U) and span(W) in host f64 (Eqn. 2.2)."""
    Q = np.linalg.qr(np.asarray(W, np.float64))[0]
    c = np.linalg.svd(U.T @ Q, compute_uv=False).min()
    s = np.linalg.norm(Q - U @ (U.T @ Q), ord=2)
    return float(s / max(c, 1e-300))


def worst_agent_tan(U: np.ndarray, W_stack) -> float:
    W_stack = np.asarray(W_stack)
    return max(tan_f64(U, W_stack[j]) for j in range(W_stack.shape[0]))


def kernels(driver) -> list:
    """Names of the Pallas TPU kernels in the program ``driver`` launched
    last, lowered at the shapes it ran with."""
    text = driver.lower_last_launch().as_text()
    return sorted(set(re.findall(r'kernel_name = "(\w+)"', text)))


def route(driver) -> dict:
    """The gossip route ``driver`` ran last, for :func:`report`."""
    names = kernels(driver)
    return {"backend": driver.engine.backend,
            "route": "kernel" if GOSSIP_KERNEL in names else "reference",
            "pallas_kernels": ",".join(names) or "none"}


def require_kernel(phase: str, r: dict) -> None:
    """On the chip, fail unless the engine is ``pallas`` and the gossip
    kernel ran."""
    if on_tpu() and (r["backend"] != "pallas" or r["route"] != "kernel"):
        raise RuntimeError(
            f"[{phase}] fp32 phase ran backend={r['backend']} with kernels "
            f"{r['pallas_kernels']}; want pallas with {GOSSIP_KERNEL}")


def timed(fn):
    """``(result, seconds)`` of one call, waiting for the device."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check(phase: str, name: str, value: float, limit: float) -> None:
    report(phase, **{name: f"{value:.3e}", "limit": f"{limit:.0e}"})
    if not value <= limit:
        raise RuntimeError(f"[{phase}] {name}={value:.3e} exceeds {limit:.0e}")


def init_basis(d: int, k: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.linalg.qr(rng.standard_normal((d, k)))[0].astype(np.float32)


def solver(topo, K: int, backend: str):
    from repro.core import ConsensusEngine, IterationDriver, PowerStep
    eng = ConsensusEngine.for_algorithm("deepca", topo, K=K, backend=backend)
    return IterationDriver(step=PowerStep.for_algorithm("deepca", K),
                           engine=eng)


# ------------------------------------------------------------------ phases
def phase_paper(seed: int, *, m=50, d=300, n=995, k=5, K=8, T=40):
    """a. The paper's w8a-shaped solve through ``run_batch``."""
    import jax.numpy as jnp
    from repro.core import erdos_renyi
    from repro.core.operators import libsvm_like

    topo = erdos_renyi(m, p=0.5, seed=seed)
    ops = libsvm_like(m, n, d, seed=seed)
    W0 = jnp.asarray(init_basis(d, k, seed))[None]
    report("a", shapes=f"m={m},d={d},n={n},k={k},K={K},T={T},B=1",
           dtype="float32")
    bases = {}
    for backend in ("auto", "stacked"):
        drv = solver(topo, K, backend)
        W, cold = timed(lambda: drv.run_batch([ops], W0, T=T).W)
        _, warm = timed(lambda: drv.run_batch([ops], W0, T=T).W)
        r = route(drv)
        report("a", requested=backend, **r,
               first_call_s=f"{cold:.3f}", warm_ms=f"{warm * 1e3:.3f}",
               timing="smoke-not-benchmark", peak_bytes_in_use=peak_bytes())
        if backend == "auto":
            require_kernel("a", r)
        bases[backend] = np.asarray(W[0])
    U = ref_basis(ops.data, k)
    check("a", "tan_theta_worst_agent_vs_f64",
          worst_agent_tan(U, bases["auto"]), PAPER_TAN_MAX)
    check("a", "tan_theta_vs_stacked_on_chip",
          tan_f64(np.linalg.qr(bases["stacked"].mean(0))[0],
                  bases["auto"].mean(0)), PAPER_AGREE_MAX)


def phase_batched(seed: int, *, B=8, m=16, d=1024, n=1024, k=16, K=6, T=30):
    """b. Batched serving: B problems in one ``run_batch`` program.

    The problems are ``synthetic_spiked`` draws at heterogeneity 0.2: at
    the default 1.0 and d=1024 the per-agent rotations (spectral norm
    about 0.1 * sqrt(d) = 3.2) scramble the spike, and the mean covariance
    keeps an eigenvalue ratio of 0.99 at k that 30 iterations cannot
    resolve; at 0.2 it is about 0.35.  Every problem starts from its
    agent 0's local top-k basis: from a random start at d=1024 the
    iterate's columns keep rotating inside the top-k subspace while
    nearly orthogonal to their W0 columns, Alg. 2's sign reference then
    flips on single agents, and the worst agent spikes to 1e-2 for a few
    iterations (CPU rehearsal: at iteration 56 of one problem).
    """
    import jax.numpy as jnp
    from repro.core import erdos_renyi
    from repro.core.operators import synthetic_spiked

    topo = erdos_renyi(m, p=0.5, seed=seed)
    t0 = time.perf_counter()
    problems = [synthetic_spiked(m, d, k, n_per_agent=n, seed=seed + 17 * b,
                                 heterogeneity=0.2) for b in range(B)]
    W0 = jnp.stack([jnp.asarray(ref_basis(p.data[:1], k), jnp.float32)
                    for p in problems])
    report("b", shapes=f"B={B},m={m},d={d},n={n},k={k},K={K},T={T}",
           data_bytes=B * m * n * d * 4,
           setup_s=f"{time.perf_counter() - t0:.3f}")
    drv = solver(topo, K, "auto")
    W, cold = timed(lambda: drv.run_batch(problems, W0, T=T).W)
    _, warm = timed(lambda: drv.run_batch(problems, W0, T=T).W)
    r = route(drv)
    report("b", **r, first_call_s=f"{cold:.3f}",
           warm_ms=f"{warm * 1e3:.3f}", timing="smoke-not-benchmark",
           peak_bytes_in_use=peak_bytes())
    require_kernel("b", r)
    W = np.asarray(W)
    worst = max(worst_agent_tan(ref_basis(p.data, k), W[b])
                for b, p in enumerate(problems))
    check("b", "tan_theta_worst_problem_agent_vs_f64", worst, BATCH_TAN_MAX)


def phase_tracker(seed: int, *, m=16, d=512, k=8, n=64, K=6, T_tick=3,
                  ticks=6, rate=0.03):
    """c. A ``StreamingDeEPCA`` tracker over a slowly rotating stream,
    ingested as ``serve --workload pca-stream`` does: a prefetch thread
    draws each tick in NumPy, this thread puts it on the device.

    Data is drawn at heterogeneity 0.2 for the reason given in
    :func:`phase_batched`.
    """
    from repro.core import erdos_renyi
    from repro.data.synthetic import PrefetchIterator
    from repro.streaming import DriftPolicy, SlowRotationStream, \
        StreamingDeEPCA

    topo = erdos_renyi(m, p=0.5, seed=seed)
    stream = SlowRotationStream(m=m, d=d, k=k, n_per_agent=n, rate=rate,
                                heterogeneity=0.2, seed=seed)
    tracker = StreamingDeEPCA(k=k, T_tick=T_tick, K=K, topology=topo,
                              backend="auto", W0=stream.init_W0(),
                              policy=DriftPolicy())
    report("c.tracker", shapes=f"m={m},d={d},n={n},k={k},K={K},"
           f"T_tick={T_tick},ticks={ticks},rate={rate}")

    def one_tick(tick):
        tracker.tick(tick.ops, tick.U)
        return tracker.W

    times = []
    with PrefetchIterator(stream.host_ticks(ticks), depth=2) as lane:
        for host in lane:
            tick = stream.on_device(host)
            times.append(timed(lambda: one_tick(tick))[1])
    r = route(tracker.driver)
    report("c.tracker", **r, first_tick_s=f"{times[0]:.3f}",
           warm_tick_ms=f"{np.mean(times[1:]) * 1e3:.3f}",
           timing="smoke-not-benchmark", peak_bytes_in_use=peak_bytes())
    require_kernel("c.tracker", r)
    check("c.tracker", "tan_theta_worst_agent_vs_f64_last_tick",
          worst_agent_tan(ref_basis(tick.ops.data, k), tracker.W),
          TRACKER_TAN_MAX)


def phase_service(seed: int, *, m=16, d=512, k=8, n=512, K=12, T=30,
                  requests=24):
    """c. ``PCAService`` serving a ragged mix of one-shot requests.

    Sample counts are ``n`` +- 8 and ranks ``k - 1`` or ``k``, as in the
    serve demo's mix, but each request's spike has its own rank, so its
    top-``k_i`` subspace is separated from the rest, and each agent holds
    more rows than ``d``.  With fewer rows than ``d`` an agent's covariance
    is far from the mean one, and after 30 iterations the worst agent is
    still held back by consensus error (CPU rehearsal at n=64, K=6:
    5e-2).  Requests start from their agent 0's local basis, like phase
    b's problems.
    """
    import jax.numpy as jnp
    from repro.core import erdos_renyi
    from repro.core.operators import synthetic_spiked
    from repro.streaming import AdmissionPolicy, PCAService

    topo = erdos_renyi(m, p=0.5, seed=seed)
    svc = PCAService(topo, T=T, K=K, backend="auto",
                     policy=AdmissionPolicy(max_batch=8, max_wait=0.01))
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(requests):
        n_i = int(rng.choice([n - 8, n, n + 8]))
        k_i = int(rng.choice([k - 1, k]))
        ops = synthetic_spiked(m, d, k_i, n_per_agent=n_i,
                               seed=seed + 31 * i, heterogeneity=0.2)
        reqs.append((ops, jnp.asarray(ref_basis(ops.data[:1], k_i),
                                      jnp.float32)))
    report("c.service", shapes=f"m={m},d={d},n={n}+-8,k={k - 1}|{k},K={K},"
           f"T={T},requests={requests}")

    def serve_all():
        ids = [svc.submit(ops, W0) for ops, W0 in reqs]
        svc.flush()
        return [svc.result(i).W for i in ids]

    _, cold = timed(serve_all)
    cold_launches = svc.stats["cold_launches"]
    outs, warm = timed(serve_all)
    r = route(svc.driver)
    report("c.service", **r, first_pass_s=f"{cold:.3f}",
           warm_pass_ms=f"{warm * 1e3:.3f}", cold_launches=cold_launches,
           warm_pass_cold_launches=svc.stats["cold_launches"] - cold_launches,
           timing="smoke-not-benchmark", peak_bytes_in_use=peak_bytes())
    require_kernel("c.service", r)
    if svc.stats["cold_launches"] != cold_launches:
        raise RuntimeError("[c.service] the warm pass compiled again")
    worst = max(worst_agent_tan(ref_basis(ops.data, W0.shape[1]), W)
                for (ops, W0), W in zip(reqs, outs))
    check("c.service", "tan_theta_worst_request_agent_vs_f64", worst,
          SERVICE_TAN_MAX)


def fleet_accuracy(fleet, last) -> dict:
    """Worst agent's tan-theta against host f64, per tenant, on the last
    tick's data."""
    k = fleet.k
    return {tid: worst_agent_tan(ref_basis(last[tid].ops.data, k),
                                 fleet.tenant_W(tid))
            for tid in fleet.tenants}


def require_fleet(phase: str, fleet, steady_cold: int) -> None:
    if fleet.program_count > 2 or steady_cold:
        raise RuntimeError(f"[{phase}] programs={fleet.program_count} "
                           f"steady cold launches={steady_cold}; want <= 2 "
                           "and 0")


def phase_fleet_cli(seed: int, *, tenants=64, m=16, d=512, k=8, ticks=6):
    """c. ``serve_pca_fleet`` as ``serve --workload pca-fleet`` runs it:
    the CLI's tenant mix (56-74 rows per agent, heterogeneity 1.0), random
    ``init_W0`` starts, threaded ingest and mid-run churn.  Programs, cold
    launches, the kernel route and the worst tenant's accuracy are
    gated."""
    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--workload", "pca-fleet", "--tenants", str(tenants), "--m", str(m),
        "--d", str(d), "--k-top", str(k), "--ticks", str(ticks),
        "--seed", str(seed)])
    report("c.fleet_cli", shapes=f"tenants={tenants},m={m},d={d},k={k},"
           f"K={args.rounds},T_tick={args.tick_iters},ticks={ticks},"
           f"n_per_agent={args.n_per_agent}-8..+10,heterogeneity=1.0")
    out, wall = timed(lambda: serve.serve_pca_fleet(args))
    fleet = out["fleet"]
    r = route(fleet.driver)
    report("c.fleet_cli", **r, warmup_tick_ms=f"{out['warmup_ms']:.3f}",
           steady_tick_ms=f"{np.mean(out['steady_ms']):.3f}",
           wall_s=f"{wall:.3f}", programs=fleet.program_count,
           steady_cold_launches=out["steady_cold"],
           timing="smoke-not-benchmark", peak_bytes_in_use=peak_bytes())
    require_kernel("c.fleet_cli", r)
    require_fleet("c.fleet_cli", fleet, out["steady_cold"])
    acc = fleet_accuracy(fleet, out["last"])
    worst = max(acc, key=acc.get)
    report("c.fleet_cli", worst_tenant=worst)
    check("c.fleet_cli", "tan_theta_worst_tenant_agent_vs_f64_last_tick",
          acc[worst], FLEET_CLI_TAN_MAX)


def phase_fleet(seed: int, *, tenants=64, m=16, d=512, k=8, n=64, K=6,
                T_tick=3, ticks=6, rate=0.03):
    """c. A ``TrackerFleet`` with the serve CLI's mixed sample counts and
    its threaded ingest (``serve.fleet_tenant_mix``,
    ``serve.fleet_ingest``, ``serve.ingest_tick``), at heterogeneity 0.2 (see
    :func:`phase_batched`).  Tenants start from their agent 0's local
    basis of the first tick, like phase b's problems, and join once that
    tick is ingested; :func:`phase_fleet_cli` runs the CLI's random
    starts."""
    from repro.core import erdos_renyi
    from repro.launch import serve
    from repro.streaming import DriftPolicy, TrackerFleet

    topo = erdos_renyi(m, p=0.5, seed=seed)
    fleet = TrackerFleet(k=k, T_tick=T_tick, K=K, topology=topo,
                         backend="auto", policy=DriftPolicy())
    streams = serve.fleet_tenant_mix(m, d, k, tenants=tenants,
                                     n_per_agent=n, rate=rate, seed=seed,
                                     heterogeneity=0.2)
    report("c.fleet", shapes=f"tenants={tenants},m={m},d={d},k={k},K={K},"
           f"T_tick={T_tick},ticks={ticks},n="
           f"{sorted({st.n_per_agent for st in streams.values()})}")
    latencies, walls, steady_cold = [], [], 0
    with serve.fleet_ingest(streams, ticks) as mux:
        for t in range(ticks):
            t0 = time.perf_counter()
            items = serve.ingest_tick(streams, mux)
            if t == 0:
                for tid, st in streams.items():
                    fleet.join(tid, ref_basis(items[tid].ops.data[:1], k)
                               .astype(np.float32), n=st.n_per_agent)
            rep = fleet.tick(items)
            walls.append(time.perf_counter() - t0)
            latencies.append(rep.latency_ms)
            if t:
                steady_cold += rep.cold_launches
    r = route(fleet.driver)
    report("c.fleet", **r, warmup_tick_ms=f"{latencies[0]:.3f}",
           steady_tick_ms=f"{np.mean(latencies[1:]):.3f}",
           warmup_tick_with_ingest_s=f"{walls[0]:.3f}",
           steady_tick_with_ingest_ms=f"{np.mean(walls[1:]) * 1e3:.3f}",
           programs=fleet.program_count, steady_cold_launches=steady_cold,
           timing="smoke-not-benchmark", peak_bytes_in_use=peak_bytes())
    require_kernel("c.fleet", r)
    require_fleet("c.fleet", fleet, steady_cold)
    check("c.fleet", "tan_theta_worst_tenant_agent_vs_f64_last_tick",
          max(fleet_accuracy(fleet, items).values()), FLEET_TAN_MAX)


def phase_distributed(seed: int, devices, *, d=1024, n=4096, k=16, K=6,
                      T=30):
    """Four chips: ``DistributedDeEPCA``, one agent per chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import DistributedDeEPCA, complete, deepca, ring
    from repro.core.operators import StackedOperators

    m = len(devices)
    mesh = Mesh(np.asarray(devices), ("agents",))
    t0 = time.perf_counter()
    # spiked covariance generated on the host, so each agent's rows go
    # straight to its own chip
    rng = np.random.default_rng(seed)
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0]
    evals = np.full(d, 0.3)
    evals[:k] = 1.0 + 0.5 * np.arange(k, 0, -1)
    host = np.stack([(rng.standard_normal((n, d)) * np.sqrt(evals)) @ basis.T
                     for _ in range(m)]).astype(np.float32)
    W0 = init_basis(d, k, seed)
    report("dist", shapes=f"m={m},d={d},n={n},k={k},K={K},T={T}",
           devices=m, setup_s=f"{time.perf_counter() - t0:.3f}")
    U = ref_basis(host, k)
    for topo, collective in ((ring(m), "collective-permute"),
                             (complete(m), "all-gather")):
        dd = DistributedDeEPCA(mesh, topo, k=k, K=K, T=T,
                               operator_kind="data")
        (W, _), cold = timed(lambda: dd.run(host, W0))
        # run() builds its jitted step per call: the second call traces
        # again and finds the compiled program in jax's caches
        _, again = timed(lambda: dd.run(host, W0))
        shard = NamedSharding(mesh, P("agents"))
        rep = NamedSharding(mesh, P())
        carry = (jax.ShapeDtypeStruct((m, d, k), jnp.float32,
                                      sharding=shard),) * 3
        hlo = dd.step_fn().lower(
            jax.ShapeDtypeStruct(host.shape, jnp.float32, sharding=shard),
            *carry,
            jax.ShapeDtypeStruct((d, k), jnp.float32, sharding=rep)
        ).compile().as_text()
        lowered = [c for c in ("collective-permute", "all-gather",
                               "all-reduce") if c in hlo]
        report("dist", topology=topo.name, lowering=",".join(lowered),
               first_call_s=f"{cold:.3f}",
               second_call_ms=f"{again * 1e3:.3f}",
               timing="smoke-not-benchmark", peak_bytes_in_use=peak_bytes())
        if collective not in lowered:
            raise RuntimeError(f"[dist] {topo.name} did not lower to "
                               f"{collective} (found {lowered})")
        one_chip = StackedOperators(data=jax.device_put(host, devices[0]))
        ref = deepca(one_chip, topo, jax.device_put(W0, devices[0]), k=k,
                     T=T, K=K, backend="stacked").W
        W, ref = np.asarray(W), np.asarray(ref)
        check("dist", f"{topo.name}_tan_theta_vs_stacked_one_chip",
              tan_f64(np.linalg.qr(ref.mean(0))[0], W.mean(0)),
              DIST_AGREE_MAX)
        check("dist", f"{topo.name}_tan_theta_worst_agent_vs_f64",
              worst_agent_tan(U, W), DIST_TAN_MAX)


# -------------------------------------------------------------------- main
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the DistributedDeEPCA phase across "
                         "four chips")
    args = ap.parse_args(argv)
    devices = require_tpu()
    import jax
    from repro.runtime import config as runtime_config
    cache = runtime_config.enable_compile_cache()
    print(f"[setup] device={devices[0].device_kind} count={len(devices)} "
          f"compile_cache={cache}", flush=True)
    if args.chips == 4:
        if len(devices) < 4:
            raise RuntimeError(f"--chips 4 needs four chips; JAX reports "
                               f"{len(devices)}")
        phase_distributed(args.seed, devices[:4])
    else:
        phase_paper(args.seed)
        phase_batched(args.seed)
        phase_tracker(args.seed)
        phase_service(args.seed)
        phase_fleet(args.seed)
        phase_fleet_cli(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
