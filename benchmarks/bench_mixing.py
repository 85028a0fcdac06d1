"""FastMix benchmarks: Prop. 1 validation + ConsensusEngine backend sweep.

Four entry points:

* :func:`main` (used by ``benchmarks.run``) — FastMix vs naive gossip
  contraction rates, measured vs theoretical, across topologies.
* :func:`sweep_backends` (``python benchmarks/bench_mixing.py --sweep``) —
  times the engine's three gossip backends (per-round ``stacked``, fused
  ``pallas`` kernel/polynomial, ``shard_map`` collectives) over a
  (topology, m, d, k, K) grid spanning ring / Erdős–Rényi / torus graphs up
  to m=64, and emits a comparison table with the fused-vs-stacked speedup
  per config.  Run with ``--sweep`` so fake host devices are set up before
  jax initialises and the shard_map rows can execute on CPU.
* :func:`sweep_batched` (``--batched``) — the multi-problem serving column:
  times ``IterationDriver.run_batch`` (one compiled vmap-over-problems
  launch) against B sequential driver runs of the same problems and
  reports problems/s plus the batched speedup.
* :func:`sweep_block_n` (``--block-n [128,256,...]``) — fused-kernel
  column-tile tuning: times the pallas gossip launch per ``block_n`` value
  (real kernel on TPU, interpret mode elsewhere) so the roadmap's "tune
  block_n on real TPU" item is a one-flag experiment; the winning value is
  deployed with the ``REPRO_FASTMIX_BLOCK_N`` env override (engines built
  with ``block_n=None`` read it).
* :func:`sweep_degraded` (``--degraded``) — the fleet-robustness table:
  sweeps dead-agent counts x per-round edge-dropout rates over
  ring/hypercube/er graphs, reporting the surviving spectral gap, the
  Prop. 1 contraction bound and the *measured* K-round consensus
  contraction under the corresponding :class:`TopologySchedule`.  Rows
  whose survivor graph disconnects are reported as such (gossip cannot
  contract there — the failure mode ``degrade_topology`` now refuses to
  hide).

``--json PATH`` writes every produced row to a JSON file (the CI workflow
uploads it as a build artifact); ``--quick`` shrinks grids/reps for CI.
"""
from __future__ import annotations

import csv
import json
import sys

if __name__ == "__main__" and ("--sweep" in sys.argv
                               or "--batched" in sys.argv):
    # must happen before the first jax backend initialisation; configure()
    # appends to XLA_FLAGS, and an explicit
    # --xla_force_host_platform_device_count already present in it wins
    from repro.runtime.config import configure
    configure(host_device_count=16)

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (ConsensusEngine, complete, consensus_error,
                        erdos_renyi, fastmix, fastmix_eta, hypercube,
                        naive_mix, ring, torus2d)

TOPOLOGIES = [
    ("er50_p0.5", lambda: erdos_renyi(50, p=0.5, seed=0)),   # paper setting
    ("ring16", lambda: ring(16)),
    ("torus16x16", lambda: torus2d(16, 16)),                 # TPU pod fabric
    ("hypercube256", lambda: hypercube(256)),
]

# (topology, m, d, k, K) grid for the backend sweep; the ring (16, 1024,
# 8, 8) point is the acceptance config tracked in CHANGES.md / the PR
# table.  er/torus rows and the m=64 points cover the roadmap's "grow the
# grid" item (torus is the TPU-fabric-shaped graph; er is the paper's
# setting).  m=64 exceeds the 16 fake host devices, so those shard_map
# cells report as skipped off-pod.
SWEEP_CONFIGS = [
    ("ring", 8, 256, 8, 4),
    ("ring", 8, 1024, 8, 8),
    ("ring", 16, 256, 8, 4),
    ("ring", 16, 1024, 8, 4),
    ("ring", 16, 1024, 8, 8),
    ("ring", 16, 4096, 8, 8),
    ("er", 16, 1024, 8, 8),
    ("torus", 16, 1024, 8, 8),
    ("ring", 64, 1024, 8, 8),
    ("er", 64, 1024, 8, 8),
    ("torus", 64, 1024, 8, 8),
]

QUICK_SWEEP_CONFIGS = [
    ("ring", 8, 256, 8, 4),
    ("er", 16, 256, 8, 4),
    ("torus", 16, 256, 8, 4),
]


def _sweep_topology(kind: str, m: int):
    if kind == "ring":
        return ring(m)
    if kind == "er":
        return erdos_renyi(m, p=0.5, seed=0)
    if kind == "torus":
        side = int(round(m ** 0.5))
        if side * side != m:
            raise ValueError(f"torus sweep point needs square m, got {m}")
        return torus2d(side, side)
    raise ValueError(f"unknown sweep topology kind {kind!r}")


def main(writer=None) -> None:
    own = writer is None
    if own:
        writer = csv.writer(sys.stdout)
        writer.writerow(["name", "us_per_call", "derived"])
    rng = np.random.default_rng(0)
    for name, make in TOPOLOGIES:
        topo = make()
        S = jnp.asarray(rng.standard_normal((topo.m, 64, 8)), jnp.float32)
        L = jnp.asarray(topo.mixing, jnp.float32)
        eta = fastmix_eta(topo.lambda2)
        e0 = float(consensus_error(S))
        for K in (5, 10, 20):
            t0 = time.perf_counter()
            out_f = fastmix(S, L, eta, K)
            out_f.block_until_ready()
            dt_f = time.perf_counter() - t0
            out_n = naive_mix(S, L, K)
            ef = float(consensus_error(out_f)) / e0
            en = float(consensus_error(out_n)) / e0
            writer.writerow([
                f"mixing/{name}/K{K}", f"{dt_f * 1e6:.1f}",
                f"fastmix={ef:.3e};naive={en:.3e};"
                f"bound={topo.fastmix_rate(K):.3e};"
                f"gap={topo.spectral_gap:.4f}"])


# ---------------------------------------------------------- backend sweep

def _median_us(fn, reps: int = 100) -> float:
    fn().block_until_ready()                  # compile + warm cache
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _backend_fns(topo, S, K):
    """Per-backend jitted mix closures for one config (None = unavailable)."""
    m = topo.m
    fns = {}
    eng_s = ConsensusEngine(topo, K=K, backend="stacked")
    fns["stacked"] = ("per-round einsum", lambda: eng_s.mix(S))

    eng_p = ConsensusEngine(topo, K=K, backend="pallas")
    flavour = ("pallas kernel" if jax.default_backend() == "tpu"
               else "poly fallback")
    fns["pallas-fused"] = (flavour, lambda: eng_p.mix(S))

    if len(jax.devices()) >= m:
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:m]), ("agents",))
        eng_d = ConsensusEngine(topo, K=K, backend="shard_map", mesh=mesh)
        fns["shard_map"] = ("collective_permute", lambda: eng_d.mix(S))
    else:
        fns["shard_map"] = (f"skipped ({len(jax.devices())} devices < {m})",
                            None)
    return fns


def sweep_backends(writer=None, configs=SWEEP_CONFIGS, reps: int = 100,
                   markdown: bool = False):
    """Time every gossip backend over the (topology, m, d, k, K) grid."""
    own = writer is None
    if own and not markdown:
        writer = csv.writer(sys.stdout)
        writer.writerow(["name", "us_per_call", "derived"])
    rows = []
    rng = np.random.default_rng(0)
    for (kind, m, d, k, K) in configs:
        topo = _sweep_topology(kind, m)
        S = jnp.asarray(rng.standard_normal((m, d, k)), jnp.float32)
        fns = _backend_fns(topo, S, K)
        timings = {}
        for backend, (flavour, fn) in fns.items():
            us = _median_us(fn, reps) if fn is not None else float("nan")
            timings[backend] = (flavour, us)
            if writer is not None:
                writer.writerow([
                    f"mixing_backend/{topo.name}/d{d}k{k}K{K}/{backend}",
                    f"{us:.1f}", flavour])
        speedup = timings["stacked"][1] / timings["pallas-fused"][1]
        rows.append(((topo.name, m, d, k, K), timings, speedup))
    if markdown:
        _print_markdown(rows)
    return rows


def _print_markdown(rows) -> None:
    host = jax.default_backend()
    print(f"\n### FastMix backend sweep (host backend: {host}, "
          f"{len(jax.devices())} devices)\n")
    print("| topology | m | d | k | K | stacked (per-round) | pallas-fused | "
          "shard_map | fused speedup |")
    print("|----------|---|---|---|---|---------------------|--------------|"
          "-----------|---------------|")
    for (name, m, d, k, K), t, speedup in rows:
        def cell(b):
            flavour, us = t[b]
            if us != us:                      # NaN -> unavailable
                return flavour
            return f"{us:.0f} µs ({flavour})"
        print(f"| {name} | {m} | {d} | {k} | {K} | {cell('stacked')} | "
              f"{cell('pallas-fused')} | {cell('shard_map')} | "
              f"**{speedup:.2f}×** |")


# ---------------------------------------------------------- block_n sweep

#: Tile widths for the fused-kernel block_n sweep (the roadmap's "tune
#: block_n on real TPU" knob; REPRO_FASTMIX_BLOCK_N is the env override).
BLOCK_N_VALUES = (128, 256, 512, 1024)

BLOCK_N_CONFIGS = [
    ("ring", 16, 1024, 8, 8),           # the acceptance config
    ("er", 16, 4096, 8, 8),             # wider column axis: more tiles
]

QUICK_BLOCK_N_CONFIGS = [
    ("ring", 8, 256, 8, 4),
]


def sweep_block_n(values=BLOCK_N_VALUES, configs=BLOCK_N_CONFIGS,
                  reps: int = 20, markdown: bool = False,
                  record: bool = False):
    """Time the fused gossip launch across column-tile widths.

    On TPU this times the real Pallas kernel (the tuning experiment the
    roadmap asks for); elsewhere the kernel runs in interpret mode — far
    slower in absolute terms, but it exercises the block_n plumbing
    end-to-end so the one-flag experiment is already wired when a TPU host
    picks it up.

    ``record=True`` (CLI ``--record``) writes each config's winning width
    into the persistent autotune cache (kernel ``fastmix``, keyed on the
    kernel-facing ``(m, d*k)`` bucket), which every engine built with
    ``block_n=None`` then picks up automatically — the measure→deploy loop
    with no env var needed (``REPRO_FASTMIX_BLOCK_N`` still wins when set).
    """
    from repro.kernels import autotune
    from repro.kernels.fastmix import DEFAULT_BLOCK_N
    on_tpu = jax.default_backend() == "tpu"
    flavour = "pallas kernel" if on_tpu else "interpret mode"
    interpret = None if on_tpu else True
    rows = []
    rng = np.random.default_rng(0)
    for (kind, m, d, k, K) in configs:
        topo = _sweep_topology(kind, m)
        S = jnp.asarray(rng.standard_normal((m, d, k)), jnp.float32)
        per = []
        for bn in values:
            eng = ConsensusEngine(topo, K=K, backend="pallas",
                                  interpret=interpret, block_n=int(bn))
            per.append((int(bn), _median_us(lambda: eng.mix(S), reps)))
        base = dict(per).get(DEFAULT_BLOCK_N, per[0][1])
        rows.append(((topo.name, m, d, k, K), per, base))
        if record:
            best_bn, best_us = min(per, key=lambda p: p[1])
            key = autotune.record("fastmix", (m, d * k), S.dtype,
                                  {"block_n": best_bn,
                                   "us": round(best_us, 1)})
            print(f"[autotune] recorded {key}: block_n={best_bn}",
                  file=sys.stderr)
    if markdown:
        print(f"\n### Fused FastMix block_n sweep ({flavour}; "
              f"default block_n={DEFAULT_BLOCK_N}, "
              f"override with REPRO_FASTMIX_BLOCK_N)\n")
        header = "| topology | m | d | k | K | " + " | ".join(
            f"bn={bn}" for bn, _ in rows[0][1]) + " | best |"
        print(header)
        print("|" + "---|" * (5 + len(rows[0][1]) + 1))
        for (name, m, d, k, K), per, base in rows:
            best_bn = min(per, key=lambda p: p[1])[0]
            cells = " | ".join(f"{us:.0f} µs ({base / us:.2f}×)"
                               for _, us in per)
            print(f"| {name} | {m} | {d} | {k} | {K} | {cells} | "
                  f"**bn={best_bn}** |")
    return rows, flavour


# ---------------------------------------------------------- batched sweep

# (B, m, d, k, T, K) grid for run_batch vs sequential driver runs; the
# (8, ...) row is the acceptance config ("run_batch(B=8) beats 8 sequential
# driver runs on the CPU bench host").
BATCHED_CONFIGS = [
    (4, 8, 256, 4, 20, 5),
    (8, 16, 256, 4, 20, 6),
    (8, 16, 1024, 8, 20, 6),
    (16, 16, 256, 4, 20, 6),
]

QUICK_BATCHED_CONFIGS = [
    (4, 8, 64, 3, 10, 4),
    (8, 8, 64, 3, 10, 4),
]


def sweep_batched(writer=None, configs=BATCHED_CONFIGS, reps: int = 10,
                  markdown: bool = False):
    """run_batch (one vmapped launch) vs B sequential driver runs."""
    import time

    from repro.core import (ConsensusEngine, IterationDriver, PowerStep,
                            synthetic_problem_batch)

    own = writer is None
    if own and not markdown:
        writer = csv.writer(sys.stdout)
        writer.writerow(["name", "us_per_call", "derived"])
    rows = []
    for (B, m, d, k, T, K) in configs:
        topo = erdos_renyi(m, p=0.5, seed=0)
        problems, W0 = synthetic_problem_batch(B, m, d, k, n_per_agent=32,
                                               seed=0)
        driver = IterationDriver(
            step=PowerStep.for_algorithm("deepca", K),
            engine=ConsensusEngine.for_algorithm(
                "deepca", topo, K=K, backend="stacked"))

        jax.block_until_ready(driver.run_batch(problems, W0, T=T).W)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(driver.run_batch(problems, W0, T=T).W)
        batch_us = (time.perf_counter() - t0) / reps * 1e6

        # baseline 1 — warm driver: repeated run() calls on ONE driver hit
        # its jitted-program cache (per-(T, kind); added with run_batch)
        for p, w in zip(problems, W0):          # warm per-problem paths
            jax.block_until_ready(driver.run(p, w, T=T).carry[1])
        t0 = time.perf_counter()
        for _ in range(reps):
            for p, w in zip(problems, W0):
                jax.block_until_ready(driver.run(p, w, T=T).carry[1])
        warm_us = (time.perf_counter() - t0) / reps * 1e6

        # baseline 2 — fresh driver per request (B independent driver
        # runs, the deepca()-per-call serving pattern): every run
        # re-traces its scan, so this measures what run_batch's single
        # launch actually replaces in a naive server
        fresh_reps = min(reps, 3)
        t0 = time.perf_counter()
        for _ in range(fresh_reps):
            for p, w in zip(problems, W0):
                d2 = IterationDriver(
                    step=PowerStep.for_algorithm("deepca", K),
                    engine=ConsensusEngine.for_algorithm(
                        "deepca", topo, K=K, backend="stacked"))
                jax.block_until_ready(d2.run(p, w, T=T).carry[1])
        fresh_us = (time.perf_counter() - t0) / fresh_reps * 1e6

        speedup_warm = warm_us / batch_us
        speedup_fresh = fresh_us / batch_us
        pps = B / (batch_us / 1e6)
        if writer is not None:
            writer.writerow([
                f"mixing_batched/{topo.name}/B{B}d{d}k{k}T{T}K{K}",
                f"{batch_us:.1f}",
                f"seq_warm={warm_us:.1f};seq_fresh={fresh_us:.1f};"
                f"speedup_vs_warm={speedup_warm:.2f};"
                f"speedup_vs_fresh={speedup_fresh:.2f};"
                f"problems_per_s={pps:.1f}"])
        rows.append(((B, m, d, k, T, K), batch_us, warm_us, fresh_us,
                     speedup_warm, speedup_fresh, pps))
    if markdown:
        _print_batched_markdown(rows)
    return rows


def _print_batched_markdown(rows) -> None:
    print(f"\n### Batched multi-problem serving (host backend: "
          f"{jax.default_backend()}; run_batch = one vmapped launch; "
          "'warm' = one driver's jit cache reused, 'fresh' = driver per "
          "request)\n")
    print("| B | m | d | k | T | K | run_batch | B seq (warm) | "
          "B seq (fresh) | vs warm | vs fresh | problems/s |")
    print("|---|---|---|---|---|---|-----------|--------------|"
          "---------------|---------|----------|------------|")
    for (B, m, d, k, T, K), bus, wus, fus, sw, sf, pps in rows:
        print(f"| {B} | {m} | {d} | {k} | {T} | {K} | {bus / 1e3:.1f} ms | "
              f"{wus / 1e3:.1f} ms | {fus / 1e3:.1f} ms | {sw:.2f}× | "
              f"**{sf:.2f}×** | {pps:.0f} |")


# ---------------------------------------------------------- degraded sweep

DEAD_COUNTS = (0, 1, 2, 4)
DROP_RATES = (0.0, 0.1, 0.3)


def sweep_degraded(writer=None, m: int = 16, K: int = 8, steps: int = 6,
                   dead_counts=DEAD_COUNTS, drops=DROP_RATES,
                   markdown: bool = False, seed: int = 0):
    """Dead-agents x edge-dropout robustness sweep over ring/hypercube/er."""
    from repro.core import TopologySchedule, DynamicConsensusEngine
    from repro.runtime import DisconnectedTopologyError, degrade_topology

    own = writer is None
    if own and not markdown:
        writer = csv.writer(sys.stdout)
        writer.writerow(["name", "us_per_call", "derived"])
    rng = np.random.default_rng(seed)
    topologies = [ring(m), hypercube(m), erdos_renyi(m, p=0.5, seed=seed)]
    rows = []
    for topo in topologies:
        for nd in dead_counts:
            dead = sorted(rng.choice(m, size=nd, replace=False).tolist())
            try:
                base = degrade_topology(topo, dead) if nd else topo
            except DisconnectedTopologyError:
                for p in drops:
                    rows.append((topo.name, nd, p, None))
                    if writer is not None:
                        writer.writerow([
                            f"mixing_degraded/{topo.name}/dead{nd}/drop{p}",
                            "nan", "disconnected"])
                continue
            for p in drops:
                sched = TopologySchedule.edge_dropout(base, p, seed=seed + 1)
                eng = DynamicConsensusEngine(schedule=sched, K=K,
                                             backend="stacked")
                S = jnp.asarray(
                    rng.standard_normal((base.m, 64, 8)), jnp.float32)
                e0 = float(consensus_error(S))
                gaps, contractions, bounds = [], [], []
                for t in range(steps):
                    tp = sched.topology_at(t)
                    gaps.append(tp.spectral_gap)
                    bounds.append(tp.fastmix_rate(K))
                    contractions.append(
                        float(consensus_error(
                            eng.engine_at(t).mix(S))) / e0)
                row = (topo.name, nd, p,
                       (float(np.min(gaps)), float(np.mean(contractions)),
                        float(np.mean(bounds)), base.m))
                rows.append(row)
                if writer is not None:
                    gap, meas, bound, surv = row[3]
                    writer.writerow([
                        f"mixing_degraded/{topo.name}/dead{nd}/drop{p}",
                        f"{meas:.3e}",
                        f"survivors={surv};min_gap={gap:.4f};"
                        f"bound={bound:.3e};K={K}"])
    if markdown:
        _print_degraded_markdown(rows, m, K, steps)
    return rows


def _print_degraded_markdown(rows, m: int, K: int, steps: int) -> None:
    print(f"\n### Fault-degraded FastMix sweep (m={m}, K={K}, "
          f"{steps} schedule steps, measured = mean K-round consensus "
          f"contraction)\n")
    print("| topology | dead agents | edge dropout | survivors | min gap | "
          "measured contraction | Prop. 1 bound |")
    print("|----------|-------------|--------------|-----------|---------|"
          "----------------------|---------------|")
    for name, nd, p, stats in rows:
        if stats is None:
            print(f"| {name} | {nd} | {p} | — | — | DISCONNECTED "
                  "(gossip cannot contract) | — |")
            continue
        gap, meas, bound, surv = stats
        print(f"| {name} | {nd} | {p} | {surv} | {gap:.4f} | {meas:.3e} | "
              f"{bound:.3e} |")


def _arg_value(flag: str, default=None):
    if flag in sys.argv:
        idx = sys.argv.index(flag) + 1
        if idx < len(sys.argv):         # bare trailing flag -> default
            return sys.argv[idx]
    return default


if __name__ == "__main__":
    quick = "--quick" in sys.argv
    reps = int(_arg_value("--reps", 5 if quick else 0) or 0)
    report = {"host_backend": jax.default_backend(),
              "devices": len(jax.devices())}
    ran_any = False
    if "--sweep" in sys.argv:
        rows = sweep_backends(
            writer=None, markdown=True,
            configs=QUICK_SWEEP_CONFIGS if quick else SWEEP_CONFIGS,
            reps=reps or 100)
        report["sweep"] = [
            {"topology": name, "m": m, "d": d, "k": k, "K": K,
             # skipped cells carry us=NaN, which is not valid JSON -> null
             "timings_us": {b: {"flavour": fl,
                                "us": us if us == us else None}
                            for b, (fl, us) in t.items()},
             "fused_speedup": sp}
            for (name, m, d, k, K), t, sp in rows]
        ran_any = True
    if "--batched" in sys.argv:
        rows = sweep_batched(
            writer=None, markdown=True,
            configs=QUICK_BATCHED_CONFIGS if quick else BATCHED_CONFIGS,
            reps=reps or 10)
        report["batched"] = [
            {"B": B, "m": m, "d": d, "k": k, "T": T, "K": K,
             "run_batch_us": bus, "sequential_warm_us": wus,
             "sequential_fresh_us": fus, "speedup_vs_warm": sw,
             "speedup_vs_fresh": sf, "problems_per_s": pps}
            for (B, m, d, k, T, K), bus, wus, fus, sw, sf, pps in rows]
        ran_any = True
    if "--block-n" in sys.argv:
        vals = _arg_value("--block-n")
        # bare `--block-n` (or `--block-n` followed by another flag) runs
        # the default width grid; otherwise a comma list: --block-n 128,256
        if vals is None or vals.startswith("--"):
            values = BLOCK_N_VALUES
        else:
            values = tuple(int(v) for v in vals.split(","))
        rows, flavour = sweep_block_n(
            values=values, markdown=True,
            configs=QUICK_BLOCK_N_CONFIGS if quick else BLOCK_N_CONFIGS,
            reps=reps or 20, record="--record" in sys.argv)
        report["block_n"] = {
            "flavour": flavour,
            "rows": [{"topology": name, "m": m, "d": d, "k": k, "K": K,
                      "timings_us": {str(bn): us for bn, us in per}}
                     for (name, m, d, k, K), per, _ in rows]}
        ran_any = True
    if "--degraded" in sys.argv:
        rows = sweep_degraded(writer=None, markdown=True)
        report["degraded"] = [
            {"topology": name, "dead": nd, "drop": p,
             "stats": None if stats is None else
             {"min_gap": stats[0], "measured_contraction": stats[1],
              "prop1_bound": stats[2], "survivors": stats[3]}}
            for name, nd, p, stats in rows]
        ran_any = True
    if not ran_any:
        main()
    json_path = _arg_value("--json")
    if json_path and ran_any:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"\n[json] wrote {json_path}")
