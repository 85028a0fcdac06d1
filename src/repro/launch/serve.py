"""Batched serving drivers: LM prefill+decode, and multi-problem PCA.

Three workloads share this entry point:

* ``--workload lm`` (default) — prefill + greedy decode loop::

      PYTHONPATH=src python -m repro.launch.serve --arch smollm_135m \
          --reduced --batch 4 --prompt-len 32 --gen 16

* ``--workload pca`` — decentralized-PCA serving on the batched driver
  substrate: ONE compiled program
  (:meth:`repro.core.driver.IterationDriver.run_batch`) runs ``--batch``
  independent DeEPCA problems per launch, amortising compilation and
  dispatch across every concurrent request::

      PYTHONPATH=src python -m repro.launch.serve --workload pca \
          --batch 8 --m 16 --d 256 --k-top 4 --iters 30 --rounds 6

* ``--workload pca-stream`` — the streaming subsystem end-to-end: an
  online :class:`~repro.streaming.tracker.StreamingDeEPCA` warm-starts a
  few iterations per tick over a drifting stream (prefetched on a
  background thread), then a ragged one-shot request mix is served
  through the dynamic-batching :class:`~repro.streaming.service
  .PCAService` queue::

      PYTHONPATH=src python -m repro.launch.serve --workload pca-stream \
          --m 8 --d 64 --k-top 4 --ticks 8 --tick-iters 3 --rounds 5 \
          --requests 24 --max-batch 8

* ``--workload pca-fleet`` — multi-tenant fleet serving: ``--tenants``
  independent drifting streams (a mixed-shape tenant mix) ride ONE
  compiled window program per padded-shape bucket through
  :class:`~repro.streaming.fleet.TrackerFleet`, with threaded per-tenant
  ingest (:class:`~repro.data.synthetic.MultiStreamPrefetcher`) and
  mid-run join/leave churn demonstrating the zero-retrace slot pool::

      PYTHONPATH=src python -m repro.launch.serve --workload pca-fleet \
          --m 8 --d 48 --k-top 3 --tenants 12 --ticks 8 --tick-iters 3 \
          --rounds 5
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np


def serve_lm(args) -> None:
    from repro.configs import get_config, get_reduced
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models import init_params

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    max_seq = args.prompt_len + args.gen + (cfg.n_patches or 0)

    embeds = None
    if cfg.n_patches:
        embeds = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_patches, cfg.d_model)) * 0.02, jnp.bfloat16)
    elif cfg.is_encdec:
        embeds = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_frames, cfg.d_model)) * 0.02, jnp.bfloat16)

    prefill_fn = jax.jit(make_prefill_step(cfg, max_seq))
    decode_fn = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    t0 = time.perf_counter()
    logits, cache = prefill_fn(params, tokens, embeds) if embeds is not None \
        else prefill_fn(params, tokens)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    out = [tok]
    for _ in range(args.gen - 1):
        logits, cache = decode_fn(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out.append(tok)
    gen = jnp.concatenate(out, axis=1)
    dt = time.perf_counter() - t0
    print(f"generated {gen.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(np.asarray(gen)[:, :12])


def serve_pca(args) -> None:
    """Serve B concurrent DeEPCA problems through one batched driver."""
    from repro.core import (ConsensusEngine, IterationDriver, PowerStep,
                            erdos_renyi, metrics, synthetic_problem_batch,
                            top_k_eigvecs)

    B, m, d, k = args.batch, args.m, args.d, args.k_top
    topo = erdos_renyi(m, p=0.5, seed=args.seed)
    problems, W0 = synthetic_problem_batch(
        B, m, d, k, n_per_agent=args.n_per_agent, seed=args.seed)

    from repro.core.algorithms import resolve_acceleration
    from repro.core.consensus import EF_WIRE_DTYPES

    wire = args.wire_dtype if args.wire_dtype is not None \
        else ("bf16" if args.wire_bf16 else None)
    if wire in ("none", "fp32"):
        wire = None
    engine = ConsensusEngine.for_algorithm("deepca", topo, K=args.rounds,
                                           backend="auto",
                                           wire_dtype=wire)
    if wire:
        ef = " + error feedback" if wire in EF_WIRE_DTYPES else ""
        print(f"[serve] gossip wire precision: {wire}{ef} "
              "(fp32 tracking/QR accumulation); "
              f"{engine.bytes_per_round(d, k)} B/agent/round")
    accelerated, momentum = resolve_acceleration(
        True if args.accel else None, args.momentum)
    if accelerated:
        print(f"[serve] accelerated power iterations (momentum="
              f"{momentum:g})")
    from repro.runtime.diagnostics import resolve_diagnostics
    diag = resolve_diagnostics(args.diag)
    driver = IterationDriver(step=PowerStep.for_algorithm(
        "deepca", args.rounds, accelerated=accelerated, momentum=momentum,
        ef_wire=engine.ef_wire), engine=engine, diagnostics=diag)
    if diag is not None:
        print(f"[serve] in-graph diagnostics: "
              f"{','.join(diag.names(driver.step))} "
              f"(wire floor {driver.quantization_floor():.1e})")

    if args.profile_stages:
        stages = driver.profile_stages(problems[0], W0[0])
        total = sum(stages.values())
        parts = " ".join(f"{s}={us:.0f}us({100 * us / total:.0f}%)"
                         for s, us in stages.items())
        print(f"[serve] per-stage wall clock: {parts}")

    out = driver.run_batch(problems, W0, T=args.iters)     # compile + warm
    jax.block_until_ready(out.W)
    t0 = time.perf_counter()
    for _ in range(args.reps):
        out = driver.run_batch(problems, W0, T=args.iters)
        jax.block_until_ready(out.W)
    dt = (time.perf_counter() - t0) / args.reps

    from repro.core.step import qr_orth   # shared CholeskyQR2 fast path
    tans = []
    for b, ops in enumerate(problems):
        U, _ = top_k_eigvecs(ops.mean_matrix(), k)
        Wbar = qr_orth(jnp.mean(out.W[b], axis=0))
        tans.append(float(metrics.tan_theta_k(U, Wbar)))
    print(f"served {B} PCA problems (m={m}, d={d}, k={k}, "
          f"T={args.iters}, K={args.rounds}) in {dt * 1e3:.1f} ms/launch "
          f"({B / dt:.1f} problems/s, {B * args.iters / dt:.0f} iters/s)")
    print(f"tan_theta: max={max(tans):.3e} mean={np.mean(tans):.3e}")


def serve_pca_stream(args) -> None:
    """Streaming workload: online tracking + dynamic-batching queue."""
    from repro.core import erdos_renyi, metrics, top_k_eigvecs
    from repro.data.synthetic import PrefetchIterator
    from repro.streaming import (AdmissionPolicy, DriftPolicy, PCAService,
                                 SlowRotationStream, StreamingDeEPCA,
                                 ragged_requests)

    m, d, k = args.m, args.d, args.k_top
    topo = erdos_renyi(m, p=0.5, seed=args.seed)

    # --- 1. online tracker over a drifting stream (prefetched ingest) ----
    stream = SlowRotationStream(m=m, d=d, k=k, n_per_agent=args.n_per_agent,
                                rate=args.drift_rate, seed=args.seed)
    wire = args.wire_dtype if args.wire_dtype is not None \
        else ("bf16" if args.wire_bf16 else None)
    if wire in ("none", "fp32"):
        wire = None
    tracker = StreamingDeEPCA(
        k=k, T_tick=args.tick_iters, K=args.rounds, topology=topo,
        backend="auto", W0=stream.init_W0(),
        policy=DriftPolicy(target=args.target),
        accelerated=args.accel or None, momentum=args.momentum,
        wire_dtype=wire, diagnostics=args.diag)
    print(f"[stream] m={m} d={d} k={k} rate={args.drift_rate}/tick "
          f"T_tick={args.tick_iters} K={args.rounds} target={args.target}")
    t0 = time.perf_counter()
    # the prefetch thread draws NumPy only; JAX work stays on this thread
    with PrefetchIterator(stream.host_ticks(args.ticks), depth=2) as ticks:
        for host in ticks:
            tick = stream.on_device(host)
            r = tracker.tick(tick.ops, tick.U)
            flags = ("R" if r.restarted else "") + ("D" if r.drift else "")
            print(f"[stream] tick {r.tick:3d}: iters={r.iterations} "
                  f"rounds={r.comm_rounds:5.0f} tan_theta={r.stat:.2e} "
                  f"{flags}")
    dt = time.perf_counter() - t0
    total = tracker.reports[-1].total_rounds
    print(f"[stream] {args.ticks} ticks in {dt:.2f}s "
          f"({total / args.ticks:.1f} comm rounds/tick warm-started)")

    # --- 2. ragged one-shot requests through the dynamic-batching queue --
    svc = PCAService(topo, T=args.iters, K=args.rounds, backend="auto",
                     policy=AdmissionPolicy(max_batch=args.max_batch,
                                            max_wait=args.max_wait),
                     diagnostics=args.diag)
    reqs = ragged_requests(m, d, k, args.requests,
                           n_base=args.n_per_agent, seed=args.seed)
    t0 = time.perf_counter()
    ids = [svc.submit(ops, W0) for ops, W0 in reqs]
    svc.poll()
    svc.flush()
    dt = time.perf_counter() - t0
    tans = []
    for rid, (ops, W0) in zip(ids, reqs):
        resp = svc.result(rid)
        if resp is None:                 # must survive python -O
            raise RuntimeError(f"request {rid} was never served")
        U, _ = top_k_eigvecs(ops.mean_matrix(), resp.W.shape[-1])
        from repro.core.step import qr_orth
        Wbar = qr_orth(jnp.mean(resp.W, axis=0))
        tans.append(float(metrics.tan_theta_k(U, Wbar)))
    s = svc.stats
    print(f"[queue] served {s['served']} ragged requests in {dt:.2f}s "
          f"({s['served'] / dt:.1f} req/s) over {s['batches']} batches "
          f"(cold={s['cold_launches']} warm={s['warm_launches']} "
          f"padded={s['padded_requests']})")
    print(f"[queue] tan_theta: max={max(tans):.3e} "
          f"mean={float(np.mean(tans)):.3e}")


def fleet_tenant_mix(m: int, d: int, k: int, *, tenants: int,
                     n_per_agent: int, rate: float, seed: int,
                     heterogeneity: float = 1.0) -> dict:
    """The fleet workload's tenants: ``tenant000..`` drifting streams with
    10 distinct per-agent sample counts (``n_per_agent - 8`` in steps of
    2), which the fleet's ``pad_n=16`` bucketing collapses onto two
    compiled window programs."""
    from repro.streaming import SlowRotationStream

    def n_of(i: int) -> int:
        return max(k + 2, n_per_agent - 8 + 2 * (i % 10))

    return {f"tenant{i:03d}": SlowRotationStream(
        m=m, d=d, k=k, n_per_agent=n_of(i), rate=rate,
        heterogeneity=heterogeneity, seed=seed + i) for i in range(tenants)}


@contextlib.contextmanager
def fleet_ingest(streams: dict, ticks: int):
    """Threaded per-tenant ingest: a
    :class:`~repro.data.synthetic.MultiStreamPrefetcher` with one lane per
    stream over its ``host_ticks(ticks)``, yielded for
    :func:`ingest_tick`.  The lanes draw NumPy only.  While they run,
    BLAS is held to one thread per caller: OpenBLAS's own pool, called
    from many lanes at once, spins against itself (8 lanes drawing one
    16-agent d=512 tick each: 34.6 s, against 0.69 s single-threaded, on
    an 8-core host)."""
    from threadpoolctl import threadpool_limits
    from repro.data.synthetic import MultiStreamPrefetcher
    with threadpool_limits(limits=1, user_api="blas"), \
            MultiStreamPrefetcher({tid: st.host_ticks(ticks)
                                   for tid, st in streams.items()},
                                  depth=2) as mux:
        yield mux


def ingest_tick(streams: dict, mux) -> dict:
    """One fleet tick from :func:`fleet_ingest`'s lanes, put on the device
    with its ground truth here, on the serving thread: JAX calls on the
    lanes' threads would compile concurrently with this one and wait on
    the persistent compile cache's file lock."""
    return {tid: streams[tid].on_device(host)
            for tid, host in mux.tick().items()}


def serve_pca_fleet(args) -> dict:
    """Fleet workload: N drifting tenants, one program per shape bucket.

    Returns the fleet, the last tick's items and the tick latencies
    (``warmup_ms``, ``steady_ms``) and steady-state cold launches."""
    from repro.core import erdos_renyi
    from repro.streaming import DriftPolicy, SlowRotationStream, TrackerFleet

    m, d, k = args.m, args.d, args.k_top
    topo = erdos_renyi(m, p=0.5, seed=args.seed)
    wire = args.wire_dtype if args.wire_dtype is not None \
        else ("bf16" if args.wire_bf16 else None)
    if wire in ("none", "fp32"):
        wire = None
    fleet = TrackerFleet(
        k=k, T_tick=args.tick_iters, K=args.rounds, topology=topo,
        backend="auto", policy=DriftPolicy(target=args.target),
        slots=args.slots, slo_ms=args.slo_ms,
        accelerated=args.accel or None, momentum=args.momentum,
        wire_dtype=wire, diagnostics=args.diag)

    streams = fleet_tenant_mix(m, d, k, tenants=args.tenants,
                               n_per_agent=args.n_per_agent,
                               rate=args.drift_rate, seed=args.seed)
    for tid, st in streams.items():
        fleet.join(tid, st.init_W0(), n=st.n_per_agent)
    shapes = sorted({st.n_per_agent for st in streams.values()})
    print(f"[fleet] m={m} d={d} k={k} tenants={args.tenants} "
          f"n-shapes={shapes} T_tick={args.tick_iters} K={args.rounds}")

    half = max(1, args.ticks // 2)
    steady_cold = n_steady = 0
    steady_ms = []
    t0 = time.perf_counter()
    with fleet_ingest(streams, args.ticks) as mux:
        items = ingest_tick(streams, mux)
        rep = fleet.tick(items)             # warm-up: compiles the buckets
        warmup_ms = rep.latency_ms
        print(f"[fleet] warm-up tick: {rep.cold_launches} cold compiles, "
              f"programs={fleet.program_count} {warmup_ms:.1f} ms")
        t0 = time.perf_counter()
        for t in range(1, args.ticks):
            if t == half:
                # membership churn mid-run: evict one tenant and admit a
                # fresh one into the vacated slot — zero retraces
                old = next(iter(fleet.tenants))
                n_old = streams[old].n_per_agent
                fleet.leave(old)
                mux.close(old)
                joiner = SlowRotationStream(
                    m=m, d=d, k=k, n_per_agent=n_old,
                    rate=args.drift_rate, seed=args.seed + 9999)
                streams["joiner"] = joiner
                mux.add("joiner", joiner.host_ticks(args.ticks - t),
                        depth=2)
                fleet.join("joiner", joiner.init_W0(), n=n_old)
                print(f"[fleet] tick {t}: churn — evicted {old}, "
                      f"admitted joiner (same bucket slot)")
            items = ingest_tick(streams, mux)
            rep = fleet.tick(items)
            steady_cold += rep.cold_launches
            steady_ms.append(rep.latency_ms)
            n_steady += 1
            worst = max(rep.tenants.values(), key=lambda r: r.stat)
            print(f"[fleet] tick {t}: windows={rep.windows} "
                  f"warm={rep.warm_launches} cold={rep.cold_launches} "
                  f"worst tan_theta={worst.stat:.2e} ({worst.tenant}) "
                  f"{rep.latency_ms:.1f} ms")
    dt = time.perf_counter() - t0
    n_ten = len(fleet.tenants)
    print(f"[fleet] {n_steady} steady ticks x {n_ten} tenants in {dt:.2f}s "
          f"({n_steady / dt:.1f} fleet ticks/s, "
          f"{n_steady * n_ten / dt:.1f} tenant-ticks/s)")
    print(f"[fleet] programs={fleet.program_count} "
          f"steady cold launches={steady_cold}")
    s = fleet.stats
    print(f"[fleet] joins={s['joins']} leaves={s['leaves']} "
          f"restarts={s['restarts']} escalations={s['escalations']} "
          f"slo_breaches={s['slo_breaches']}")
    return {"fleet": fleet, "last": items, "warmup_ms": warmup_ms,
            "steady_ms": steady_ms, "steady_cold": steady_cold}


def build_parser() -> argparse.ArgumentParser:
    """The ``serve`` command line."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lm",
                    choices=["lm", "pca", "pca-stream", "pca-fleet"])
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # --workload pca knobs
    ap.add_argument("--m", type=int, default=16, help="agents per problem")
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--k-top", type=int, default=4)
    ap.add_argument("--n-per-agent", type=int, default=64)
    ap.add_argument("--iters", type=int, default=30, help="power iterations")
    ap.add_argument("--rounds", type=int, default=6, help="FastMix rounds K")
    ap.add_argument("--wire-bf16", action="store_true",
                    help="gossip iterates travel in bf16 (tracking/QR stay "
                         "fp32); shorthand for --wire-dtype bf16")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["none", "fp32", "bf16", "int8", "fp8"],
                    help="gossip wire precision; int8/fp8 add error "
                         "feedback (see README 'Wire modes'); default: "
                         "$REPRO_WIRE_DTYPE or fp32")
    ap.add_argument("--accel", action="store_true",
                    help="momentum-accelerated power iterations "
                         "(see README 'Acceleration')")
    ap.add_argument("--momentum", type=float, default=None,
                    help="momentum coefficient for --accel "
                         "(default: $REPRO_ACCEL or 0.25)")
    ap.add_argument("--profile-stages", action="store_true",
                    help="measure per-stage (apply/mix/orth) wall clock "
                         "once before serving; emits 'stage' telemetry")
    ap.add_argument("--reps", type=int, default=10, help="timed launches")
    # --workload pca-stream knobs
    ap.add_argument("--ticks", type=int, default=8, help="stream ticks")
    ap.add_argument("--tick-iters", type=int, default=3,
                    help="warm-start power iterations per tick")
    ap.add_argument("--drift-rate", type=float, default=0.03,
                    help="subspace rotation per tick (radians)")
    ap.add_argument("--target", type=float, default=None,
                    help="per-tick tan-theta target (escalates until met)")
    ap.add_argument("--requests", type=int, default=24,
                    help="ragged one-shot requests for the queue demo")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="admission policy: batch-size cap")
    ap.add_argument("--max-wait", type=float, default=0.01,
                    help="admission policy: max queue wait (s)")
    # --workload pca-fleet knobs
    ap.add_argument("--tenants", type=int, default=12,
                    help="concurrent drifting streams in the fleet")
    ap.add_argument("--slots", type=int, default=None,
                    help="fleet slot-pool capacity per shape bucket "
                         "(default: $REPRO_FLEET_SLOTS or 8)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="fleet per-tick latency objective in ms "
                         "(default: $REPRO_FLEET_SLO_MS; unset disables)")
    ap.add_argument("--telemetry", default=None, metavar="SPEC",
                    help="event sink: 'null', 'log', 'jsonl:PATH', or "
                         "'jsonl+buffer:PATH' (default: $REPRO_TELEMETRY "
                         "if set); streams per-iteration contraction "
                         "rate/comm rounds, warm-vs-cold launches, "
                         "drift/restart events")
    ap.add_argument("--diag", nargs="?", const="on", default=None,
                    metavar="OBS",
                    help="in-graph convergence diagnostics: bare --diag "
                         "enables every observable; or a comma list from "
                         "consensus,movement,ef_residual,momentum "
                         "(default: $REPRO_DIAG if set).  Emits 'diag' "
                         "events and arms the live health monitor "
                         "(see README 'Observability')")
    ap.add_argument("--trace", default=None, metavar="SPEC",
                    help="span tracing: 'chrome:PATH' writes a Chrome "
                         "trace-event JSON (open in Perfetto), "
                         "'chrome+jax:PATH' also wraps spans in "
                         "jax.profiler annotations, 'jax' annotates only "
                         "(default: $REPRO_TRACE if set)")
    return ap


def main() -> None:
    args = build_parser().parse_args()

    from repro.runtime import config as runtime_config
    from repro.runtime import diagnostics, telemetry, tracing
    runtime_config.enable_compile_cache()
    cfg = runtime_config.get_config()
    spec = args.telemetry if args.telemetry is not None else cfg.telemetry
    sink = telemetry.sink_from_spec(spec)
    telemetry.set_sink(sink)
    monitor = None
    if diagnostics.resolve_diagnostics(args.diag) is not None:
        monitor = diagnostics.install_health_monitor()
    tracer = tracing.tracer_from_spec(
        args.trace if args.trace is not None else cfg.trace)
    if tracer is not None:
        tracing.set_tracer(tracer)
    telemetry.emit("config", workload=args.workload,
                   **runtime_config.describe())
    try:
        with tracing.span("serve.request", workload=args.workload):
            if args.workload == "pca":
                serve_pca(args)
            elif args.workload == "pca-stream":
                serve_pca_stream(args)
            elif args.workload == "pca-fleet":
                serve_pca_fleet(args)
            else:
                serve_lm(args)
    finally:
        # finalize/summarize BEFORE the sink closes so the summary health
        # event (and any trailing buffered events) land in the sink
        if monitor is not None:
            diagnoses = monitor.finalize()
            if diagnoses:
                print(f"[health] {len(diagnoses)} diagnosis(es) raised:")
                for dgn in diagnoses:
                    print(f"[health]   {dgn['rule']}: {dgn['message']}")
            else:
                print("[health] ok — no diagnoses raised")
        if tracer is not None:
            tracing.set_tracer(None)
            tracer.save()
            if getattr(tracer, "path", None):
                print(f"[trace] {len(tracer)} spans -> {tracer.path} "
                      "(load in Perfetto / chrome://tracing)")
        telemetry.get_sink().close()


if __name__ == "__main__":
    main()
