#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic and system are found by name (``bench/harness.py``).
Set-up draws the data on the device from ``--seed`` and warms every shape
the traffic uses; the window then runs ``--seconds``; after it the answers
are compared with the plain float64 reference.  With ``--trace 0`` the
result reports the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit); the checks are also the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
# JAX's persistent compilation cache lives inside the checkout, at a fixed
# path (part of every entry's key), so only a cell's first run there
# compiles; the program's enable_compile_cache takes it from here
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.chips)
    import jax
    from repro.runtime import config as runtime_config
    cache = runtime_config.enable_compile_cache()
    # every program of the cell, however quick to compile, is read back
    # from the cache by the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    kind = devices[0].device_kind
    peaks = harness.peaks(kind)
    print(f"[bench] cell={cell.name} seed={args.seed} device={kind} "
          f"chips={len(devices)} compile_cache={cache}", file=sys.stderr,
          flush=True)

    ctx = harness.Context(cell, args.seed, devices)
    system = harness.system(cell)
    state = system.setup(ctx)
    setup_s = time.perf_counter() - T_START
    print(f"[bench] setup_s={setup_s:.3f} " + " ".join(
        f"{k}={v:.3f}" for k, v in state.setup_parts.items()),
        file=sys.stderr, flush=True)

    trace_dir = os.path.join(harness.RESULTS, cell.name, "trace")
    seconds = args.seconds
    if args.trace:
        # a trace holds every XLA op (some 250 thousand a second in the
        # service cells), so the traced window is the traffic's shorter one
        seconds = min(seconds, float(cell.traffic["trace_seconds"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    win = system.window(ctx, state, seconds)
    if args.trace:
        jax.profiler.stop_trace()
    memory = harness.memory_peak_bytes(devices)
    print(f"[bench] route {system.route(state)}", file=sys.stderr, flush=True)
    system.release(state)
    gc.collect()
    checks = system.check(ctx, state, win)
    correct = all(c.ok for c in checks) and win.failed == 0

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    breakdown = None
    if args.trace:
        import tracereduce
        trace = tracereduce.from_xplane(
            tracereduce.newest_xplane(trace_dir), len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = trace.mean_busy_s()
        device["window_s"] = trace.window_s()
        metrics = harness.read_per_layer(
            harness.RunView(cell, win.counters, trace, peaks))
        breakdown = trace.breakdown()
    else:
        e2e = dict(win.e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    for c in checks:
        print(f"[check] {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    print(f"[check] failed = {win.failed} of {win.attempted}",
          file=sys.stderr, flush=True)
    print(json.dumps(harness.result_line(
        cell, correct=correct, window=win, metrics=metrics, device=device,
        checks=checks, breakdown=breakdown)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
