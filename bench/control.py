#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's, the control's and
the planted faults'.

    python bench/control.py --workload <cell> --seeds 1,2,3 --control 3 \\
        --seconds <s> [--fault half_batch] [--set T=60] [--set start=random]

For each seed, in this one process: the cell's set-up and a window of
``--seconds`` at the cell's own load, then the numbers its check compares
(the program's readings); for the first ``--control`` seeds also the same
numbers with every answer replaced by the control's, the reference put in
the program's place in the nearest precision below the configuration's
(``control`` of the cell's system).  ``--fault`` plants one of the
system's faults (``faults/<system>.py``) under every seed; ``--set`` gives
a configuration or traffic key another value (a JSON value), for readings
of a variant that is not a cell.  One JSON line per seed on standard
output.  The benchmark's own runs never run this; limits are set from its
readings (PERF.md).  Needs the chips the cell asks for.
"""
import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

import harness  # noqa: E402


def readings(cell, seed, seconds, control, devices=None):
    """``(program, control, served)``: dicts of the compared numbers for one
    seed (``control`` is ``None`` unless asked for) and the answers served."""
    system = harness.system(cell)
    ctx = harness.Context(cell, seed, devices)
    st = system.setup(ctx)
    win = system.window(ctx, st, seconds)
    system.release(st)
    gc.collect()
    prog = {c.name: c.value for c in system.check(ctx, st, win)}
    if hasattr(system, "detail"):
        prog["detail"] = system.detail(ctx, st, win)
    ctrl = None
    if control:
        ctrl = {c.name: c.value
                for c in system.check(ctx, st, system.control(ctx, st, win))}
    return prog, ctrl, win.counters.get("solves")


def override(cell, settings):
    """Set each ``key=value`` in the cell's configuration, or in its
    traffic where the traffic has the key."""
    for item in settings:
        key, _, text = item.partition("=")
        target = cell.traffic if key in cell.traffic else cell.config
        target[key] = json.loads(text) if text[:1] in "-0123456789[{tfn" \
            else text


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    override(cell, args.set)
    devices = harness.require_devices(cell.chips)
    import jax
    from repro.runtime import config as runtime_config
    runtime_config.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.fault:
        import pytest
        faults = harness.load_module(os.path.join(
            BENCH, "faults", cell.config["system"] + ".py"))
        getattr(faults, args.fault)(pytest.MonkeyPatch())
    for j, seed in enumerate(int(s) for s in args.seeds.split(",")):
        prog, ctrl, served = readings(cell, seed, args.seconds,
                                      j < args.control, devices)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "fault": args.fault, "set": args.set,
                          "served": served, "program": prog,
                          "control": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
