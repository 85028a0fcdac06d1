"""CPU rehearsal: every cell's system, traffic and per-layer readers at a
tiny size, called directly (``bench/run.py`` itself needs a TPU)."""
import json
import os

import numpy as np
import pytest

import harness
import tracereduce
from conftest import CELLS, faults, tiny_cell

PEAKS = harness.peaks("TPU v5 lite")


def _busy_trace(window_ns=1_000_000_000):
    """A trace in which every kernel of the benchmark ran once, 1 ms each,
    inside a 1 s window, with one collective that half overlaps compute."""
    ops = [("fusion.1", 100_000_000, 101_000_000, "fusion.1"),
           ("_fastmix_track_fused.7", 200_000_000, 201_000_000,
            "_fastmix_track_fused.7"),
           ("vmap_jit__gram__.24", 300_000_000, 301_000_000,
            "vmap_jit__gram__.24"),
           ("cp", 400_000_000, 402_000_000, "collective-permute-done"),
           ("fusion.2", 401_000_000, 402_000_000, "fusion.2")]
    return tracereduce.Trace(
        devices={"/device:TPU:0": ops},
        spans=[("bench.window", 0, window_ns)])


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name, ctx_of):
    ctx = ctx_of(name)
    system = harness.system(ctx.cell)
    st = system.setup(ctx)
    win = system.window(ctx, st, 1.0)
    assert win.attempted > 0 and win.failed == 0
    assert system.route(st)
    system.release(st)
    checks = system.check(ctx, st, win)
    assert checks and all(c.ok for c in checks), checks
    want = {m["name"] for m in ctx.cell.end_to_end} - {"setup_s"}
    assert want
    assert want <= set(win.e2e) and all(win.e2e[k] > 0 for k in want)


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_every_listed_per_layer_metric(name, ctx_of):
    """Each per-layer metric the cell lists finds something to read in a
    window's counters and a trace in which its kernels ran."""
    ctx = ctx_of(name)
    system = harness.system(ctx.cell)
    st = system.setup(ctx)
    win = system.window(ctx, st, 0.5)
    system.release(st)
    got = harness.read_per_layer(
        harness.RunView(ctx.cell, win.counters, _busy_trace(), PEAKS))
    assert set(got) == {m["name"] for m in ctx.cell.per_layer}
    for m in ctx.cell.per_layer:
        assert got[m["name"]]["unit"] == m["unit"]
        if m["unit"] == "%":
            assert 0.0 <= got[m["name"]]["value"] <= 100.0


def test_readers_find_nothing_without_a_trace(ctx_of):
    """A device metric is left out, never 0, where no trace was taken."""
    cell = harness.load_cell("w8a-m50.batch")
    got = harness.read_per_layer(harness.RunView(
        cell, {"waited_s": [0.01], "iterations": 40,
               "shape": {"m": 50, "d": 300, "k": 5, "K": 8}}, None, PEAKS))
    assert set(got) == {"service.wait_ms"}
    assert got["service.wait_ms"]["value"] == pytest.approx(10.0)


def test_every_cell_has_its_tiny_size_and_faults():
    """A cell of ``BENCHMARK.json`` is rehearsed from its committed files,
    cut by a ``tiny/<config>.json`` of its own, with its system's faults."""
    for name in CELLS:
        assert set(tiny_cell(name).config) == set(
            harness.load_cell(name).config)
        assert faults(name)


def test_every_named_file_exists():
    spec = harness.benchmark()
    for c in spec["configs"]:
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert os.path.exists(os.path.join(
            harness.BENCH, "systems", cfg["system"] + ".py"))
    for w in spec["workloads"]:
        harness.load_cell(w["name"])
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_seed_keys_take_seeds_past_32_bits():
    import jax
    a = jax.random.key_data(harness.seed_key(2 ** 31 + 5))
    b = jax.random.key_data(harness.seed_key(2 ** 40 + 5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(jax.random.key_data(
            harness.seed_key(2 ** 31 + 5))))


def test_result_line_puts_checks_last():
    cell = harness.load_cell("w8a-m50.batch")
    line = harness.result_line(
        cell, correct=True, window=harness.Window(3, 0, {}, {}),
        metrics={}, device={"platform": "tpu"},
        checks=[harness.Check("tan_worst_agent", 1e-6, 1e-4)],
        breakdown={"device_ops": [], "idle_gaps": []})
    assert list(json.loads(json.dumps(line)))[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device",
            "breakdown"} <= set(line)


def test_random_starts_run(ctx_of):
    """The service's ``random`` starts (the cells left out until the
    sign-reference fault is fixed) still drive the loop."""
    ctx = ctx_of("w8a-m50.single")
    ctx.cell.traffic = dict(ctx.cell.traffic, start="random")
    system = harness.system(ctx.cell)
    st = system.setup(ctx)
    win = system.window(ctx, st, 0.5)
    assert win.attempted > 0 and win.failed == 0
    assert len({W.tobytes() for _, _, W in win.answers}) > 1
