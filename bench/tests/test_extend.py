"""A later cell, traffic mix or per-layer metric is added by adding files
and entries: here to a copy of the benchmark, with no existing file
edited."""
import json
import os
import shutil

import harness

DUMMY_METRIC = '''"""Requests answered in the window (a dummy for this test)."""


def read(run):
    return run.counters.get("solves")
'''


def test_new_metric_and_traffic_by_files_and_entries(tmp_path, ctx_of):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = harness.benchmark()
    (root / "bench" / "metrics" / "dummy.solves.py").write_text(DUMMY_METRIC)
    (root / "bench" / "traffic" / "quad.json").write_text(
        json.dumps({"loop": "closed", "clients": 4, "start": "agent0",
                    "trace_seconds": 2}))
    spec["workloads"].append(
        {"name": "w8a-m50.quad", "config": "w8a-m50", "traffic": "quad",
         "chips": 1, "why": "four closed-loop clients"})
    spec["per_layer"].append(
        {"name": "dummy.solves", "unit": "solves", "better": "higher",
         "source": "program_counter", "layer": "serving",
         "moves": "solves_per_s", "workloads": ["w8a-m50.quad"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    before = {p: open(os.path.join(harness.BENCH, p)).read()
              for p in ("harness.py", "run.py", "systems/service.py")}

    cell = harness.load_cell("w8a-m50.quad", root=str(root))
    assert cell.traffic["clients"] == 4
    assert "dummy.solves" in [m["name"] for m in cell.per_layer]
    ctx = ctx_of("w8a-m50.batch")
    ctx.cell.traffic, ctx.cell.per_layer = cell.traffic, cell.per_layer
    system = harness.system(ctx.cell)
    st = system.setup(ctx)
    win = system.window(ctx, st, 0.5)
    system.release(st)
    got = harness.read_per_layer(
        harness.RunView(ctx.cell, win.counters, None, {}),
        metrics_dir=str(root / "bench" / "metrics"))
    assert got["dummy.solves"]["value"] == win.counters["solves"] > 0
    assert set(got) == {"dummy.solves"}
    for p, text in before.items():
        assert open(os.path.join(harness.BENCH, p)).read() == text
