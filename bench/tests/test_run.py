"""``bench/run.py`` exits non-zero and prints no result without a TPU, and
in a directory that holds only ``BENCHMARK.json`` and ``bench/``."""
import os
import shutil
import subprocess
import sys

import harness

ARGS = ["--workload", "w8a-m50.batch", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(harness.ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "{" not in out.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert "{" not in out.stdout
