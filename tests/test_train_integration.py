"""End-to-end training integration: loss decreases; crash/resume works."""
import os
import subprocess
import sys

import pytest

_ENV = dict(os.environ,
            PYTHONPATH=os.path.abspath(os.path.join(
                os.path.dirname(__file__), "..", "src")),
            # serve turns the persistent compile cache on (in the
            # checkout's .jax_cache/ unless JAX_COMPILATION_CACHE_DIR is
            # set); the tests keep it off
            JAX_ENABLE_COMPILATION_CACHE="false")


def _run(*args, timeout=1200):
    return subprocess.run([sys.executable, "-m", "repro.launch.train", *args],
                          env=_ENV, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.slow
def test_train_loss_decreases(tmp_path):
    out = _run("--arch", "smollm_135m", "--reduced", "--steps", "60",
               "--batch", "8", "--seq", "64", "--log-every", "10")
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("step")]
    first = float(lines[0].split()[3])
    last = float(lines[-1].split()[3])
    assert last < first - 0.5, (first, last, out.stdout[-800:])


@pytest.mark.slow
def test_train_crash_resume_bitwise_data_order(tmp_path):
    ck = str(tmp_path / "ck")
    # run A: uninterrupted 40 steps
    a = _run("--arch", "smollm_135m", "--reduced", "--steps", "40",
             "--batch", "4", "--seq", "32", "--ckpt-dir", ck + "A",
             "--ckpt-every", "10", "--log-every", "40")
    assert a.returncode == 0, a.stderr[-2000:]
    # run B: crash at step 25, then resume to 40
    b1 = _run("--arch", "smollm_135m", "--reduced", "--steps", "40",
              "--batch", "4", "--seq", "32", "--ckpt-dir", ck + "B",
              "--ckpt-every", "10", "--crash-at", "25", "--log-every", "40")
    assert b1.returncode != 0
    b2 = _run("--arch", "smollm_135m", "--reduced", "--steps", "40",
              "--batch", "4", "--seq", "32", "--ckpt-dir", ck + "B",
              "--ckpt-every", "10", "--resume", "--log-every", "40")
    assert b2.returncode == 0, b2.stderr[-2000:]
    assert "[resume] step 20" in b2.stdout
    fa = [l for l in a.stdout.splitlines() if l.startswith("final")][0]
    fb = [l for l in b2.stdout.splitlines() if l.startswith("final")][0]
    # same final loss to float32 print precision -> same data order + state
    assert fa.split()[2] == fb.split()[2], (fa, fb)


@pytest.mark.slow
def test_serve_runs():
    out = subprocess.run([sys.executable, "-m", "repro.launch.serve",
                          "--arch", "xlstm_350m", "--reduced",
                          "--batch", "2", "--prompt-len", "16", "--gen", "4"],
                         env=_ENV, capture_output=True, text=True,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "generated" in out.stdout
