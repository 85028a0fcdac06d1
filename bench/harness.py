"""The benchmark's data-driven core: cells, discovery and the result line.

Everything that belongs to one configuration, traffic mix, system or
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives:

* ``configs/<config>.json``: the deployment's sizes (its ``file`` entry);
* ``traffic/<traffic>.json``: the mix's parameters;
* ``systems/<system>.py``: the general generator and client loop for the
  entry point a configuration names under ``"system"``;
* ``gen/<generator>.py``: a device-side data generator a configuration
  names under ``"data"``;
* ``metrics/<metric>.py``: one per-layer metric's reader, ``read(run)``.

So a later cell, mix or metric is added by adding files and entries; no
file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: results of runs that ask for files (traces) go here, inside the checkout
RESULTS = os.path.join(ROOT, "results", "bench")


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with its configuration and traffic loaded."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it passes at or below its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit   # NaN fails


@dataclasses.dataclass
class Window:
    """What a system's measured window hands back to the harness.

    ``e2e`` holds the end-to-end values the system measured by the host
    clock; ``counters`` whatever its per-layer readers need (program
    counters and the logical work done); ``answers`` what the check
    compares once the window has closed.
    """

    attempted: int
    failed: int
    e2e: Dict[str, float]
    counters: Dict[str, Any]
    answers: Any = None


@dataclasses.dataclass
class Context:
    """One run: the cell, its seed and the devices it holds (``None``
    where a test runs a system on the CPU)."""

    cell: Cell
    seed: int
    devices: Any = None


@dataclasses.dataclass
class RunView:
    """What a per-layer reader sees: the cell, the window's counters, the
    reduced device trace (``None`` without ``--trace 1``) and the peaks of
    the device the run used."""

    cell: Cell
    counters: Dict[str, Any]
    trace: Any
    peaks: Dict[str, float]


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The named cell of ``<root>/BENCHMARK.json``, with its files read."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))

    def mine(metric):
        return name in metric.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


def load_module(path: str, name: Optional[str] = None):
    """Import a file of the benchmark by its path."""
    mod_name = name or "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def system(cell: Cell):
    return load_module(os.path.join(BENCH, "systems",
                                    cell.config["system"] + ".py"))


def generator(name: str):
    return load_module(os.path.join(BENCH, "gen", name + ".py"))


def metric_reader(name: str, metrics_dir: Optional[str] = None
                  ) -> Callable[[RunView], Optional[float]]:
    path = os.path.join(metrics_dir or os.path.join(BENCH, "metrics"),
                        name + ".py")
    return load_module(path, "bench_metric_" + name.replace(".", "_")).read


def read_per_layer(view: RunView, metrics_dir: Optional[str] = None
                   ) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of the cell that its reader finds; a reader
    that finds nothing returns ``None`` and the metric is left out."""
    out = {}
    for m in view.cell.per_layer:
        value = metric_reader(m["name"], metrics_dir)(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind`` (``peaks.json``); a kind not
    in the table is an error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(kinds)})")
    return kinds[device_kind]


def require_devices(chips: int):
    """The first ``chips`` TPU devices; exits non-zero, printing no result,
    when JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX reports platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX reports "
                         f"{len(devs)}")
    return devs[:chips]


def seed_key(seed: int, *salt: int):
    """A JAX PRNG key from any whole ``seed`` (and optional salts), through
    NumPy's ``SeedSequence``, so seeds past 32 bits work."""
    import numpy as np
    import jax
    words = np.random.SeedSequence([int(seed), *map(int, salt)]
                                   ).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32))


def random_basis(seed: int, i: int, d: int, k: int):
    """Request ``i``'s random ``(d, k)`` orthonormal start, from
    ``(seed, i)``."""
    import numpy as np
    rng = np.random.default_rng([int(seed), 7, int(i)])
    return np.linalg.qr(rng.standard_normal((d, k)))[0].astype(np.float32)


def route(driver) -> str:
    """The engine's backend and the Pallas kernels in the program a driver
    launched last, lowered at the shapes it ran with."""
    import re
    text = driver.lower_last_launch().as_text()
    names = sorted(set(re.findall(r'kernel_name = "(\w+)"', text)))
    return (f"backend={driver.engine.backend} "
            f"kernels={','.join(names) or 'none'}")


def memory_peak_bytes(devices) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_))


def quantile(values, q: float) -> float:
    """The ``q``-quantile of all ``values`` (linear interpolation)."""
    import numpy as np
    return float(np.quantile(np.asarray(values, float), q))


def result_line(cell: Cell, *, correct: bool, window: Window,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                checks: List[Check], breakdown=None) -> Dict[str, Any]:
    out = {"correct": bool(correct), "attempted": int(window.attempted),
           "failed": int(window.failed), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out

