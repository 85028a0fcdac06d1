"""Tests of the benchmark itself, on the CPU at tiny sizes.

Run from the root of the checkout:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The cells are those of ``BENCHMARK.json``; each configuration is cut to a
size the CPU runs in seconds by ``tiny/<config>.json``, whose keys are the
only difference from the committed file.
"""
from __future__ import annotations

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

import harness  # noqa: E402

#: the names of the cells of ``BENCHMARK.json``
CELLS = [w["name"] for w in harness.benchmark()["workloads"]]


def tiny_cell(name: str, **over) -> harness.Cell:
    """The cell ``name`` with its configuration cut by
    ``tiny/<config>.json`` (and ``over``)."""
    cell = copy.deepcopy(harness.load_cell(name))
    cfg = cell.config
    tiny = harness.load_json(os.path.join(HERE, "tiny",
                                          cell.config_name + ".json"))
    for key, val in {**tiny, **over}.items():
        if isinstance(val, dict):
            cfg[key] = {**cfg.get(key, {}), **val}
        else:
            cfg[key] = val
    return cell


def faults(name: str) -> list:
    """The faults planted under the cell's system (``faults/<system>.py``)."""
    system = harness.load_cell(name).config["system"]
    return harness.load_module(os.path.join(BENCH, "faults",
                                            system + ".py")).FAULTS


@pytest.fixture
def ctx_of():
    def make(name, seed=2 ** 31 + 17, **over):
        return harness.Context(tiny_cell(name, **over), seed)
    return make
