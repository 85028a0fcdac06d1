"""A run with the timed path broken underneath comes out not correct.

Each fault of the cell's system (``bench/faults/<system>.py``) is planted
in the program (monkeypatched) and the rest of a run is driven as
``bench/run.py`` drives it, past the look for a chip: set-up, the window,
the release of the program's state, and the check.
"""
import pytest

import harness
from conftest import CELLS, faults

CASES = [(cell, f) for cell in CELLS for f in faults(cell)]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_fault_makes_the_run_incorrect(cell, fault, ctx_of, monkeypatch):
    ctx = ctx_of(cell)
    system = harness.system(ctx.cell)
    fault(monkeypatch)
    st = system.setup(ctx)
    win = system.window(ctx, st, 0.5)
    system.release(st)
    checks = system.check(ctx, st, win)
    assert not (all(c.ok for c in checks) and win.failed == 0), checks
