"""The control comes out not correct, where the program is correct: the
reference put in the program's place, computed one precision below the
configuration's float32 (bfloat16), at a size a test run holds."""
import pytest

import harness
from conftest import CELLS


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(name, ctx_of):
    ctx = ctx_of(name)
    system = harness.system(ctx.cell)
    st = system.setup(ctx)
    win = system.window(ctx, st, 1.0)
    system.release(st)
    program = system.check(ctx, st, win)
    control = system.check(ctx, st, system.control(ctx, st, win))
    assert all(c.ok for c in program), program
    assert not all(c.ok for c in control), control
