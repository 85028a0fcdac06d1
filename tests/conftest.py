"""Test-session setup: a gated fallback for the optional `hypothesis` dep.

The property tests use hypothesis (declared in the ``test`` extra of
pyproject.toml).  On hosts where it is not installed — e.g. hermetic
containers where nothing may be pip-installed — we register a *minimal,
deterministic* stand-in under ``sys.modules['hypothesis']`` before the test
modules import it, so collection never fails on the missing module.

The stub covers exactly the API surface this repo uses (``given``,
``settings``, ``strategies.integers``) and turns each ``@given`` test into
``max_examples`` deterministic cases: the per-strategy lower bounds, the
upper bounds, then seeded-random draws.  When the real hypothesis is
installed it is used untouched.
"""
from __future__ import annotations

import functools
import inspect
import sys
import types

try:
    import hypothesis  # noqa: F401  (real library present: nothing to do)
except ImportError:
    import numpy as np

    class _IntegersStrategy:
        def __init__(self, lo: int, hi: int):
            self.lo, self.hi = int(lo), int(hi)

        def draw(self, rng) -> int:
            return int(rng.integers(self.lo, self.hi + 1))

    def _settings(**kw):
        def deco(f):
            f._stub_settings = kw
            return f
        return deco

    def _given(*strategies):
        def deco(f):
            @functools.wraps(f)
            def wrapper(*args, **kwargs):
                # read settings at call time so @settings works whether it
                # is applied above @given (lands on the wrapper) or below
                # it (lands on the inner fn) — both are legal orders
                conf = getattr(wrapper, "_stub_settings",
                               getattr(f, "_stub_settings", {}))
                n = int(conf.get("max_examples", 20))
                rng = np.random.default_rng(0)
                for i in range(n):
                    if i == 0:
                        vals = [s.lo for s in strategies]
                    elif i == 1:
                        vals = [s.hi for s in strategies]
                    else:
                        vals = [s.draw(rng) for s in strategies]
                    f(*args, *vals, **kwargs)

            # pytest must not mistake the strategy-filled params for
            # fixtures: hide the wrapped signature entirely.
            del wrapper.__wrapped__
            wrapper.__signature__ = inspect.Signature()
            wrapper.is_hypothesis_stub = True
            return wrapper
        return deco

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _IntegersStrategy

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    _hyp.is_stub = True

    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
