"""Collection-regression guard: every `repro.*` module must import.

The seed repo shipped a `from jax import shard_map` that only exists on
newer jax, so `import repro.core` — and with it a third of the test suite —
failed at collection time.  This test walks the whole package so any
version-portability break (or missing optional dep leaking into module
scope) fails loudly as ONE test instead of as silent collection errors.
"""
import importlib
import pkgutil

import pytest

import repro


def _all_modules():
    mods = ["repro"]
    for info in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        mods.append(info.name)
    return sorted(mods)


@pytest.mark.parametrize("name", _all_modules())
def test_module_imports(name):
    importlib.import_module(name)


def test_compat_shim_resolved_a_shard_map():
    # every shard_map caller imports it from jax and passes check_vma=
    import inspect
    from jax import shard_map
    assert callable(shard_map)
    assert "check_vma" in inspect.signature(shard_map).parameters
