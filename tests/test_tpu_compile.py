"""AOT compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed alongside jax even where no chip is
attached: a described ``v5e:2x2`` topology lets it compile (not run) each
kernel at the widths a deployment uses, so what interpret mode cannot see
-- the 128-lane padding of the TPU path, Mosaic's lowering rules, the
kernel's scoped-VMEM footprint -- is checked on every CPU test run.  Each
compiled program must hold a ``tpu_custom_call``, i.e. the Pallas kernel
itself and not a fallback.

The topology is described inside a module-scoped fixture only: describing
it loads the TPU library, which one process at a time may hold, so it
must never happen while a module is imported or collected.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels import fastmix as fm
from repro.kernels.cholqr import cholqr2

#: the paper's w8a split (Section 5): m=50 agents, d=300, k=5, K=8
W8A = (50, 300, 5)
#: batched serving: B=8 problems at m=16, d=1024, k=16, K=6
BATCH = (8, 16, 1024, 16)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _track_w8a(S):
    m, d, k = W8A
    return (lambda s, g, gp, L: fm._fastmix_track_fused(
                s, g, gp, L, 0.3, 8, block_n=512, interpret=False,
                wire_bf16=False),
            [S((m, d, k))] * 3 + [S((m, m))])


def _track_vmap8(S):
    B, m, d, k = BATCH
    return (jax.vmap(lambda s, g, gp, L: fm._fastmix_track_fused(
                s, g, gp, L, 0.3, 6, block_n=512, interpret=False,
                wire_bf16=False)),
            [S((B, m, d, k))] * 3 + [S((B, m, m))])


def _fastmix_bf16(S):
    m, d, k = W8A
    return (lambda s, L: fm._fastmix_fused(s, L, 0.3, 8, block_n=512,
                                           interpret=False, wire_bf16=True),
            [S((m, d, k)), S((m, m))])


def _cholqr2_gram(S):
    return (lambda x: cholqr2(x, use_kernel=True), [S(W8A)])


def _apply_track_w8a(S):
    m, d, k = W8A
    bd, be = fm.apply_track_default_tiles(m, d, k)
    return (lambda A, W, s, gp, L: fm._apply_track_fused(
                A, W, s, gp, L, 0.3, 8, block_d=bd, block_e=be,
                interpret=False, wire_bf16=False),
            [S((m, d, d))] + [S((m, d, k))] * 3 + [S((m, m))])


def _fp8_ef(S):
    m, d, k = W8A
    return (lambda s, e, L: fm._fastmix_ef_fused(s, e, L, 0.3, 8,
                                                 block_n=512,
                                                 interpret=False),
            [S((m, d, k))] * 2 + [S((m, m))])


def _fp8_track_ef(S):
    m, d, k = W8A
    return (lambda s, g, gp, e, L: fm._fastmix_track_ef_fused(
                s, g, gp, e, L, 0.3, 8, block_n=512, interpret=False),
            [S((m, d, k))] * 4 + [S((m, m))])


@pytest.mark.parametrize("build", [
    _track_w8a, _track_vmap8, _fastmix_bf16, _cholqr2_gram,
    _apply_track_w8a, _fp8_ef, _fp8_track_ef,
], ids=lambda f: f.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(build, one_chip, monkeypatch):
    # tiles resolve against the budget of the chip compiled for
    monkeypatch.setattr(autotune, "_DEVICE_KIND", "tpu_v5_lite")
    fn, args = build(lambda shape: _spec(shape, one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [
    (8, 50, 300, 8),      # w8a-m50.batch: 8 solves, k=5 padded to 8
    (1, 50, 300, 8),      # w8a-m50.single
    (64, 16, 512, 8),     # the fleet's 64 tenants
], ids=str)
def test_cholqr2_gram_compiles_unpadded(shape, one_chip, monkeypatch):
    """CholeskyQR2's Gram is the Pallas kernel at the served shapes, and
    no factor is padded to a (512, 128) panel on the way."""
    monkeypatch.setattr(autotune, "_DEVICE_KIND", "tpu_v5_lite")
    x = _spec(shape, one_chip)
    text = jax.jit(lambda x: cholqr2(x, use_kernel=True)).lower(
        x).compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"\[(?:\d+,)*512,128\]", text)


def test_w8a_batch_applies_the_gram_on_v5e(one_chip, monkeypatch):
    """The served w8a batch (8 problems, 1008 padded rows of d=300, k=5
    padded to 8) compiles for the chip with the Gram formed before the
    scan: the loop carries each agent's (300, 300) Gram and not its rows,
    so no dot in the scan body reads a (1008, 300) operand, and tracking
    and gossip stay in the FastMix kernel (not the dense apply kernel)."""
    from repro.core import (ConsensusEngine, IterationDriver, PowerStep,
                            erdos_renyi)
    from repro.core.operators import gram_form_pays
    monkeypatch.setattr(autotune, "_DEVICE_KIND", "tpu_v5_lite")
    # pick the chip's routes (kernels, not the off-TPU fallbacks)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, m, n, d, k, T = 8, 50, 1008, 300, 8, 40
    assert gram_form_pays(n, d, T)
    driver = IterationDriver(
        step=PowerStep.for_algorithm("deepca", 8),
        engine=ConsensusEngine.for_algorithm(
            "deepca", erdos_renyi(m, p=0.5, seed=0), K=8, backend="pallas"))
    fn, _ = driver.program(T, "data", "gram", batched=True,
                           with_history=False, resume=False)
    lowered = fn.lower(_spec((B, m, n, d), one_chip),
                       _spec((B, d, k), one_chip), None)
    names = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    assert "_fastmix_track_kernel" in names
    assert not any("apply_track" in name for name in names)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    loops = [line for line in text.splitlines() if " while(" in line]
    assert loops
    for line in loops:
        assert f"{m},{d},{d}]" in line
        assert f"{n},{d}]" not in line
