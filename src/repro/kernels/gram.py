"""Pallas TPU kernel: local Gram matrix ``A_j = X^T X`` (paper Eqn. 5.1).

This is the covariance-formation hot spot of decentralized PCA when agents
hold raw data.  TPU adaptation: tile the (d, d) output into MXU-aligned
(bd x bd) VMEM blocks and stream (bn x bd) panels of X from HBM, accumulating
in fp32 across the n (reduction) grid axis.

Grid: (d/bd, d/bd, n/bn) — the reduction axis is innermost, so each output
block stays resident in VMEM for the whole reduction (TPU grid revisiting
semantics), and is written back to HBM exactly once.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune


def _gram_kernel(xi_ref, xj_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xi = xi_ref[...]          # (bn, bd_i) panel of X
    xj = xj_ref[...]          # (bn, bd_j) panel of X
    o_ref[...] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def gram(x: jax.Array, *, block_d: Optional[int] = None,
         block_n: Optional[int] = None,
         interpret: bool = False) -> jax.Array:
    """``x`` (n, d) -> ``x.T @ x`` (d, d) in fp32.

    ``block_* = None`` resolves through the persistent autotune cache
    (kernel name ``gram``, keyed on device kind / padded shape bucket /
    dtype — see :mod:`repro.kernels.autotune`) before falling back to the
    built-in (128, 512) tiling.

    Shapes are padded up to block multiples; zero padding is exact for a Gram
    matrix (zero rows contribute nothing).  VMEM working set per step is
    ``2*block_n*block_d + block_d^2`` fp32 words (default: 2*512*128*4 +
    128^2*4 = 0.6 MiB, far under the ~16 MiB v5e VMEM budget, leaving room
    for double buffering of the streamed panels).
    """
    if block_d is None:
        block_d = autotune.resolve("gram", "block_d", x.shape, x.dtype,
                                   default=128)
    if block_n is None:
        block_n = autotune.resolve("gram", "block_n", x.shape, x.dtype,
                                   default=512)
    return _gram(x, block_d=int(block_d), block_n=int(block_n),
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_d", "block_n", "interpret"))
def _gram(x: jax.Array, *, block_d: int, block_n: int,
          interpret: bool) -> jax.Array:
    n, d = x.shape
    dp = -(-d // block_d) * block_d
    np_ = -(-n // block_n) * block_n
    if (dp, np_) != (d, n):
        x = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    out = pl.pallas_call(
        _gram_kernel,
        grid=(dp // block_d, dp // block_d, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, s: (s, i)),
            pl.BlockSpec((block_n, block_d), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_d, block_d), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dp, dp), jnp.float32),
        interpret=interpret,
    )(x, x)
    return out[:d, :d]
