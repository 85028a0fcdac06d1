"""Pallas TPU kernels for Gram matrices ``X^T X``.

:func:`gram` forms the local covariance ``A_j = X^T X`` (paper Eqn. 5.1).
This is the covariance-formation hot spot of decentralized PCA when agents
hold raw data.  TPU adaptation: tile the (d, d) output into MXU-aligned
(bd x bd) VMEM blocks and stream (bn x bd) panels of X from HBM, accumulating
in fp32 across the n (reduction) grid axis.

Grid: (d/bd, d/bd, n/bn) — the reduction axis is innermost, so each output
block stays resident in VMEM for the whole reduction (TPU grid revisiting
semantics), and is written back to HBM exactly once.

:func:`gram_tall` forms the ``(k, k)`` Gram of every factor of a stack of
tall-skinny ``(d, k)`` factors, the reduction CholeskyQR2
(:mod:`repro.kernels.cholqr`) runs twice or three times per
orthonormalization.  ``k`` is in the units or tens, so the factor is read
with ``d`` on the 128 lanes and ``k`` on the sublanes (the Gram of ``X^T``
as an NT product): a ``(300, 8)`` factor then takes ``(8, 384)`` words of
VMEM, where ``k`` on the lanes would take ``(304, 128)``.  XLA lays the
factors out ``d``-minor where the kernel asks for it, so the transpose in
front of the call is a bitcast, not a copy.  One call covers the whole
flattened stack: its grid runs over blocks of factors and, only where one
factor outgrows the block budget, over ``d`` panels as an inner reduction
axis with the output block resident.
"""
from __future__ import annotations

import functools
import math
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


# ------------------------------------------------------------ tall-skinny
#: Modeled VMEM bytes (:func:`gram_tall_vmem_words`) of one grid step's
#: blocks.  Past a few hundred KiB the block size hardly moves the kernel's
#: time on a v5e; this leaves the rest of VMEM to the compiler.
_TALL_VMEM_BYTES = 4 * 1024 * 1024


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def gram_tall_vmem_words(d: int, k: int, block_f: int, block_d: int) -> int:
    """Modeled fp32-word VMEM working set of one :func:`gram_tall` grid
    step: the ``(block_f, k, block_d)`` input block and the
    ``(block_f, k, k)`` output block, each padded to the ``(8, 128)`` tile
    and double buffered.  The static budget checker
    (:mod:`repro.analysis.budget`) calls this same function."""
    return 2 * block_f * _round_up(k, 8) * (
        _round_up(min(block_d, d), 128) + _round_up(k, 128))


def gram_tall_tiles(n: int, d: int, k: int):
    """``(block_f, block_d)`` for ``n`` factors of shape ``(d, k)``.

    As many whole factors per grid step as fit :data:`_TALL_VMEM_BYTES`,
    cut down to a divisor of ``n`` where one is at least half that many: a
    ragged last block measured 20-25% slower on a v5e (12-13 us against
    10 us for 400 factors of ``(300, 8)``).  A factor too large to fit
    alone is cut into ``d`` panels, a multiple of 128 lanes, that the
    grid's inner axis reduces over.
    """
    per = gram_tall_vmem_words(d, k, 1, d) * 4
    if per <= _TALL_VMEM_BYTES:
        cap = min(n, _TALL_VMEM_BYTES // per)
        div = max(b for b in range(1, cap + 1) if n % b == 0)
        return (div if 2 * div >= cap else cap), d
    lanes = _TALL_VMEM_BYTES // (8 * _round_up(k, 8)) - _round_up(k, 128)
    return 1, max(128, lanes // 128 * 128)


def _gram_tall_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]            # (block_f, k, block_d): d on the lanes
    o_ref[...] += jax.lax.dot_general(
        x, x, (((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def gram_tall(x: jax.Array, *, interpret: bool = False) -> jax.Array:
    """``x`` ``(..., d, k)`` -> ``x^T x`` per factor, ``(..., k, k)`` fp32.

    One ``pallas_call`` over the ``prod(...)`` factors, tiled by
    :func:`gram_tall_tiles`.  Zero padding of ``d`` (panels only) is exact
    for a Gram; a last block of factors that runs past the stack computes
    Grams nobody reads and writes nothing.
    """
    lead, (d, k) = x.shape[:-2], x.shape[-2:]
    n = math.prod(lead)
    block_f, block_d = gram_tall_tiles(n, d, k)
    out = _gram_tall(x.reshape(n, d, k), block_f=block_f, block_d=block_d,
                     interpret=interpret)
    return out.reshape(*lead, k, k)


def _gram_tall_call(x: jax.Array, *, block_f: int, block_d: int,
                    interpret: bool) -> jax.Array:
    n, d, k = x.shape
    xt = jnp.swapaxes(x, 1, 2)                 # (n, k, d)
    dp = _round_up(d, block_d)
    if dp != d:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, dp - d)))
    return pl.pallas_call(
        _gram_tall_kernel,
        grid=(pl.cdiv(n, block_f), dp // block_d),
        in_specs=[pl.BlockSpec((block_f, k, block_d),
                               lambda f, s: (f, 0, s))],
        out_specs=pl.BlockSpec((block_f, k, k), lambda f, s: (f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k, k), jnp.float32),
        interpret=interpret,
    )(xt)


# XLA names the kernel's op after the jitted function (``jit__gram_tall.N``);
# the benchmark's ``gram_roofline.solve`` sums the device time of the ops
# whose name holds ``__gram_``.
_gram_tall_call.__name__ = "jit__gram_tall"
_gram_tall = jax.jit(_gram_tall_call,
                     static_argnames=("block_f", "block_d", "interpret"))
