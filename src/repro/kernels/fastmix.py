"""Fused Pallas TPU kernel for FastMix (Alg. 3): K Chebyshev rounds, 1 launch.

FastMix is the communication hot loop of every DeEPCA power iteration::

    S^{k+1} = (1 + eta) * L S^k - eta * S^{k-1}

The per-round stacked implementation (:func:`repro.core.mixing.fastmix`)
materialises each ``S^k`` in HBM — K launches, 2K HBM round-trips of the
``(m, d*k)`` iterate.  Because every *column* of the stacked iterate evolves
independently under the recursion (the mixing acts only on the agent axis),
the whole K-round loop can be fused: this kernel tiles the column axis,
keeps the ``(m, m)`` mixing matrix and **both iterate buffers resident in
VMEM across all K rounds**, and writes each output tile exactly once.
Arithmetic is fp32 on the MXU regardless of input dtype.

Two fused execution paths are exposed (the ConsensusEngine picks one):

* :func:`fastmix_fused` — the Pallas kernel (TPU, or ``interpret=True``
  anywhere for testing).
* :func:`fastmix_poly` — algebraic fusion for hosts without a TPU: the
  recursion is linear in ``S``, so ``S_out = P_K(L) S`` where ``P_K`` is the
  degree-K Chebyshev-like polynomial of the ``(m, m)`` mixing matrix.
  ``P_K(L)`` is built with K tiny ``(m, m)`` matmuls, then applied with ONE
  pass over the iterate — the same single-HBM-trip structure as the kernel.

Both have *tracked* twins (:func:`fastmix_track_fused` /
:func:`fastmix_track_poly`) that additionally fold the DeEPCA
subspace-tracking combine (Eqn. 3.1, :func:`tracking_update`) into the same
launch, so a full power-iteration gossip costs one HBM read of
``(S, G, G_prev)`` and one write — no materialised tracked intermediate.

Both agree with the per-round reference to fp32 round-off (property-tested
in tests/test_consensus.py) and both preserve the agent mean exactly in
exact arithmetic (``L`` is doubly stochastic, and the recursion's
coefficients sum to one).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import config as runtime_config

from . import autotune

#: Env var overriding the fused kernels' column-tile width (``block_n``).
#: Owned/validated by :mod:`repro.runtime.config`.
BLOCK_N_ENV = runtime_config.ENV_FASTMIX_BLOCK_N

#: Built-in column-tile width when neither the env override nor an
#: autotune-cache entry decides.  512 fp32 lanes x a 128-padded agent axis
#: keeps both iterate buffers + L comfortably in VMEM for every shipped
#: sweep config; the right value per TPU generation comes from the
#: ``bench_mixing.py --block-n --record`` sweep through the autotune cache.
DEFAULT_BLOCK_N = 512


def default_block_n(shape=None, dtype=jnp.float32) -> int:
    """The fused kernels' column-tile width for ``shape``.

    Resolution precedence (PR-5 autotuner contract, shared by every
    kernel): the ``RuntimeConfig.fastmix_block_n`` override
    (``REPRO_FASTMIX_BLOCK_N``, validated by :mod:`repro.runtime.config`),
    then the persistent autotune-cache entry for
    ``(fastmix, device kind, shape bucket, dtype)`` when ``shape`` (the
    kernel-facing ``(m, columns)``) is given, then
    :data:`DEFAULT_BLOCK_N`.  The kernels consult this through their
    ``block_n=None`` defaults at trace time, so a tuned machine runs tuned
    tiles with no code or env change; programs traced before a cache/env
    change keep their resolved value.
    """
    return autotune.resolve("fastmix", "block_n",
                            shape if shape is not None else (),
                            dtype,
                            override=runtime_config.get_config()
                            .fastmix_block_n,
                            default=DEFAULT_BLOCK_N)


#: Wire payload bytes per element for each wire mode (``None`` = full
#: fp32).  The int8 payload is 1 byte/element plus one fp32 scale per agent
#: per round — accounted separately in the engines' ``bytes_per_round``.
WIRE_ITEMSIZE = {None: 4, "bf16": 2, "int8": 1, "fp8": 1}

#: Wire modes coarse enough to *require* the error-feedback wire state (the
#: ``PowerStep`` ``ef`` carry slot holding each agent's replica): their
#: plain round-trip error is O(1e-2)-scale and would floor tan-theta
#: without the difference-quantized EF send (:func:`ef_quantize`).
EF_WIRE_DTYPES = ("int8", "fp8")


def quantize_wire(x: jax.Array, wire_dtype=jnp.bfloat16) -> jax.Array:
    """Round-trip through the wire dtype: THE wire-precision compute site.

    Emulates reduced-precision gossip: the value an agent *sends* each
    round is rounded to the wire dtype, while every receiver keeps
    accumulating in the full compute dtype.  Both the per-round stacked
    references (:func:`repro.core.mixing.fastmix_wire` /
    ``fastmix_wire_ef``) and the fused kernels' wire paths quantize
    through this exact rounding, so they agree to fp32 round-off.

    Modes (``wire_dtype`` may be a dtype or one of the engine's mode
    strings):

    * ``bf16`` / ``jnp.bfloat16`` — plain truncation round-trip (2 B/elem);
    * ``"fp8"`` — ``float8_e4m3fn`` round-trip (1 B/elem, +-448 range,
      ~2^-4 relative rounding; scale-free, so it mirrors elementwise
      inside the Pallas kernels);
    * ``"int8"`` — symmetric linear quantization with a *per-agent*
      dynamic scale ``absmax / 127`` over the trailing axes (1 B/elem +
      one fp32 scale per agent).  The scale floor at the dtype's smallest
      normal keeps zero and subnormal inputs exact/NaN-free.

    int8/fp8 are coarse enough that plain round-tripping floors accuracy;
    the engines pair them with the difference-quantized EF send
    (:func:`ef_quantize`), which quantizes the *innovation* against a
    carried replica so the injected noise vanishes with convergence.
    """
    if wire_dtype == "int8" or wire_dtype is jnp.int8:
        axes = tuple(range(1, x.ndim)) if x.ndim > 1 else (0,)
        absmax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
        scale = jnp.maximum(absmax / 127.0, jnp.finfo(x.dtype).tiny)
        q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
        return q.astype(x.dtype) * scale
    if wire_dtype == "fp8":
        # e4m3fn has no inf: an out-of-range cast yields NaN, so the wire
        # saturates at the format max (+-448) instead — matching hardware
        # fp8 semantics and keeping divergent iterates finite.
        lim = float(jnp.finfo(jnp.float8_e4m3fn).max)
        x = jnp.clip(x, -lim, lim)
        wire_dtype = jnp.float8_e4m3fn
    elif wire_dtype == "bf16":
        wire_dtype = jnp.bfloat16
    return x.astype(wire_dtype).astype(x.dtype)


def _cbrt(x: jax.Array) -> jax.Array:
    """Sign-preserving real cube root from ``exp``/``log``.

    Mosaic has no lowering for ``cbrt``; ``exp``, ``log``, ``abs`` and
    ``where`` lower in the kernels and in XLA alike, so the fp8 EF send
    and its in-kernel mirror compand through this one definition.
    ``log(0) = -inf`` maps to ``exp(-inf) = 0``, so zero stays exact.
    """
    r = jnp.exp(jnp.log(jnp.abs(x)) * (1.0 / 3.0))
    return jnp.where(x < 0, -r, r)


def ef_quantize(x: jax.Array, h: jax.Array, wire_dtype) -> jax.Array:
    """Difference-quantized EF send: THE quantized-gossip EF site.

    CHOCO-style replica tracking (Koloskova et al.): each agent keeps —
    and every receiver reconstructs, so nothing extra travels — a wire
    replica ``h`` of its iterate.  One send transmits the quantized
    *innovation* and both sides advance the replica:

        h_new = h + quantize_wire(x - h)

    This IS error feedback with the residual carried implicitly: the
    quantization leftover ``x - h_new`` is exactly what the next send's
    innovation re-injects (``x' - h_new = (x' - x) + (x - h_new)``), so
    one carry slot (the ``PowerStep`` ``ef`` slot, zeros on the first
    call / after a restart) covers both the replica and the residual.
    Because the int8/fp8 quantizers are *relative* (dynamic per-agent
    scale / elementwise exponent), the injected noise is proportional to
    the innovation — which vanishes at the algorithm's linear rate — so
    the quantized wire converges exactly instead of flooring at the wire
    precision the way a plain round-tripped send does.

    The ``"fp8"`` innovation rides the wire *cube-root companded* —
    ``fp8(cbrt(delta))`` on the wire, cubed back by the receiver.
    ``e4m3fn``'s native window (smallest subnormal ``2^-9`` to max 448) is
    far too narrow for a signal that starts O(1) and shrinks to the f64
    envelope: un-companded, the innovation underflows to zero once it drops
    below ~2e-3 and tan-theta floors near 1e-4 (and any fixed pre-gain
    that rebrases the window low enough saturates the early rounds into
    divergence on some grids).  Cube-rooting expands the representable
    dynamic range cubically — underflow at ``2^-27`` (~7.5e-9), overflow
    not until ``448^3`` (~9e7) — at a worst-case relative step of
    ``3 * 2^-4 ≈ 19%``, which EF absorbs like any relative quantizer: the
    noise stays proportional to the vanishing innovation.  The transform
    is static, elementwise and sign-preserving, so it costs zero wire
    bytes and mirrors exactly inside the fused kernels.  int8's dynamic
    per-agent scale needs no companding.

    Returns ``h_new``: the value receivers mix *and* the carried state.
    """
    if wire_dtype == "fp8":
        fq = quantize_wire(_cbrt(x - h), wire_dtype)
        return h + fq * fq * fq
    return h + quantize_wire(x - h, wire_dtype)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def tracking_update(S: jax.Array, G: jax.Array, G_prev: jax.Array) -> jax.Array:
    """Eqn. (3.1), the subspace-tracking update — THE single compute site.

    Every substrate (stacked scan, traced-operand scan, unrolled loop,
    shard_map local slices, the fused-kernel fallbacks, the PowerSGD
    gradient tracker) routes its tracking arithmetic through this function;
    the only other place the same arithmetic exists is inside the fused
    Pallas kernel body below, where it runs on VMEM-resident tiles.
    """
    return S + G - G_prev


def _rounds(L, prev, cur, eta, K: int, wire_bf16: bool):
    """The K unrolled Chebyshev rounds shared by every fused kernel body.

    With ``wire_bf16`` the value each agent *sends* is rounded to bf16
    (mirroring :func:`quantize_wire`) while ``prev``/``cur`` — the local
    recursion state — stay fp32, i.e. reduced wire precision with
    full-precision accumulation.
    """
    for _ in range(K):      # K is small and static: unrolled, no HBM traffic
        sent = (cur.astype(jnp.bfloat16).astype(jnp.float32)
                if wire_bf16 else cur)
        mixed = jax.lax.dot_general(
            L, sent, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        prev, cur = cur, (1.0 + eta) * mixed - eta * prev
    return cur


def _rounds_ef(L, prev, cur, h, eta, K: int):
    """K unrolled Chebyshev rounds over the fp8 difference-quantized wire.

    The in-kernel mirror of :func:`ef_quantize` for ``wire="fp8"``: the
    replica update is purely elementwise, so it tiles exactly like the
    bf16 mirror in :func:`_rounds` — no cross-tile state.  (int8 has *no*
    in-kernel mirror: its per-agent scale is a full-row reduction the
    column-tiled kernels cannot see; the engines run the per-round
    stacked reference for int8 instead.)  The receiver combine is the
    mean-preserving CHOCO form ``cur + (L - I) h``: the correction term
    has zero agent-mean under the doubly-stochastic ``L``, so wire
    quantization cannot bias the tracked mean (Lemma 2's invariant).
    ``prev``/``cur``/``h`` stay fp32; only the innovation is quantized,
    riding the wire cube-root companded exactly as in :func:`ef_quantize`.
    """
    lim = float(jnp.finfo(jnp.float8_e4m3fn).max)
    for _ in range(K):
        # companded send: fp8(cbrt(delta)), cubed back on receipt.  The
        # clip only guards the e4m3fn no-inf cast (it binds at 448^3).
        f = jnp.clip(_cbrt(cur - h), -lim, lim)
        fq = f.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        h = h + fq * fq * fq
        mixed = cur + jax.lax.dot_general(
            L, h, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) - h
        prev, cur = cur, (1.0 + eta) * mixed - eta * prev
    return cur, h


def _fastmix_kernel(eta_ref, l_ref, x_ref, o_ref, *, K: int,
                    wire_bf16: bool):
    """One column tile: run all K rounds with prev/cur resident in VMEM."""
    eta = eta_ref[0, 0]
    prev = x_ref[...].astype(jnp.float32)
    o_ref[...] = _rounds(l_ref[...], prev, prev, eta, K, wire_bf16)


def _fastmix_ef_kernel(eta_ref, l_ref, x_ref, e_ref, o_ref, eo_ref, *,
                       K: int):
    """One column tile of fp8-EF gossip: iterate + wire replica in, out."""
    eta = eta_ref[0, 0]
    prev = x_ref[...].astype(jnp.float32)
    h = e_ref[...].astype(jnp.float32)
    o_ref[...], eo_ref[...] = _rounds_ef(l_ref[...], prev, prev, h,
                                         eta, K)


def _block_n_for(S, block_n: Optional[int]) -> int:
    """Resolve a kernel call's column-tile width (explicit > env > cache >
    default); the cache key is the kernel-facing ``(m, columns)`` shape."""
    if block_n is not None:
        return int(block_n)
    n = 1
    for s in S.shape[1:]:
        n *= s
    return default_block_n((S.shape[0], n), S.dtype)


def fastmix_fused(S: jax.Array, L: jax.Array, eta, K: int, *,
                  block_n: Optional[int] = None, interpret: bool = False,
                  wire_bf16: bool = False) -> jax.Array:
    """All K FastMix rounds in one Pallas launch.

    Args:
      S: ``(m, ...)`` stacked agent variables (trailing dims are flattened
         into one column axis internally).
      L: ``(m, m)`` symmetric doubly-stochastic mixing matrix.  Both ``L``
         and ``eta`` are *traced* operands (``eta`` rides in SMEM), so the
         jit/kernel cache is keyed on shape only — time-varying topologies
         swap mixing matrices without retracing or recompiling.
      eta: FastMix momentum (``eta=0.0`` degenerates to fused naive gossip
         ``L^K S``).
      K: number of gossip rounds (static, unrolled inside the kernel).
      block_n: column-tile width; ``None`` resolves through
        :func:`default_block_n` (env override > autotune cache > default).
      wire_bf16: round each round's *sent* iterate to bf16 (wire-precision
        mode); accumulation stays fp32.
    Returns:
      ``(m, ...)`` mixed variables in fp32, same logical shape as ``S``.
    """
    return _fastmix_fused(S, L, eta, K, block_n=_block_n_for(S, block_n),
                          interpret=interpret, wire_bf16=wire_bf16)


@functools.partial(jax.jit, static_argnames=("K", "block_n", "interpret",
                                             "wire_bf16"))
def _fastmix_fused(S: jax.Array, L: jax.Array, eta, K: int, *,
                   block_n: int, interpret: bool,
                   wire_bf16: bool) -> jax.Array:
    if K <= 0:
        return S.astype(jnp.float32)
    m = S.shape[0]
    if L.shape != (m, m):
        raise ValueError(f"L must be ({m}, {m}) for S {S.shape}; "
                         f"got {L.shape}")
    n = 1
    for s in S.shape[1:]:
        n *= s
    x = S.reshape(m, n).astype(jnp.float32)

    # Pad the agent axis once for MXU/VPU tiling (zeros are exact: padded
    # rows/cols of L are zero, so the padded region stays identically zero
    # through every round) and the column axis to the tile width.
    mp = _round_up(m, 8 if interpret else 128)
    bn = _round_up(min(block_n, n), 128)    # lane dim must stay 128-aligned
    npad = _round_up(n, bn)
    l_p = jnp.pad(L.astype(jnp.float32), ((0, mp - m), (0, mp - m)))
    x_p = jnp.pad(x, ((0, mp - m), (0, npad - n)))
    eta_p = jnp.asarray(eta, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_fastmix_kernel, K=int(K), wire_bf16=wire_bf16),
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0),
                         memory_space=pltpu.SMEM),      # eta: traced scalar
            pl.BlockSpec((mp, mp), lambda j: (0, 0)),   # L: resident
            pl.BlockSpec((mp, bn), lambda j: (0, j)),   # S tile: read once
        ],
        out_specs=pl.BlockSpec((mp, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((mp, npad), jnp.float32),
        interpret=interpret,
    )(eta_p, l_p, x_p)
    return out[:m, :n].reshape(S.shape)


def fastmix_ef_fused(S: jax.Array, err: jax.Array, L: jax.Array, eta,
                     K: int, *, wire: str = "fp8",
                     block_n: Optional[int] = None,
                     interpret: bool = False):
    """All K EF-quantized FastMix rounds in one Pallas launch.

    The fp8 twin of :func:`fastmix_fused`: each round sends the
    ``float8_e4m3fn``-quantized innovation against the per-agent wire
    replica ``err`` (the in-kernel :func:`ef_quantize` mirror — purely
    elementwise and therefore tile-local), carried alongside the
    iterate.  Only ``wire="fp8"`` has an in-kernel mirror — int8's
    per-agent scale is a cross-tile reduction, so the engines route int8
    through the per-round stacked reference
    (:func:`repro.core.mixing.fastmix_wire_ef`) instead.

    Returns ``(S_out, err_out)``, both fp32, same logical shapes as in.
    """
    if wire != "fp8":
        raise ValueError(
            f"fastmix_ef_fused supports wire='fp8' only (got {wire!r}); "
            "int8's per-agent scale needs a full-row reduction — use the "
            "per-round reference repro.core.mixing.fastmix_wire_ef")
    if S.shape != err.shape:
        raise ValueError(f"S/err shapes must match; got {S.shape}, "
                         f"{err.shape}")
    return _fastmix_ef_fused(S, err, L, eta, K,
                             block_n=_block_n_for(S, block_n),
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("K", "block_n", "interpret"))
def _fastmix_ef_fused(S: jax.Array, err: jax.Array, L: jax.Array, eta,
                      K: int, *, block_n: int, interpret: bool):
    if K <= 0:
        return S.astype(jnp.float32), err.astype(jnp.float32)
    m = S.shape[0]
    if L.shape != (m, m):
        raise ValueError(f"L must be ({m}, {m}) for S {S.shape}; "
                         f"got {L.shape}")
    n = 1
    for s in S.shape[1:]:
        n *= s
    mp = _round_up(m, 8 if interpret else 128)
    bn = _round_up(min(block_n, n), 128)
    npad = _round_up(n, bn)

    def _pad(x):
        return jnp.pad(x.reshape(m, n).astype(jnp.float32),
                       ((0, mp - m), (0, npad - n)))

    l_p = jnp.pad(L.astype(jnp.float32), ((0, mp - m), (0, mp - m)))
    eta_p = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    tile = pl.BlockSpec((mp, bn), lambda j: (0, j))

    out, err_out = pl.pallas_call(
        functools.partial(_fastmix_ef_kernel, K=int(K)),
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0),
                         memory_space=pltpu.SMEM),      # eta: traced scalar
            pl.BlockSpec((mp, mp), lambda j: (0, 0)),   # L: resident
            tile, tile,                                 # S, err tiles
        ],
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct((mp, npad), jnp.float32),
                   jax.ShapeDtypeStruct((mp, npad), jnp.float32)),
        interpret=interpret,
    )(eta_p, l_p, _pad(S), _pad(err))
    return (out[:m, :n].reshape(S.shape),
            err_out[:m, :n].reshape(S.shape))


def _fastmix_track_kernel(eta_ref, l_ref, s_ref, g_ref, gp_ref, o_ref, *,
                          K: int, wire_bf16: bool):
    """One column tile of the fused tracking+gossip step.

    The subspace-tracking combine (Eqn. 3.1) happens on the VMEM-resident
    tiles right after load, so the tracked iterate is never materialised in
    HBM — one fewer full pass over the ``(m, d*k)`` iterate per power
    iteration than tracking-then-:func:`fastmix_fused`.
    """
    eta = eta_ref[0, 0]
    s = s_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    gp = gp_ref[...].astype(jnp.float32)
    prev = s + g - gp            # in-register Eqn. (3.1); mirrors tracking_update
    o_ref[...] = _rounds(l_ref[...], prev, prev, eta, K, wire_bf16)


def fastmix_track_fused(S: jax.Array, G: jax.Array, G_prev: jax.Array,
                        L: jax.Array, eta, K: int, *,
                        block_n: Optional[int] = None,
                        interpret: bool = False,
                        wire_bf16: bool = False) -> jax.Array:
    """Fused subspace tracking + all K FastMix rounds in one Pallas launch.

    Semantically ``fastmix_fused(tracking_update(S, G, G_prev), L, eta, K)``,
    but the tracked iterate is formed tile-by-tile in VMEM instead of making
    a round-trip through HBM first (the roadmap's "extend the fusion into
    the tracking update" item).  Same padding/dtype/``block_n``-resolution
    contract as :func:`fastmix_fused`: fp32 MXU arithmetic, fp32 output.
    """
    return _fastmix_track_fused(S, G, G_prev, L, eta, K,
                                block_n=_block_n_for(S, block_n),
                                interpret=interpret, wire_bf16=wire_bf16)


@functools.partial(jax.jit, static_argnames=("K", "block_n", "interpret",
                                             "wire_bf16"))
def _fastmix_track_fused(S: jax.Array, G: jax.Array, G_prev: jax.Array,
                         L: jax.Array, eta, K: int, *, block_n: int,
                         interpret: bool, wire_bf16: bool) -> jax.Array:
    m = S.shape[0]
    if not (S.shape == G.shape == G_prev.shape):
        raise ValueError("S/G/G_prev shapes must match; got "
                         f"{S.shape}, {G.shape}, {G_prev.shape}")
    if L.shape != (m, m):
        raise ValueError(f"L must be ({m}, {m}) for S {S.shape}; "
                         f"got {L.shape}")
    if K <= 0:
        return tracking_update(S, G, G_prev).astype(jnp.float32)
    n = 1
    for s_ in S.shape[1:]:
        n *= s_

    mp = _round_up(m, 8 if interpret else 128)
    bn = _round_up(min(block_n, n), 128)
    npad = _round_up(n, bn)

    def _pad(x):
        return jnp.pad(x.reshape(m, n).astype(jnp.float32),
                       ((0, mp - m), (0, npad - n)))

    l_p = jnp.pad(L.astype(jnp.float32), ((0, mp - m), (0, mp - m)))
    eta_p = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    tile = pl.BlockSpec((mp, bn), lambda j: (0, j))

    out = pl.pallas_call(
        functools.partial(_fastmix_track_kernel, K=int(K),
                          wire_bf16=wire_bf16),
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0),
                         memory_space=pltpu.SMEM),      # eta: traced scalar
            pl.BlockSpec((mp, mp), lambda j: (0, 0)),   # L: resident
            tile, tile, tile,                           # S, G, G_prev tiles
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((mp, npad), jnp.float32),
        interpret=interpret,
    )(eta_p, l_p, _pad(S), _pad(G), _pad(G_prev))
    return out[:m, :n].reshape(S.shape)


def _fastmix_track_ef_kernel(eta_ref, l_ref, s_ref, g_ref, gp_ref, e_ref,
                             o_ref, eo_ref, *, K: int):
    """One column tile of fused tracking + fp8-EF gossip."""
    eta = eta_ref[0, 0]
    s = s_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    gp = gp_ref[...].astype(jnp.float32)
    h = e_ref[...].astype(jnp.float32)
    prev = s + g - gp            # in-register Eqn. (3.1); mirrors tracking_update
    o_ref[...], eo_ref[...] = _rounds_ef(l_ref[...], prev, prev, h,
                                         eta, K)


def fastmix_track_ef_fused(S: jax.Array, G: jax.Array, G_prev: jax.Array,
                           err: jax.Array, L: jax.Array, eta, K: int, *,
                           wire: str = "fp8",
                           block_n: Optional[int] = None,
                           interpret: bool = False):
    """Fused subspace tracking + K fp8-EF-quantized FastMix rounds.

    Semantically ``fastmix_wire_ef(tracking_update(S, G, G_prev), err, L,
    eta, K, "fp8")`` in one launch, with the tracked iterate and the EF
    wire replica both formed/updated tile-by-tile in VMEM.  Same fp8-only
    contract as :func:`fastmix_ef_fused`.  Returns ``(S_new, err_out)``.
    """
    if wire != "fp8":
        raise ValueError(
            f"fastmix_track_ef_fused supports wire='fp8' only (got "
            f"{wire!r}); int8 routes through the per-round reference")
    if not (S.shape == G.shape == G_prev.shape == err.shape):
        raise ValueError("S/G/G_prev/err shapes must match; got "
                         f"{S.shape}, {G.shape}, {G_prev.shape}, "
                         f"{err.shape}")
    return _fastmix_track_ef_fused(S, G, G_prev, err, L, eta, K,
                                   block_n=_block_n_for(S, block_n),
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("K", "block_n", "interpret"))
def _fastmix_track_ef_fused(S: jax.Array, G: jax.Array, G_prev: jax.Array,
                            err: jax.Array, L: jax.Array, eta, K: int, *,
                            block_n: int, interpret: bool):
    m = S.shape[0]
    if L.shape != (m, m):
        raise ValueError(f"L must be ({m}, {m}) for S {S.shape}; "
                         f"got {L.shape}")
    if K <= 0:
        return (tracking_update(S, G, G_prev).astype(jnp.float32),
                err.astype(jnp.float32))
    n = 1
    for s_ in S.shape[1:]:
        n *= s_
    mp = _round_up(m, 8 if interpret else 128)
    bn = _round_up(min(block_n, n), 128)
    npad = _round_up(n, bn)

    def _pad(x):
        return jnp.pad(x.reshape(m, n).astype(jnp.float32),
                       ((0, mp - m), (0, npad - n)))

    l_p = jnp.pad(L.astype(jnp.float32), ((0, mp - m), (0, mp - m)))
    eta_p = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    tile = pl.BlockSpec((mp, bn), lambda j: (0, j))

    out, err_out = pl.pallas_call(
        functools.partial(_fastmix_track_ef_kernel, K=int(K)),
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0),
                         memory_space=pltpu.SMEM),      # eta: traced scalar
            pl.BlockSpec((mp, mp), lambda j: (0, 0)),   # L: resident
            tile, tile, tile, tile,             # S, G, G_prev, err tiles
        ],
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct((mp, npad), jnp.float32),
                   jax.ShapeDtypeStruct((mp, npad), jnp.float32)),
        interpret=interpret,
    )(eta_p, l_p, _pad(S), _pad(G), _pad(G_prev), _pad(err))
    return (out[:m, :n].reshape(S.shape),
            err_out[:m, :n].reshape(S.shape))


@functools.partial(jax.jit, static_argnames=("K",))
def fastmix_track_poly(S: jax.Array, G: jax.Array, G_prev: jax.Array,
                       L: jax.Array, eta, K: int) -> jax.Array:
    """Off-TPU fused tracking+gossip: bit-identical to tracking-then-poly.

    The tracked iterate is built by :func:`tracking_update` (the shared
    compute site) and immediately consumed by :func:`fastmix_poly`'s single
    ``P_K(L)`` application — XLA fuses the element-wise combine into the
    one pass over the iterate, so this path also avoids the extra HBM trip
    while staying bit-for-bit equal to the unfused stacked reference
    composition ``fastmix_poly(tracking_update(...))``.
    """
    return fastmix_poly(tracking_update(S, G, G_prev), L, eta, K)


@functools.partial(jax.jit, static_argnames=("K",))
def fastmix_poly(S: jax.Array, L: jax.Array, eta: jax.Array | float,
                 K: int) -> jax.Array:
    """Algebraically fused FastMix: build ``P_K(L)`` then apply it once.

    The FastMix recursion is linear in the iterate, so K rounds collapse to
    a single mixing with the matrix polynomial ``P_K`` defined by
    ``P_{-1} = P_0 = I`` and ``P_{k+1} = (1+eta) L P_k - eta P_{k-1}``.
    Building ``P_K`` costs K ``(m, m) @ (m, m)`` matmuls (m is the agent
    count — tiny), after which the ``(m, d*k)`` iterate makes exactly one
    trip through memory instead of K.  This is the engine's fused fallback
    on hosts where the Pallas kernel cannot compile.
    """
    if K <= 0:
        return S
    I = jnp.eye(L.shape[0], dtype=L.dtype)

    def body(carry, _):
        prev, cur = carry
        nxt = (1.0 + eta) * (L @ cur) - eta * prev
        return (cur, nxt), None

    (_, P), _ = jax.lax.scan(body, (I, I), None, length=K)
    return jnp.einsum("ij,j...->i...", P, S,
                      precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------------
# apply -> track -> mix fusion: the whole DeEPCA gossip half-iteration in
# one launch (PR 5 tentpole b).
# --------------------------------------------------------------------------
def _apply_track_kernel(eta_ref, l_ref, a_ref, w_ref, s_ref, gp_ref,
                        snew_ref, g_ref, *, K: int, n_s: int,
                        wire_bf16: bool):
    """One (d-row block, contraction block) grid step.

    The contraction axis is innermost: the ``G`` output block stays
    resident in VMEM while ``G_j = A_j W_j`` accumulates across it (TPU
    grid revisiting semantics, exactly like the `gram` kernel); on the last
    contraction step the Eqn. (3.1) combine and all K Chebyshev rounds run
    on the still-resident tiles and write the mixed block once.  ``G``
    itself is written once as a second output (the next iteration's
    ``G_prev``) — it never makes the HBM round-trip between the local apply
    and the gossip that the unfused composition pays.
    """
    sidx = pl.program_id(1)

    @pl.when(sidx == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    # per-agent local power step: (mp, bd, be) x (mp, be, kp) batched over
    # the agent axis -> accumulate (mp, bd, kp)
    g_ref[...] += jax.lax.dot_general(
        a_ref[...], w_ref[...], (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(sidx == n_s - 1)
    def _finish():
        eta = eta_ref[0, 0]
        s = s_ref[...].astype(jnp.float32)
        gp = gp_ref[...].astype(jnp.float32)
        prev = s + g_ref[...] - gp   # Eqn. (3.1); mirrors tracking_update
        cur = prev
        for _ in range(K):
            sent = (cur.astype(jnp.bfloat16).astype(jnp.float32)
                    if wire_bf16 else cur)
            # gossip contraction over the leading agent axis of the 3-D
            # tile: (mp, mp) x (mp, bd, kp) -> (mp, bd, kp)
            mixed = jax.lax.dot_general(
                l_ref[...], sent, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            prev, cur = cur, (1.0 + eta) * mixed - eta * prev
        snew_ref[...] = cur


def apply_track_vmem_words(m: int, d: int, k: int, block_d: int,
                           block_e: int, *, interpret: bool = False) -> int:
    """Modeled fp32-word VMEM working set of one ``apply_track`` grid step.

    Every block is double buffered: ``L``, the streamed A tile and W
    panel, the S and G_prev input blocks and the S_new and G output
    blocks; three more ``(mp, bd, kp)`` copies (prev, cur, mixed) live
    through the K rounds.  At (50, 300, 5) with (32, 128) tiles this gives
    18.4 MiB against the 18.12 MiB the TPU compiler reports for the
    kernel.  The static budget checker (:mod:`repro.analysis.budget`)
    calls this same function, so the kernel's default resolution and
    CI's over-budget gate agree by construction.
    """
    mp = _round_up(m, 8)
    kp = _round_up(k, 8 if interpret else 128)
    bd = _round_up(min(block_d, d), 8)
    be = _round_up(min(block_e, d), 8 if interpret else 128)
    return 2 * mp * mp + mp * (2 * bd * be + 2 * be * kp + 11 * bd * kp)


def apply_track_default_tiles(m: int, d: int, k: int, *,
                              interpret: bool = False):
    """Shape-aware built-in ``(block_d, block_e)`` for ``apply_track``.

    Starts from the bench-tuned (64, 256) and halves the tiles —
    contraction width first, it is the bigger buffer — until the modeled
    working set fits the VMEM budget of the device kind in use (an
    unlisted kind raises).  The agent axis rides the tiles as a batch
    dim, so large-m problems need smaller tiles: the paper's w8a shape
    (50, 300, 5) resolves (16, 128), ~12.7 MiB.  An autotune-cache entry
    still overrides (and the budget pass checks every recorded entry).
    """
    from repro.analysis.registry import vmem_budget
    budget_words = vmem_budget(autotune.device_kind()) // 4
    bd, be = 64, 256
    floor_e = 8 if interpret else 128
    while (apply_track_vmem_words(m, d, k, bd, be, interpret=interpret)
           > budget_words and (bd > 8 or be > floor_e)):
        if be > floor_e:
            be //= 2
        else:
            bd //= 2
    return bd, be


def apply_track_fused(A: jax.Array, W: jax.Array, S: jax.Array,
                      G_prev: jax.Array, L: jax.Array, eta, K: int, *,
                      block_d: Optional[int] = None,
                      block_e: Optional[int] = None,
                      interpret: bool = False,
                      wire_bf16: bool = False):
    """Fused local apply + subspace tracking + K FastMix rounds, one launch.

    Semantically::

        G = einsum('mde,mek->mdk', A, W)        # local power step
        S_new = fastmix_track_fused(S, G, G_prev, L, eta, K)
        return S_new, G

    but ``G`` is produced tile-by-tile in VMEM and consumed by the combine
    + rounds in place — it is written to HBM exactly once (as the next
    iteration's ``G_prev``) instead of written-then-reread between two
    launches.  Dense ``(m, d, d)`` operators only; the engine composes the
    unfused (bit-equal) path for Gram-form data operators and off-TPU
    hosts (:meth:`repro.core.consensus.ConsensusEngine.apply_mix_track`).

    Tile sizes: ``block_d`` (output rows) and ``block_e`` (contraction)
    resolve through the autotune cache (kernel name ``apply_track``), else
    :func:`apply_track_default_tiles`.  The agent axis is padded to 8, not
    128: the 3-D tiles carry it as a batch dim, so the VMEM per step
    (:func:`apply_track_vmem_words`) grows with ``m``.  The gossip matmul
    underfeeds the MXU at small m; the apply contraction dominates the
    flops, which is what the tiling optimises.

    Returns:
      ``(S_new, G)`` — both ``(m, d, k)`` fp32.
    """
    m, d, k = W.shape
    if A.shape != (m, d, d):
        raise ValueError(f"A must be ({m}, {d}, {d}) for W {W.shape}; "
                         f"got {A.shape}")
    if not (S.shape == G_prev.shape == (m, d, k)):
        raise ValueError(f"S/G_prev must be ({m}, {d}, {k}); got "
                         f"{S.shape}, {G_prev.shape}")
    if L.shape != (m, m):
        raise ValueError(f"L must be ({m}, {m}); got {L.shape}")
    bd0, be0 = apply_track_default_tiles(m, d, k, interpret=interpret)
    if block_d is None:
        block_d = autotune.resolve("apply_track", "block_d", (m, d, k),
                                   W.dtype, default=bd0)
    if block_e is None:
        block_e = autotune.resolve("apply_track", "block_e", (m, d, k),
                                   W.dtype, default=be0)
    return _apply_track_fused(A, W, S, G_prev, L, eta, K,
                              block_d=int(block_d), block_e=int(block_e),
                              interpret=interpret, wire_bf16=wire_bf16)


@functools.partial(jax.jit, static_argnames=("K", "block_d", "block_e",
                                             "interpret", "wire_bf16"))
def _apply_track_fused(A, W, S, G_prev, L, eta, K: int, *, block_d: int,
                       block_e: int, interpret: bool, wire_bf16: bool):
    m, d, k = W.shape
    f32 = jnp.float32
    if K <= 0:
        G = jnp.einsum("mde,mek->mdk", A.astype(f32), W.astype(f32),
                       precision=jax.lax.Precision.HIGHEST)
        return tracking_update(S.astype(f32), G, G_prev.astype(f32)), G

    mp = _round_up(m, 8)
    kp = _round_up(k, 8 if interpret else 128)
    bd = _round_up(min(block_d, d), 8)
    be = _round_up(min(block_e, d), 8 if interpret else 128)
    dr = _round_up(d, bd)          # padded row axis
    dc = _round_up(d, be)          # padded contraction axis

    a_p = jnp.pad(A.astype(f32), ((0, mp - m), (0, dr - d), (0, dc - d)))
    w_p = jnp.pad(W.astype(f32), ((0, mp - m), (0, dc - d), (0, kp - k)))
    s_p = jnp.pad(S.astype(f32), ((0, mp - m), (0, dr - d), (0, kp - k)))
    gp_p = jnp.pad(G_prev.astype(f32),
                   ((0, mp - m), (0, dr - d), (0, kp - k)))
    l_p = jnp.pad(L.astype(f32), ((0, mp - m), (0, mp - m)))
    eta_p = jnp.asarray(eta, f32).reshape(1, 1)
    n_s = dc // be
    vtile = pl.BlockSpec((mp, bd, kp), lambda i, s: (0, i, 0))

    S_new, G = pl.pallas_call(
        functools.partial(_apply_track_kernel, K=int(K), n_s=n_s,
                          wire_bf16=wire_bf16),
        grid=(dr // bd, n_s),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, s: (0, 0),
                         memory_space=pltpu.SMEM),          # eta
            pl.BlockSpec((mp, mp), lambda i, s: (0, 0)),    # L: resident
            pl.BlockSpec((mp, bd, be), lambda i, s: (0, i, s)),   # A tile
            pl.BlockSpec((mp, be, kp), lambda i, s: (0, s, 0)),   # W panel
            vtile,                                          # S tile
            vtile,                                          # G_prev tile
        ],
        out_specs=(vtile, vtile),
        out_shape=(jax.ShapeDtypeStruct((mp, dr, kp), f32),
                   jax.ShapeDtypeStruct((mp, dr, kp), f32)),
        interpret=interpret,
    )(eta_p, l_p, a_p, w_p, s_p, gp_p)
    return S_new[:m, :d, :k], G[:m, :d, :k]
