"""Public jit'd wrappers around the Pallas kernels.

On a machine without TPUs the kernels run in ``interpret=True`` mode (the
kernel body executes on CPU with identical block semantics); on TPU they
compile to Mosaic.  ``interpret`` is resolved once at import from the
default backend, overridable per call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import fastmix as _fm
from . import flash_attention as _fa
from . import gram as _gram
from . import power_matmul as _pm


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def gram(x: jax.Array, *, block_d: Optional[int] = None,
         block_n: Optional[int] = None,
         interpret: Optional[bool] = None) -> jax.Array:
    """Local covariance ``X^T X`` (paper Eqn. 5.1) via the Pallas kernel.

    ``block_* = None`` consults the persistent autotune cache
    (:mod:`repro.kernels.autotune`) before the built-in tiling.
    """
    it = _default_interpret() if interpret is None else interpret
    return _gram.gram(x, block_d=block_d, block_n=block_n, interpret=it)


def power_matmul(a: jax.Array, w: jax.Array, *,
                 block_m: Optional[int] = None,
                 block_k: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Power-iteration step ``A @ W`` via the Pallas kernel."""
    it = _default_interpret() if interpret is None else interpret
    return _pm.power_matmul(a, w, block_m=block_m, block_k=block_k,
                            interpret=it)


def fastmix_fused(S: jax.Array, L: jax.Array, eta: float, K: int, *,
                  block_n: Optional[int] = None,
                  interpret: Optional[bool] = None,
                  wire_bf16: bool = False) -> jax.Array:
    """All-K-rounds fused FastMix (Alg. 3) via the Pallas kernel."""
    it = _default_interpret() if interpret is None else interpret
    return _fm.fastmix_fused(S, L, float(eta), K, block_n=block_n,
                             interpret=it, wire_bf16=wire_bf16)


def apply_track_fused(A: jax.Array, W: jax.Array, S: jax.Array,
                      G_prev: jax.Array, L: jax.Array, eta: float, K: int,
                      *, block_d: Optional[int] = None,
                      block_e: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      wire_bf16: bool = False):
    """Fused local apply + tracking + K FastMix rounds -> ``(S_new, G)``."""
    it = _default_interpret() if interpret is None else interpret
    return _fm.apply_track_fused(A, W, S, G_prev, L, float(eta), K,
                                 block_d=block_d, block_e=block_e,
                                 interpret=it, wire_bf16=wire_bf16)


def cholqr2(X: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
    """Batched CholeskyQR2 orthonormalization (Eqn. 3.3 fast path)."""
    from . import cholqr as _cq
    it = _default_interpret() if interpret is None else interpret
    return _cq.cholqr2(X, interpret=it)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_kv: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Batched GQA flash attention.

    q: (B, H, Sq, hd); k, v: (B, Hkv, Skv, hd) with H % Hkv == 0.
    Returns (B, H, Sq, hd).
    """
    it = _default_interpret() if interpret is None else interpret
    b, h, sq, hd = q.shape
    hkv = k.shape[1]
    if h % hkv:
        raise ValueError(f"H={h} not a multiple of Hkv={hkv}")
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    fn = functools.partial(_fa.flash_attention_single, causal=causal,
                           block_q=block_q, block_kv=block_kv, interpret=it)
    return jax.vmap(jax.vmap(fn))(q, k, v)
