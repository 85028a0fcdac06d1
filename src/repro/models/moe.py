"""Mixture-of-Experts FFN with capacity-bounded scatter dispatch.

Dispatch strategy: *sort-free scatter* (MegaBlocks-style, adapted): each
token's top-k assignments get a slot index inside its expert via a cumsum
rank; tokens beyond ``capacity`` are dropped (standard GShard semantics).
Expert inputs are built with one scatter (T*k -> (E*C, d)) and results
returned with one gather — O(0) extra matmul FLOPs, unlike the classic
one-hot-einsum dispatch whose (T, E, C, d) contraction costs more FLOPs
than the experts themselves at E=160.

Sharding: expert weight tensors are expert-parallel over the "model" mesh
axis; with token activations data-parallel, GSPMD lowers the scatter/gather
pair into the dispatch/return collectives.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import Params, dense_init
from .partitioning import BATCH, EXPERT, FF, constrain


def moe_init(cfg: ArchConfig, key, dtype=jnp.float32) -> Params:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.expert_ff
    ks = jax.random.split(key, 5)
    scale = d ** -0.5

    def stack(k, i, o):
        return (jax.random.truncated_normal(k, -2., 2., (e, i, o), jnp.float32)
                * scale).astype(dtype)

    p = {"router": dense_init(ks[0], d, e, dtype),
         "wi_gate": stack(ks[1], d, ff),
         "wi_up": stack(ks[2], d, ff),
         "wo": stack(ks[3], ff, d) * (ff ** -0.5) * (d ** 0.5)}
    if cfg.n_shared_experts:
        sf = ff * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {"wi_gate": dense_init(k1, d, sf, dtype),
                       "wi_up": dense_init(k2, d, sf, dtype),
                       "wo": dense_init(k3, sf, d, dtype)}
    return p


def _capacity(cfg: ArchConfig, tokens: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.moe_top_k / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def moe_forward(cfg: ArchConfig, p: Params, x: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss).

    Two execution paths:
    * single-device / no-mesh: scatter dispatch (below) — reference math.
    * mesh installed: :func:`_moe_shard_map` — expert-parallel shard_map
      with *zero token movement* (activations are replicated across the EP
      axis under our sharding, so each EP shard locally selects the tokens
      routed to its experts and a single psum over EP combines outputs).
      This replaced a GSPMD-partitioned scatter whose dispatch all-gathered
      ~16 TB/chip/step on deepseek-v2 train_4k (see EXPERIMENTS.md §Perf).
    """
    from . import partitioning as part
    mesh = part._CTX["mesh"]
    ep = part._CTX["map"].get(part.EXPERT) if mesh is not None else None
    if (not FORCE_REFERENCE and mesh is not None and ep in mesh.shape
            and cfg.n_experts % mesh.shape[ep] == 0 and mesh.shape[ep] > 1):
        return _moe_shard_map(cfg, p, x, mesh, ep)
    return _moe_reference(cfg, p, x)


# perf-iteration knob: force the GSPMD scatter path even under a mesh
# (the paper-faithful-era baseline; see EXPERIMENTS.md §Perf iteration 1).
FORCE_REFERENCE = False


def _moe_reference(cfg: ArchConfig, p: Params, x: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    dt = x.dtype
    t = b * s
    cap = _capacity(cfg, t)
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf, p["router"].astype(dt)
                        ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # (t, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32),
                  axis=0)
    aux = e * jnp.sum(me * ce)

    # ---- slot assignment: rank of each (token, j) within its expert -------
    flat_expert = expert_idx.reshape(-1)                       # (t*k,)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)   # (t*k, e)
    ranks = (jnp.cumsum(onehot, axis=0) - onehot) * onehot     # rank per slot
    rank = jnp.sum(ranks, axis=-1)                             # (t*k,)
    keep = rank < cap
    slot = jnp.where(keep, flat_expert * cap + rank, e * cap)  # overflow slot

    # ---- dispatch: scatter tokens into (E*C + 1, d) ----------------------
    src = jnp.repeat(xf, k, axis=0)                            # (t*k, d)
    buf = jnp.zeros((e * cap + 1, d), dt).at[slot].set(src)
    buf = constrain(buf[:e * cap].reshape(e, cap, d), EXPERT, None, None)

    # ---- expert computation (batched over E, expert-parallel) -------------
    g = jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"].astype(dt))
    h = constrain(jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u,
                  EXPERT, None, None)
    y = constrain(jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt)),
                  EXPERT, None, None)

    # ---- combine: gather back and weight ----------------------------------
    yf = y.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], yf[jnp.minimum(slot, e * cap - 1)],
                         jnp.zeros((), dt))
    w = (gate_vals.reshape(-1) * keep).astype(dt)
    out = jnp.sum((gathered * w[:, None]).reshape(t, k, d), axis=1)

    if "shared" in p:
        out = out + _shared_expert(p["shared"], xf)

    return out.reshape(b, s, d), aux


def _shared_expert(sp: Params, xf: jax.Array) -> jax.Array:
    dt = xf.dtype
    g = jnp.einsum("td,df->tf", xf, sp["wi_gate"].astype(dt))
    u = jnp.einsum("td,df->tf", xf, sp["wi_up"].astype(dt))
    return jnp.einsum("tf,fd->td",
                      jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u,
                      sp["wo"].astype(dt))


def _moe_shard_map(cfg: ArchConfig, p: Params, x: jax.Array, mesh, ep: str
                   ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE with explicit collectives (shard_map).

    Invariants exploited:
    * activations are replicated across the EP ("model") axis, so every EP
      shard can evaluate routing for its local tokens identically — tokens
      never move, only the output psum crosses the EP axis;
    * expert weights are (E, d, ff) sharded P(ep, fsdp, -) — shard_map's
      input resharding performs the per-layer FSDP all-gather.
    Wire cost per layer: one psum of (T_loc, d) over EP + the FSDP gather,
    versus the scatter-dispatch GSPMD lowering that replicated the token
    buffer across the mesh.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from . import partitioning as part

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    dt = x.dtype
    nshard = mesh.shape[ep]
    e_loc = e // nshard
    bspec = part._CTX["map"].get(part.BATCH)
    dp_axes = tuple([bspec] if isinstance(bspec, str) else (bspec or ()))

    def body(router, wi_gate, wi_up, wo, x_loc):
        bl, sl, _ = x_loc.shape
        xf = x_loc.reshape(-1, d)
        t = xf.shape[0]
        cap = _capacity(cfg, t)
        logits = jnp.einsum("td,de->te", xf, router.astype(dt)
                            ).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32),
                      axis=0)
        aux = e * jnp.sum(me * ce)

        e_start = jax.lax.axis_index(ep) * e_loc
        flat = expert_idx.reshape(-1)
        is_local = (flat >= e_start) & (flat < e_start + e_loc)
        lidx = jnp.where(is_local, flat - e_start, e_loc)
        onehot = jax.nn.one_hot(lidx, e_loc, dtype=jnp.int32)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, -1)
        keep = is_local & (rank < cap)
        slot = jnp.where(keep, lidx * cap + rank, e_loc * cap)

        src = jnp.repeat(xf, k, axis=0)
        buf = jnp.zeros((e_loc * cap + 1, d), dt).at[slot].set(src)
        buf = buf[:e_loc * cap].reshape(e_loc, cap, d)

        g = jnp.einsum("ecd,edf->ecf", buf, wi_gate.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf, wi_up.astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        y = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt)).reshape(-1, d)

        gathered = jnp.where(keep[:, None],
                             y[jnp.minimum(slot, e_loc * cap - 1)],
                             jnp.zeros((), dt))
        w = (gate_vals.reshape(-1) * keep).astype(dt)
        partial = jnp.sum((gathered * w[:, None]).reshape(t, k, d), axis=1)
        out = jax.lax.psum(partial, ep)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return out.reshape(bl, sl, d), aux

    bsp = bspec
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(ep, None, None), P(ep, None, None),
                  P(ep, None, None), P(bsp, None, None)),
        out_specs=(P(bsp, None, None), P()),
        check_vma=False)
    out, aux = fn(p["router"], p["wi_gate"], p["wi_up"], p["wo"], x)

    if "shared" in p:
        xf = x.reshape(-1, d)
        out = out + _shared_expert(p["shared"], xf).reshape(b, s, d)
    return out, aux
