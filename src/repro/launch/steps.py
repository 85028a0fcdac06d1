"""The jitted step functions that the dry-run lowers and the trainer runs."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import decode_step, loss_fn, prefill
from repro.models.config import ArchConfig
from repro.optim import AdamW

PyTree = Any


def make_train_step(cfg: ArchConfig, opt: AdamW):
    def train_step(params: PyTree, opt_state, batch: Dict[str, jax.Array]
                   ) -> Tuple[PyTree, Any, jax.Array]:
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch))(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss
    return train_step


def make_prefill_step(cfg: ArchConfig, max_seq: int):
    def prefill_step(params, tokens, embeds=None):
        return prefill(cfg, params, tokens, embeds=embeds, max_seq=max_seq)
    return prefill_step


def make_decode_step(cfg: ArchConfig):
    def serve_step(params, cache, token):
        return decode_step(cfg, params, cache, token)
    return serve_step


def make_train_step_compressed(cfg: ArchConfig, opt: AdamW, mesh, topo, *,
                               rank: int = 32, K: int = 4,
                               axis: str = "agents"):
    """Decentralized data-parallel training: every device is a DeEPCA agent.

    Params are replicated; each agent computes gradients on its local batch
    shard and the ONLY cross-device communication in the whole train step is
    the subspace-tracked FastMix gossip of rank-r PowerSGD factors
    (collective_permute ring traffic — there is no all-reduce anywhere).
    This is the paper's algorithm as the distributed-training transport.

    Returns (step_fn, init_comp_state_stacked) where comp state is stacked
    over agents (leading axis m, sharded over ``axis``).
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compression.sharded import compress_local, init_state
    from repro.core.consensus import ConsensusEngine
    from jax import shard_map

    m = int(np.prod(list(mesh.shape.values())))
    engine = ConsensusEngine.for_algorithm(
        "deepca", topo, K=K, backend="shard_map", mesh=mesh, axis=axis)
    round_fn = engine.local_round_fn(axis)
    eta = engine.eta

    def init_comp_state(params):
        grads_t = jax.eval_shape(lambda p: p, params)
        one = init_state(grads_t, rank)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (m,) + a.shape),
                            one)

    def local_step(params, opt_state, comp_state, batch):
        # local (un-averaged) gradients on this agent's batch shard
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch))(params)
        cstate = jax.tree.map(lambda a: a[0], comp_state)   # strip agent dim
        ghat, new_cstate = compress_local(grads, cstate, round_fn=round_fn,
                                          eta=eta, K=K)
        params, opt_state = opt.update(ghat, opt_state, params)
        loss = jax.lax.pmean(loss, axis)
        new_cstate = jax.tree.map(lambda a: a[None], new_cstate)
        return params, opt_state, new_cstate, loss

    pspec, ospec = P(), P()
    bspec = P(axis)
    cspec = P(axis)

    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(pspec, ospec, cspec, bspec),
        out_specs=(pspec, ospec, cspec, P()),
        check_vma=False)
    return step, init_comp_state
