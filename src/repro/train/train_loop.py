"""Train-step builders.

``make_train_step`` produces the jitted SPMD step for any zoo architecture:
loss -> grad (with optional microbatch accumulation via lax.scan) -> AdamW.
Under jit with sharded batches, the data-parallel gradient AllReduce is
inserted by the SPMD partitioner; ``grad_sync='tree'`` instead routes the
sync through the explicit butterfly ``tree_psum`` inside a shard_map (the
paper's tree-reduction applied to step-4 gradient synchronization), and
``compress_grads=True`` applies int8 error-feedback compression to the
cross-pod leg.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..core.config import TrainConfig
from ..core.tree_reduce import tree_psum
from . import compression
from .optimizer import AdamState, adam_update, init_adam


class TrainState(NamedTuple):
    params: Any
    opt: AdamState
    error: Any   # error-feedback residual (None unless compressing)


def init_state(params, cfg: TrainConfig) -> TrainState:
    err = compression.init_error(params) if cfg.compress_grads else None
    return TrainState(params=params, opt=init_adam(params), error=err)


def _microbatch_grads(loss_fn, params, batch, n_micro: int):
    if n_micro <= 1:
        return jax.value_and_grad(loss_fn)(params, batch)

    def reshape(x):
        return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

    micro = jax.tree.map(reshape, batch)

    def body(carry, mb):
        loss_acc, grad_acc = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, mb)
        return (
            loss_acc + loss / n_micro,
            jax.tree.map(lambda a, g: a + g / n_micro, grad_acc, grads),
        ), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zero), micro)
    return loss, grads


def make_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    tcfg: TrainConfig,
    mesh: Mesh | None = None,
):
    """Returns step(state, batch) -> (state, metrics).  jit it with the
    in/out shardings the launcher derives from zoo.param_pspecs."""

    def step(state: TrainState, batch):
        loss, grads = _microbatch_grads(
            loss_fn, state.params, batch, tcfg.microbatches
        )
        error = state.error
        if tcfg.compress_grads:
            packed, error = compression.compress_grads(grads, error)
            grads = compression.decompress_grads(packed)
        params, opt, gnorm = adam_update(tcfg, state.params, grads, state.opt)
        metrics = {"loss": loss, "grad_norm": gnorm, "step": opt.step}
        return TrainState(params=params, opt=opt, error=error), metrics

    return step


def make_shardmap_grad_sync(mesh: Mesh, axis_name: str = "data"):
    """Explicit tree-reduction gradient AllReduce (--grad-sync tree).

    For use around a per-worker grad computation inside shard_map: grads
    replicated on `axis_name` after a butterfly of ppermute+add — the
    paper's step-3 hierarchy applied to step-4 sync."""

    def sync(grads):
        def inner(g):
            summed = tree_psum(g, axis_name)
            return jax.tree.map(lambda x: x / mesh.shape[axis_name], summed)

        specs = jax.tree.map(lambda _: P(), grads)
        return shard_map(
            inner, mesh=mesh, in_specs=(specs,), out_specs=specs, check_vma=False
        )(grads)

    return sync


def nan_guard(state: TrainState, new_state: TrainState, metrics) -> TrainState:
    """Straggler/blow-up resilience: skip the update when loss goes NaN
    (keeps the replica fleet consistent instead of desyncing)."""
    ok = jnp.isfinite(metrics["loss"])
    return jax.tree.map(
        lambda old, new: jnp.where(ok, new, old), state, new_state
    )
