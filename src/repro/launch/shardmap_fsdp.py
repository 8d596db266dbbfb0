"""Manual-collective FSDP train step via shard_map (beyond-paper §Perf).

The GSPMD findings in EXPERIMENTS.md §Perf: (a) the gradient all-reduce is
pinned at fp32 because the accumulator's convert fuses into the AR producer,
and (b) Megatron-style sequence parallelism cannot be expressed with
constraints alone.  Both need MANUAL collectives.  This module provides the
shard_map data-parallel step with explicit control of the reduction dtype:

  * params live fully replicated inside the per-shard body (pure-DP FSDP
    variant: the weight all-gather is done once by the caller's sharding);
  * each data shard computes LOCAL gradients (no automatic psum — the loss
    is per-shard mean);
  * gradients are cast to **bf16 BEFORE the cross-shard reduction**
    (`jax.lax.psum` on bf16 = half the wire bytes of the GSPMD fp32 AR),
    then accumulated into fp32 for the optimizer.

For a (data,)-sharded mesh this is exact data parallelism with a 2x cheaper
gradient reduction; numerics change only by bf16 rounding of the per-shard
gradient (the same trade every bf16-reduce production stack makes).
Correctness vs the pjit step is asserted in tests/test_shardmap_fsdp.py.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.api import Transform, apply_updates, clip_by_global_norm, global_norm
from repro.core.combinators import family_sharding
from repro.models.transformer import Model
from repro.sharding import family_state_sharding

PyTree = Any


def make_shardmap_train_step(
    model: Model,
    optimizer: Transform,
    mesh: Mesh,
    *,
    grad_clip: float = 0.0,
    reduce_dtype=jnp.bfloat16,
    data_axis: str = "data",
    shard_state: bool = False,
):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    Params replicated; batch sharded on axis 0 over ``data_axis``.

    ``shard_state=False`` (pure DP): opt_state replicated too.

    ``shard_state=True`` (ZeRO-style, requires a ``fuse_families=True``
    optimizer): family-stacked projectors and projected moments partition on
    ``data_axis`` along the member-stack dim (``family_state_sharding``).
    That layout is right here because the params and gradients are
    replicated, so slicing a stack moves nothing: the steady-state collective
    schedule is UNCHANGED — still exactly one reduce-dtype gradient psum plus
    one loss pmean, zero gathers (the per-family optimizer math is
    leading-axis-parallel, so GSPMD partitions it from the state shardings
    alone); the only addition is one cond-gated ``all_gather`` per shardable
    family at projector-refresh boundaries, re-materializing the full stacked
    gradient for the SVD before the new projectors are sliced back out
    sharded (see ``combinators.family_sharding``).  The Trainer's GSPMD step
    over FSDP-sharded params lays the projectors out by the members instead
    (``sharding.opt_state_sharding``).
    """
    cfg = model.cfg

    def local_loss(params, batch):
        logits, aux, _ = model.forward(params, batch["tokens"])
        return model.loss(logits, batch["tokens"], aux)

    def grad_body(params, batch):
        # runs PER SHARD: local grads, then an explicitly-bf16 psum.  The
        # optimization_barrier pins the convert: without it XLA's
        # excess-precision pass re-promotes the all-reduce to fp32
        # (convert-around-collective reassociation), silently undoing the
        # 2x wire saving.  The psum is tree-level on purpose — one
        # multi-operand reduction for the whole gradient, not one per leaf,
        # which is both fewer collectives on the wire and the exact
        # "one gradient reduction per steady-state step" contract the
        # collective-schedule auditor (repro.analysis.collectives) asserts.
        loss, grads = jax.value_and_grad(local_loss)(params, batch)
        grads = jax.tree_util.tree_map(lambda g: g.astype(reduce_dtype), grads)
        grads = jax.lax.optimization_barrier(grads)
        grads = jax.lax.psum(grads, data_axis)
        grads = jax.lax.optimization_barrier(grads)
        loss = jax.lax.pmean(loss, data_axis)
        return loss, grads

    n_shards = mesh.shape[data_axis]
    replicated = P()
    batch_spec = {"tokens": P(data_axis)}

    sharded_grad = jax.shard_map(
        grad_body,
        mesh=mesh,
        in_specs=(replicated, batch_spec),
        out_specs=(replicated, replicated),
        check_vma=False,
    )

    def train_step(params, opt_state, batch):
        loss, grads = sharded_grad(params, batch)
        # fp32 accumulate AFTER the bf16 wire reduction
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) / n_shards, grads
        )
        if grad_clip > 0:
            grads = clip_by_global_norm(grads, grad_clip)
        gnorm = global_norm(grads)
        if shard_state:
            with family_sharding(mesh, data_axis):
                updates, opt_state = optimizer.update(grads, opt_state, params)
        else:
            updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss.astype(jnp.float32),
                                   "grad_norm": gnorm,
                                   "update_applied": jnp.bool_(True)}

    def jit_step(params, opt_state):
        psh = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params)
        if shard_state:
            osh = family_state_sharding(opt_state, mesh, data_axis)
        else:
            osh = jax.tree_util.tree_map(
                lambda x: NamedSharding(mesh, P()) if hasattr(x, "shape") else None,
                opt_state,
            )
        bsh = {"tokens": NamedSharding(mesh, P(data_axis))}
        return jax.jit(
            train_step,
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, None),
            donate_argnums=(0, 1),
        )

    # Static contract read by the collective/buffer auditor
    # (repro.analysis.collectives / .buffers): the declared reduction dtype,
    # mesh axis, shard count and donation wiring this step was built with.
    step_info = {
        "reduce_dtype": jnp.dtype(reduce_dtype),
        "data_axis": data_axis,
        "n_shards": int(n_shards),
        "grad_clip": float(grad_clip),
        "donate_argnums": (0, 1),
        "shard_state": bool(shard_state),
    }
    train_step.sharded_step_info = step_info
    jit_step.sharded_step_info = step_info

    return train_step, jit_step
