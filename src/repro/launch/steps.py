"""train_step / serve_step builders + ShapeDtypeStruct input specs.

The same builders serve the real trainer and the multi-pod dry-run:
``input_specs`` returns weak-type-correct, shardable stand-ins (no device
allocation) for every model input of a given (arch, shape) cell.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import api as core_api
from repro.core.api import Transform, apply_updates, clip_by_global_norm
from repro.models.transformer import Model, init_cache
from repro.sharding import resolve_spec, validate_spec

PyTree = Any


# ---------------------------------------------------------------------------
# batch construction / specs
# ---------------------------------------------------------------------------


def batch_struct(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStructs for one global batch of this cell."""
    B, S = shape.global_batch, shape.seq_len
    out: dict[str, jax.ShapeDtypeStruct] = {}
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "decode":
        out["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    elif cfg.frontend == "frames":
        out["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        out["targets"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    else:
        out["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.family == "vlm" and shape.kind != "decode":
        out["images"] = jax.ShapeDtypeStruct((B, cfg.n_image_tokens, cfg.d_model), dt)
    return out


def batch_shardings(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> dict:
    fsdp = resolve_spec(("fsdp",), mesh)[0]
    out = {}
    for k, s in batch_struct(cfg, shape).items():
        spec = (fsdp,) + (None,) * (len(s.shape) - 1)
        out[k] = NamedSharding(mesh, validate_spec(s.shape, P(*spec), mesh))
    return out


def cache_struct(cfg: ModelConfig, shape: ShapeConfig) -> PyTree:
    return jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len)
    )


def cache_shardings(cache: PyTree, cfg: ModelConfig, mesh: Mesh) -> PyTree:
    """KV caches: batch over fsdp, cache-seq over the model axis (sequence-
    sharded cache — DESIGN.md §5); mamba states: heads/channels over tp."""
    from repro.core.api import tree_paths

    fsdp = resolve_spec(("fsdp",), mesh)[0]
    tp = resolve_spec(("tp",), mesh)[0]
    paths = tree_paths(cache)

    def one(path, x):
        nd = len(x.shape)
        leaf = path.rsplit("/", 1)[-1]
        spec = [None] * nd
        if leaf in ("k", "v", "xk", "xv"):
            # (..., B, S, KV, hd): batch -> fsdp, cache-seq -> model
            spec[-4] = fsdp
            spec[-3] = tp
        elif leaf == "conv":
            spec[-3] = fsdp  # (L, B, W-1, C): batch
            spec[-1] = tp
        elif leaf == "ssm":
            spec[-4] = fsdp  # (L, B, H, N, P): batch, heads
            spec[-3] = tp
        return NamedSharding(mesh, validate_spec(x.shape, P(*spec), mesh))

    return jax.tree_util.tree_map(one, paths, cache)


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------


def _loss_from_batch(model: Model, params, batch, cfg: ModelConfig):
    from repro.models.transformer import chunked_lm_loss

    kwargs = {}
    if "images" in batch:
        kwargs["images"] = batch["images"]
    if cfg.frontend == "frames":
        inputs, targets, shift = None, batch["targets"], False
        kwargs["frames"] = batch["frames"]
    else:
        inputs, targets, shift = batch["tokens"], batch["tokens"], True

    if cfg.logit_chunk > 0:
        hidden, aux, _ = model.forward(params, inputs, return_hidden=True, **kwargs)
        return chunked_lm_loss(params, cfg, hidden, targets, aux, shift=shift)
    logits, aux, _ = model.forward(params, inputs, **kwargs)
    return model.loss(logits, targets, aux, shift=shift)


def make_train_step(
    model: Model,
    optimizer: Transform,
    *,
    grad_clip: float = 0.0,
    microbatches: int = 1,
    lowrank_accum=None,
    fault_gate=None,
    extra_metrics: bool = False,
):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``optimizer`` is any :class:`repro.core.api.Transform` — in practice a
    combinator chain from :mod:`repro.core.combinators` (built by
    ``build_optimizer`` or composed by hand, e.g.
    ``chain(lowrank(layerwise_unbias(scale_by_muon())), scale_by_lr(lr))``).

    ``microbatches > 1`` runs gradient accumulation via lax.scan over
    microbatch slices (fp32 accumulator), preserving the global batch size.

    ``lowrank_accum`` (a :class:`repro.core.gum.GUMAccumTools`) switches the
    accumulator to the PROJECTED space (beyond-paper): low-rank families
    accumulate Pᵀ G (+ the gamma sampled full blocks) instead of full-shape
    fp32 gradients — update-equivalent by Property I (see gum.py).  The
    tools' project/reconstruct and the refresh hook run through the same
    kernel dispatch layer as the optimizer itself (``kernel_impl`` /
    ``pad_rank_to`` are threaded in by the caller, e.g. launch/dryrun.py),
    so accumulating steps lower the same hot path as plain training.

    **Fault tolerance — the in-jit NaN/Inf guard (resilience rung 0).**
    Buffers are donated to the jitted step, so by the time the host sees a
    bad loss the old params/opt_state are gone — a non-finite loss or
    gradient therefore has to be neutralized *inside* the step: the guard
    zeroes the gradients AND the emitted updates and reverts every
    optimizer-state array to its pre-step value (``jnp.where(finite, ...)``
    elementwise), so a poisoned step is a pure no-op that still returns a
    metrics dict (``update_applied=False``).  The low-rank step counter
    does not advance on a skipped step, which keeps projector-refresh and
    rank-policy boundaries aligned with *applied* updates.  Detection and
    escalation beyond rung 0 (loss spikes, subspace collapse, rollback /
    restore) live host-side in :mod:`repro.resilience` — see the README
    "Resilience" section for the full fault→detector→recovery table.

    ``fault_gate`` (a :class:`repro.resilience.inject.FaultGate`) compiles a
    traced gradient-corruption gate into the step: the returned function
    takes a fourth argument ``fault = {"mode": int32, "scale": float32}``
    and corrupts the raw gradients pre-clip (mode 0 is elementwise-identical
    to the stock step — arming a fault is a host value, not a recompile).

    ``extra_metrics=True`` adds the health monitor's in-jit signals:
    ``grad_norm_raw`` (pre-clip — reused as the clip's own norm, so it is
    free when ``grad_clip`` is on), ``update_norm`` (global norm of the
    applied parameter delta) and ``update_norm_lowrank`` (the same norm
    restricted to the leaves ``default_lowrank_filter`` routes through the
    low-rank stage — the dead-subspace detector's signal).  Both update
    norms share one fused subtract-square-reduce pass over the delta, so
    the whole monitor costs a single extra pass per step.

    **Stage names.** Every op of the step sits under exactly one
    ``jax.named_scope`` (visible in the compiled program's op metadata and
    so in a device trace): ``loss_and_grads`` (the forward/backward and any
    accumulation), ``grad_clip``, ``optimizer`` (``optimizer.update``, and
    ``apply_updates`` inside it) and ``guard`` (the fault gate, the
    non-finite guard's reverts and the metrics).  No name contains ``jvp``
    or ``transpose``, which autodiff puts in the name stack itself.
    """
    cfg = model.cfg

    def single_grad(params, batch):
        return jax.value_and_grad(lambda p: _loss_from_batch(model, p, batch, cfg))(params)

    if lowrank_accum is not None and microbatches > 1:
        if fault_gate is not None:
            raise NotImplementedError(
                "fault injection is not wired into the projected-space "
                "accumulation step")
        return _make_lowrank_accum_step(
            model, lowrank_accum, single_grad, grad_clip, microbatches
        )

    def _step(params, opt_state, batch, fault):
        with jax.named_scope("loss_and_grads"):
            loss, grads = _loss_and_grads(params, batch)
        with jax.named_scope("guard"):
            if fault_gate is not None:
                grads = fault_gate.apply(grads, fault)
        with jax.named_scope("grad_clip"):
            grads, gnorm_raw = _clip(grads)
        with jax.named_scope("guard"):
            gnorm, finite, grads = _guard_grads(loss, grads)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
        with jax.named_scope("guard"):
            updates, opt_state = _guard_update(finite, updates, new_opt_state,
                                               opt_state)
        with jax.named_scope("optimizer"), jax.named_scope("apply_updates"):
            new_params = apply_updates(params, updates)
        with jax.named_scope("guard"):
            metrics = {"loss": loss.astype(jnp.float32),
                       "grad_norm": gnorm,
                       "update_applied": finite}
            if extra_metrics:
                metrics.update(_health_metrics(gnorm_raw, new_params, params))
        return new_params, opt_state, metrics

    def _loss_and_grads(params, batch):
        if microbatches > 1:
            def slice_mb(x):
                B = x.shape[0]
                return x.reshape((microbatches, B // microbatches) + x.shape[1:])

            mb = jax.tree_util.tree_map(slice_mb, batch)
            # Seed the fp32 accumulator from the first microbatch's real
            # gradients so the accumulator inherits the gradients' sharding
            # (a fresh zeros tree can end up replicated under GSPMD).
            first = jax.tree_util.tree_map(lambda x: x[0], mb)
            loss0, g0 = single_grad(params, first)
            acc0 = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), g0)

            def acc_body(carry, mbatch):
                loss_acc, grad_acc = carry
                loss, g = single_grad(params, mbatch)
                grad_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), grad_acc, g
                )
                return (loss_acc + loss, grad_acc), None

            rest = jax.tree_util.tree_map(lambda x: x[1:], mb)
            (loss, grads), _ = jax.lax.scan(acc_body, (loss0, acc0), rest)
            loss = loss / microbatches
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        else:
            loss, grads = single_grad(params, batch)
        return loss, grads

    def _clip(grads):
        if extra_metrics:
            # The clip computes this exact reduction internally; doing it
            # here and clipping inline keeps grad_norm_raw free of an extra
            # pass over the gradients (bit-identical to clip_by_global_norm).
            gnorm_raw = core_api.global_norm(grads)
            if grad_clip > 0:
                scale = jnp.minimum(1.0, grad_clip / (gnorm_raw + 1e-12))
                grads = jax.tree_util.tree_map(
                    lambda g: g * scale.astype(g.dtype), grads)
        else:
            gnorm_raw = None
            if grad_clip > 0:
                grads = clip_by_global_norm(grads, grad_clip)
        return grads, gnorm_raw

    def _health_metrics(gnorm_raw, new_params, params):
        from repro.core import default_lowrank_filter

        # One fused subtract-square-reduce pass per leaf; both norms
        # combine the same per-leaf partial sums.
        delta_sq = jax.tree_util.tree_map(
            lambda a, b: jnp.sum(jnp.square((a - b).astype(jnp.float32))),
            new_params, params)
        # Restricted to the leaves the low-rank stage treats
        # (default_lowrank_filter): a dead subspace zeroes exactly these
        # while embeddings/norms keep updating, so the global norm would
        # mask the collapse.
        lr_paths = core_api.tree_paths(new_params)
        lr_sq = jax.tree_util.tree_map(
            lambda p, s, a: s if default_lowrank_filter(p, a)
            else jnp.zeros((), s.dtype),
            lr_paths, delta_sq, new_params)
        return {"grad_norm_raw": gnorm_raw,
                "update_norm": jnp.sqrt(sum(jax.tree_util.tree_leaves(delta_sq))),
                "update_norm_lowrank": jnp.sqrt(
                    sum(jax.tree_util.tree_leaves(lr_sq)))}

    if fault_gate is not None:
        def train_step(params, opt_state, batch, fault):
            return _step(params, opt_state, batch, fault)
    else:
        def train_step(params, opt_state, batch):
            return _step(params, opt_state, batch, None)

    return train_step


def _guard_grads(loss, grads):
    """NaN/Inf guard, resilience rung 0 (see :func:`make_train_step`):
    (norm, finite, grads zeroed unless the loss and norm are finite)."""
    gnorm = core_api.global_norm(grads)
    finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
    grads = jax.tree_util.tree_map(
        lambda g: jnp.where(finite, g, jnp.zeros_like(g)), grads
    )
    return gnorm, finite, grads


def _guard_update(finite, updates, new_opt_state, opt_state):
    """The guard's second half: zero the updates and revert every optimizer
    state array to its pre-step value unless the step was finite."""
    updates = jax.tree_util.tree_map(
        lambda u: None if u is None else jnp.where(finite, u, jnp.zeros_like(u)),
        updates,
        is_leaf=lambda x: x is None,
    )
    opt_state = jax.tree_util.tree_map(
        lambda new, old: jnp.where(finite, new, old) if hasattr(new, "shape") else new,
        new_opt_state, opt_state,
    )
    return updates, opt_state


def _make_lowrank_accum_step(model, tools, single_grad, grad_clip, microbatches):
    def accumulate(params, opt_state, batch):
        def slice_mb(x):
            B = x.shape[0]
            return x.reshape((microbatches, B // microbatches) + x.shape[1:])

        mb = jax.tree_util.tree_map(slice_mb, batch)
        first = jax.tree_util.tree_map(lambda x: x[0], mb)

        # microbatch 0: raw grads -> (cond'd) projector refresh -> project
        loss0, g0 = single_grad(params, first)
        opt_state = tools.refresh(g0, opt_state, params)
        acc0 = tools.project(g0, opt_state, params)

        def body(carry, mbatch):
            loss_acc, acc = carry
            loss, g = single_grad(params, mbatch)
            c = tools.project(g, opt_state, params)
            acc = jax.tree_util.tree_map(
                lambda a, b: a if b is None else a + b, acc, c,
                is_leaf=lambda x: x is None,
            )
            return (loss_acc + loss, acc), None

        rest = jax.tree_util.tree_map(lambda x: x[1:], mb)
        (loss, acc), _ = jax.lax.scan(body, (loss0, acc0), rest)
        loss = loss / microbatches
        acc = jax.tree_util.tree_map(
            lambda a: a / microbatches, acc
        )
        return loss, tools.reconstruct(acc, opt_state, params), opt_state

    def train_step(params, opt_state, batch):
        with jax.named_scope("loss_and_grads"):
            loss, grads, opt_state = accumulate(params, opt_state, batch)
        with jax.named_scope("grad_clip"):
            if grad_clip > 0:
                grads = clip_by_global_norm(grads, grad_clip)
        with jax.named_scope("guard"):
            gnorm, finite, grads = _guard_grads(loss, grads)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = tools.transform.update(grads, opt_state, params)
        with jax.named_scope("guard"):
            updates, opt_state = _guard_update(finite, updates, new_opt_state,
                                               opt_state)
        with jax.named_scope("optimizer"), jax.named_scope("apply_updates"):
            params = apply_updates(params, updates)
        with jax.named_scope("guard"):
            metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                       "update_applied": finite}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(model: Model):
    """Forward pass producing logits + populated KV cache (inference prefill)."""
    cfg = model.cfg

    def prefill_step(params, batch):
        kwargs = {}
        if "images" in batch:
            kwargs["images"] = batch["images"]
        want_cache = cfg.family in ("dense", "moe", "vlm")
        if cfg.frontend == "frames":
            logits, _, cache = model.forward(
                params, frames=batch["frames"], return_cache=want_cache, **kwargs
            )
        else:
            logits, _, cache = model.forward(
                params, batch["tokens"], return_cache=want_cache, **kwargs
            )
        return logits, cache

    return prefill_step


def make_serve_step(model: Model):
    """One decode step: (params, cache, tokens (B,1), pos) -> (logits, cache)."""

    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cache=cache, tokens=tokens, pos=pos)

    return serve_step
