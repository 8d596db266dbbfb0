"""Minimal functional optimizer API (optax-style, zero dependencies).

A :class:`Transform` is a pair of pure functions:

    init(params)                     -> state
    update(grads, state, params)     -> (updates, new_state)

``updates`` are *added* to params (they already include the -lr sign), so

    params = apply_updates(params, updates)

All states are pytrees of arrays (jit/pjit friendly, checkpointable).  A step
counter is threaded through every optimizer's state as ``state.count``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

PyTree = Any
Schedule = Union[float, Callable[[jax.Array], jax.Array]]


class Transform(NamedTuple):
    init: Callable[[PyTree], PyTree]
    update: Callable[[PyTree, PyTree, PyTree], tuple[PyTree, PyTree]]


def schedule_value(lr: Schedule, count: jax.Array) -> jax.Array:
    return jnp.asarray(lr(count) if callable(lr) else lr, dtype=jnp.float32)


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    def one(p, u):
        if u is None:
            return p
        if hasattr(u, "materialize_update"):
            # A deferred-epilogue leaf (combinators.PendingBack) from a chain
            # that ended without scale_by_lr: materialize it leaf-by-leaf
            # (correct, just not family-grouped).
            u = u.materialize_update()
        return p + u.astype(p.dtype)

    return jax.tree_util.tree_map(
        one, params, updates, is_leaf=lambda x: x is None
    )


def global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def clip_by_global_norm(grads: PyTree, max_norm: float) -> PyTree:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype), grads)


# ---------------------------------------------------------------------------
# Label-partitioned composition (like optax.multi_transform).
# ---------------------------------------------------------------------------


class MultiState(NamedTuple):
    inner: dict  # label -> state


def multi_transform(
    transforms: dict[str, Transform], label_fn: Callable[[PyTree], PyTree]
) -> Transform:
    """Route each leaf to the transform named by ``label_fn(params)``.

    ``label_fn`` returns a pytree of the same structure whose leaves are label
    strings.  Each inner transform sees the full tree with non-owned leaves
    replaced by ``None`` (masked), mirroring optax semantics.
    """

    def mask(tree: PyTree, labels: PyTree, label: str) -> PyTree:
        return jax.tree_util.tree_map(
            lambda x, l: x if l == label else None, tree, labels
        )

    def unmask_merge(trees: dict[str, PyTree], labels: PyTree) -> PyTree:
        def pick(l, *leaves_by_label):
            return leaves_by_label[list(transforms).index(l)]

        per_label = [trees[k] for k in transforms]
        return jax.tree_util.tree_map(
            pick, labels, *per_label, is_leaf=lambda x: x is None
        )

    def init(params: PyTree) -> MultiState:
        labels = label_fn(params)
        return MultiState(
            inner={k: t.init(mask(params, labels, k)) for k, t in transforms.items()}
        )

    def update(grads: PyTree, state: MultiState, params: PyTree):
        labels = label_fn(params)
        new_inner, upds = {}, {}
        for k, t in transforms.items():
            with jax.named_scope(k):  # each label is a stage of the step
                u, s = t.update(mask(grads, labels, k), state.inner[k],
                                mask(params, labels, k))
            upds[k], new_inner[k] = u, s
        merged = unmask_merge(upds, labels)
        return merged, MultiState(inner=new_inner)

    # Static composition metadata for the analysis layer (repro.analysis):
    # per-branch chain_info plus the label_fn itself, so the chain linter /
    # launch model can resolve the actual leaf routing from a params tree.
    update.chain_info = {
        "kind": "multi_transform",
        "branches": {
            k: dict(getattr(t.update, "chain_info", None) or {"kind": "opaque"})
            for k, t in transforms.items()
        },
        "label_fn": label_fn,
    }
    return Transform(init, update)


def tree_paths(tree: PyTree) -> PyTree:
    """Pytree of '/'-joined key paths, same structure as ``tree``."""

    def name(kp) -> str:
        parts = []
        for k in kp:
            if isinstance(k, jax.tree_util.DictKey):
                parts.append(str(k.key))
            elif isinstance(k, jax.tree_util.SequenceKey):
                parts.append(str(k.idx))
            elif isinstance(k, jax.tree_util.GetAttrKey):
                parts.append(str(k.name))
            else:
                parts.append(str(k))
        return "/".join(parts)

    paths_leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    flat = [name(kp) for kp, _ in paths_leaves]
    treedef = jax.tree_util.tree_structure(tree)
    return jax.tree_util.tree_unflatten(treedef, flat)


def state_bytes(state: PyTree) -> int:
    """Total bytes of all arrays in an optimizer state (memory benchmarks)."""
    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(state)
        if hasattr(x, "dtype")
    )


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Config resolved by :func:`repro.core.factory.build_optimizer`."""

    # gum | galore | galore_muon | golore | muon | adamw | sgdm | fira | lisa
    # | unbiased_galore_adam (combinator-only composition, PR 2)
    name: str = "gum"
    lr: float = 1e-3
    weight_decay: float = 0.0
    beta: float = 0.95          # momentum (muon-family)
    b1: float = 0.9             # adam
    b2: float = 0.999
    eps: float = 1e-8
    rank: int = 128             # low-rank projection rank
    q: float = 0.25             # full-rank sampling probability (gum) == gamma/L
    gamma: int = 2              # full-rank layers per period (gum/lisa)
    period: int = 200           # K, projector refresh / resampling period
    projector: str = "svd"      # svd | subspace | random | grass
    base: str = "muon"          # base optimizer inside low-rank space
    ns_steps: int = 5
    compensation: str = "paper"  # paper | finetune (App. C.1 variant)
    grad_clip: float = 0.0
    seed: int = 0
    # Hot-loop implementation: auto | jnp | pallas | interpret — "auto" runs
    # the fused Pallas kernels on TPU and the jnp reference elsewhere
    # (see repro.kernels.dispatch).
    kernel_impl: str = "auto"
    # Opt-in lane-aligned rank padding for the low-rank Pallas kernels:
    # 128 rounds ragged ranks (e.g. r=96) up to a full MXU lane multiple for
    # peak systolic-array utilization; 0 keeps the minimal sublane granule.
    pad_rank_to: int = 0
    # Family-stacked fused execution: group same-shape leaves into stacked
    # (L, m, n) super-leaves so the lowrank() pipeline launches once per
    # shape family instead of once per leaf.  Trajectory-identical to the
    # per-leaf path (per-member PRNG preserved) but the optimizer-state
    # layout changes — off by default so existing trajectories/checkpoints
    # are bit-for-bit unchanged.
    fuse_families: bool = False
    # Fold chain-tail elementwise epilogues (scale_by_lr /
    # add_decayed_weights / scale_by_factor) into the back-projection GEMM
    # via the fused back_project_epilogue kernel.  Not bit-exact (the
    # epilogue redistributes multiplications), hence a separate opt-in.
    fused_epilogue: bool = False
    # Muon's sqrt(max(1, m/n)) RMS-matching factor.  None = each optimizer's
    # default (muon: on, matching Jordan et al.; gum: off, matching Alg. 2).
    use_muon_scale: bool | None = None
    # Rank policy (repro.core.rank_policy): when and what rank each shape
    # family runs at.  None = static cfg.rank (unchanged behavior).  Accepts
    # a RankPolicy object or a CLI spec string — "fixed:64",
    # "stepwise:0=128,500=64", "family:512x512=32,...", "spectral:0.99".
    # Policies decide at projector-refresh boundaries; the Trainer migrates
    # optimizer state and re-jits (bounded by the policy's rank ladder).
    rank_policy: Any = None
    # Declared rank ladder for adaptive policies (bounds recompilation; with
    # pad_rank_to=128, ladder steps inside one 128-lane bucket share kernel
    # shapes).  Empty = the policy's default (powers of two).
    rank_ladder: tuple[int, ...] = ()
    # ZeRO-style sharded projected state (requires fuse_families=True and a
    # data-parallel mesh): partition each family's projectors and projected
    # moments across the data axis along the member-stack dim, all-gathering
    # full gradients only at projector-refresh boundaries.  Read by the step
    # builders (launch.shardmap_fsdp / train.Trainer) and the sharded
    # auditor — the factory-built transform itself is layout-agnostic.
    shard_state: bool = False
    # In-jit telemetry (repro.telemetry): store projector drift and a
    # sampled per-step bias residual in the spectrum-probe dicts (implies
    # probe_spectrum).  Write-only from the update's point of view — the
    # parameter trajectory is bit-exact with telemetry off, and the state
    # gains zero leaves when off.  What tracing costs on the chip is kept
    # in PERF.md; these probes' own step time is not measured there yet.
    telemetry: bool = False
