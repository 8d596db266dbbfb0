"""Logical sharding rules shared by models and the launcher.

Models annotate activations with *logical* axis names; the launcher resolves
them against whichever mesh is active.  Logical axes:

  "fsdp"  -> ("pod", "data") on the multi-pod mesh, ("data",) on single-pod
  "tp"    -> ("model",)
  "ep"    -> ("model",)   (expert parallelism reuses the model axis)
  None    -> replicated

Param rules (DESIGN.md §5) are path-based so any pytree layout works.
"""
from __future__ import annotations

import contextlib
import functools
import re
import threading
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Activate a mesh for logical-axis resolution and as JAX's context mesh
    (``jax.set_mesh``), which the kernel dispatcher reads at trace time."""
    prev = _mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def resolve_axis(logical: Optional[str], mesh: Mesh) -> Any:
    if logical is None:
        return None
    names = mesh.axis_names
    if logical == "fsdp":
        axes = tuple(a for a in ("pod", "data") if a in names)
        return axes if len(axes) > 1 else (axes[0] if axes else None)
    if logical in ("tp", "ep"):
        return "model" if "model" in names else None
    if logical in names:
        return logical
    return None


def resolve_spec(logical_spec: Sequence[Optional[str]], mesh: Optional[Mesh] = None) -> P:
    mesh = mesh or _mesh()
    if mesh is None:
        return P()
    return P(*(resolve_axis(ax, mesh) for ax in logical_spec))


def logical_axis_size(logical: str) -> int:
    """Size of a logical axis on the active mesh (1 if no mesh)."""
    mesh = _mesh()
    if mesh is None:
        return 1
    return _axis_size(resolve_axis(logical, mesh), mesh)


def _axis_size(ax: Any, mesh: Mesh) -> int:
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def validate_spec(shape, spec: P, mesh: Mesh) -> P:
    """Drop axes whose dim isn't divisible by the shard count (e.g. batch=1
    in long_500k, vocab=504 on a 16-way model axis)."""
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        out.append(ax if ax is not None and dim % _axis_size(ax, mesh) == 0 else None)
    return P(*out)


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate an activation with a logical sharding; no-op without a mesh."""
    mesh = _mesh()
    if mesh is None:
        return x
    spec = validate_spec(x.shape, resolve_spec(logical_axes, mesh), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules: ordered (regex on path, logical spec) pairs.
# Specs are per-dimension logical names, right-aligned is NOT assumed — they
# must match the rank (leading stacked-layer dims get None automatically).
# ---------------------------------------------------------------------------

PARAM_RULES: list[tuple[str, tuple[Optional[str], ...]]] = [
    # embeddings / lm head: vocab tensor-parallel, d_model fsdp
    (r"embed", ("tp", "fsdp")),
    (r"lm_head", ("fsdp", "tp")),
    # MoE experts (E, d_in, d_out): expert-parallel over model axis, fsdp rows
    (r"experts?.*(w_in|w_gate)", ("ep", "fsdp", None)),
    (r"experts?.*w_out", ("ep", None, "fsdp")),
    (r"router", ("fsdp", None)),
    # attention projections
    (r"(wq|wk|wv|wqkv|q_proj|k_proj|v_proj|in_proj)", ("fsdp", "tp")),
    (r"(wo|o_proj|out_proj)", ("tp", "fsdp")),
    # mlp
    (r"(w_in|w_gate|w_up|gate_proj|up_proj)", ("fsdp", "tp")),
    (r"(w_out|w_down|down_proj)", ("tp", "fsdp")),
    # mamba projections
    (r"(ssm_in)", ("fsdp", "tp")),
    (r"(ssm_out)", ("tp", "fsdp")),
    (r"conv_w", (None, "fsdp")),
    (r"pos_embed", ("fsdp", None)),
    (r"frame_proj", ("fsdp", "tp")),
    # everything 1-D (norms, biases, dt, A) replicated
]


def spec_for_param(path: str, p: Any) -> tuple[Optional[str], ...]:
    ndim = p.ndim if hasattr(p, "ndim") else len(p.shape)
    if ndim <= 1:
        return (None,) * ndim
    for pat, spec in PARAM_RULES:
        if re.search(pat, path):
            pad = ndim - len(spec)
            if pad < 0:
                # rule is for the trailing dims; keep the trailing ones
                return spec[-ndim:]
            return (None,) * pad + tuple(spec)
    # default: fsdp on the penultimate dim
    return (None,) * (ndim - 2) + ("fsdp", None)


def param_spec(path: str, p: Any, mesh: Mesh) -> P:
    """The resolved spec of the param at ``path`` on ``mesh``: its rule,
    with the axes its dims do not divide dropped."""
    return validate_spec(p.shape, resolve_spec(spec_for_param(path, p), mesh),
                         mesh)


def param_specs(params: Any) -> Any:
    """Pytree of logical specs matching ``params``."""
    from repro.core.api import tree_paths  # local import to avoid cycles

    paths = tree_paths(params)
    return jax.tree_util.tree_map(
        lambda path, p: spec_for_param(path, p), paths, params
    )


def named_sharding_tree(params: Any, mesh: Mesh) -> Any:
    from repro.core.api import tree_paths  # local import to avoid cycles

    paths = tree_paths(params)
    return jax.tree_util.tree_map(
        lambda path, p: NamedSharding(mesh, param_spec(path, p, mesh)),
        paths,
        params,
    )


def per_shard_bytes(tree: Any, mesh: Mesh) -> int:
    """Static bytes ONE device holds for ``tree`` sharded under the param
    rules on ``mesh`` — nbytes divided by the shard count of every resolved
    (and divisibility-surviving) spec axis.  Works on ShapeDtypeStructs;
    this is the per-SHARD number the analysis buffer pass (RA605) checks
    runtime shardings against, not the per-replica total."""
    from repro.core.api import tree_paths  # local import to avoid cycles

    paths = tree_paths(tree)
    total = 0
    for path, x in zip(jax.tree_util.tree_leaves(paths),
                       jax.tree_util.tree_leaves(tree)):
        if not hasattr(x, "shape"):
            continue
        nelem = 1
        for d in x.shape:
            nelem *= int(d)
        nbytes = nelem * jax.numpy.dtype(x.dtype).itemsize
        shards = 1
        for ax in param_spec(path, x, mesh):
            shards *= _axis_size(ax, mesh)
        total += nbytes // max(shards, 1)
    return total


def _family_stack_leaf_ids(opt_state: Any) -> set:
    """ids of the leaves living inside FUSED (family-list layout)
    ``LowRankState`` nodes — the stacked projectors, projected moments and
    probes the ZeRO sharding partitions.  Per-leaf lowrank states (projs is a
    params-shaped tree, not a list) are excluded: their leading dims are
    block dims of one parameter, not a member stack."""
    from repro.core.combinators import find_lowrank_states  # lazy (cycles)

    ids: set = set()
    for st in find_lowrank_states(opt_state):
        if not isinstance(st.projs, list):
            continue
        for leaf in jax.tree_util.tree_leaves(st):
            ids.add(id(leaf))
    return ids


def _family_shardable(x: Any, n_shards: int) -> bool:
    from repro.core.lowrank_common import stack_shardable

    return (hasattr(x, "ndim") and x.ndim >= 2
            and stack_shardable(int(x.shape[0]), n_shards))


def family_state_sharding(opt_state: Any, mesh: Mesh,
                          axis: str = "data") -> Any:
    """ZeRO-style sharding tree for a ``fuse_families=True`` optimizer state
    whose params are replicated (the pure-DP ``shard_map`` step): every
    family-stacked low-rank leaf (projectors, projected moments, whatever the
    inner transform allocated per family) partitions on mesh ``axis`` along
    its leading stack dim — members of a family land on different shards —
    and everything else stays replicated.  With replicated members, slicing
    a stack is free, so this is the whole rule there; the GSPMD step over
    FSDP-sharded params lays the projectors out by the members instead (see
    :func:`opt_state_sharding`).  Families whose stack doesn't divide the
    axis fall back to replicated (mirroring the runtime refresh fallback in
    ``combinators``)."""
    n = _axis_size(axis, mesh)
    fam_ids = _family_stack_leaf_ids(opt_state)

    def leaf_sharding(x):
        if not hasattr(x, "shape"):
            return None
        if id(x) in fam_ids and _family_shardable(x, n) and n > 1:
            return NamedSharding(mesh, P(axis))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(leaf_sharding, opt_state)


def family_state_bytes(opt_state: Any, n_shards: int) -> tuple[int, int]:
    """``(total, per_shard)`` bytes of the family-stacked low-rank state
    under ``n_shards``-way ZeRO sharding — the closed-form the sharded-step
    benchmark and the static memory accountant report (works on
    ShapeDtypeStructs).  Non-divisible families are charged replicated."""
    fam_ids = _family_stack_leaf_ids(opt_state)
    total = per_shard = 0
    for x in jax.tree_util.tree_leaves(opt_state):
        if id(x) not in fam_ids or not hasattr(x, "shape"):
            continue
        nelem = 1
        for d in x.shape:
            nelem *= int(d)
        nbytes = nelem * jax.numpy.dtype(x.dtype).itemsize
        total += nbytes
        if _family_shardable(x, n_shards):
            per_shard += nbytes // max(n_shards, 1)
        else:
            per_shard += nbytes
    return total, per_shard


def opt_state_sharding(opt_state: Any, mesh: Mesh, *,
                       family_axis: Optional[str] = None,
                       optimizer: Any = None, params: Any = None) -> Any:
    """Sharding for optimizer states.  State leaves live under the param path
    they belong to (e.g. families/blocks/attn/wq/r_low), so the param rules
    apply directly; full-shape moments inherit the param's exact spec, and
    low-rank states keep whichever trailing axes still divide.

    With ``family_axis`` (the ZeRO-sharded fused step), family-stacked
    low-rank leaves instead partition on that axis along their leading stack
    dim, as :func:`family_state_sharding` lays them out.  Given the
    ``optimizer`` and the ``params`` it updates, a family's projector
    ``(L, s, r)`` follows its members: where a member's param spec shards the
    dim the projector spans (``s``: rows ``m`` on the left side, columns
    ``n`` on the right), the projector shards ``s`` on ``family_axis``, so
    the step projects and back-projects each member in its own FSDP layout
    (``combinators.family_layout`` is the rule, read by the step too).  The
    projected moments and full-rank slots keep the stack dim; families whose
    members are replicated keep it for the projector too."""
    from repro.core.api import tree_paths

    paths = tree_paths(opt_state)
    fam_ids = _family_stack_leaf_ids(opt_state) if family_axis else set()
    fam_n = _axis_size(family_axis, mesh) if family_axis else 1
    by_members = set()  # ids of projectors sharded on their s dim
    if fam_ids and fam_n > 1 and optimizer is not None and params is not None:
        from repro.core.combinators import family_layouts

        for st, _, lays in family_layouts(
                optimizer, opt_state, params, family_axis, fam_n,
                functools.partial(param_spec, mesh=mesh)):
            by_members.update(id(proj) for proj, lay in zip(st.projs, lays)
                              if lay is not None and lay.proj in ("m", "n"))

    def leaf_sharding(path, x):
        if id(x) in by_members:
            return NamedSharding(mesh, P(None, family_axis, None))
        if family_axis and id(x) in fam_ids and fam_n > 1 \
                and _family_shardable(x, fam_n):
            return NamedSharding(mesh, P(family_axis))
        if not hasattr(x, "ndim") or x.ndim <= 1:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, param_spec(path, x, mesh))

    return jax.tree_util.tree_map(leaf_sharding, paths, opt_state)
