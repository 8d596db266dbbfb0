"""Collective-schedule auditor for the shard_map'ped train step (RA6xx).

The sharded data-parallel step (:mod:`repro.launch.shardmap_fsdp`) encodes
wire-level invariants that silently rot: the gradient reduction must happen
exactly once per step, at the *declared* ``reduce_dtype`` (the
``optimization_barrier`` pin is what keeps XLA's excess-precision pass from
re-promoting the bf16 all-reduce to fp32), and nothing may gather a full
gradient in the steady state.  This pass makes those invariants
machine-checked the same way :mod:`repro.analysis.launch_model` checks
kernel-launch counts:

  * :func:`collect_collectives` walks a ``jax.make_jaxpr`` trace of the step
    — recursing into ``shard_map`` / ``cond`` / ``pjit`` sub-jaxprs — and
    extracts every collective equation (primitive, mesh axes, operand
    dtypes, per-shard payload bytes, whether it is gated behind a refresh
    ``cond``, whether its operands are barrier-pinned).  Each collective
    also records into :mod:`repro.kernels.launch_count` counters, so a
    single ``count_launches()`` context sees dispatch ops and collectives
    side by side.
  * :func:`expected_collective_schedule` derives the closed-form schedule
    from ``chain_info`` × :class:`~repro.core.family_plan.FamilyPlan` ×
    mesh shape: one gradient psum at ``reduce_dtype`` over all param
    leaves, one scalar loss psum (the ``pmean``), and — until ZeRO-style
    sharded projected state lands — zero refresh-boundary gathers (the
    per-family geometry is still reported, since it is exactly what the
    sharded-projector PR will turn into boundary all-gathers).
  * :func:`collective_schedule_findings` diffs traced vs expected and emits
    RA601 (reduction not pinned at the declared dtype), RA602
    (boundary-only collective running unconditionally), RA603
    (full-gradient gather in steady state) and RA606 (schedule divergence).
  * :func:`wire_bytes_model` is the per-step wire-bytes accountant — ring
    coefficients per collective kind, analogous to ``launch_model``'s
    launch-coefficient table.

Everything works on abstract traces over ``ShapeDtypeStruct`` trees and an
``AbstractMesh`` — no devices are needed to audit an N-way mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterable

import jax
import jax.numpy as jnp

from repro.core.api import Transform
from repro.kernels import launch_count

from .findings import Finding
from .jaxpr_passes import _subjaxprs, abstract_tree
from .launch_model import lowrank_plan_stats

PyTree = Any

# Primitives treated as collectives when walking the trace.  ``pmean`` never
# appears as its own primitive — jax lowers it to psum + div — so a scalar
# psum is how the loss mean shows up.
COLLECTIVE_PRIMS = frozenset(launch_count.COLLECTIVE_OPS)

# Primitives whose equations gate their sub-jaxprs behind a predicate; a
# collective under one of these runs only when the branch is taken (the
# refresh-boundary pattern), not every step.
_GATED_PRIMS = ("cond",)


@dataclasses.dataclass(frozen=True)
class CollectiveRecord:
    """One collective equation extracted from the traced step."""

    primitive: str                       # psum / all_gather / ...
    axes: tuple[str, ...]                # mesh axis names reduced/gathered over
    dtypes: tuple[str, ...]              # distinct operand element dtypes
    shapes: tuple[tuple[int, ...], ...]  # operand shapes (as seen per shard)
    n_operands: int
    payload_bytes: int                   # sum over operands of shard bytes
    under_cond: bool                     # gated behind a cond => boundary-only
    pinned: bool                         # every operand produced by an
                                         # optimization_barrier equation
    path: tuple[str, ...]                # enclosing primitive names

    @property
    def scalar_only(self) -> bool:
        return all(s == () for s in self.shapes)


def _eqn_axes(eqn) -> tuple[str, ...]:
    for key in ("axes", "axis_name", "axis_index_groups_axis_name"):
        val = eqn.params.get(key)
        if val is None:
            continue
        if isinstance(val, (tuple, list)):
            return tuple(str(a) for a in val)
        return (str(val),)
    return ()


def collect_collectives(jaxpr) -> list[CollectiveRecord]:
    """Every collective equation in ``jaxpr``, recursing into ``shard_map`` /
    ``cond`` / ``pjit`` / ``scan`` sub-jaxprs.  Also records one
    ``launch_count.record(primitive)`` per collective, so active
    ``count_launches()`` contexts count collectives alongside dispatch ops."""
    records: list[CollectiveRecord] = []

    def walk(j, under_cond: bool, path: tuple[str, ...]) -> None:
        core = j.jaxpr if hasattr(j, "jaxpr") else j
        producer: dict[int, Any] = {}   # id(var) -> (primitive, id(eqn))
        # A tree-level psum binds one equation per leaf (jax >= 0.9); the
        # leaves of one barrier-pinned tree are one reduction, which XLA's
        # all-reduce combiner sends as one wire operation: merge them.
        tree_psums: dict[tuple, int] = {}
        for eqn in core.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVE_PRIMS:
                avals = [getattr(v, "aval", None) for v in eqn.invars]
                avals = [a for a in avals if a is not None]
                shapes = tuple(tuple(a.shape) for a in avals)
                dtypes = tuple(sorted({a.dtype.name for a in avals}))
                payload = sum(
                    int(a.size) * a.dtype.itemsize for a in avals
                )
                sources = {producer.get(id(v), ("", None)) for v in eqn.invars}
                pinned = bool(avals) and all(
                    src == "optimization_barrier" for src, _ in sources)
                rec = CollectiveRecord(
                    primitive=name,
                    axes=_eqn_axes(eqn),
                    dtypes=dtypes,
                    shapes=shapes,
                    n_operands=len(eqn.invars),
                    payload_bytes=payload,
                    under_cond=under_cond,
                    pinned=pinned,
                    path=path,
                )
                key = (name, rec.axes, next(iter(sources))[1]) \
                    if pinned and len(sources) == 1 else None
                if key is not None and key in tree_psums:
                    i = tree_psums[key]
                    prev = records[i]
                    records[i] = dataclasses.replace(
                        prev,
                        dtypes=tuple(sorted(set(prev.dtypes) | set(dtypes))),
                        shapes=prev.shapes + shapes,
                        n_operands=prev.n_operands + rec.n_operands,
                        payload_bytes=prev.payload_bytes + payload,
                    )
                else:
                    if key is not None:
                        tree_psums[key] = len(records)
                    launch_count.record(name)
                    records.append(rec)
            for v in eqn.outvars:
                producer[id(v)] = (name, id(eqn))
            gated = under_cond or name in _GATED_PRIMS
            for val in eqn.params.values():
                for sub in _subjaxprs(val):
                    walk(sub, gated, path + (name,))

    walk(jaxpr, False, ())
    return records


# ---------------------------------------------------------------------------
# closed-form schedule model
# ---------------------------------------------------------------------------


def expected_collective_schedule(
    transform: Transform | dict,
    params: PyTree,
    *,
    n_shards: int,
    reduce_dtype=jnp.bfloat16,
    data_axis: str = "data",
    shard_state: bool = False,
) -> dict:
    """The collective schedule the shard_map step must show, derived
    statically from the param tree, the optimizer's ``chain_info`` ×
    :class:`~repro.core.family_plan.FamilyPlan` geometry, and the mesh.

    Steady state (both variants): exactly ONE gradient psum (tree-level, one
    operand per param leaf) at ``reduce_dtype`` plus one scalar f32 loss
    psum (the ``pmean``) — the ZeRO-sharded family math is
    leading-axis-parallel, so sharding the projected state adds nothing to
    the steady schedule.

    Boundary: with replicated state (``shard_state=False``) a projector
    refresh implies no extra wire traffic — zero gathers.  With ZeRO-style
    sharded projected state (``shard_state=True``) the refresh
    re-materializes each shardable family's full stacked gradient: exactly
    one cond-gated ``all_gather`` per fused family whose stack divides the
    mesh axis (``lowrank_common.stack_shardable`` — the same rule the
    runtime applies), with the per-shard fp32 gradient slice as payload.
    """
    from repro.core.lowrank_common import stack_shardable

    rd = jnp.dtype(reduce_dtype)
    leaves = [x for x in jax.tree_util.tree_leaves(params)
              if hasattr(x, "shape")]
    grad_payload = sum(int(_size(x)) * rd.itemsize for x in leaves)
    try:
        plan_rows = lowrank_plan_stats(transform, params)
        n_families = sum(int(r.get("n_families", 0)) for r in plan_rows)
    except Exception:
        plan_rows, n_families = [], 0
    n_gather = gather_payload = 0
    if shard_state:
        for row in plan_rows:
            if not row.get("fused"):
                continue
            for L, m, n in row.get("stack_dims", []):
                if stack_shardable(int(L), int(n_shards)):
                    n_gather += 1
                    # payload as the trace accounts it: the per-shard operand
                    # (the local fp32 gradient slice) of the all_gather
                    gather_payload += int(L) * int(m) * int(n) * 4 \
                        // max(int(n_shards), 1)
    return {
        "grad_psum": {
            "count": 1,
            "dtype": rd.name,
            "operands": len(leaves),
            "payload_bytes": int(grad_payload),
            "axis": data_axis,
            "phase": "steady",
        },
        "loss_psum": {
            "count": 1,
            "dtype": "float32",
            "operands": 1,
            "payload_bytes": 4,
            "axis": data_axis,
            "phase": "steady",
        },
        "boundary_gather": {
            # replicated projected state => refresh implies no gathers;
            # sharded state => one all_gather per shardable fused family.
            "count": int(n_gather),
            "families": int(n_families),
            "payload_bytes": int(gather_payload),
            "phase": "boundary",
        },
        "n_shards": int(n_shards),
        "shard_state": bool(shard_state),
    }


def _size(x) -> int:
    n = 1
    for d in jnp.shape(x):
        n *= int(d)
    return n


# ---------------------------------------------------------------------------
# traced-vs-model findings (RA601/602/603/606)
# ---------------------------------------------------------------------------


def collective_schedule_findings(
    records: Iterable[CollectiveRecord],
    expected: dict,
    *,
    reduce_dtype=jnp.bfloat16,
    params: PyTree | None = None,
    where: str = "sharded-step",
) -> list[Finding]:
    """Diff the traced collectives against the closed-form schedule."""
    records = list(records)
    rd = jnp.dtype(reduce_dtype)
    out: list[Finding] = []

    steady = [r for r in records if not r.under_cond]
    boundary = [r for r in records if r.under_cond]
    grad_red = [r for r in steady if r.primitive == "psum"
                and not r.scalar_only]
    loss_red = [r for r in steady if r.primitive == "psum" and r.scalar_only]
    gathers = [r for r in steady
               if r.primitive in ("all_gather", "all_to_all", "ppermute")]

    param_shapes = set()
    if params is not None:
        param_shapes = {tuple(jnp.shape(x))
                        for x in jax.tree_util.tree_leaves(params)
                        if hasattr(x, "shape")}

    # RA601 — gradient reduction must run at the declared reduce_dtype and,
    # when that dtype is narrower than f32, be barrier-pinned so XLA's
    # excess-precision pass cannot re-promote it on the wire.
    for r in grad_red:
        wide = [dt for dt in r.dtypes if jnp.dtype(dt).itemsize > rd.itemsize]
        if wide:
            out.append(Finding(
                code="RA601", where=where,
                message=f"gradient psum carries {'/'.join(wide)} operands "
                        f"where reduce_dtype={rd.name} was declared — "
                        f"{_bytes(r.payload_bytes)} on the wire instead of "
                        f"{_bytes(r.payload_bytes * rd.itemsize // max(jnp.dtype(wide[0]).itemsize, 1))}",
                hint="cast gradients to the declared reduce_dtype before "
                     "jax.lax.psum (see launch/shardmap_fsdp.grad_body)",
                detail={"dtypes": list(r.dtypes), "declared": rd.name},
            ))
        elif rd.itemsize < 4 and not r.pinned:
            out.append(Finding(
                code="RA601", where=where,
                message=f"gradient psum at {rd.name} is not "
                        "optimization_barrier-pinned — XLA's excess-precision "
                        "pass may fold the convert into the all-reduce and "
                        "re-promote it to fp32, silently doubling wire bytes",
                hint="wrap the casted gradients in "
                     "jax.lax.optimization_barrier before the psum "
                     "(the guard launch/shardmap_fsdp.grad_body documents)",
                detail={"dtypes": list(r.dtypes), "declared": rd.name},
            ))

    # RA602/RA603 — no gathers in steady state on this path.
    for r in gathers:
        shapes = set(r.shapes)
        full = bool(param_shapes and (
            shapes & param_shapes
            or {s[1:] for s in shapes if len(s) > 1} & param_shapes))
        if full:
            out.append(Finding(
                code="RA603", where=where,
                message=f"steady-state {r.primitive} materializes a "
                        "full-gradient/param-shaped buffer "
                        f"({_bytes(r.payload_bytes)}) every step — gathers "
                        "belong at refresh boundaries only",
                hint="gate the gather behind the refresh cond (one gather "
                     "per family per boundary), compute sharded otherwise",
                detail={"shapes": [list(s) for s in r.shapes]},
            ))
        else:
            out.append(Finding(
                code="RA602", where=where,
                message=f"unconditional {r.primitive} over "
                        f"axes={list(r.axes)} in the steady-state step — the "
                        "schedule model marks this collective boundary-only",
                hint="move it under the refresh cond / boundary branch",
                detail={"primitive": r.primitive,
                        "payload_bytes": r.payload_bytes},
            ))

    # RA606 — counts / operands / payload must match the closed-form model.
    exp_g = expected["grad_psum"]
    got = {
        "count": len(grad_red),
        "operands": sum(r.n_operands for r in grad_red),
        "payload_bytes": sum(r.payload_bytes for r in grad_red),
    }
    want = {k: exp_g[k] for k in got}
    # dtype mismatches are RA601's finding; exclude their payload delta so a
    # single root cause doesn't double-report.
    dtype_ok = all(
        not [dt for dt in r.dtypes if jnp.dtype(dt).itemsize > rd.itemsize]
        for r in grad_red
    )
    if got["count"] != want["count"] or got["operands"] != want["operands"] \
            or (dtype_ok and got["payload_bytes"] != want["payload_bytes"]):
        out.append(Finding(
            code="RA606", where=where,
            message="traced gradient-reduction schedule diverges from the "
                    f"closed-form model: traced {got}, expected {want}",
            hint="one tree-level psum over every param leaf at reduce_dtype "
                 "is the contract; per-leaf psums or dropped leaves break it",
            detail={"traced": got, "expected": want},
        ))
    if len(loss_red) != expected["loss_psum"]["count"]:
        out.append(Finding(
            code="RA606", where=where,
            message=f"{len(loss_red)} scalar loss reduction(s) traced, "
                    f"expected {expected['loss_psum']['count']} (the pmean)",
            detail={"traced": len(loss_red)},
        ))
    exp_b = expected.get("boundary_gather", {"count": 0})
    n_boundary = len([r for r in boundary
                      if r.primitive in ("all_gather", "reduce_scatter",
                                         "all_to_all")])
    if n_boundary != exp_b["count"]:
        out.append(Finding(
            code="RA606", where=where,
            message=f"{n_boundary} boundary-gated gather(s) traced, expected "
                    f"{exp_b['count']} (refresh implies "
                    f"{exp_b['count']} per boundary on this path)",
            detail={"traced": n_boundary, "expected": exp_b["count"]},
        ))
    return out


# ---------------------------------------------------------------------------
# wire-bytes accountant
# ---------------------------------------------------------------------------

# Bytes each shard moves over the wire per payload byte, ring algorithms
# (the coefficient table — launch_model.py's _BASE_COEFFS analogue).
_RING_COEFF = {
    "psum": lambda n: 2.0 * (n - 1) / n,            # reduce-scatter+all-gather
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0 if n > 1 else 0.0,
}


def wire_bytes_model(records: Iterable[CollectiveRecord],
                     n_shards: int) -> dict:
    """Per-step wire bytes each shard sends, from the traced collectives and
    ring coefficients.  ``steady_bytes_per_step`` counts unconditional
    collectives; ``boundary_bytes`` counts the cond-gated ones (paid only on
    refresh steps)."""
    n = max(int(n_shards), 1)
    per: list[dict] = []
    steady = boundary = 0
    for r in records:
        coeff = _RING_COEFF.get(r.primitive)
        if coeff is None:
            continue
        wire = int(r.payload_bytes * coeff(n)) if n > 1 else 0
        per.append({
            "primitive": r.primitive,
            "payload_bytes": r.payload_bytes,
            "wire_bytes": wire,
            "phase": "boundary" if r.under_cond else "steady",
            "dtypes": list(r.dtypes),
        })
        if r.under_cond:
            boundary += wire
        else:
            steady += wire
    return {
        "n_shards": n,
        "steady_bytes_per_step": steady,
        "boundary_bytes": boundary,
        "per_collective": per,
    }


def _bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"


# ---------------------------------------------------------------------------
# tracing the sharded step without devices
# ---------------------------------------------------------------------------


def trace_sharded_step(model, optimizer: Transform, *, n_shards: int,
                       batch_size: int = 8, seq_len: int | None = None,
                       reduce_dtype=jnp.bfloat16, grad_clip: float = 1.0,
                       data_axis: str = "data", shard_state: bool = False):
    """Abstractly trace :func:`repro.launch.shardmap_fsdp.make_shardmap_train_step`
    on an ``AbstractMesh`` of ``n_shards`` devices — no real devices needed.

    Returns ``(jaxpr, records, counts, structs)`` where ``records`` are the
    extracted :class:`CollectiveRecord`s, ``counts`` the launch counter over
    the whole step (dispatch ops + collectives), and ``structs`` the
    ``(params, opt_state, batch)`` ShapeDtypeStructs the trace used.
    """
    from jax.sharding import AbstractMesh

    from repro.launch.shardmap_fsdp import make_shardmap_train_step

    mesh = AbstractMesh((int(n_shards),), (data_axis,))
    step, _ = make_shardmap_train_step(
        model, optimizer, mesh,
        grad_clip=grad_clip, reduce_dtype=reduce_dtype, data_axis=data_axis,
        shard_state=shard_state,
    )
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = abstract_tree(params)
    opt_state = jax.eval_shape(optimizer.init, params)
    if batch_size % int(n_shards):
        raise ValueError(
            f"batch_size={batch_size} not divisible by n_shards={n_shards}")
    seq = int(seq_len if seq_len is not None else min(64, model.cfg.max_seq))
    batch = {"tokens": jax.ShapeDtypeStruct((int(batch_size), seq),
                                            jnp.int32)}
    with launch_count.count_launches() as counts:
        jaxpr = jax.make_jaxpr(step)(params, opt_state, batch)
        records = collect_collectives(jaxpr)
    return jaxpr, records, counts, (params, opt_state, batch)
