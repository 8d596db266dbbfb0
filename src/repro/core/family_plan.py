"""Static family plan: group same-shape pytree leaves into stacked super-leaves.

The per-leaf Python loop in ``lowrank()`` issues separate project / momentum /
back-project launches per parameter leaf, with full HBM round-trips between
stages.  The dispatch layer already runs native ``(L, m, n)`` batch grids —
but only for leaves that arrive pre-stacked.  A :class:`FamilyPlan` closes the
gap: at ``init`` time it groups every leaf with the same *family signature*
``(lead, m, n, side, rank, dtype)`` into one stacked ``(M·prod(lead), m, n)``
super-leaf, so the whole optimizer pipeline runs one batched launch per shape
family instead of one per leaf, then scatters results back through the
treedef.

Only leaves with IDENTICAL signatures stack: equal ``lead`` keeps the
per-member block count ``L`` — and with it ``layerwise_unbias``'s sampling
ratio ``q = gamma/L`` and compensation coefficients — uniform across the
stack, which is what makes stacked execution trajectory-identical to the
per-leaf path (per-member PRNG keys are stacked, never merged; see
:class:`StackSeg`).

The stack flattens ``(M, *lead)`` into one leading axis.  That reshape is
exactly the one :func:`repro.kernels.dispatch._flatten_lead` already performs
for every Pallas call: the fused path runs per-device (replicated optimizer
math / under shard_map), so the no-lead-reshape GSPMD rule in
``lowrank_common`` does not apply here — which is why ``fuse_families`` is an
opt-in knob, not the default.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .lowrank_common import (
    FamilyShape,
    family_shape,
    gather_blocks,
    scatter_blocks,
)


class StackSeg(NamedTuple):
    """Static segment geometry of a stacked super-leaf.

    ``members`` original leaves, each contributing ``member_L`` blocks
    (``member_L = prod(member_lead)``); global block ``j*member_L + b`` is
    block ``b`` of member ``j``.  Carried on ``ProjGrad``/``ProjInit`` leaves
    so protocol-aware wrappers (``layerwise_unbias``) sample per *member*,
    preserving the per-leaf trajectories exactly."""

    members: int
    member_L: int


class Family(NamedTuple):
    """One shape family: the stacked geometry plus its member leaf indices."""

    fs: FamilyShape           # stacked: lead = (members * member_L,)
    member_fs: FamilyShape    # geometry of ONE member leaf
    seg: StackSeg
    members: tuple[int, ...]  # flat leaf indices (order of first occurrence)


class FamilyPlan(NamedTuple):
    families: tuple[Family, ...]
    n_leaves: int


def family_signature(p, rank) -> tuple:
    """The static grouping key: leaves stack iff their signatures are equal.
    ``rank`` may be an int or a per-shape ``RankMap`` (resolved per leaf by
    ``family_shape``); the resolved rank is part of the signature, so a rank
    change re-plans the families — same-(m, n) leaves always share one rank,
    which keeps the grouping itself stable across rank migrations."""
    fs = family_shape(p, rank)
    return (fs.lead, fs.m, fs.n, fs.side, fs.rank, jnp.result_type(p).name)


def build_family_plan(leaves, rank) -> FamilyPlan:
    """Group the non-``None`` leaves of a flattened params list into families
    (first-occurrence order — deterministic across init/update/refresh, which
    all flatten the same params tree)."""
    groups: dict[tuple, list[int]] = {}
    member_fs: dict[tuple, FamilyShape] = {}
    for i, p in enumerate(leaves):
        if p is None:
            continue
        sig = family_signature(p, rank)
        groups.setdefault(sig, []).append(i)
        member_fs.setdefault(sig, family_shape(p, rank))
    families = []
    for sig, members in groups.items():
        mfs = member_fs[sig]
        seg = StackSeg(members=len(members), member_L=mfs.L)
        stacked = FamilyShape(
            lead=(seg.members * seg.member_L,), L=seg.members * seg.member_L,
            m=mfs.m, n=mfs.n, side=mfs.side, rank=mfs.rank,
        )
        families.append(Family(fs=stacked, member_fs=mfs, seg=seg,
                               members=tuple(members)))
    return FamilyPlan(families=tuple(families), n_leaves=len(leaves))


def plan_stats(plan: FamilyPlan) -> dict:
    """Static geometry summary of a plan, JSON-serializable — consumed by the
    analysis layer's audit summary so a one-line startup log can show how the
    routed leaves collapse into launch units."""
    return {
        "n_families": len(plan.families),
        "n_leaves": plan.n_leaves,
        "n_stacked": sum(f.seg.members for f in plan.families),
        "families": [
            f"{f.member_fs.m}x{f.member_fs.n}r{f.member_fs.rank}"
            f"x{f.seg.members}"
            for f in plan.families
        ],
        # Stacked super-leaf dims [L, m, n] per family — the geometry the
        # ZeRO-sharded schedule model needs: a family shards (and therefore
        # all-gathers its L*m*n fp32 gradient at refresh boundaries) iff
        # L % n_shards == 0 (see lowrank_common.stack_shardable).
        "stack_dims": [
            [f.fs.L, f.member_fs.m, f.member_fs.n] for f in plan.families
        ],
    }


def stack_family(fam: Family, leaves: list) -> jax.Array:
    """Stack member leaves ``(*lead, a, b)`` -> ``(members*member_L, a, b)``.
    Row-major, so member ``j``'s blocks occupy rows
    ``[j*member_L, (j+1)*member_L)`` in unravel order — matching
    :func:`jax.numpy.unravel_index` on the member's own lead dims."""
    return _stack_parts(fam, [leaves[i] for i in fam.members])


def _stack_parts(fam: Family, parts: list) -> jax.Array:
    stacked = jnp.stack(parts)
    return stacked.reshape((fam.seg.members * fam.seg.member_L,)
                           + stacked.shape[1 + len(fam.member_fs.lead):])


class MemberStack:
    """A family stack kept as its members: ``parts[j]`` is member ``j``'s
    ``(*member_lead, a, b)`` array, in the layout its own leaf has.

    Stands in for the stacked ``(L, a, b)`` array where stacking would move
    data between chips: under an FSDP mesh the members of one family may
    shard different dims (``wq`` its rows, ``wo`` its columns), and no single
    layout of the stack holds them all in place.  Block indices are the
    stack's own (member ``j``'s blocks are ``[j*member_L, (j+1)*member_L)``),
    so :meth:`gather` / :meth:`scatter` take the per-member sampled indices
    ``layerwise_unbias`` keeps, member ``j``'s ``g_f`` entries at
    ``[j*g_f, (j+1)*g_f)``."""

    __slots__ = ("fam", "parts")

    def __init__(self, fam: Family, parts: list):
        self.fam = fam
        self.parts = list(parts)

    def stacked(self) -> jax.Array:
        """The ``(L, a, b)`` stack (moves data when the layouts differ)."""
        return _stack_parts(self.fam, self.parts)

    def map(self, fn) -> "MemberStack":
        return MemberStack(self.fam, [fn(x) for x in self.parts])

    def __mul__(self, f) -> "MemberStack":
        return self.map(lambda x: x * f)

    __rmul__ = __mul__

    def _split(self, idx: jax.Array):
        """Per member: its slice of ``idx`` made local to the member."""
        g_f = int(idx.shape[0]) // self.fam.seg.members
        L = self.fam.seg.member_L
        return [(j, slice(j * g_f, (j + 1) * g_f),
                 idx[j * g_f:(j + 1) * g_f] - j * L)
                for j in range(self.fam.seg.members)]

    def gather(self, idx: jax.Array) -> jax.Array:
        """``(len(idx), a, b)``: the stack's blocks ``idx``."""
        return jnp.concatenate([
            gather_blocks(self.parts[j], loc, self.fam.member_fs)
            for j, _, loc in self._split(idx)])

    def scatter(self, idx: jax.Array, vals: jax.Array) -> "MemberStack":
        """The stack with its blocks ``idx`` set to ``vals``."""
        return MemberStack(self.fam, [
            scatter_blocks(self.parts[j], loc, vals[sl], self.fam.member_fs)
            for j, sl, loc in self._split(idx)])


def unstack_family(fam: Family, stacked: jax.Array) -> list[jax.Array]:
    """Inverse of :func:`stack_family` on any ``(members*member_L, *tail)``
    result: a list of per-member ``(*lead, *tail)`` arrays in member order."""
    tail = stacked.shape[1:]
    parts = stacked.reshape((fam.seg.members,) + fam.member_fs.lead + tail)
    return [parts[j] for j in range(fam.seg.members)]


def member_keys(fam: Family, base_key: jax.Array) -> jax.Array:
    """Per-member PRNG keys, stacked ``(members, 2)`` — bit-identical to the
    per-leaf ``jax.random.fold_in(base_key, i)`` derivation (vmap is
    semantics-preserving per element)."""
    idx = jnp.asarray(fam.members, dtype=jnp.int32)
    return jax.vmap(lambda i: jax.random.fold_in(base_key, i))(idx)
