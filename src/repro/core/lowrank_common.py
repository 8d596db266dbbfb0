"""Shared machinery for blockwise low-rank optimizers (GaLore / GUM / GoLore).

A *family* is one pytree leaf of shape ``(*lead, m, n)`` whose leading dims
are stacked blocks (scan-stacked layers ``(L, m, n)``, stacked MoE experts
``(L, E, m, n)``).  All per-block linear algebra is expressed with
leading-ellipsis einsums and batched QR/SVD — NEVER a reshape that merges a
leading (possibly expert-sharded) dim into the block count, because GSPMD
cannot repartition such reshapes without a full rematerialization (observed
as "[SPMD] Involuntary full rematerialization" on MoE cells).

The projector ``P`` acts on the shorter matrix side per GaLore:
  left  (m <= n): state = Pᵀ G in (*lead, r, n);  back-projection  P @ S
  right (m >  n): state = G P in (*lead, m, r);   back-projection  S @ Pᵀ

The per-step hot loop (momentum update / projection) is dispatched through
:func:`lowrank_momentum_update` / :func:`project_dispatched`, whose
``kernel_impl`` knob ("auto" | "jnp" | "pallas" | "interpret") selects the
fused Pallas TPU kernels or the jnp reference (see repro.kernels.dispatch).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .rank_policy import resolve_rank


class FamilyShape(NamedTuple):
    lead: tuple[int, ...]  # leading block dims
    L: int                 # total block count = prod(lead)
    m: int
    n: int
    side: str              # "left" | "right"
    rank: int


def family_shape(p: jax.Array, rank) -> FamilyShape:
    """``rank`` is an int or a per-shape assignment (``rank_policy.RankMap``,
    duck-typed via ``rank_for(m, n)``) — the rank-policy engine threads one
    map through every call site that used to take a single static int."""
    if p.ndim < 2:
        raise ValueError(f"low-rank families need >=2 dims, got {p.shape}")
    m, n = int(p.shape[-2]), int(p.shape[-1])
    lead = tuple(int(d) for d in p.shape[:-2])
    L = 1
    for d in lead:
        L *= d
    side = "left" if m <= n else "right"
    rank = min(resolve_rank(rank, m, n), m, n)
    return FamilyShape(lead=lead, L=L, m=m, n=n, side=side, rank=rank)


def proj_dim(fs: FamilyShape) -> int:
    """Dim P projects: m for left, n for right."""
    return fs.m if fs.side == "left" else fs.n


def proj_shape(fs: FamilyShape) -> tuple[int, ...]:
    return fs.lead + (proj_dim(fs), fs.rank)


def lowrank_state_shape(fs: FamilyShape) -> tuple[int, ...]:
    """(*lead, r, n) for left, (*lead, m, r) for right."""
    if fs.side == "left":
        return fs.lead + (fs.rank, fs.n)
    return fs.lead + (fs.m, fs.rank)


def stack_shardable(L: int, n_shards: int) -> bool:
    """Whether an ``(L, ...)`` family stack partitions evenly over
    ``n_shards`` data shards.  This single predicate is applied by BOTH the
    runtime (the sharded projector refresh in ``combinators``) and the
    closed-form collective-schedule model (``repro.analysis.collectives``) —
    keeping them one rule is what makes the audited boundary-gather count
    always match what actually traces.  Non-divisible families stay
    replicated (no gather) rather than padding the stack."""
    return n_shards >= 1 and L % n_shards == 0


def stacked_grad_bytes(fs: FamilyShape) -> int:
    """fp32 bytes of one family's stacked gradient ``(L, m, n)`` — the
    operand of the boundary ``all_gather`` in the sharded fused step (the
    refresh gathers the gradient, never the moments)."""
    return fs.L * fs.m * fs.n * 4


def project(p: jax.Array, g: jax.Array, side: str) -> jax.Array:
    """Low-rank projection. p: (*lead, s, r), g: (*lead, m, n)."""
    if side == "left":
        return jnp.einsum("...mr,...mn->...rn", p, g)
    return jnp.einsum("...mn,...nr->...mr", g, p)


def back_project(p: jax.Array, s: jax.Array, side: str) -> jax.Array:
    """Back-projection of low-rank states to (*lead, m, n)."""
    if side == "left":
        return jnp.einsum("...mr,...rn->...mn", p, s)
    return jnp.einsum("...mr,...nr->...mn", s, p)


def reconstruct(p: jax.Array, g: jax.Array, side: str) -> jax.Array:
    """P Pᵀ G (left) or G P Pᵀ (right): the biased low-rank gradient."""
    return back_project(p, project(p, g, side), side)


def lowrank_momentum_update(
    p: jax.Array,
    g: jax.Array,
    r_state: jax.Array,
    beta: float,
    coeff: float,
    side: str,
    kernel_impl: str = "jnp",
) -> jax.Array:
    """The per-step hot loop ``R' = beta·R + coeff·⟨P, G⟩`` with kernel
    dispatch: ``kernel_impl`` routes to the fused Pallas kernel ("pallas" on
    TPU, "interpret" in the interpreter anywhere) or the jnp einsum path
    ("jnp"; also what "auto" resolves to off-TPU).  All impls agree within
    fp32 roundoff; the jnp path is bit-identical to the pre-dispatch code."""
    from repro.kernels import dispatch  # lazy: kernels imports this module's peers

    return dispatch.lowrank_update(
        p, g, r_state, beta, coeff, side=side, impl=kernel_impl
    )


def project_dispatched(
    p: jax.Array, g: jax.Array, side: str, kernel_impl: str = "jnp"
) -> jax.Array:
    """``project`` routed through the projection kernel when requested —
    used by the Adam-based low-rank optimizers that need the projected
    gradient itself (for second moments / residuals)."""
    from repro.kernels import dispatch

    return dispatch.project(p, g, side=side, impl=kernel_impl)


def block_index(idx: jax.Array, fs: FamilyShape):
    """Flat block ids (gamma,) -> tuple of per-lead-dim index arrays usable
    for advanced-indexing gather/scatter on the UNreshaped leaf."""
    if len(fs.lead) == 1:
        return (idx,)
    return jnp.unravel_index(idx, fs.lead)


def gather_blocks(x: jax.Array, idx: jax.Array, fs: FamilyShape) -> jax.Array:
    """(*lead, a, b) -> (gamma, a, b) without reshaping the source."""
    if not fs.lead:  # single-block family: gamma is necessarily 1
        return x[None]
    return x[block_index(idx, fs)]


def scatter_blocks(x: jax.Array, idx: jax.Array, vals: jax.Array, fs: FamilyShape) -> jax.Array:
    if not fs.lead:
        return vals[0]
    return x.at[block_index(idx, fs)].set(vals)


def compute_projectors(
    kind: str,
    g: jax.Array,
    rank: int,
    key: jax.Array,
    side: str,
    subspace_iters: int = 2,
) -> jax.Array:
    """Batched per-block projectors; returns (*lead, s, rank), orthonormal
    columns per block (Property I).  Uses batched QR/SVD — no reshapes."""
    if side == "right":
        g = jnp.swapaxes(g, -1, -2)
    g32 = g.astype(jnp.float32)
    lead = g.shape[:-2]
    m, n = g.shape[-2], g.shape[-1]

    if kind == "svd":
        u, _, _ = jnp.linalg.svd(g32, full_matrices=False)
        return u[..., :, :rank]
    if kind in ("subspace", "rsvd"):
        # "rsvd" is the randomized range finder (Halko et al.): the
        # zero-power-iteration member of the subspace family, so refresh
        # costs one sketch GEMM + one thin QR instead of a full per-block
        # float32 SVD (see projectors.rsvd_projector).
        iters = 0 if kind == "rsvd" else subspace_iters
        omega = jax.random.normal(key, lead + (n, rank), jnp.float32)
        y = g32 @ omega
        for _ in range(iters):
            y, _ = jnp.linalg.qr(y)
            y = g32 @ (jnp.swapaxes(g32, -1, -2) @ y)
        q, _ = jnp.linalg.qr(y)
        return q
    if kind == "random":
        z = jax.random.normal(key, lead + (m, rank), jnp.float32)
        q, _ = jnp.linalg.qr(z)
        return q
    if kind == "grass":
        row_norms = jnp.linalg.norm(g32, axis=-1)  # (*lead, m)
        logits = jnp.log(row_norms + 1e-30)
        gumbel = jax.random.gumbel(key, logits.shape)
        _, idx = jax.lax.top_k(logits + gumbel, rank)  # (*lead, rank)
        p = jax.nn.one_hot(idx, m, dtype=jnp.float32)  # (*lead, rank, m)
        return jnp.swapaxes(p, -1, -2)
    raise ValueError(f"unknown projector kind: {kind!r}")


def default_lowrank_filter(path: str, p) -> bool:
    """Which leaves get low-rank treatment: hidden matrices, like GaLore's
    target-module convention (attention + MLP kernels).  Embeddings / head /
    norms / biases / routers / conv taps / per-layer vector stacks fall
    through to the base/fallback optimizer."""
    if p.ndim < 2:
        return False
    if min(int(p.shape[-1]), int(p.shape[-2])) < 8:
        return False  # per-layer vectors stacked into 2-D, conv taps, gates
    lowered = path.lower()
    return not any(
        k in lowered
        for k in ("embed", "lm_head", "norm", "scale", "bias",
                  "conv_w", "skip_d", "a_log", "router")
    )
