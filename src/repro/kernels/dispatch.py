"""Kernel dispatch: route optimizer hot loops to Pallas or pure-jnp.

Every low-rank optimizer step has two hot loops — the projected momentum
update ``R' = beta·R + coeff·PᵀG`` and the Muon Newton–Schulz iteration.
The fused Pallas TPU kernels for both live in
:mod:`repro.kernels.lowrank_update` / :mod:`repro.kernels.newton_schulz`;
this module is the single entry point that decides, per call, which
implementation actually runs:

  impl="auto"      — Pallas on TPU, the jnp reference elsewhere (default).
  impl="jnp"/"xla" — the pure-jnp reference path, everywhere.
  impl="pallas"    — the compiled Pallas kernel; raises off-TPU.
  impl="interpret" — the Pallas kernel in the interpreter, on any backend
                     (what the CPU parity tests ask for).

On top of backend selection the dispatchers add what the raw kernels
deliberately do not have:

  * shape-legality checks — shapes whose VMEM working set cannot fit
    (rank > MAX_LOWRANK_RANK, NS Gram side > MAX_NS_DIM) fall back to the
    jnp reference instead of failing to compile, and each fallback is
    recorded with its op and shape (``launch_count.count_fallbacks``);
  * padding-aware wrappers — ragged (non tile-divisible) ``(m, n)`` are
    zero-padded to legal tiles and the result sliced back, which is exact
    for both ops (zero rows/columns contribute nothing to PᵀG or X Xᵀ and
    stay zero through the NS iteration);
  * family batching — ``(*lead, m, n)`` stacked families are flattened to
    one leading axis and run through the kernels' native batch grid, so a
    whole family is a single ``pallas_call``.  (The kernels carry their own
    batch grid axis rather than relying on ``jax.vmap``, whose batching
    rule would renumber the ``pl.program_id`` axes inside the kernels.)
  * per-device calls under a mesh — GSPMD cannot partition a Pallas TPU
    kernel (the compiler refuses it), so under a context mesh each call
    runs through ``jax.shard_map`` on every device's share of the family
    (:func:`_per_device`).

``KernelEntry``/``REGISTRY`` (re-exported as ``repro.kernels.KERNEL_REGISTRY``)
name each dispatched op with its reference oracle and legality predicate, so
benchmarks and tests can enumerate the dispatch surface.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from . import launch_count, ref
from .fused_step import back_project_epilogue_batched
from .lowrank_update import (
    back_project_batched,
    lowrank_update_batched,
    project_batched,
)
from .newton_schulz import newton_schulz_pallas

VALID_IMPLS = ("auto", "jnp", "xla", "pallas", "interpret")

# VMEM working-set bounds (fp32): the lowrank kernel keeps an (r, block_n)
# accumulator plus (block_m, r) / (block_m, block_n) tiles resident; the NS
# kernels keep the whole (m, m) Gram matrix resident.
MAX_LOWRANK_RANK = 512
MAX_NS_DIM = 1024

_LANE = 128   # TPU lane width: last-dim tiling granule
_SUBLANE = 8  # fp32 sublane granule


def _rank_granule(pad_rank_to: int) -> int:
    """Opt-in lane-aligned rank padding: ``pad_rank_to=128`` rounds the rank
    axis up to a full MXU lane multiple (e.g. r=96 -> 128) so the (bm, r) /
    (r, bn) tiles hit peak systolic-array utilization; 0 keeps the minimal
    fp32 sublane granule.  Zero-padding the rank axis is exact for every
    dispatched op: padded P columns are zero, so PᵀG gains zero rows (sliced
    off), R gains zero rows (beta·0 stays 0), and P @ S is untouched."""
    if pad_rank_to < 0:
        raise ValueError(f"pad_rank_to must be >= 0, got {pad_rank_to}")
    return max(_SUBLANE, _round_up(pad_rank_to, _SUBLANE)) if pad_rank_to else _SUBLANE


def backend() -> str:
    """The default JAX backend ("tpu" | "gpu" | "cpu")."""
    return jax.default_backend()


def resolve_impl(impl: str) -> str:
    """Normalize an impl request to one of {"jnp", "pallas", "interpret"}.

    "auto" picks Pallas on TPU and jnp elsewhere.  An explicit "pallas" off
    TPU raises: the interpreter is asked for by name, as "interpret".
    """
    if impl not in VALID_IMPLS:
        raise ValueError(f"impl must be one of {VALID_IMPLS}, got {impl!r}")
    if impl in ("jnp", "xla"):
        return "jnp"
    if impl == "auto":
        return "pallas" if backend() == "tpu" else "jnp"
    if impl == "pallas" and backend() != "tpu":
        raise ValueError(
            f"impl='pallas' compiles the kernels for a TPU, but the backend "
            f"is {backend()!r}; ask for impl='interpret' to run them in the "
            "Pallas interpreter")
    return impl


def _resolve_legal(impl: str, op: str, legal: bool, shape) -> str:
    """resolve_impl plus the VMEM legality bound: an illegal shape runs the
    jnp reference, and the fallback is recorded with its op and shape."""
    impl = resolve_impl(impl)
    if impl != "jnp" and not legal:
        launch_count.record_fallback(op, tuple(int(d) for d in shape))
        return "jnp"
    return impl


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _pad_and_block(dim: int, target: int, granule: int) -> tuple[int, int]:
    """(padded dim, block) for tiling one axis.  Prefers a granule-multiple
    block in [target/4, target] that divides the granule-padded dim exactly
    (zero extra padding); when none exists (e.g. 8·prime dims, whose only
    divisor-block would be a tiny MXU-starving granule), pads up to a full
    target multiple instead — bounded extra padding, full-size blocks."""
    target = max(granule, _round_up(target, granule))
    dim_pad = _round_up(dim, granule)
    if dim_pad <= target:
        return dim_pad, dim_pad  # single block
    floor = max(granule, target // 4)
    for b in range(target, floor - 1, -granule):
        if dim_pad % b == 0:
            return dim_pad, b
    return _round_up(dim_pad, target), target


def _pad_axis(x: jax.Array, axis: int, new_dim: int) -> jax.Array:
    pad = new_dim - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flatten_lead(x: jax.Array) -> jax.Array:
    """(*lead, a, b) -> (L, a, b).  Pallas calls are per-device
    (:func:`_per_device`), so this reshape is outside what GSPMD partitions
    inside the kernel — the no-lead-reshape rule in lowrank_common applies
    to the partitioned jnp path, not here."""
    return x.reshape((-1,) + x.shape[-2:])


def _per_device(kernel: Callable, *operands: jax.Array) -> jax.Array:
    """Call ``kernel`` on (L, a, b) family operands, once per device under
    the context mesh (``jax.set_mesh``; the trainer enters it).

    GSPMD cannot partition a Pallas TPU kernel, so with a mesh the call goes
    through ``jax.shard_map`` over the mesh's non-manual axes: the leading
    family axis of every 3-D operand and of the result splits over them when
    L divides their size, and is replicated otherwise (each device then
    computes the whole family).  Other operands are replicated.  Without a
    mesh, or inside a shard_map over all of it, the kernel is called as is.
    """
    mesh = jax.sharding.get_abstract_mesh()
    axes = tuple(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                 if t != AxisType.Manual and mesh.shape[a] > 1)
    if not axes:
        return kernel(*operands)
    n = math.prod(mesh.shape[a] for a in axes)
    spec = P(axes) if operands[0].shape[0] % n == 0 else P()
    return jax.shard_map(
        kernel, mesh=mesh, axis_names=set(axes), check_vma=False,
        in_specs=tuple(spec if x.ndim == 3 else P() for x in operands),
        out_specs=spec,
    )(*operands)


# --------------------------------------------------------------------------
# Fused low-rank momentum update:  R' = beta·R + coeff·<P, G>
# --------------------------------------------------------------------------


def lowrank_update_supported(p: jax.Array, g: jax.Array, side: str) -> bool:
    """Legality of the fused kernel for this family shape."""
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    return int(p.shape[-1]) <= MAX_LOWRANK_RANK


def _project_jnp(p: jax.Array, g: jax.Array, side: str) -> jax.Array:
    """The fp32 jnp oracle for PᵀG / G P shared by every fallback path —
    delegates to lowrank_common.project (safe non-lazy import: lowrank_common
    only imports this module inside function bodies)."""
    from repro.core.lowrank_common import project

    return project(p.astype(jnp.float32), g.astype(jnp.float32), side)


def _lowrank_kernel_form(p, g, r_state, side, pad_rank_to: int = 0):
    """Normalize (p, g[, r_state]) to the kernel's left-side batched layout:
    flatten leads, transpose the right side ((G P)ᵀ = Pᵀ Gᵀ), zero-pad to
    tile-legal shapes.  Zero rows/cols are exact: they add nothing to PᵀG,
    and padded R rows/cols are zero so beta·R stays zero there.  Returns the
    prepared operands plus everything needed to undo the normalization."""
    lead = g.shape[:-2]
    if side == "right":
        g = jnp.swapaxes(g, -1, -2)
        if r_state is not None:
            r_state = jnp.swapaxes(r_state, -1, -2)
    pk, gk = _flatten_lead(p), _flatten_lead(g)
    m, r = int(pk.shape[-2]), int(pk.shape[-1])
    n = int(gk.shape[-1])
    m_pad, bm = _pad_and_block(m, 256, _SUBLANE)
    n_pad, bn = _pad_and_block(n, 512, _LANE)
    r_pad = _round_up(r, _rank_granule(pad_rank_to))
    pk = _pad_axis(_pad_axis(pk, -2, m_pad), -1, r_pad)
    gk = _pad_axis(_pad_axis(gk, -2, m_pad), -1, n_pad)
    rk = None
    if r_state is not None:
        rk = _pad_axis(_pad_axis(_flatten_lead(r_state), -2, r_pad), -1, n_pad)
    return pk, gk, rk, (lead, r, n, bm, bn)


def _lowrank_unkernel_form(out, lead, r, n, side):
    out = out[..., :r, :n].reshape(lead + (r, n))
    return jnp.swapaxes(out, -1, -2) if side == "right" else out


def lowrank_update(
    p: jax.Array,
    g: jax.Array,
    r_state: jax.Array,
    beta: float,
    coeff: float,
    *,
    side: str = "left",
    impl: str = "auto",
    pad_rank_to: int = 0,
) -> jax.Array:
    """Dispatched momentum update over a family ``g (*lead, m, n)``.

    left  side: p (*lead, m, r), r_state (*lead, r, n) -> beta·R + coeff·PᵀG
    right side: p (*lead, n, r), r_state (*lead, m, r) -> beta·R + coeff·G P

    Returns fp32, identical (within fp32 roundoff) across impls.
    ``pad_rank_to`` opts into lane-aligned rank padding (see _rank_granule).
    """
    impl = _resolve_legal(impl, "lowrank_update",
                          lowrank_update_supported(p, g, side), g.shape)
    launch_count.record("lowrank_update")
    if impl == "jnp":
        return beta * r_state.astype(jnp.float32) + coeff * _project_jnp(p, g, side)

    pk, gk, rk, (lead, r, n, bm, bn) = _lowrank_kernel_form(
        p, g, r_state, side, pad_rank_to
    )
    out = _per_device(functools.partial(
        lowrank_update_batched, beta=beta, coeff=coeff, block_m=bm,
        block_n=bn, interpret=(impl == "interpret")), pk, gk, rk)
    return _lowrank_unkernel_form(out, lead, r, n, side)


def project(p: jax.Array, g: jax.Array, *, side: str = "left",
            impl: str = "auto", pad_rank_to: int = 0) -> jax.Array:
    """Plain low-rank projection PᵀG / G P through the projection kernel —
    the dispatched counterpart of ``lowrank_common.project`` (used by the
    Adam-based optimizers, which consume the projected gradient itself)."""
    impl = _resolve_legal(impl, "project",
                          lowrank_update_supported(p, g, side), g.shape)
    launch_count.record("project")
    if impl == "jnp":
        return _project_jnp(p, g, side)

    pk, gk, _, (lead, r, n, bm, bn) = _lowrank_kernel_form(
        p, g, None, side, pad_rank_to
    )
    out = _per_device(functools.partial(
        project_batched, coeff=1.0, block_m=bm, block_n=bn,
        interpret=(impl == "interpret")), pk, gk)
    return _lowrank_unkernel_form(out, lead, r, n, side)


# --------------------------------------------------------------------------
# Back-projection GEMM:  P @ S  /  S @ Pᵀ
# --------------------------------------------------------------------------


def back_project_supported(p: jax.Array, s: jax.Array, side: str) -> bool:
    """The back-projection kernel keeps the whole rank axis resident."""
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    return int(p.shape[-1]) <= MAX_LOWRANK_RANK


def _back_project_jnp(p: jax.Array, s: jax.Array, side: str) -> jax.Array:
    from repro.core.lowrank_common import back_project as bp

    return bp(p.astype(jnp.float32), s.astype(jnp.float32), side)


def _back_project_kernel_form(p, s, w, side, pad_rank_to: int):
    """Shared Pallas prologue for both back-projection entry points:
    left-side normalization ((S @ Pᵀ)ᵀ = P @ Sᵀ; W rides along), lead
    flattening, tile padding.  Returns the prepared operands plus everything
    needed to undo the normalization."""
    lead = s.shape[:-2]
    if side == "right":
        s = jnp.swapaxes(s, -1, -2)
        if w is not None:
            w = jnp.swapaxes(w, -1, -2)
    pk, sk = _flatten_lead(p), _flatten_lead(s)
    m, r = int(pk.shape[-2]), int(pk.shape[-1])
    n = int(sk.shape[-1])
    m_pad, bm = _pad_and_block(m, 256, _SUBLANE)
    n_pad, bn = _pad_and_block(n, 512, _LANE)
    r_pad = _round_up(r, _rank_granule(pad_rank_to))
    pk = _pad_axis(_pad_axis(pk, -2, m_pad), -1, r_pad)
    sk = _pad_axis(_pad_axis(sk, -2, r_pad), -1, n_pad)
    wk = None
    if w is not None:
        wk = _pad_axis(_pad_axis(_flatten_lead(w), -2, m_pad), -1, n_pad)
    return pk, sk, wk, (lead, m, n, bm, bn)


def _back_project_unkernel_form(out, lead, m, n, side):
    out = out[..., :m, :n].reshape(lead + (m, n))
    return jnp.swapaxes(out, -1, -2) if side == "right" else out


def back_project(p: jax.Array, s: jax.Array, *, side: str = "left",
                 impl: str = "auto", pad_rank_to: int = 0) -> jax.Array:
    """Dispatched back-projection of a projected-space array ``s`` to full
    ``(*lead, m, n)`` shape — the fused counterpart of
    ``lowrank_common.back_project`` used on every optimizer step's write-back
    path (``W <- W - lr * P NS(R)``).

    left  side: p (*lead, m, r), s (*lead, r, n) -> P @ S
    right side: p (*lead, n, r), s (*lead, m, r) -> S @ Pᵀ
    """
    impl = _resolve_legal(impl, "back_project",
                          back_project_supported(p, s, side), s.shape)
    launch_count.record("back_project")
    if impl == "jnp":
        return _back_project_jnp(p, s, side)

    pk, sk, _, (lead, m, n, bm, bn) = _back_project_kernel_form(
        p, s, None, side, pad_rank_to
    )
    out = _per_device(functools.partial(
        back_project_batched, block_m=bm, block_n=bn,
        interpret=(impl == "interpret")), pk, sk)
    return _back_project_unkernel_form(out, lead, m, n, side)


def back_project_epilogue(
    p: jax.Array,
    s: jax.Array,
    *,
    w: jax.Array | None = None,
    scale=1.0,
    decay=0.0,
    side: str = "left",
    impl: str = "auto",
    pad_rank_to: int = 0,
) -> jax.Array:
    """Fused write-back of a projected-space update: ``scale·back_project(p,
    s) + decay·W`` in one launch, with the GEMM tile staying in VMEM through
    the affine epilogue (see :mod:`repro.kernels.fused_step`).  This is the
    materialization path of the chained API's deferred epilogue
    (``combinators.PendingBack``): scale carries -lr (and GaLore's alpha),
    decay carries -lr·wd, ``w`` the (possibly family-stacked) params.

    ``scale`` / ``decay`` may be traced scalars (schedule-driven lr).
    left  side: p (*lead, m, r), s (*lead, r, n), w (*lead, m, n)
    right side: p (*lead, n, r), s (*lead, m, r), w (*lead, m, n)
    """
    impl = _resolve_legal(impl, "back_project_epilogue",
                          back_project_supported(p, s, side), s.shape)
    launch_count.record("back_project_epilogue")
    if impl == "jnp":
        out = scale * _back_project_jnp(p, s, side)
        if w is not None:
            out = out + decay * w.astype(jnp.float32)
        return out

    pk, sk, wk, (lead, m, n, bm, bn) = _back_project_kernel_form(
        p, s, w, side, pad_rank_to
    )
    sd = jnp.stack([jnp.asarray(scale, jnp.float32),
                    jnp.asarray(decay, jnp.float32)]).reshape(1, 2)
    kernel = functools.partial(back_project_epilogue_batched, block_m=bm,
                               block_n=bn, interpret=(impl == "interpret"))
    if wk is None:
        out = _per_device(lambda p, s, sd: kernel(p, s, None, sd), pk, sk, sd)
    else:
        out = _per_device(kernel, pk, sk, wk, sd)
    return _back_project_unkernel_form(out, lead, m, n, side)


# --------------------------------------------------------------------------
# Newton–Schulz orthogonalization
# --------------------------------------------------------------------------


def newton_schulz_supported(x: jax.Array) -> bool:
    """The NS kernels hold the (s, s) Gram matrix (s = short side) in VMEM."""
    return min(int(x.shape[-2]), int(x.shape[-1])) <= MAX_NS_DIM


# Scoped VMEM the NS kernels plan for: the TPU compiler's default scoped limit
# is 16 MiB, and it refuses m=1024 at block_n=512 (16.31 MiB on v5e).
_NS_VMEM_BUDGET = 14 * 2**20


def _ns_block_target(m: int, block_n: int) -> int:
    """Cap the NS column tile so the kernels fit ``_NS_VMEM_BUDGET``: each
    keeps one (m, m) fp32 block and two (m, block_n) fp32 tiles resident,
    all double-buffered."""
    cap = (_NS_VMEM_BUDGET - 2 * 4 * m * m) // (2 * 2 * 4 * m)
    return max(_LANE, min(block_n, cap // _LANE * _LANE))


def newton_schulz(
    x: jax.Array, *, steps: int = 5, eps: float = 1e-7, impl: str = "auto",
    block_n: int = 512,
) -> jax.Array:
    """Dispatched Newton–Schulz over (..., m, n), matching
    :func:`repro.core.newton_schulz.newton_schulz` semantics."""
    from repro.core.newton_schulz import newton_schulz as ns_jnp

    impl = _resolve_legal(impl, "newton_schulz", newton_schulz_supported(x),
                          x.shape)
    if impl == "jnp":
        # ns_jnp records the launch itself (jnp body), so don't double count.
        return ns_jnp(x, steps=steps, eps=eps)
    launch_count.record("newton_schulz")

    interpret = impl == "interpret"
    orig_dtype = x.dtype
    lead = x.shape[:-2]

    transposed = x.shape[-2] > x.shape[-1]
    if transposed:
        x = jnp.swapaxes(x, -1, -2)
    m, n = int(x.shape[-2]), int(x.shape[-1])
    # Zero padding is exact for NS: padded rows/cols of X are zero, stay zero
    # through every iteration (Gram gains zero blocks; a·X + A2·X preserves
    # them), and the Frobenius norm used for the initial scaling is unchanged.
    m_pad = _round_up(m, _SUBLANE)
    n_pad, bn = _pad_and_block(n, _ns_block_target(m_pad, block_n), _LANE)
    xk = _flatten_lead(_pad_axis(_pad_axis(x, -2, m_pad), -1, n_pad))

    out = _per_device(functools.partial(
        newton_schulz_pallas, steps=steps, eps=eps, block_n=bn,
        interpret=interpret), xk)[..., :m, :n]
    out = out.reshape(lead + (m, n))
    if transposed:
        out = jnp.swapaxes(out, -1, -2)
    return out.astype(orig_dtype)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One dispatched op: its entry point, jnp oracle, and legality check."""

    name: str
    fn: Callable        # dispatching wrapper; accepts impl=
    reference: Callable  # pure-jnp oracle (repro.kernels.ref)
    supported: Callable  # shape-legality predicate for the Pallas path


REGISTRY: dict[str, KernelEntry] = {}


def register(entry: KernelEntry) -> KernelEntry:
    if entry.name not in launch_count.DISPATCH_OPS:
        raise ValueError(
            f"kernel name {entry.name!r} is not in launch_count.DISPATCH_OPS "
            f"{launch_count.DISPATCH_OPS} — the closed-form launch model "
            "(repro.analysis.launch_model) requires the vocabulary to be "
            "closed; extend DISPATCH_OPS first"
        )
    REGISTRY[entry.name] = entry
    return entry


def get_kernel(name: str) -> KernelEntry:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {sorted(REGISTRY)}"
        ) from None


register(KernelEntry(
    name="lowrank_update",
    fn=lowrank_update,
    reference=ref.lowrank_update_ref,
    supported=lowrank_update_supported,
))
register(KernelEntry(
    name="project",
    fn=project,
    reference=lambda p, g, *, side="left": _project_jnp(p, g, side),
    supported=lowrank_update_supported,
))
register(KernelEntry(
    name="back_project",
    fn=back_project,
    reference=ref.back_project_ref,
    supported=back_project_supported,
))
register(KernelEntry(
    name="back_project_epilogue",
    fn=back_project_epilogue,
    reference=ref.back_project_epilogue_ref,
    supported=back_project_supported,
))
def _newton_schulz_ref(x, *, steps=5, eps=1e-7):
    from repro.core.newton_schulz import newton_schulz as ns_jnp

    return ns_jnp(x, steps=steps, eps=eps)


register(KernelEntry(
    name="newton_schulz",
    fn=newton_schulz,
    reference=_newton_schulz_ref,
    supported=newton_schulz_supported,
))
