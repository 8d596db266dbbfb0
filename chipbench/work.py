"""Operations the algorithms need at their shapes, bytes a kernel moves
through HBM, and the least time the chip could take for them: the yardstick
of the kernel rooflines.

Operations are counted from the work the algorithm requires at the called
shapes, not from what a kernel launches, so a kernel written another way
reads against the same numbers.
"""
from __future__ import annotations

import math

BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}


def ns_gram(L: int, m: int, n: int) -> int:
    """Flops of the Gram product A = X Xᵀ of a Newton–Schulz iteration over
    L (m, n) blocks."""
    return 2 * L * m * m * n


def ns_apply(L: int, m: int, n: int) -> int:
    """Flops of the iteration's update a X + B X with B (m, m).  (B = b A +
    c A², an m³ product, runs outside the two products timed and is not
    counted.)"""
    return L * (2 * m * m * n + 2 * m * n)


def lowrank_update(L: int, m: int, n: int, r: int) -> int:
    """Flops of ``R <- beta R + c PᵀG`` over L blocks, G (m, n) projected on
    its m side: the projection 2 m n r and the update 3 r n."""
    return L * (2 * m * n * r + 3 * r * n)


def project(L: int, m: int, n: int, r: int) -> int:
    """Flops of ``PᵀG`` alone over L blocks, G (m, n) projected on its m
    side: 2 m n r."""
    return L * 2 * m * n * r


def hbm_bytes(tensors) -> int:
    """Bytes of the (dtype, shape, in_hbm) tensors that live in HBM; a
    tensor the compiler placed in on-chip memory moves no HBM bytes."""
    return sum(BYTES[dt] * math.prod(shape) for dt, shape, in_hbm in tensors if in_hbm)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The larger of the compute bound and the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
