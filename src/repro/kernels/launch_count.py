"""Trace-time kernel-launch counting for the dispatch layer.

Every dispatched optimizer op (``lowrank_update``, ``project``,
``back_project``, ``back_project_epilogue``, ``newton_schulz``) records one
count per *call* while a :func:`count_launches` context is active.  Because
the dispatchers run at trace time under ``jit``, counting the Python-level
calls counts exactly the kernel launches (``pallas_call``s, or their jnp
fallback ops) the compiled step will contain — which is how
``benchmarks/fused_step.py`` proves the family-stacked engine launches per
shape family, not per leaf.

Usage::

    with count_launches() as counts:
        jax.eval_shape(lambda: opt.update(grads, state, params))
    # counts == {"lowrank_update": 3, "newton_schulz": 3, ...}

:func:`assert_launches` upgrades the counter to a trace-time *assertion*:
the static-analysis layer (``repro.analysis``) computes the closed-form
expected counts from the optimizer's chain composition and
:class:`~repro.core.family_plan.FamilyPlan`, and a mismatch raises
:class:`LaunchCountMismatch` before a single real step runs.

Deliberately dependency-free itself (no jax import); :mod:`repro.core`
callers lazy-import it inside function bodies because the kernels package's
module-load imports run the other way (kernels.newton_schulz pulls
NS_COEFFS from core.newton_schulz).
"""
from __future__ import annotations

import contextlib
from typing import Iterator

# Every op name the dispatch layer may record — the closed vocabulary the
# closed-form launch model (repro.analysis.launch_model) and the assertion
# below validate against.
DISPATCH_OPS = (
    "lowrank_update",
    "project",
    "back_project",
    "back_project_epilogue",
    "newton_schulz",
)

# Collective primitives the sharded-step auditor
# (repro.analysis.collectives) records alongside the dispatch ops when it
# walks a shard_map'ped jaxpr — one count per collective *equation*, so a
# tree-level psum over N gradient leaves counts once, mirroring the single
# wire operation it becomes.
COLLECTIVE_OPS = (
    "psum",
    "all_gather",
    "reduce_scatter",
    "all_to_all",
    "ppermute",
)

_KNOWN_OPS = DISPATCH_OPS + COLLECTIVE_OPS

_ACTIVE: list[dict[str, int]] = []
_FALLBACKS: list[list[tuple[str, tuple[int, ...]]]] = []


def record(op: str) -> None:
    """Count one launch of ``op`` in every active counter (no-op otherwise)."""
    for counts in _ACTIVE:
        counts[op] = counts.get(op, 0) + 1


def record_fallback(op: str, shape: tuple[int, ...]) -> None:
    """Note that a Pallas request for ``op`` at ``shape`` ran the jnp
    reference because the shape breaks a VMEM bound (no-op outside
    :func:`count_fallbacks`)."""
    for log in _FALLBACKS:
        log.append((op, shape))


@contextlib.contextmanager
def count_fallbacks() -> Iterator[list[tuple[str, tuple[int, ...]]]]:
    """Collect every legality fallback traced in the body as ``(op, shape)``."""
    log: list[tuple[str, tuple[int, ...]]] = []
    _FALLBACKS.append(log)
    try:
        yield log
    finally:
        _FALLBACKS.remove(log)


@contextlib.contextmanager
def count_launches() -> Iterator[dict[str, int]]:
    counts: dict[str, int] = {}
    _ACTIVE.append(counts)
    try:
        yield counts
    finally:
        _ACTIVE.remove(counts)


class LaunchCountMismatch(AssertionError):
    """Traced launch counts diverged from the closed-form expectation."""

    def __init__(self, expected: dict[str, int], actual: dict[str, int]):
        self.expected = dict(expected)
        self.actual = dict(actual)
        diff = []
        for op in sorted(set(expected) | set(actual)):
            e, a = expected.get(op, 0), actual.get(op, 0)
            if e != a:
                diff.append(f"{op}: expected {e}, traced {a}")
        super().__init__(
            "kernel-launch count mismatch — " + "; ".join(diff)
            + f" (expected {format_counts(expected)},"
            + f" traced {format_counts(actual)})"
        )


def format_counts(counts: dict[str, int]) -> str:
    """Stable one-line rendering: ``total [op=n, ...]`` in op order."""
    total = sum(counts.values())
    parts = [f"{op}={counts[op]}" for op in _KNOWN_OPS if counts.get(op)]
    parts += [f"{op}={n}" for op, n in sorted(counts.items())
              if op not in _KNOWN_OPS]
    return f"{total} [{', '.join(parts)}]"


@contextlib.contextmanager
def assert_launches(expected: dict[str, int]) -> Iterator[dict[str, int]]:
    """Count launches over the body and raise :class:`LaunchCountMismatch`
    unless they equal ``expected`` exactly (ops absent from ``expected``
    must not appear at all).  Run the body under ``jax.eval_shape`` /
    ``jax.make_jaxpr`` for a pure trace-time check — no math executes::

        with assert_launches({"project": 3, "back_project": 3}):
            jax.eval_shape(lambda: opt.update(grads, state, params))
    """
    for op in expected:
        if op not in _KNOWN_OPS:
            raise ValueError(f"unknown op in expectation: {op!r} "
                             f"(known: {_KNOWN_OPS})")
    with count_launches() as counts:
        yield counts
    clean = {op: n for op, n in expected.items() if n}
    if counts != clean:
        raise LaunchCountMismatch(clean, counts)
