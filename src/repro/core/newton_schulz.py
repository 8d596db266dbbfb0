"""Newton–Schulz orthogonalization (msign) used by Muon.

``newton_schulz(X)`` approximates ``msign(X) = U V^T`` for ``X = U Σ V^T``.
We use Keller Jordan's quintic iteration with the standard coefficients
(a, b, c) = (3.4445, -4.7750, 2.0315), 5 steps, computed in bf16-or-f32.

Implementation dispatch (the ``impl`` argument):

  * ``"jnp"`` / ``"xla"`` — the pure-jnp path below (bit-stable reference).
  * ``"auto"``            — :mod:`repro.kernels.dispatch` picks the fused
                            Pallas TPU kernels on TPU and this jnp path
                            elsewhere (shape-illegal inputs also fall back).
  * ``"pallas"``          — the compiled Pallas kernels; raises off-TPU.
  * ``"interpret"``       — the Pallas kernels in the interpreter, on any
                            backend (what the CPU tests use).

Key property for the paper (Lemma 1 / Property II):
``newton_schulz(P @ X) == P @ newton_schulz(X)`` whenever ``PᵀP = I`` —
tested exactly in tests/test_unbiasedness.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NS_COEFFS = (3.4445, -4.7750, 2.0315)
NS_STEPS = 5


def newton_schulz(
    x: jax.Array, *, steps: int = NS_STEPS, eps: float = 1e-7, impl: str = "jnp"
) -> jax.Array:
    """Quintic Newton–Schulz iteration toward the matrix sign/polar factor.

    Works on (..., m, n); iterates on the transposed problem when m > n so the
    Gram matrix XXᵀ is the small side (exactly Muon's reference trick).
    """
    if impl not in ("jnp", "xla"):
        # Lazy import: repro.kernels.newton_schulz imports NS_COEFFS from here.
        from repro.kernels import dispatch

        resolved = dispatch.resolve_impl(impl)
        if resolved != "jnp":
            return dispatch.newton_schulz(x, steps=steps, eps=eps, impl=resolved)

    # Lazy import: at module-load time repro.kernels.newton_schulz imports
    # NS_COEFFS from here, so a top-level kernels import would be circular.
    # (This does pull in the kernels package on first call.)
    from repro.kernels import launch_count

    launch_count.record("newton_schulz")
    # The stage name the kernels' dispatcher gives its jnp path, so the
    # device trace names this path alike whichever entry point it took.
    with jax.named_scope("newton_schulz.jnp"):
        a, b, c = NS_COEFFS
        orig_dtype = x.dtype
        x = x.astype(jnp.float32)

        transposed = x.shape[-2] > x.shape[-1]
        if transposed:
            x = jnp.swapaxes(x, -1, -2)

        # Spectral-norm-ish normalization so singular values land in the basin.
        norm = jnp.linalg.norm(x, axis=(-2, -1), keepdims=True)
        x = x / (norm + eps)

        def body(_, x):
            xxt = x @ jnp.swapaxes(x, -1, -2)          # (..., m, m), m <= n
            bxx = b * xxt + c * (xxt @ xxt)            # quintic combination
            return a * x + bxx @ x

        x = jax.lax.fori_loop(0, steps, body, x)

        if transposed:
            x = jnp.swapaxes(x, -1, -2)
        return x.astype(orig_dtype)


def msign_exact(x: jax.Array) -> jax.Array:
    """Exact UVᵀ via SVD — the oracle for Assumption 4 and kernel tests."""
    u, _, vt = jnp.linalg.svd(x.astype(jnp.float32), full_matrices=False)
    return u @ vt


def muon_scale(shape: tuple[int, int]) -> float:
    """Muon's shape-dependent update scale: sqrt(max(1, m/n)) keeps the RMS of
    the orthogonalized update comparable across aspect ratios (Jordan et al.).
    Applied by ``muon`` (default on) and, behind ``use_muon_scale``, by GUM."""
    m, n = shape[-2], shape[-1]
    return max(1.0, m / n) ** 0.5
