"""Fused Pallas kernels vs jnp reference: per-step time for the two low-rank
optimizer hot loops at GaLore/GUM's production operating point (rank <= 512
against (m, n) hidden matrices, stacked (L, m, n) families).

Emits a step-time table comparing the dispatch paths:

  jnp       — the pure-jnp reference (what "auto" runs off-TPU)
  fused     — the Pallas kernels via repro.kernels.dispatch ("auto" on TPU;
              off-TPU this script falls back to the interpreter and the
              numbers measure correctness plumbing, not kernel speed — the
              table says which path actually ran)

Usage: PYTHONPATH=src python benchmarks/kernel_dispatch.py [--steps N]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

# (L, m, n, r): stacked-family shapes at the paper's operating points.
SHAPES = [
    (1, 1024, 1024, 128),
    (4, 1024, 4096, 128),
    (4, 4096, 1024, 256),   # right-side projection (m > n)
    (8, 2048, 2048, 512),
    (1, 1000, 768, 96),     # ragged: exercises the padding wrappers
]

# Off-TPU the "fused" path is the Pallas *interpreter* — orders of magnitude
# slower than compiled code and only meaningful as a plumbing check, so the
# sweep drops to toy shapes that finish in seconds.
SHAPES_INTERPRET = [
    (1, 128, 128, 16),
    (2, 128, 256, 32),
    (2, 256, 128, 32),      # right-side projection
    (1, 100, 76, 12),       # ragged: exercises the padding wrappers
]


def _time_fn(fn, *args, steps: int, warmup: int = 2) -> float:
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def bench_lowrank(L, m, n, r, *, steps: int, pallas_impl: str):
    side = "left" if m <= n else "right"
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    s = min(m, n)
    p = jax.random.normal(ks[0], (L, s, r))
    g = jax.random.normal(ks[1], (L, m, n))
    rst = jax.random.normal(
        ks[2], (L, r, n) if side == "left" else (L, m, r)
    )

    def run(impl):
        f = jax.jit(
            lambda p, g, rs: dispatch.lowrank_update(
                p, g, rs, 0.95, 4.0 / 3, side=side, impl=impl
            )
        )
        return _time_fn(f, p, g, rst, steps=steps)

    return run("jnp"), run(pallas_impl)


def bench_ns(L, m, n, r, *, steps: int, pallas_impl: str):
    # NS runs on the projected momentum (r, n) per block — the GUM hot loop.
    x = jax.random.normal(jax.random.PRNGKey(1), (L, r, max(m, n)))

    def run(impl):
        f = jax.jit(lambda x: dispatch.newton_schulz(x, impl=impl))
        return _time_fn(f, x, steps=steps)

    return run("jnp"), run(pallas_impl)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")

    pallas_impl = "pallas" if dispatch.backend() == "tpu" else "interpret"
    shapes = SHAPES if pallas_impl == "pallas" else SHAPES_INTERPRET
    print(f"# backend={dispatch.backend()} fused_path={pallas_impl} "
          f"steps={args.steps}")
    print("op,L,m,n,r,jnp_ms,fused_ms,speedup")
    for L, m, n, r in shapes:
        t_ref, t_fused = bench_lowrank(L, m, n, r, steps=args.steps,
                                       pallas_impl=pallas_impl)
        print(f"lowrank_update,{L},{m},{n},{r},{t_ref*1e3:.3f},"
              f"{t_fused*1e3:.3f},{t_ref/max(t_fused,1e-12):.2f}x")
        t_ref, t_fused = bench_ns(L, m, n, r, steps=args.steps,
                                  pallas_impl=pallas_impl)
        print(f"newton_schulz,{L},{m},{n},{r},{t_ref*1e3:.3f},"
              f"{t_fused*1e3:.3f},{t_ref/max(t_fused,1e-12):.2f}x")


if __name__ == "__main__":
    main()
