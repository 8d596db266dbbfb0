"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Heavy suites can be selected
with BENCH_ONLY=<name>; default runs everything.  ``--smoke`` runs every
suite at 1–2 steps with result-JSON writes disabled — no timing claims, just
an end-to-end execution check (a tier-1 test invokes it, so suites cannot
silently bit-rot; this harness itself had un-importable suites before that
test existed).

  synthetic_counterexample  — Fig. 1 (GaLore fails, GUM converges)
  memory_table              — Tables 1 & 3 (optimizer-state memory)
  pretrain_proxy            — Table 4 (optimizer comparison on LLaMA-60M)
  bias_residual             — Fig. 4 (GaLore's chi_t bias curve)
  stable_rank               — Figs. 2/3/5 (stable rank & spectra)
  roofline_report           — §Roofline aggregation from the dry-run JSONs
  optimizer_api             — per-leaf chained vs family-stacked per-step
                              overhead (PR 2/3; writes BENCH_optimizer_api.json)
  fused_step                — family-stacked fused engine vs per-leaf
                              chained: step time + kernel-launch counts
                              (PR 3; writes BENCH_fused_step.json)
  rank_policy               — rank-policy engine: projected-state bytes +
                              step time, fixed vs stepwise vs spectral
                              (writes BENCH_rank_policy.json)
  audit_matrix              — static-audit pass matrix: every factory
                              optimizer x fuse_families x fused_epilogue,
                              abstract tracing only (PR 6; writes
                              BENCH_audit_matrix.json)
  resilience                — health-monitor overhead, snapshot/rollback
                              latency, per-save checksum cost (PR 8;
                              writes BENCH_resilience.json)
  sharded_step              — ZeRO-sharded fused step: per-device state
                              bytes vs mesh size, boundary-gather wire
                              bytes, steady-step time sharded vs
                              replicated (PR 9; writes
                              BENCH_sharded_step.json)
  kernel_micro              — per-kernel wall-time microbenchmarks (CPU
                              interpret/xla; indicative only, not TPU)
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# Make `benchmarks.<suite>` (and the suites' `_smoke` import) resolvable no
# matter where the harness is launched from: repo root for the package form,
# this directory for the script form.
_HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.dirname(_HERE), _HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def kernel_micro() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    key = jax.random.PRNGKey(0)

    def bench(fn, *args, n=5):
        fn(*args)  # compile
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        return (time.time() - t0) / n * 1e6

    q = jax.random.normal(key, (2, 256, 8, 64))
    k = jax.random.normal(key, (2, 256, 2, 64))
    us = bench(lambda q, k: ops.attention(q, k, k, causal=True, impl="xla"), q, k)
    print(f"kernel_attention_xla_b2s256,{us:.0f},oracle_path")

    x = jax.random.normal(key, (256, 1024))
    us = bench(lambda x: ops.newton_schulz(x, impl="xla"), x)
    print(f"kernel_newton_schulz_256x1024,{us:.0f},xla_path")

    p = jax.random.normal(key, (1024, 128))
    g = jax.random.normal(key, (1024, 2048))
    r = jax.random.normal(key, (128, 2048))
    us = bench(lambda p, g, r: ops.lowrank_update(p, g, r, 0.95, 1.0, impl="xla"), p, g, r)
    print(f"kernel_lowrank_update_1024x2048_r128,{us:.0f},xla_path")

    xs = jax.random.normal(key, (1, 512, 4, 32)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(key, (1, 512, 4)))
    a = -jnp.exp(jax.random.normal(key, (4,)) * 0.3)
    b = jax.random.normal(key, (1, 512, 16)) * 0.3
    d = jnp.ones((4,)) * 0.1
    us = bench(lambda: ops.ssd(xs, dt, a, b, b, d, chunk=64, impl="xla"))
    print(f"kernel_ssd_s512,{us:.0f},chunked_xla_path")


SUITES = [
    "synthetic_counterexample",
    "memory_table",
    "pretrain_proxy",
    "bias_residual",
    "stable_rank",
    "roofline_report",
    "optimizer_api",
    "fused_step",
    "rank_policy",
    "audit_matrix",
    "resilience",
    "sharded_step",
]

# Suites that commit a results/BENCH_*.json trajectory.  A registered suite
# whose JSON is missing means someone added (or regenerated) the suite and
# forgot to commit the numbers — warn loudly so it can't slip through CI.
RESULT_JSON = {
    "optimizer_api": "BENCH_optimizer_api.json",
    "fused_step": "BENCH_fused_step.json",
    "rank_policy": "BENCH_rank_policy.json",
    "audit_matrix": "BENCH_audit_matrix.json",
    "resilience": "BENCH_resilience.json",
    "sharded_step": "BENCH_sharded_step.json",
}

# Suites that deliberately do NOT commit a result JSON — paper-figure
# reproductions whose output is the figure/table itself (stdout CSV or a
# plot), not a machine-checked trajectory.  Every SUITES entry must appear
# in exactly one of RESULT_JSON / NO_RESULT_JSON; anything in neither is
# registry drift and warns below.
NO_RESULT_JSON = {
    "synthetic_counterexample": "Fig. 1 reproduction; CSV trajectory only",
    "memory_table": "Tables 1 & 3; formula-derived rows, nothing to time",
    "pretrain_proxy": "Table 4; hours-long at paper scale, CSV rows only",
    "bias_residual": "Fig. 4; closed-form bias curve, CSV rows only",
    "stable_rank": "Figs. 2/3/5; spectra depend on the sampled checkpoint",
    "roofline_report": "aggregates results/dryrun/*.json, writes nothing new",
}


def warn_missing_results() -> None:
    results_dir = os.path.join(os.path.dirname(_HERE), "results")
    for suite, fname in RESULT_JSON.items():
        if not os.path.exists(os.path.join(results_dir, fname)):
            print(f"WARNING: suite '{suite}' is registered but "
                  f"results/{fname} is not committed — run "
                  f"PYTHONPATH=src python benchmarks/{suite}.py to record it",
                  file=sys.stderr, flush=True)
    for suite in SUITES:
        if suite not in RESULT_JSON and suite not in NO_RESULT_JSON:
            print(f"WARNING: suite '{suite}' is in neither RESULT_JSON nor "
                  f"NO_RESULT_JSON — declare whether it commits a results "
                  f"JSON (benchmarks/run.py registry drift)",
                  file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1-2 steps per suite, no timing claims, no "
                         "result-JSON writes (CI execution check)")
    args, _ = ap.parse_known_args()
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    only = os.environ.get("BENCH_ONLY")
    warn_missing_results()
    ran_header = False
    for name in SUITES:
        if only and only != name:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        # each suite prints its own CSV header; dedupe by capturing
        print(f"# --- {name} ---", file=sys.stderr)
        mod.main()
        ran_header = True
    if not only or only == "kernel_micro":
        if not ran_header:
            print("name,us_per_call,derived")
        kernel_micro()
    if args.smoke:
        print("# smoke run complete", flush=True)


if __name__ == "__main__":
    main()
