"""Training launcher: ``python -m repro.launch.train --arch llama-350m ...``

Runs a real training loop on the devices JAX finds.  On a TPU the optimizer
hot loops run the Pallas kernels (``--kernel-impl auto``); with
``JAX_PLATFORMS=cpu`` (the test suite) they run the jnp reference, and
``--kernel-impl interpret`` runs the Pallas kernels in the interpreter.
``python chip_smoke.py`` at the repo root drives this entry point in process
on one chip (``--chips 4`` for the four-chip mesh phase).

``main(argv)`` is callable in process and returns ``(trainer, result)``.
It is ``run(*build_trainer(argv))``: ``build_trainer`` stops before the
first step, so a caller can compile the step ahead of ``run``.  Outside the
CPU it points JAX's persistent compilation cache at
``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``
(:func:`repro.launch.devices.enable_compile_cache`).

``--audit`` runs the full static audit before step 0 (chain lint, launch
model, dtype flow, recompile hazards, and — when ``--mesh`` is set — the
sharded collective-schedule and donation/buffer passes) and exits non-zero
on any error finding, so a misconfigured launch dies before it burns a
single step.  ``--mesh data=4`` trains over a data mesh of the real devices;
under ``JAX_PLATFORMS=cpu`` it forces that many host CPU devices.
"""
from __future__ import annotations

import argparse
import math
import sys


def build_trainer(argv=None):
    """Parse ``argv`` and build the run's :class:`~repro.train.Trainer`
    (running ``--audit`` first); returns ``(trainer, args)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--opt", default="gum")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--gamma", type=int, default=2)
    ap.add_argument("--period", type=int, default=200)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "jnp", "pallas", "interpret"],
                    help="optimizer hot-loop implementation "
                         "(OptimizerConfig.kernel_impl): auto = fused Pallas "
                         "kernels on TPU, jnp reference elsewhere")
    ap.add_argument("--pad-rank-to", type=int, default=0,
                    help="opt-in lane-aligned rank padding for the low-rank "
                         "Pallas kernels (e.g. 128)")
    ap.add_argument("--fuse-families", action="store_true",
                    help="family-stacked fused optimizer execution: one "
                         "batched launch per shape family instead of one "
                         "per parameter leaf (trajectory-identical)")
    ap.add_argument("--shard-state", action="store_true",
                    help="ZeRO-style sharding of the family-stacked low-rank "
                         "optimizer state over the data axis (requires "
                         "--fuse-families and --mesh): steady steps stay "
                         "fully sharded; full gradients are gathered only "
                         "at projector-refresh boundaries")
    ap.add_argument("--fused-epilogue", action="store_true",
                    help="fold chain-tail epilogues (-lr, weight decay) into "
                         "the back-projection GEMM (back_project_epilogue "
                         "kernel; not bit-exact vs the unfused tail; applies "
                         "to galore-family optimizers — inert for gum/fira, "
                         "whose inners emit full-shape updates)")
    ap.add_argument("--rank-policy", default=None,
                    help="time-varying / per-family rank "
                         "(repro.core.rank_policy): 'fixed:64', "
                         "'stepwise:0=128,500=64', 'family:512x512=32,...', "
                         "'spectral[:target_energy]' — decisions land on "
                         "projector-refresh boundaries; the trainer migrates "
                         "optimizer state and re-jits (bounded by the ladder); "
                         "policy state rides in checkpoint extras so resume "
                         "is exact across rank changes")
    ap.add_argument("--rank-ladder", default="",
                    help="comma-separated ranks an adaptive policy may emit, "
                         "e.g. 32,64,128 (bounds recompilation; empty = "
                         "powers of two up to --rank)")
    ap.add_argument("--mesh", default="", metavar="AXIS=N",
                    help="train over a data mesh of the real devices, e.g. "
                         "data=4 on a four-chip host (with JAX_PLATFORMS=cpu "
                         "that many host CPU devices are forced)")
    ap.add_argument("--resilience", nargs="?", const="", default=None,
                    metavar="SPEC",
                    help="turn on the health monitor + recovery ladder "
                         "(repro.resilience): bare flag = defaults, or a "
                         "knob spec like 'ring=3,snapshot_every=5,spike_z=4' "
                         "(any ResilienceConfig field)")
    ap.add_argument("--inject", default=None, metavar="PLAN",
                    help="deterministic fault injection (requires/implies "
                         "nothing about --resilience; combine them to "
                         "exercise recovery): 'kind@step[*scale][#arg];...' "
                         "e.g. 'grad_nan@5;grad_spike@9*1e6;refresh_zero@13;"
                         "ckpt_bitflip@20;kill_save@40#3'")
    ap.add_argument("--inject-seed", type=int, default=0,
                    help="seed for the fault plan's corruption RNG "
                         "(bit positions etc.)")
    ap.add_argument("--telemetry", nargs="?", const="", default=None,
                    metavar="SPEC",
                    help="turn on the telemetry run log (repro.telemetry): "
                         "bare flag = defaults, or a knob spec like "
                         "'every=10,stdout=0,memory=256' (any "
                         "TelemetryConfig field).  One run writes one "
                         "schema-versioned events.jsonl (step metrics, "
                         "health/recovery/fault/rank-policy/checkpoint "
                         "events, timing spans) plus in-jit subspace "
                         "instrumentation (captured energy, projector "
                         "drift, sampled bias residual); summarize with "
                         "python -m repro.telemetry.report")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="events.jsonl path override "
                         "(default <ckpt-dir>/events.jsonl)")
    ap.add_argument("--profile-steps", default=None, metavar="A:B",
                    help="jax.profiler trace window covering steps [A, B), "
                         "written under <ckpt-dir>/profile")
    ap.add_argument("--audit", action="store_true",
                    help="run the full static audit — including the sharded "
                         "collective/buffer passes when --mesh is set — "
                         "before step 0, exiting non-zero on any error "
                         "finding (parity with dryrun.py --audit)")
    args = ap.parse_args(argv)

    from repro.launch.devices import (
        cpu_requested,
        enable_compile_cache,
        force_host_device_count,
    )

    # device forcing must precede the first jax backend use below
    mesh_axes = None
    if args.mesh:
        from repro.analysis.audit import _parse_mesh

        mesh_axes = _parse_mesh(args.mesh)
        if cpu_requested():
            force_host_device_count(math.prod(size for _, size in mesh_axes))

    import jax

    enable_compile_cache()

    from repro.configs import RunConfig, get_config, get_smoke
    from repro.core import OptimizerConfig
    from repro.data import DataConfig
    from repro.models import build_model
    from repro.train import Trainer

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(
        name=args.opt, lr=args.lr, rank=args.rank, gamma=args.gamma,
        period=args.period, kernel_impl=args.kernel_impl,
        pad_rank_to=args.pad_rank_to,
        fuse_families=args.fuse_families or args.shard_state,
        fused_epilogue=args.fused_epilogue,
        shard_state=args.shard_state,
        rank_policy=args.rank_policy,
        rank_ladder=tuple(int(r) for r in args.rank_ladder.split(",") if r),
        telemetry=args.telemetry is not None,
    )
    run_cfg = RunConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir, resume=not args.no_resume,
        ckpt_every=max(args.steps // 4, 1), log_every=10,
    )
    data_cfg = DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        num_hosts=jax.process_count(), host_id=jax.process_index(),
    )

    mesh = None
    if mesh_axes is not None:
        from repro.launch.mesh import make_mesh

        sizes = tuple(size for _, size in mesh_axes)
        names = tuple(axis for axis, _ in mesh_axes)
        n = math.prod(sizes)
        if n > jax.device_count():
            raise SystemExit(f"--mesh {args.mesh} needs {n} devices, found "
                             f"{jax.device_count()} (with JAX_PLATFORMS=cpu "
                             "that many host CPU devices are forced)")
        mesh = make_mesh(sizes, names, devices=jax.devices()[:n])

    if args.audit:
        # The full static audit of exactly what is about to train, before
        # step 0: chain lint + launch model + dtype flow + recompile pass on
        # the optimizer, and — when a mesh is configured — the sharded
        # collective-schedule / donation / per-shard-buffer passes.  Any
        # error finding aborts the launch (parity with dryrun.py --audit).
        from repro.analysis import audit_optimizer, audit_sharded

        params_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        reports = [audit_optimizer(opt_cfg, params_abs,
                                   ladder=opt_cfg.rank_ladder)]
        if mesh_axes is not None:
            reports.append(audit_sharded(
                opt_cfg, model=model, mesh_axes=mesh_axes,
                grad_clip=run_cfg.grad_clip,
                batch_size=args.batch))
        for rep in reports:
            print(rep.format(), flush=True)
        if not all(rep.ok for rep in reports):
            print("audit: error finding(s) before step 0 — not training",
                  flush=True)
            sys.exit(1)

    inject = None
    if args.inject:
        from repro.resilience import FaultPlan

        inject = FaultPlan.parse(args.inject, seed=args.inject_seed)

    trainer = Trainer(model, opt_cfg, run_cfg, data_cfg, mesh=mesh,
                      microbatches=args.microbatches,
                      resilience=args.resilience, inject=inject,
                      telemetry=args.telemetry, events_out=args.events_out,
                      profile_steps=args.profile_steps)
    return trainer, args


def run(trainer, args):
    """Train the run ``build_trainer`` built; returns its TrainResult."""
    result = trainer.train()
    print(
        f"done: step={result.final_step} "
        f"first_loss={result.losses[0]:.4f} last_loss={result.losses[-1]:.4f} "
        f"skipped={result.skipped_nonfinite} stragglers={len(result.straggler_steps)}"
        + (f" resumed_from={result.resumed_from}" if result.resumed_from else "")
    )
    if result.recovery_counts:
        fired = {k: v for k, v in result.recovery_counts.items() if v}
        print(f"resilience: recoveries={fired or '{}'} "
              f"health_events={len(result.health_events)} "
              f"faults_fired={len(result.fault_log)}")
    if result.events_path:
        # train() already emitted the closing counters record; only the
        # sink handles remain, and process exit covers those.
        print(f"telemetry: {result.events_path} "
              f"(python -m repro.telemetry.report {args.ckpt_dir})")
    return result


def main(argv=None):
    trainer, args = build_trainer(argv)
    return trainer, run(trainer, args)


if __name__ == "__main__":
    main()
