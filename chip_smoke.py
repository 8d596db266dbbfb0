#!/usr/bin/env python3
"""Chip smoke test: the paper's LLaMA-350M trained with GUM on a TPU through
the normal entry point (``repro.launch.train``), in this one process.

    python chip_smoke.py             # one chip: kernel parity, then 8 steps
    python chip_smoke.py --chips 4   # four chips: data=4 mesh, sharded vs
                                     # replicated optimizer state, 6 steps

One chip:
  1. kernel parity — each of the five dispatched optimizer ops, compiled
     Pallas against the jnp reference at ``highest`` matmul precision, at
     llama-350m widths (rank 128); fails above ``PARITY_BOUND``;
  2. training — ``train.main`` with GUM (rank 128, gamma 2, period 4), batch
     8 x 1024, 8 steps, random weights from seed 0, checkpoints in a temporary
     directory; fails unless every loss is finite, the last is below the
     first, the compiled step holds Pallas kernels (``tpu_custom_call``) and
     no dispatched op fell back to the jnp reference.

Four chips (``--chips 4``): the same model over ``--mesh data=4
--fuse-families``, once with ``--shard-state`` and once with replicated
state, 6 steps at period 3 (``train.build_trainer``, both steps compiled side
by side, then ``train.run``); fails unless the two loss curves agree within
``SHARDED_LOSS_RTOL``.

Off a TPU it exits non-zero and names the platform it found.  Every phase
runs even after another has failed; any failure gives a non-zero exit and no
result line.  The last line of stdout is ``{"ok": true, "device": {...}}``;
the lines before it are information, not metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

RANK = 128
# max|pallas - ref| / max|ref| per op.  A tiling, padding or index error
# gives O(1); one bf16 pass over fp32 operands gives about 2^-9 (~2e-3).
# GEMMs: 5x that.  Newton-Schulz: 5 iterations, each one a bf16 pass in the
# worst case, so 25x.
PARITY_BOUND = {
    "lowrank_update": 1e-2,
    "project": 1e-2,
    "back_project": 1e-2,
    "back_project_epilogue": 1e-2,
    "newton_schulz": 5e-2,
}
# Sharded vs replicated optimizer state: same math, other partitioning, so
# only the f32 accumulation order differs.
SHARDED_LOSS_RTOL = 1e-3

TRAIN_ARGS = ["--arch", "llama-350m", "--opt", "gum", "--rank", str(RANK),
              "--gamma", "2", "--batch", "8", "--seq", "1024", "--no-resume"]


FAILURES: list[str] = []


def fail(msg: str) -> None:
    """Record a failed check; the run goes on and exits non-zero at its end."""
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    FAILURES.append(msg)


def die(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def phase(name: str, fn, *args):
    """Run one phase; an exception fails it without stopping the next."""
    try:
        return fn(*args)
    except Exception as e:
        traceback.print_exc()
        fail(f"{name}: {type(e).__name__}: {e}")
        return None


# ------------------------------------------------------------ kernel parity


def parity_cases(jax, jnp):
    """(op, shape label, fn(*args, impl), args) at llama-350m widths: the
    attention (1024x1024) and MLP (1024x2736, 2736x1024) matrices, a stacked
    lead of 2, rank 128, on the projector side GUM picks (left iff m <= n)."""
    from repro.kernels import dispatch

    key = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    rnd = lambda *shape: jax.random.normal(next(key), shape, jnp.float32)
    lead, r = 2, RANK
    cases = []
    for m, n in ((1024, 1024), (1024, 2736), (2736, 1024)):
        side = "left" if m <= n else "right"
        k = m if side == "left" else n
        p = jnp.linalg.qr(rnd(lead, k, r))[0]
        g = rnd(lead, m, n)
        s = rnd(lead, r, n) if side == "left" else rnd(lead, m, r)
        w = rnd(lead, m, n)
        label = f"{m}x{n} {side}"
        ops = {
            "lowrank_update": (lambda p, g, s, impl, side=side:
                               dispatch.lowrank_update(p, g, s, 0.95, 1.5,
                                                       side=side, impl=impl),
                               (p, g, s)),
            "project": (lambda p, g, impl, side=side:
                        dispatch.project(p, g, side=side, impl=impl), (p, g)),
            "back_project": (lambda p, s, impl, side=side:
                             dispatch.back_project(p, s, side=side, impl=impl),
                             (p, s)),
            "back_project_epilogue": (
                lambda p, s, w, impl, side=side:
                dispatch.back_project_epilogue(p, s, w=w, scale=-1e-3,
                                               decay=-1e-5, side=side,
                                               impl=impl),
                (p, s, w)),
        }
        for op, (fn, args) in ops.items():
            cases.append((op, label, fn, args))
        # Newton-Schulz on the projected state (GUM's low-rank step) and on
        # the full matrix (its gamma sampled full-rank blocks).
        ns = lambda x, impl: dispatch.newton_schulz(x, impl=impl)
        cases.append(("newton_schulz", f"{tuple(s.shape[1:])} projected",
                      ns, (s,)))
        cases.append(("newton_schulz", f"{m}x{n} full", ns, (g,)))
    return cases


def kernel_parity(jax, jnp) -> None:
    from repro.kernels import launch_count

    worst: dict[str, float] = {}
    with launch_count.count_fallbacks() as fell_back:
        for op, label, fn, args in parity_cases(jax, jnp):
            got = jax.jit(lambda *a, fn=fn: fn(*a, impl="pallas"))(*args)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda *a, fn=fn: fn(*a, impl="jnp"))(*args)
            err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
            worst[op] = max(worst.get(op, 0.0), err)
            print(f"parity {op} [{label}] max_rel_err={err:.3e} "
                  f"bound={PARITY_BOUND[op]:.0e}", flush=True)
    for op, err in worst.items():
        print(f"parity {op}: worst max_rel_err={err:.3e} "
              f"bound={PARITY_BOUND[op]:.0e}", flush=True)
    if fell_back:
        fail(f"parity shapes fell back to jnp: {fell_back}")
    bad = {op: e for op, e in worst.items()
           if not e <= PARITY_BOUND[op]}
    if bad:
        fail(f"kernel parity above bound: {bad}")


# ------------------------------------------------------------ training


class CompileLog:
    """Backend compile durations JAX reports while a run is traced."""

    def __init__(self, jax):
        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), float(secs)))

    def since(self, mark: int):
        return self.events[mark:]


def run_training(start, compiles: CompileLog, tag: str):
    """``start()`` trains through the entry point and returns (trainer,
    result); returns (trainer, result, compiled step, legality fallbacks)."""
    from repro.kernels import launch_count

    mark = len(compiles.events)
    with launch_count.count_fallbacks() as fell_back:
        trainer, result = start()
    run_compiles = compiles.since(mark)
    mark = len(compiles.events)
    compiled = trainer.lower_step().compile()
    recompiled = compiles.since(mark)
    name, secs = max(run_compiles, key=lambda e: e[1], default=("-", 0.0))
    print(f"{tag}: longest compile in the run {secs:.1f} s ({name}); "
          f"{len(run_compiles)} programs compiled in the run, "
          f"{sum(s for _, s in run_compiles):.1f} s in all; "
          f"compiles on re-reading the step: {len(recompiled)}", flush=True)
    return trainer, result, compiled, list(fell_back)


def report_steps(result, tag: str) -> None:
    losses, times = result.losses, result.step_times
    for i, (loss, dt) in enumerate(zip(losses, times)):
        print(f"{tag}: step {i} loss={loss:.6f} wall={dt:.4f} s", flush=True)
    if len(times) > 1:
        print(f"{tag}: steady step wall median={statistics.median(times[1:]):.4f}"
              f" s (steps 1..{len(times) - 1})", flush=True)


def check_losses(losses, n: int, tag: str) -> None:
    if len(losses) != n or not all(math.isfinite(x) for x in losses):
        fail(f"{tag}: expected {n} finite losses, got {losses}")
    elif not losses[-1] < losses[0]:
        fail(f"{tag}: last loss {losses[-1]} not below first {losses[0]}")


def check_step(jax, compiled, fell_back, tag: str):
    """Print and check what the compiled step holds; returns its text."""
    text = compiled.as_text()
    n_custom = len(re.findall(r'custom_call_target="tpu_custom_call"', text))
    mem = compiled.memory_analysis()
    print(f"{tag}: tpu_custom_call in compiled step: {n_custom}", flush=True)
    print(f"{tag}: legality fallbacks to jnp: {len(fell_back)} {fell_back}",
          flush=True)
    print(f"{tag}: step memory_analysis args={mem.argument_size_in_bytes} "
          f"temp={mem.temp_size_in_bytes} out={mem.output_size_in_bytes} "
          f"alias={mem.alias_size_in_bytes} bytes (per device)", flush=True)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    print(f"{tag}: peak_bytes_in_use per device={peaks} bytes_limit="
          f"{(jax.devices()[0].memory_stats() or {}).get('bytes_limit')}",
          flush=True)
    if n_custom <= 0:
        fail(f"{tag}: the compiled step holds no Pallas kernel "
             "(tpu_custom_call)")
    if fell_back:
        fail(f"{tag}: dispatched ops fell back to jnp: {fell_back}")
    return text


def train_one_chip(jax, compiles: CompileLog) -> None:
    from repro.launch import train

    argv = TRAIN_ARGS + ["--period", "4", "--steps", "8"]
    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        _, result, compiled, fell_back = run_training(
            lambda: train.main(argv + ["--ckpt-dir", ckpt]), compiles, "train")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    report_steps(result, "train")
    check_step(jax, compiled, fell_back, "train")
    check_losses(result.losses, 8, "train")


FOUR_CHIP_RUNS = {
    tag: TRAIN_ARGS + ["--period", "3", "--steps", "6", "--mesh", "data=4",
                       "--fuse-families"] + extra
    for tag, extra in (("sharded", ["--shard-state"]), ("replicated", []))
}


def precompile(trainers, compiles: CompileLog) -> None:
    """Compile the first train step of several built runs side by side.

    A cold step compile takes minutes and leaves most host cores idle, so
    the compiles run on threads at once.  Each is lowered on the arrays the
    trainer's ``init_state`` makes, so the run's own first step finds its
    executable in JAX's in-memory cache."""
    from concurrent.futures import ThreadPoolExecutor

    t0, mark = time.time(), len(compiles.events)
    lowered = []
    for trainer in trainers:
        params, opt_state = trainer.init_state()
        lowered.append(trainer.lower_step(params, opt_state))
        del params, opt_state
    with ThreadPoolExecutor(len(lowered)) as pool:
        for future in [pool.submit(low.compile) for low in lowered]:
            future.result()
    steps = [f"{name} {secs:.1f} s" for name, secs in compiles.since(mark)
             if secs > 1.0]
    print(f"precompile: {len(lowered)} steps side by side in "
          f"{time.time() - t0:.1f} s wall; step compiles: {steps}", flush=True)


def train_four_chips(jax, compiles: CompileLog, tag: str, trainer, args):
    """One built data=4 run; returns its losses."""
    from repro.launch import train

    trainer, result, compiled, fell_back = run_training(
        lambda: (trainer, train.run(trainer, args)), compiles, tag)
    if trainer.mesh.devices.size != 4:
        fail(f"{tag}: trained on a mesh of {trainer.mesh.devices.size}")
    report_steps(result, tag)
    text = check_step(jax, compiled, fell_back, tag)
    n_gather = len(re.findall(r"= \S+ all-gather(?:-start)?\(", text))
    print(f"{tag}: all-gather in compiled step: {n_gather}", flush=True)
    check_losses(result.losses, 6, tag)
    return result.losses


def four_chips(jax, compiles: CompileLog) -> None:
    from repro.launch import train

    if jax.device_count() != 4:
        die(f"--chips 4 needs 4 devices, found {jax.device_count()}")
    ckpts = {tag: tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
             for tag in FOUR_CHIP_RUNS}
    try:
        built = {tag: train.build_trainer(argv + ["--ckpt-dir", ckpts[tag]])
                 for tag, argv in FOUR_CHIP_RUNS.items()}
        for trainer, _ in built.values():
            # a sharded save every 3 steps, not every step: four chips cost
            # four times as much for each second the host spends saving
            trainer.run = dataclasses.replace(trainer.run, ckpt_every=3)
        phase("precompile", precompile, [t for t, _ in built.values()],
              compiles)
        curves = {tag: phase(tag, train_four_chips, jax, compiles, tag,
                             *built[tag])
                  for tag in FOUR_CHIP_RUNS}
    finally:
        for ckpt in ckpts.values():
            shutil.rmtree(ckpt, ignore_errors=True)
    if None in curves.values():
        return
    rel = [abs(a - b) / abs(b)
           for a, b in zip(curves["sharded"], curves["replicated"])]
    print(f"sharded vs replicated loss: max rel diff={max(rel):.3e} "
          f"bound={SHARDED_LOSS_RTOL:.0e} per step={[f'{x:.2e}' for x in rel]}",
          flush=True)
    if not max(rel) <= SHARDED_LOSS_RTOL:
        fail("sharded and replicated optimizer state disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        die(f"needs a TPU; JAX found platform {platform!r} "
            f"({len(devices)} device(s))")
    try:
        from repro.launch.devices import enable_compile_cache
    except ImportError as e:
        die(f"cannot import the repo's package from {HERE}/src: {e}")
    cache = enable_compile_cache()
    print(f"device_kind={devices[0].device_kind} platform={platform} "
          f"count={len(devices)} jax={jax.__version__} compile_cache={cache}",
          flush=True)

    compiles = CompileLog(jax)
    t0 = time.time()
    if args.chips == 4:
        four_chips(jax, compiles)
    else:
        phase("kernel parity", kernel_parity, jax, jnp)
        phase("train", train_one_chip, jax, compiles)
    print(f"total wall {time.time() - t0:.1f} s", flush=True)
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
