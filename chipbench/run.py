"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: make the weights and the optimizer state on the device from the
seed, compile the Trainer's jitted train step ahead of time, call it once
and drop the result (a program's first call is slower than the rest), run
the cell's first steps from the seed again (step 0 is a projector refresh
and is timed as the refresh sample), warm up, then measure steady steps for
``--seconds``.  Afterwards the program's state is freed and the plain
reference recomputes the first steps; ``correct`` says whether the program
stayed within the cell's limits.  Each phase of the run is timed on the
host clock and printed to standard error (``setup_phases``).  The last line
of standard output is the result as JSON.  With ``--trace 1`` the window
runs under the profiler and the result carries the per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

WORK_DIR = os.path.join(ROOT, ".chipbench_run")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
GIB = 1024 ** 3


class NoChip(RuntimeError):
    pass


class Phases:
    """Host seconds of the phases of a run, in the order they ran: each
    ``mark`` closes the phase that began at the one before (the first at
    process start), so together they cover the whole run: the set-up up to
    the window, the window, the per-layer reading and the reference."""

    def __init__(self, start: float = _T0):
        self.last, self.seconds = start, {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self.last
        self.last = now


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != "tpu":
        raise NoChip(f"no TPU found: JAX's platform is {platform!r} "
                     f"({len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)} "
                     f"{platform} device(s)")
    return devs[:chips]


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however short its compile."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build_trainer(cell, devices):
    """The program under test: a Trainer for the cell's model and optimizer
    (the normal path: Trainer -> make_train_step -> lowrank -> dispatch),
    over the mesh the traffic mix names when the cell has more than one
    chip."""
    import dataclasses

    from repro.configs.base import ModelConfig, RunConfig
    from repro.core import OptimizerConfig
    from repro.data import DataConfig
    from repro.models import build_model
    from repro.train import Trainer

    model = build_model(ModelConfig(**cell.config["program"]))
    fields = {f.name for f in dataclasses.fields(OptimizerConfig)}
    opt_cfg = OptimizerConfig(**{k: v for k, v in cell.traffic["optimizer"].items()
                                 if k in fields})
    run_cfg = RunConfig(steps=1, ckpt_dir=os.path.join(WORK_DIR, "ckpt"),
                        resume=False, ckpt_every=0, log_every=0,
                        grad_clip=float(cell.traffic["grad_clip"]))
    data_cfg = DataConfig(vocab=model.cfg.vocab,
                          seq_len=int(cell.traffic["seq_len"]),
                          global_batch=int(cell.traffic["batch"]))
    axes = cell.traffic.get("mesh") or {}
    mesh = None
    if axes:
        from repro.launch.mesh import make_mesh

        sizes = tuple(int(n) for n in axes.values())
        if math.prod(sizes) != len(devices):
            raise ValueError(f"mesh {axes} needs {math.prod(sizes)} chips, "
                             f"the cell has {len(devices)}")
        mesh = make_mesh(sizes, tuple(axes), devices=devices)
    elif len(devices) > 1:
        raise ValueError("a cell on more than one chip names its mesh in its traffic mix")
    return Trainer(model, opt_cfg, run_cfg, data_cfg, mesh=mesh)


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            wrap_step=None, phases=None) -> dict:
    """Set up, run the first steps and the window; returns what was
    measured, the program's readings for the check, and the trace."""
    import jax
    import jax.numpy as jnp

    from chipbench import check, inputs, period, reference

    phases = phases or Phases()
    phases.mark("start")
    trainer = build_trainer(cell, devices)
    abstract = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    make_params = inputs.make_params_fn(abstract)
    pkey = inputs.stream_key(seed, inputs.PARAMS_STREAM)
    tkey = inputs.stream_key(seed, inputs.TOKENS_STREAM)
    phases.mark("build")
    # The step is compiled on shapes; weights, state and tokens are then
    # made where the compiled step takes them (its shards on a mesh).
    compiled = trainer.lower_step(abstract).compile()
    phases.mark("compile")
    p_sh, o_sh, b_sh = compiled.input_shardings[0][:3]
    make = jax.jit(make_params, out_shardings=p_sh)
    init = jax.jit(trainer.optimizer.init, out_shardings=o_sh)
    step_fn = compiled if wrap_step is None else wrap_step(compiled)
    make_tokens = jax.jit(inputs.make_tokens_fn(
        cell.traffic, int(cell.config["vocab_size"])), out_shardings=b_sh["tokens"])
    first_norms = jax.jit(lambda s: {k: jnp.linalg.norm(v) for k, v in
                                     check.program_first_gradient(s).items()})
    # the initial weights made again, in the weights' own shards
    change_norms = jax.jit(lambda p, k: reference.change_norms(
        p, lambda key: jax.lax.with_sharding_constraint(make_params(key), p_sh), k))
    mem = compiled.memory_analysis()
    hlo_text = compiled.as_text() if trace else ""

    per = int(cell.traffic["optimizer"]["period"])
    log = {"refresh": [], "steady": [], "attempted": 0, "failed": 0}
    state = {}

    def start():
        """Weights and optimizer state made from the seed, in place of
        whatever the job held (one state on the chips at a time)."""
        state.clear()
        state["params"] = make(pkey)
        state["opt"] = init(state["params"])
        jax.block_until_ready(state["opt"])

    def span(name):
        # host spans on the profiler's clock, for the idle-gap breakdown
        return jax.profiler.TraceAnnotation(name) if trace else contextlib.nullcontext()

    def one(k: int) -> float:
        """Step ``k`` of the job as the Trainer takes it: feed, step, block,
        read the loss."""
        t0 = time.perf_counter()
        with span("feed"):
            tokens = make_tokens(tkey, k)
        with span("dispatch"):
            p, o, m = step_fn(state["params"], state["opt"], {"tokens": tokens})
        with span("block"):
            jax.block_until_ready((p, o))
        with span("loss"):
            loss = float(m["loss"])
            ok = bool(m["update_applied"]) and loss == loss
        t1 = time.perf_counter()
        state["params"], state["opt"] = p, o
        log["refresh" if period.is_refresh(k, per) else "steady"].append(t1 - t0)
        log["attempted"] += 1
        log["failed"] += not ok
        return loss

    # The first call of a compiled program is slower than the rest, so one
    # call goes first and its result is dropped: the check's step 0, from
    # the seed again, is then the refresh sample.
    start()
    opt_bytes = max(_bytes_on(state["opt"], d) for d in devices)
    phases.mark("make")
    one(0)
    phases.mark("first_call")
    start()
    phases.mark("make_again")
    # The check's first steps go through the window's own call and feed.
    log.update(refresh=[], steady=[], attempted=0, failed=0)
    losses = [one(0)]
    first = {k: float(v) for k, v in jax.device_get(first_norms(state["opt"])).items()}
    losses += [one(1), one(2)]
    phases.mark("check_steps")
    change = {k: float(v) for k, v in
              jax.device_get(change_norms(state["params"], pkey)).items()}
    phases.mark("change_norms")
    refresh = log["refresh"]
    k = 3 + int(cell.traffic["warmup_steps"])
    for j in range(3, k):
        one(j)
        phases.mark(f"step{j}")
    log.update(refresh=[], steady=[], attempted=0, failed=0)

    trace_dir = os.path.join(WORK_DIR, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        phases.mark("start_trace")
    t_window = time.perf_counter()
    while True:  # at least one step
        one(k)
        k += 1
        if time.perf_counter() - t_window >= seconds:
            break
    window_s = time.perf_counter() - t_window
    if trace:
        jax.profiler.stop_trace()
    peak = held_bytes(mem, devices)
    del state, step_fn, compiled
    gc.collect()
    phases.mark("window")
    log["refresh"] = refresh + log["refresh"]

    tps = period.tokens_per_s(log["refresh"], log["steady"], per,
                              cell.tokens_per_step)
    return {
        "abstract": abstract,
        "setup_s": t_window - _T0, "window_s": window_s,
        "tokens_per_s": tps, "peak_bytes": peak[0], "peak_bytes_in_use": peak[1],
        "opt_state_bytes": opt_bytes,
        "memory_analysis": {"argument": mem.argument_size_in_bytes,
                            "output": mem.output_size_in_bytes,
                            "temp": mem.temp_size_in_bytes,
                            "alias": mem.alias_size_in_bytes},
        "log": log, "trace_dir": trace_dir if trace else None, "hlo_text": hlo_text,
        "program": {"losses": losses, "first": first, "change": change},
    }


def held_bytes(mem, devices) -> tuple:
    """(bytes the job holds on the fullest chip, the allocator's peak).

    The allocator's ``peak_bytes_in_use`` counts the arrays the process
    holds but not the scratch of a running program, so the compiled step's
    own footprint on a chip (arguments, outputs not aliased to them, and
    temp, from its buffer assignment ``mem``) bounds it from below."""
    step = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    return max(step, in_use), in_use


def _bytes_on(tree, device) -> int:
    import jax

    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        for shard in x.addressable_shards:
            if shard.device == device:
                total += shard.data.nbytes
    return total


def main(argv=None, *, cell=None, require_tpu=True, wrap_step=None) -> int:
    args = parse(argv)
    from chipbench import spec

    cell = cell or spec.load_cell(args.workload)
    phases = Phases()
    try:
        devices = devices_for(cell.chips, require_tpu)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    phases.mark("devices")  # JAX imported and its backend started
    if devices[0].platform != "cpu":
        enable_cache()
    from chipbench import check, reference
    from chipbench.peaks import peaks

    if devices[0].platform == "tpu":
        peaks(devices[0].device_kind)  # an unknown chip is an error up front
    got = measure(cell, args.seed, args.seconds, bool(args.trace), devices,
                  wrap_step=wrap_step, phases=phases)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": got["peak_bytes"]}
    print(f"chipbench: memory_analysis {json.dumps(got['memory_analysis'])} "
          f"peak_bytes_in_use {got['peak_bytes_in_use']}", file=sys.stderr)

    extra = {}
    if args.trace:
        from chipbench import layers

        metrics, busy_s, breakdown = layers.read(cell, got, devices)
        device.update(busy_s=busy_s, window_s=got["window_s"])
        extra["breakdown"] = breakdown
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {"tokens_per_s": got["tokens_per_s"],
                  "peak_hbm_gib": got["peak_bytes"] / GIB,
                  "setup_s": got["setup_s"]}
        metrics = {k: {"value": values[k], "unit": units[k]} for k in units}

    phases.mark("metrics")
    ref = reference.run(cell, got["abstract"], args.seed, devices=devices, phases=phases)
    print("chipbench: setup_phases " + json.dumps(phases.seconds), file=sys.stderr)
    read = check.readings(got["program"], ref)
    ok, table = check.verdict(read, cell.limits)
    ok = ok and got["log"]["failed"] == 0
    print(f"chipbench: worst leaves: grad {read['grad_leaf']}, change "
          f"{read['change_leaf']}; left out of change: {read['left_out']}",
          file=sys.stderr)
    for name, (value, limit) in table.items():
        print(f"chipbench: check {name} {value!r} limit {limit!r}", file=sys.stderr)
    result = {"correct": ok, "attempted": got["log"]["attempted"],
              "window_steps_s": got["log"]["steady"],
              "failed": got["log"]["failed"], "metrics": metrics,
              "device": device, **extra,
              "checks": {k: {"value": v, "limit": lim} for k, (v, lim) in table.items()}}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
