"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as a process entry point (``python -m repro.launch.dryrun``) —
the first import below forces 512 placeholder host devices (via the shared
:func:`repro.launch.devices.force_host_device_count` helper, which preserves
any other ``XLA_FLAGS``) BEFORE jax initializes, so ``make_production_mesh``
can build the production meshes.

Per cell this script:
  1. builds the model + GUM optimizer (the paper's technique, first-class),
  2. lowers the appropriate step (train_step / prefill / serve_step) with
     explicit in/out shardings on the requested mesh,
  3. ``.compile()``s it (proving the distribution config is coherent),
  4. records memory_analysis / cost_analysis / the 3 roofline terms parsed
     from the post-SPMD HLO into a JSON next to EXPERIMENTS.md.
"""
from repro.launch.devices import force_host_device_count

force_host_device_count(512, verify=False)  # before jax backend init

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import all_cells, cell_supported, get_config, get_shape  # noqa: E402
from repro.core import OptimizerConfig, build_optimizer  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import (  # noqa: E402
    ICI_BW,
    ICI_LINKS,
    HBM_BW,
    PEAK_FLOPS,
    model_flops,
    roofline_from_text,
    xla_cost_dict,
)
from repro.launch.steps import (  # noqa: E402
    batch_shardings,
    batch_struct,
    cache_shardings,
    cache_struct,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models import build_model  # noqa: E402
from repro.sharding import named_sharding_tree, opt_state_sharding, use_mesh  # noqa: E402

# Per-arch gradient-accumulation factors for train_4k so activations fit HBM
# (chosen from memory_analysis iterations; see EXPERIMENTS.md §Dry-run).
TRAIN_MICROBATCHES = {
    "nemotron-4-340b": 8,
    "llama4-maverick-400b-a17b": 4,
    "dbrx-132b": 4,
    "llama-3.2-vision-11b": 2,
    "starcoder2-7b": 2,
}


def default_optimizer(arch: str, kernel_impl: str = "auto",
                      pad_rank_to: int = 0, fuse_families: bool = False,
                      fused_epilogue: bool = False,
                      rank_policy: str | None = None,
                      rank_ladder: tuple[int, ...] = (),
                      telemetry: bool = False) -> OptimizerConfig:
    # GUM (the paper's method) with the TPU-native subspace projector.
    # kernel_impl is threaded into the compiled cell so dry runs lower the
    # SAME hot path as training ("interpret" puts the kernel code into the
    # HLO on the host-CPU placeholder devices); the fusion knobs do the
    # same for the family-stacked engine; a rank policy lowers the cell at
    # the policy's INITIAL RankMap (rank changes re-lower per ladder rank).
    return OptimizerConfig(
        name="gum", lr=1e-3, rank=128, gamma=2, period=200,
        projector="subspace", base="muon", kernel_impl=kernel_impl,
        pad_rank_to=pad_rank_to, fuse_families=fuse_families,
        fused_epilogue=fused_epilogue, rank_policy=rank_policy,
        rank_ladder=rank_ladder, telemetry=telemetry,
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool, opt_name: str = "gum",
             overrides: dict | None = None, microbatches: int | None = None,
             lowrank_accum: bool = False, kernel_impl: str = "auto",
             pad_rank_to: int = 0, fuse_families: bool = False,
             fused_epilogue: bool = False, rank_policy: str | None = None,
             rank_ladder: tuple[int, ...] = (), audit: bool = False,
             telemetry: bool = False):
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = get_shape(shape_name)
    ok, reason = cell_supported(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "optimizer": opt_name, "status": "skipped", "reason": reason,
    }
    if not ok:
        return result

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    model = build_model(cfg)
    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_sh = named_sharding_tree(params_struct, mesh)

    with use_mesh(mesh):
        if shape.kind == "train":
            ocfg = default_optimizer(arch, kernel_impl, pad_rank_to,
                                     fuse_families, fused_epilogue,
                                     rank_policy, rank_ladder, telemetry)
            if opt_name != "gum":
                ocfg = OptimizerConfig(name=opt_name, rank=128, gamma=2,
                                       period=200, projector="subspace",
                                       kernel_impl=kernel_impl,
                                       pad_rank_to=pad_rank_to,
                                       fuse_families=fuse_families,
                                       fused_epilogue=fused_epilogue,
                                       rank_policy=rank_policy,
                                       rank_ladder=rank_ladder,
                                       telemetry=telemetry)
            tools = None
            if lowrank_accum:
                from repro.core.gum import gum_accum_tools

                tools = gum_accum_tools(
                    ocfg.lr, rank=ocfg.rank, gamma=ocfg.gamma,
                    period=ocfg.period, projector=ocfg.projector,
                    kernel_impl=ocfg.kernel_impl,
                    pad_rank_to=ocfg.pad_rank_to,
                    fuse_families=ocfg.fuse_families,
                    fused_epilogue=ocfg.fused_epilogue,
                )
                opt = tools.transform
            else:
                opt = build_optimizer(ocfg)
            audit_report = None
            if audit:
                # Full static audit of this cell's optimizer over the real
                # model's param structs (chain lint, launch model vs traced
                # dispatch counts, dtype flow, recompile hazards) — abstract
                # tracing only, before the expensive XLA compile below.
                # The buffer pass (donation / replication) is appended after
                # the lowering exists.
                from repro.analysis import audit_optimizer

                audit_report = audit_optimizer(ocfg, params_struct,
                                               ladder=ocfg.rank_ladder)
                result["audit"] = audit_report.to_json()
                print("  " + audit_report.format().replace("\n", "\n  "),
                      flush=True)
            opt_struct = jax.eval_shape(opt.init, params_struct)
            opt_sh = opt_state_sharding(opt_struct, mesh)
            batch = batch_struct(cfg, shape)
            batch_sh = batch_shardings(cfg, shape, mesh)
            mb = microbatches or TRAIN_MICROBATCHES.get(arch, 1)
            step = make_train_step(model, opt, grad_clip=1.0, microbatches=mb,
                                   lowrank_accum=tools)
            jit_step = jax.jit(
                step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jit_step.lower(params_struct, opt_struct, batch)
            result["microbatches"] = mb
            if audit_report is not None:
                # Buffer-lifetime pass on the lowered module: donated
                # params/opt_state must alias outputs (RA604) and the batch
                # must actually be sharded, not replicated per device
                # (RA605) — the lowering is already paid, so this is free.
                from repro.analysis import (
                    donation_findings,
                    parse_main_args,
                    replication_findings,
                )

                infos = parse_main_args(lowered.as_text())
                n_p = len(jax.tree_util.tree_leaves(params_struct))
                n_o = len(jax.tree_util.tree_leaves(opt_struct))
                cell = f"{arch}/{shape_name}"
                buf_findings = donation_findings(
                    infos, n_params=n_p, n_opt=n_o, where=cell)
                buf_findings += replication_findings(
                    infos, n_params=n_p, n_opt=n_o, n_shards=chips,
                    where=cell)
                audit_report.extend(buf_findings)
                from repro.sharding import per_shard_bytes

                audit_report.summary["buffers"] = {
                    "donated_args": sum(a.aliased for a in infos),
                    "expected_donated": n_p + n_o,
                    "total_args": len(infos),
                    # static per-shard (not per-replica) footprint under the
                    # param rules — the number RA605 keeps honest
                    "params_bytes_per_shard": per_shard_bytes(
                        params_struct, mesh),
                    "opt_state_bytes_per_shard": per_shard_bytes(
                        opt_struct, mesh),
                }
                result["audit"] = audit_report.to_json()
                print(f"  buffers: donated "
                      f"{audit_report.summary['buffers']['donated_args']}"
                      f"/{n_p + n_o} args alias outputs", flush=True)
                for f in buf_findings:
                    print("  " + f.format().replace("\n", "\n  "),
                          flush=True)
        elif shape.kind == "prefill":
            batch = batch_struct(cfg, shape)
            batch_sh = batch_shardings(cfg, shape, mesh)
            step = make_prefill_step(model)
            jit_step = jax.jit(step, in_shardings=(param_sh, batch_sh))
            lowered = jit_step.lower(params_struct, batch)
        else:  # decode
            cache = cache_struct(cfg, shape)
            cache_sh = cache_shardings(cache, cfg, mesh)
            tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            tok_sh = batch_shardings(cfg, shape, mesh)["tokens"]
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            step = make_serve_step(model)
            jit_step = jax.jit(
                step,
                in_shardings=(param_sh, cache_sh, tok_sh, None),
                out_shardings=None,
                donate_argnums=(1,),
            )
            lowered = jit_step.lower(params_struct, cache, tokens, pos)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        mem_info = {}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_info[attr] = int(v)
        # cost_analysis() is a dict on old JAX, a list-of-dicts on newer.
        cost = xla_cost_dict(compiled)

        mf = model_flops(cfg, shape) / chips
        report = roofline_from_text(compiled.as_text(), model_flops_per_device=mf)

    result.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory=mem_info,
        xla_cost={k: float(v) for k, v in cost.items()
                  if k in ("flops", "bytes accessed", "transcendentals")},
        roofline=report.to_dict(),
        hw={"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW,
            "ici_bw": ICI_BW, "ici_links": ICI_LINKS},
    )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--opt", default="gum")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true", help="run every assigned cell")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for result filenames")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--lowrank-accum", action="store_true",
                    help="accumulate microbatch grads in projected space")
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "jnp", "pallas", "interpret"],
                    help="optimizer hot-loop impl threaded into the compiled "
                         "cell (OptimizerConfig.kernel_impl) so dry runs "
                         "lower the same hot path as training")
    ap.add_argument("--pad-rank-to", type=int, default=0,
                    help="opt-in lane-aligned rank padding for the low-rank "
                         "Pallas kernels (e.g. 128)")
    ap.add_argument("--fuse-families", action="store_true",
                    help="family-stacked fused optimizer execution (one "
                         "batched launch per shape family)")
    ap.add_argument("--fused-epilogue", action="store_true",
                    help="fold chain-tail epilogues into the back-projection "
                         "GEMM (back_project_epilogue kernel)")
    ap.add_argument("--rank-policy", default=None,
                    help="rank-policy spec (repro.core.rank_policy) — the "
                         "cell lowers at the policy's initial RankMap, e.g. "
                         "'spectral:0.99' or 'family:1024x4096=64'")
    ap.add_argument("--rank-ladder", default="",
                    help="comma-separated ladder for adaptive policies, "
                         "e.g. 32,64,128")
    ap.add_argument("--audit", action="store_true",
                    help="run the repro.analysis static audit on each train "
                         "cell's optimizer (findings land in the result "
                         "JSON under 'audit')")
    ap.add_argument("--telemetry", action="store_true",
                    help="lower each train cell with the in-jit telemetry "
                         "instrumentation compiled in "
                         "(OptimizerConfig.telemetry) and write per-cell "
                         "lower/compile spans + memory metrics to "
                         "<out>/dryrun_events.jsonl — span/metric summaries "
                         "for giant configs without executing a real run")
    ap.add_argument(
        "--set", action="append", default=[],
        help="ModelConfig overrides, e.g. --set attn_impl=xla_chunked "
             "--set logit_chunk=512 --set remat_policy=dots",
    )
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("True", "False"):
            v = v == "True"
        overrides[k] = v

    if args.list:
        for a, s in all_cells():
            cfg, shape = get_config(a), get_shape(s)
            ok, reason = cell_supported(cfg, shape)
            print(f"{a:28s} {s:12s} {'RUN' if ok else 'SKIP: ' + reason}")
        return

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    tele = None
    if args.telemetry:
        from repro.telemetry import JsonlSink, Telemetry

        tele = Telemetry(
            [JsonlSink(os.path.join(args.out, "dryrun_events.jsonl"))],
            run={"mode": "dryrun", "opt": args.opt, "mesh": args.mesh})

    for arch, shape in cells:
        for multi_pod in meshes:
            mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
            tag = f"{arch}__{shape}__{mesh_name}__{args.opt}"
            if args.tag:
                tag += f"__{args.tag}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[cached] {tag}")
                continue
            print(f"[run] {tag}", flush=True)
            try:
                res = run_cell(arch, shape, multi_pod, args.opt,
                               overrides=overrides or None,
                               microbatches=args.microbatches or None,
                               lowrank_accum=args.lowrank_accum,
                               kernel_impl=args.kernel_impl,
                               pad_rank_to=args.pad_rank_to,
                               fuse_families=args.fuse_families,
                               fused_epilogue=args.fused_epilogue,
                               rank_policy=args.rank_policy,
                               rank_ladder=tuple(
                                   int(r) for r in args.rank_ladder.split(",")
                                   if r),
                               audit=args.audit,
                               telemetry=args.telemetry)
                res["overrides"] = overrides
                res["tag"] = args.tag
                if tele is not None and res["status"] == "ok":
                    tele.record_span("lower", res["lower_s"], cell=tag)
                    tele.record_span("compile", res["compile_s"], cell=tag)
                    for k, v in (res.get("memory") or {}).items():
                        tele.metric(0, f"memory.{k}", v, cell=tag)
                    tele.event("cell", f"dryrun: {tag} ok", cell=tag)
            except Exception as e:  # record failures — they are bugs to fix
                res = {
                    "arch": arch, "shape": shape, "mesh": mesh_name,
                    "optimizer": args.opt, "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-4000:],
                }
            with open(path, "w") as f:
                json.dump(res, f, indent=2)
            print(f"  -> {res['status']}"
                  + (f" ({res.get('error','')[:200]})" if res["status"] == "error" else "")
                  + (f" compile={res.get('compile_s')}s" if res["status"] == "ok" else ""),
                  flush=True)
    if tele is not None:
        tele.close()


if __name__ == "__main__":
    main()
