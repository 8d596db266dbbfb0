"""Static-analysis subsystem (PR 6): lint codes, launch model, jaxpr passes.

Three layers of guarantees:

  1. the full audit pass matrix — every factory optimizer across
     fuse_families x fused_epilogue — is clean, with the closed-form launch
     model agreeing with the dispatch layer's trace-time counts (9/step for
     fused GUM on the 3-family reference tree);
  2. every lint code has a failing case: a deliberately malformed chain /
     program is caught with the right code and an actionable message;
  3. the integration points work: ``build_optimizer(audit=True)`` raises at
     build time, ``assert_launches`` raises at trace time, the memory
     accountant agrees with the committed benchmark numbers.
"""
import itertools
import json

import jax
import jax.numpy as jnp
import pytest

import repro.core as core
from repro.analysis import (
    ChainLintError,
    audit_optimizer,
    audit_summary,
    dtype_flow_findings,
    expected_launches,
    lint_chain,
    memory_crosscheck,
    recompile_findings,
    run_matrix,
    trace_update,
)
from repro.analysis.audit import default_params, launch_findings
from repro.core import OptimizerConfig, Transform, build_optimizer
from repro.core import combinators as C
from repro.kernels import launch_count

PARAMS = default_params()


def codes(findings):
    return {f.code for f in findings}


def _msg(findings, code):
    return next(f.message for f in findings if f.code == code)


# ------------------------------------------------------------ pass matrix


def test_audit_matrix_all_clean():
    """Acceptance: every factory optimizer x fuse_families x fused_epilogue
    audits clean — chain lint, launch model vs traced dispatch counts,
    dtype flow, signature stability across the rank ladder."""
    reports = run_matrix(PARAMS)
    dirty = {k: [f.format() for f in r.errors]
             for k, r in reports.items() if not r.ok}
    assert not dirty, dirty
    # 6 lowrank optimizers x 4 fuse combos + 4 full-rank baselines
    assert len(reports) == 28


@pytest.mark.parametrize("opt,epi,want", [
    ("gum", False, {"project": 3, "newton_schulz": 3, "back_project": 3}),
    ("gum", True, {"project": 3, "newton_schulz": 3, "back_project": 3}),
    ("galore_muon", True, {"lowrank_update": 3, "newton_schulz": 3,
                           "back_project_epilogue": 3}),
    ("golore", True, {"lowrank_update": 3, "newton_schulz": 3,
                      "back_project_epilogue": 3}),  # default base=muon
], ids=["gum", "gum_epilogue", "galore_muon_epilogue", "golore_epilogue"])
def test_static_launches_match_traced_on_family_tree(opt, epi, want):
    """The closed-form expectation equals the dispatch layer's trace-time
    count on the 3-family reference tree — one launch set per family (GUM:
    9/step; the unbias emits FullUpdates so its epilogue stays unfused)."""
    cfg = OptimizerConfig(name=opt, rank=8, period=5, gamma=1,
                          kernel_impl="jnp", fuse_families=True,
                          fused_epilogue=epi)
    t = build_optimizer(cfg)
    expected, model_findings = expected_launches(t, PARAMS)
    assert not model_findings
    assert expected == want
    state = jax.eval_shape(t.init, PARAMS)
    with launch_count.assert_launches(expected):
        jax.make_jaxpr(lambda g, s, p: t.update(g, s, p))(
            PARAMS, state, PARAMS)


def test_assert_launches_raises_on_mismatch():
    cfg = OptimizerConfig(name="galore", rank=8, period=5,
                          kernel_impl="jnp", fuse_families=True)
    t = build_optimizer(cfg)
    state = jax.eval_shape(t.init, PARAMS)
    with pytest.raises(launch_count.LaunchCountMismatch, match="project"):
        with launch_count.assert_launches({"project": 999,
                                           "back_project": 3}):
            jax.make_jaxpr(lambda g, s, p: t.update(g, s, p))(
                PARAMS, state, PARAMS)
    with pytest.raises(ValueError, match="unknown op"):
        with launch_count.assert_launches({"warp_drive": 1}):
            pass


# ------------------------------------------------- chain linter (RC1xx)


def test_rc101_nested_lowrank():
    t = C.chain(
        C.lowrank(C.lowrank(C.scale_by_momentum(0.9), rank=4, period=2),
                  rank=8, period=2),
        C.scale_by_lr(1e-2),
    )
    fs = lint_chain(t)
    assert "RC101" in codes(fs)
    assert "nested" in _msg(fs, "RC101")


def test_rc102_unbias_outside_lowrank():
    t = C.chain(C.layerwise_unbias(C.scale_by_momentum(0.9), gamma=1),
                C.scale_by_lr(1e-2))
    fs = lint_chain(t)
    assert "RC102" in codes(fs)
    assert "lowrank" in _msg(fs, "RC102")


def test_rc103_scale_by_lr_not_terminal():
    t = C.chain(C.scale_by_lr(1e-2), C.scale_by_momentum(0.9))
    fs = lint_chain(t)
    assert "RC103" in codes(fs)
    assert any(f.code == "RC103" and f.severity == "error" for f in fs)
    # ... and inside lowrank() is also an error
    t2 = C.chain(
        C.lowrank(C.chain(C.scale_by_momentum(0.9), C.scale_by_lr(1e-2)),
                  rank=4, period=2),
        C.scale_by_lr(1e-2),
    )
    assert "RC103" in codes(lint_chain(t2))
    # missing entirely (with a lowrank stage) is only a warning
    t3 = C.chain(C.lowrank(C.scale_by_momentum(0.9), rank=4, period=2))
    fs3 = lint_chain(t3)
    assert any(f.code == "RC103" and f.severity == "warning" for f in fs3)
    assert not any(f.severity == "error" for f in fs3)


def test_rc104_non_monotone_ladder():
    t = C.chain(C.lowrank(C.scale_by_momentum(0.9), rank=16, period=2),
                C.scale_by_lr(1e-2))
    fs = lint_chain(t, ladder=(16, 8, 16))
    assert "RC104" in codes(fs)
    assert "strictly increasing" in _msg(fs, "RC104")


def test_rc105_initial_rank_off_ladder():
    t = C.chain(C.lowrank(C.scale_by_momentum(0.9), rank=5, period=2),
                C.scale_by_lr(1e-2))
    fs = lint_chain(t, ladder=(8, 16))
    assert "RC105" in codes(fs)
    assert "[5]" in _msg(fs, "RC105")
    # on-ladder initial rank is clean
    t2 = C.chain(C.lowrank(C.scale_by_momentum(0.9), rank=8, period=2),
                 C.scale_by_lr(1e-2))
    assert "RC105" not in codes(lint_chain(t2, ladder=(8, 16)))


def test_rc106_unaligned_pad_rank():
    t = C.chain(
        C.lowrank(C.scale_by_momentum(0.9), rank=4, period=2,
                  pad_rank_to=96),
        C.scale_by_lr(1e-2),
    )
    fs = lint_chain(t)
    assert "RC106" in codes(fs)
    assert "128" in _msg(fs, "RC106")  # the fix-it suggests the lane width


def test_build_optimizer_audit_raises():
    """audit=True turns lint errors into a build-time ChainLintError."""
    cfg = OptimizerConfig(name="gum", rank=5, period=5, gamma=1,
                          kernel_impl="jnp", rank_ladder=(8, 16))
    with pytest.raises(ChainLintError, match="RC105"):
        build_optimizer(cfg, audit=True)
    # the same config without the off-ladder rank builds fine
    build_optimizer(OptimizerConfig(name="gum", rank=8, period=5, gamma=1,
                                    kernel_impl="jnp", rank_ladder=(8, 16)),
                    audit=True)


# ------------------------------------------- dtype-flow auditor (RA2xx)


def _elementwise_transform(fn):
    return Transform(
        lambda p: (),
        lambda g, s, p: (jax.tree_util.tree_map(fn, g), s),
    )


def test_ra201_f64_leak():
    t = _elementwise_transform(lambda x: x.astype(jnp.float64))
    with jax.enable_x64(True):
        params = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        jaxpr, _ = trace_update(t, params)
        fs = dtype_flow_findings(jaxpr)
    assert "RA201" in codes(fs)
    assert "f64" in _msg(fs, "RA201")


def test_ra202_bf16_roundtrip():
    t = _elementwise_transform(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32) * 2.0)
    jaxpr, _ = trace_update(t, PARAMS)
    fs = dtype_flow_findings(jaxpr)
    assert "RA202" in codes(fs)
    # the allowlist knob suppresses it
    assert "RA202" not in codes(
        dtype_flow_findings(jaxpr, allow_bf16_roundtrip=True))


def test_dtype_flow_clean_on_factory_step():
    t = build_optimizer(OptimizerConfig(name="gum", rank=8, period=5,
                                        gamma=1, kernel_impl="jnp"))
    jaxpr, _ = trace_update(t, PARAMS)
    assert not dtype_flow_findings(jaxpr)


# ---------------------------------------- launch/fusion auditor (RA3xx)


def test_ra301_launch_divergence():
    fs = launch_findings({"project": 3, "back_project": 3},
                         {"project": 8, "back_project": 3},
                         fused_epilogue=False, where="x")
    assert codes(fs) == {"RA301"}
    assert "expected 3, traced 8" in _msg(fs, "RA301")


def test_ra302_stray_back_projection():
    fs = launch_findings(
        {"lowrank_update": 3, "back_project_epilogue": 3},
        {"lowrank_update": 3, "back_project": 3},
        fused_epilogue=True, where="x")
    assert codes(fs) == {"RA302"}
    assert "back_project" in _msg(fs, "RA302")


def test_ra303_unmodelable_stage():
    opaque = Transform(lambda p: (), lambda g, s, p: (g, s))
    t = C.chain(C.lowrank(opaque, rank=4, period=2), C.scale_by_lr(1e-2))
    _, fs = expected_launches(t, PARAMS)
    assert "RA303" in codes(fs)


# --------------------------------- recompilation-hazard detector (RA4xx)


def test_ra401_unstable_signature():
    counter = itertools.count(1)
    t = _elementwise_transform(lambda x: x * float(next(counter)))
    fs, _ = recompile_findings(lambda r: t, PARAMS, [4])
    assert "RA401" in codes(fs)


def test_ra402_weak_scalar_capture():
    weak = jnp.asarray(0.5)  # weak-typed 0-d closure capture
    t = _elementwise_transform(lambda x: x * weak)
    fs, _ = recompile_findings(lambda r: t, PARAMS, [4])
    assert "RA402" in codes(fs)
    assert all(f.severity == "warning" for f in fs if f.code == "RA402")


def test_signature_stable_per_rank_for_factory():
    cfg = OptimizerConfig(name="galore", rank=8, period=5,
                          kernel_impl="jnp", rank_ladder=(4, 8))
    from repro.core.rank_policy import RankMap

    fs, hashes = recompile_findings(
        lambda r: build_optimizer(cfg, rank_map=RankMap(r)), PARAMS, (4, 8))
    assert not [f for f in fs if f.severity == "error"]
    # ranks recompile (different shapes) but each rank's trace is stable
    assert len(set(hashes.values())) == 2


# ----------------------------------- static memory accountant (RA5xx)


def test_memory_crosscheck_matches_committed_bench():
    """The eval_shape accountant reproduces the committed runtime
    proj_bytes_final for every rank-policy cell exactly."""
    assert memory_crosscheck() == []


def test_ra501_on_doctored_bench(tmp_path):
    real = json.loads(
        open("results/BENCH_rank_policy.json").read())
    real["results"]["fixed16"]["proj_bytes_final"] += 1
    doctored = tmp_path / "BENCH_rank_policy.json"
    doctored.write_text(json.dumps(real))
    fs = memory_crosscheck(doctored)
    assert "RA501" in codes(fs)
    assert any(f.code == "RA501" and "fixed16" in f.where for f in fs)
    assert "303137" in _msg(fs, "RA501")


# --------------------------------------------------------- integration


def test_audit_summary_one_liner():
    t = build_optimizer(OptimizerConfig(name="gum", rank=8, period=5,
                                        gamma=1, kernel_impl="jnp",
                                        fuse_families=True))
    line = audit_summary(t, PARAMS, name="gum")
    assert "launches/step=9" in line
    assert "proj_state=" in line and "sig=" in line
    assert "\n" not in line


def test_audit_report_roundtrip():
    cfg = OptimizerConfig(name="golore", rank=8, period=5,
                          kernel_impl="jnp", fuse_families=True,
                          fused_epilogue=True, rank_ladder=(4, 8))
    rep = audit_optimizer(cfg, PARAMS, ladder=(4, 8))
    assert rep.ok, [f.format() for f in rep.errors]
    d = rep.to_json()
    assert d["ok"] and d["summary"]["launches_per_step"] == 9
    assert "back_project_epilogue" in d["summary"]["launch_counts"]


def test_lowrank_plan_stats_geometry():
    from repro.analysis import lowrank_plan_stats
    t = build_optimizer(OptimizerConfig(name="gum", rank=8, period=5,
                                        gamma=1, kernel_impl="jnp",
                                        fuse_families=True))
    stats = lowrank_plan_stats(t, PARAMS, name="gum")
    assert len(stats) == 1
    (s,) = stats
    assert s["fused"] and s["n_families"] == 3 and s["n_stacked"] == 8
    assert sorted(s["families"]) == ["128x64r8x2", "64x128r8x2", "64x64r8x4"]


def test_launch_model_counts_both_unbias_branches_when_q_lt_1():
    """Leaves with lead blocks (q = gamma/L < 1) trace BOTH layerwise_unbias
    branches — the compensated sample AND the plain low-rank path — and the
    closed-form model must count both (caught live on llama-60m-smoke)."""
    lead_params = {
        # L = 3 blocks per leaf, gamma = 1 -> q = 1/3 < 1
        "blocks/wq": jax.ShapeDtypeStruct((3, 64, 64), jnp.float32),
        "blocks/wo": jax.ShapeDtypeStruct((3, 64, 64), jnp.float32),
        "norm/scale": jax.ShapeDtypeStruct((64,), jnp.float32),
    }
    cfg = OptimizerConfig(name="gum", rank=8, period=5, gamma=1,
                          kernel_impl="jnp")
    t = build_optimizer(cfg)
    expected, findings = expected_launches(t, lead_params, name="gum")
    assert findings == []
    # per leaf: unbias sample (project, newton_schulz, back_project) + plain
    # muon low branch (lowrank_update, newton_schulz, back_project)
    assert expected == {"project": 2, "lowrank_update": 2,
                       "newton_schulz": 4, "back_project": 4}
    state = jax.eval_shape(t.init, lead_params)
    with launch_count.assert_launches(expected):
        jax.make_jaxpr(lambda g, s, w: t.update(g, s, w))(
            lead_params, state, lead_params)


# ------------------------------------------- sharded audit (RA6xx, PR 7)
# The clean path is covered at mesh 1/2/8 via the AbstractMesh trace (no
# devices needed); every RA6xx code then gets a doctored failing case.


from repro.analysis import (  # noqa: E402  (section-local imports)
    ArgInfo,
    CollectiveRecord,
    audit_sharded,
    collective_schedule_findings,
    donation_findings,
    expected_collective_schedule,
    parse_main_args,
    per_shard_memory,
    replication_findings,
    trace_sharded_step,
    wire_bytes_model,
)


def _rec(**kw):
    base = dict(primitive="psum", axes=("data",), dtypes=("bfloat16",),
                shapes=((64, 64),), n_operands=1, payload_bytes=8192,
                under_cond=False, pinned=True, path=("shard_map",))
    base.update(kw)
    return CollectiveRecord(**base)


def _sharded_expected(n_leaves=1, payload=8192):
    return {
        "grad_psum": {"count": 1, "dtype": "bfloat16",
                      "operands": n_leaves, "payload_bytes": payload,
                      "axis": "data", "phase": "steady"},
        "loss_psum": {"count": 1, "dtype": "float32", "operands": 1,
                      "payload_bytes": 4, "axis": "data",
                      "phase": "steady"},
        "boundary_gather": {"count": 0, "families": 0, "payload_bytes": 0,
                            "phase": "boundary"},
        "n_shards": 2,
    }


_LOSS = dict(dtypes=("float32",), shapes=((),), payload_bytes=4,
             pinned=False)


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_sharded_audit_clean_static_matches_traced(n_shards):
    """Acceptance: the traced shard_map step matches the closed-form
    schedule on 1/2/8-way meshes — one reduce_dtype gradient psum over
    every param leaf plus one scalar f32 loss psum, nothing else.
    AbstractMesh trace: runs with however many devices the host has."""
    cfg = OptimizerConfig(name="gum", rank=8, period=5, gamma=1,
                          kernel_impl="jnp")
    rep = audit_sharded(cfg, mesh_axes=(("data", n_shards),), lower=False)
    assert rep.ok, [f.format() for f in rep.errors]
    exp = rep.summary["expected_schedule"]
    assert exp["grad_psum"]["count"] == 1
    assert exp["grad_psum"]["dtype"] == "bfloat16"
    wire = rep.summary["wire"]
    if n_shards == 1:
        assert wire["steady_bytes_per_step"] == 0
    else:
        # ring psum: 2(N-1)/N bytes on the wire per payload byte
        payload = (exp["grad_psum"]["payload_bytes"]
                   + exp["loss_psum"]["payload_bytes"])
        want = int(exp["grad_psum"]["payload_bytes"]
                   * 2 * (n_shards - 1) / n_shards) + int(
                       exp["loss_psum"]["payload_bytes"]
                       * 2 * (n_shards - 1) / n_shards)
        assert wire["steady_bytes_per_step"] == want, (wire, payload)


def test_trace_sharded_step_schedule_shape():
    """The raw trace on an 8-way AbstractMesh: exactly two steady psums —
    the multi-operand bf16 gradient reduction (barrier-pinned) and the
    scalar f32 loss pmean."""
    from repro.analysis.audit import arch_model

    model = arch_model("llama-60m-smoke")
    t = build_optimizer(OptimizerConfig(name="adamw", lr=1e-3))
    _, records, counts, (params, _, _) = trace_sharded_step(
        model, t, n_shards=8)
    psums = [r for r in records if r.primitive == "psum"]
    assert len(psums) == 2
    grad = next(r for r in psums if not r.scalar_only)
    loss = next(r for r in psums if r.scalar_only)
    n_leaves = len(jax.tree_util.tree_leaves(params))
    assert grad.n_operands == n_leaves
    assert grad.dtypes == ("bfloat16",) and grad.pinned
    assert loss.dtypes == ("float32",)
    assert counts["psum"] == 2


def test_ra601_wide_dtype_on_wire():
    recs = [_rec(dtypes=("float32",), payload_bytes=16384), _rec(**_LOSS)]
    fs = collective_schedule_findings(recs, _sharded_expected())
    assert "RA601" in codes(fs)
    assert "float32" in _msg(fs, "RA601")


def test_ra601_unpinned_narrow_reduction():
    """bf16 psum without the optimization_barrier pin: XLA may re-promote
    it — the structural def-use check fires even though the jaxpr dtype
    still says bf16."""
    recs = [_rec(pinned=False), _rec(**_LOSS)]
    fs = collective_schedule_findings(recs, _sharded_expected())
    assert "RA601" in codes(fs)
    assert "barrier" in _msg(fs, "RA601")


def test_ra602_unconditional_boundary_collective():
    recs = [_rec(), _rec(**_LOSS),
            _rec(primitive="all_gather", shapes=((8, 16),),
                 payload_bytes=512, pinned=False)]
    fs = collective_schedule_findings(recs, _sharded_expected())
    assert "RA602" in codes(fs)


def test_ra603_full_gradient_gather_in_steady_state():
    params = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    recs = [_rec(), _rec(**_LOSS),
            _rec(primitive="all_gather", shapes=((64, 64),),
                 payload_bytes=16384, pinned=False)]
    fs = collective_schedule_findings(recs, _sharded_expected(),
                                      params=params)
    assert "RA603" in codes(fs)
    assert "RA602" not in codes(fs)


def test_ra606_schedule_divergence():
    # two gradient psums where the model says one (per-leaf reduction crept
    # back in)
    recs = [_rec(), _rec(), _rec(**_LOSS)]
    fs = collective_schedule_findings(recs, _sharded_expected())
    assert "RA606" in codes(fs)
    # missing loss pmean
    fs = collective_schedule_findings([_rec()], _sharded_expected())
    assert "RA606" in codes(fs)


_ALIASED = ('%arg{i}: tensor<{t}> {{tf.aliasing_output = {i} : i32, '
            'mhlo.sharding = "{{replicated}}"}}')
_PLAIN = '%arg{i}: tensor<{t}>'
_SHARDED = ('%arg{i}: tensor<{t}> '
            '{{mhlo.sharding = "{{devices=[2,1]<=[2]}}"}}')


def _module(arg_chunks):
    return ("module @jit_step {\n  func.func public @main("
            + ", ".join(arg_chunks) + ") -> (tensor<4x4xf32>) {}\n}")


def test_parse_main_args_and_donation_clean():
    txt = _module([
        _ALIASED.format(i=0, t="4x4xf32"),
        _ALIASED.format(i=1, t="4x4xf32"),
        _SHARDED.format(i=2, t="8x16xi32"),
    ])
    args = parse_main_args(txt)
    assert [a.aliased for a in args] == [True, True, False]
    assert args[0].nbytes == 64 and args[2].dtype == "i32"
    assert not args[2].replicated
    assert donation_findings(args, n_params=1, n_opt=1) == []
    assert replication_findings(args, n_params=1, n_opt=1, n_shards=2) == []


def test_ra604_lost_donation():
    txt = _module([
        _ALIASED.format(i=0, t="4x4xf32"),
        _PLAIN.format(i=1, t="4x4xf32"),      # opt-state leaf, not aliased
        _SHARDED.format(i=2, t="8x16xi32"),
    ])
    fs = donation_findings(parse_main_args(txt), n_params=1, n_opt=1)
    assert codes(fs) == {"RA604"}
    assert "opt_state" in _msg(fs, "RA604")


def test_ra605_replicated_batch():
    txt = _module([
        _ALIASED.format(i=0, t="4x4xf32"),
        _ALIASED.format(i=1, t="4x4xf32"),
        _PLAIN.format(i=2, t="8x16xi32"),     # batch with no sharding attr
    ])
    fs = replication_findings(parse_main_args(txt), n_params=1, n_opt=1,
                              n_shards=2)
    assert codes(fs) == {"RA605"}
    # mesh of 1: replication is the only option, not a finding
    assert replication_findings(parse_main_args(txt), n_params=1, n_opt=1,
                                n_shards=1) == []


def test_wire_bytes_ring_coefficients():
    recs = [_rec(payload_bytes=1000),
            _rec(primitive="all_gather", payload_bytes=1000, pinned=False,
                 under_cond=True)]
    m = wire_bytes_model(recs, 8)
    assert m["steady_bytes_per_step"] == int(1000 * 2 * 7 / 8)
    assert m["boundary_bytes"] == int(1000 * 7 / 8)
    assert wire_bytes_model(recs, 1)["steady_bytes_per_step"] == 0


def test_per_shard_memory_model():
    params = {"w": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    opt = {"mu": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    m = per_shard_memory(params, opt, batch, n_shards=8)
    assert m["params_bytes"] == 64 * 64 * 4
    assert m["grad_bytes_fp32"] == 64 * 64 * 4
    assert m["grad_wire_bytes"] == 64 * 64 * 2     # bf16 wire copy
    assert m["batch_bytes_per_shard"] == 8 * 16 * 4 // 8
    assert m["peak_bytes_per_shard"] == sum(
        m[k] for k in ("params_bytes", "opt_state_bytes", "grad_bytes_fp32",
                       "grad_wire_bytes", "batch_bytes_per_shard"))


def test_expected_schedule_counts_families():
    t = build_optimizer(OptimizerConfig(name="gum", rank=8, period=5,
                                        gamma=1, kernel_impl="jnp",
                                        fuse_families=True))
    exp = expected_collective_schedule(t, PARAMS, n_shards=4)
    assert exp["grad_psum"]["operands"] == len(
        jax.tree_util.tree_leaves(PARAMS))
    assert exp["boundary_gather"]["count"] == 0
    assert exp["boundary_gather"]["families"] == 3


def test_per_shard_bytes_divides_by_mesh():
    """sharding.per_shard_bytes charges per-shard, not per-replica: a 2-D
    fsdp-sharded matrix divides by the data-axis size, a 1-D norm vector
    (replicated by rule) does not."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.sharding import per_shard_bytes

    devs = np.asarray(jax.devices()[:1]).reshape(1)
    mesh = Mesh(devs, ("data",))
    tree = {"layers/0/attn/wq": jax.ShapeDtypeStruct((64, 64), jnp.float32),
            "norm/scale": jax.ShapeDtypeStruct((64,), jnp.float32)}
    # 1-way mesh: nothing divides
    assert per_shard_bytes(tree, mesh) == 64 * 64 * 4 + 64 * 4

    class FakeMesh:
        axis_names = ("data",)
        shape = {"data": 4}

    assert per_shard_bytes(tree, FakeMesh()) == 64 * 64 * 4 // 4 + 64 * 4
