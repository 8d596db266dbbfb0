"""Stage names in the compiled train step (``jax.named_scope``), the host
spans the trainer puts on the profiler's clock, and the start-up event that
lists the dispatched ops falling back to jnp.

A device trace names ops only by HLO instruction; the name stack in each
op's metadata is what a reading by stage (``chipbench/scopes.py``) matches,
so these tests read the same metadata from a CPU compile."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import RunConfig, get_smoke
from repro.core import OptimizerConfig, build_optimizer
from repro.core.combinators import family_sharding
from repro.data import DataConfig
from repro.kernels import dispatch
from repro.launch.steps import make_train_step
from repro.models import build_model

STEP = ("loss_and_grads", "grad_clip", "optimizer", "guard")
STAGES = ("apply_updates", "gum", "adamw", "lowrank.refresh", "lowrank.project",
          "lowrank.inner", "lowrank.back_project", "unbias.full_slots",
          "newton_schulz.jnp", "lowrank_update.jnp", "project.jnp",
          "back_project.jnp")
HOST_SPANS = ("step", "feed", "dispatch", "block", "loss")


def op_names(hlo_text: str) -> dict:
    """HLO instruction -> name stack, read as ``chipbench.trace.hlo_op_names``
    reads it."""
    return dict(re.findall(r'%(\S+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"',
                           hlo_text))


def components(stack: str) -> list:
    """The scope names in a name stack, transforms unwrapped
    (``vmap(newton_schulz.jnp)`` gives ``newton_schulz.jnp``)."""
    return [c for c in re.split(r"[/()]", stack) if c]


def _gum(fuse: bool):
    return OptimizerConfig(name="gum", lr=1e-3, rank=4, gamma=1, period=4,
                           projector="subspace", fuse_families=fuse)


def _abstract(model, opt):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return (params, jax.eval_shape(opt.init, params),
            {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)})


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """Three per-leaf GUM steps with telemetry on and step 1 profiled.  The
    start-up trace of the update takes the dispatch decisions of a TPU, with
    the Newton-Schulz bound lowered below the model's widths so that its
    full-rank slots fall back; the steps themselves run on the CPU.  Gives
    the run's directory, its log and the trainer."""
    from repro.telemetry import instrument
    from repro.telemetry.bus import read_jsonl
    from repro.train import Trainer

    cfg = get_smoke("llama-60m")
    tmp = tmp_path_factory.mktemp("run")
    crosscheck = instrument.launch_crosscheck

    def on_a_tpu(*args, **kwargs):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(dispatch, "backend", lambda: "tpu")
            return crosscheck(*args, **kwargs)

    trainer = Trainer(
        build_model(cfg), _gum(False),
        RunConfig(steps=3, ckpt_dir=str(tmp), ckpt_every=0, log_every=0,
                  resume=False, grad_clip=1.0),
        DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=2),
        telemetry="stdout=0", profile_steps="1:2")
    with pytest.MonkeyPatch.context() as m:
        m.setattr(instrument, "launch_crosscheck", on_a_tpu)
        m.setattr(dispatch, "MAX_NS_DIM", 16)
        result = trainer.train()
    return tmp, read_jsonl(result.events_path), trainer


@pytest.fixture(scope="module", params=[False, True], ids=["per_leaf", "fused"])
def step(request, run):
    """(step function, abstract arguments, op names of its compiled HLO): the
    per-leaf step the run compiled, and the family-stacked one."""
    trainer = run[2]
    if not request.param:
        # the run's own executable, from JAX's in-memory cache
        text = trainer.lower_step().compile().as_text()
        return trainer._step_fn, _abstract(trainer.model, trainer.optimizer), \
            op_names(text)
    model, opt = trainer.model, build_optimizer(_gum(True))
    fn = make_train_step(model, opt, grad_clip=1.0)
    args = _abstract(model, opt)
    return fn, args, op_names(jax.jit(fn).lower(*args).compile().as_text())


def test_compiled_step_names_every_stage(step):
    _, _, names = step
    found = {c for stack in names.values() for c in components(stack)}
    assert set(STEP + STAGES) <= found, set(STEP + STAGES) - found


def test_stages_outside_autodiff_add_no_autodiff_words(step):
    """A reading by name (``optimizer_share``) calls an op backward when
    ``transpose`` is in its name stack and forward for ``jvp``.  Outside
    ``loss_and_grads`` no name on the way to an op holds either word; only
    the op's own primitive may be a ``transpose``."""
    _, _, names = step
    for inst, stack in names.items():
        if {"grad_clip", "optimizer", "guard"} & set(components(stack)):
            path = stack.rsplit("/", 1)[0]
            assert "jvp" not in path and "transpose" not in path, (inst, stack)


def test_no_op_falls_under_two_step_stages(step):
    _, _, names = step
    for inst, stack in names.items():
        assert len([c for c in components(stack) if c in STEP]) <= 1, (inst, stack)


def test_ops_under_no_stage_read_none_of_the_steps_inputs(step):
    """JAX computes loop-invariant constants of a scan outside the loop with
    the name stack reset (the rotary tables and the causal mask of the
    layer scan), so they carry no stage.  Every other op of the step does:
    an equation without a stage reads none of the step's inputs."""
    fn, args, _ = step
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    tainted = set(map(id, jaxpr.invars))
    unstaged = 0
    for eqn in jaxpr.eqns:
        staged = any(c in STEP for c in components(str(eqn.source_info.name_stack)))
        reads = any(id(v) in tainted for v in eqn.invars if hasattr(v, "aval"))
        if reads:
            assert staged, (eqn.primitive, eqn.source_info.name_stack)
            tainted.update(map(id, eqn.outvars))
        unstaged += not staged
    assert unstaged < len(jaxpr.eqns) // 10


def test_oversized_newton_schulz_runs_under_its_jnp_name(monkeypatch):
    """Above the kernel's Gram bound a Pallas request runs the jnp path, and
    the trace names it ``newton_schulz.jnp``: the time kernel_fallback_ms
    reads."""
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    n = dispatch.MAX_NS_DIM + 8
    x = jax.ShapeDtypeStruct((n, n + 8), jnp.float32)
    text = jax.jit(lambda x: dispatch.newton_schulz(x, impl="pallas")).lower(x) \
        .compile().as_text()
    stacks = op_names(text).values()
    assert any("newton_schulz.jnp" in components(s) for s in stacks)
    assert not any("newton_schulz.pallas" in components(s) for s in stacks)


def test_sharded_refresh_gathers_under_the_refresh_stage():
    """With the family-stacked state sharded, the refresh's boundary
    all_gather is part of ``lowrank.refresh``."""
    model = build_model(get_smoke("llama-60m"))
    opt = build_optimizer(_gum(True))
    params, state, _ = _abstract(model, opt)
    mesh = jax.sharding.AbstractMesh((2,), ("data",))
    with family_sharding(mesh, "data"):
        jaxpr = jax.make_jaxpr(opt.update)(params, state, params).jaxpr

    def gathers(j, stack):
        for eqn in j.eqns:
            here = stack + components(str(eqn.source_info.name_stack))
            if eqn.primitive.name == "all_gather":
                yield here
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from gathers(sub, here)

    found = list(gathers(jaxpr, []))
    assert found and all("lowrank.refresh" in s for s in found), found


def test_profile_of_a_run_holds_the_bus_spans(run):
    from jax.profiler import ProfileData

    tmp = run[0]
    paths = glob.glob(os.path.join(tmp, "profile", "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(paths[0])
    host = {e.name for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}
    assert set(HOST_SPANS) <= host, set(HOST_SPANS) - host


def test_run_log_lists_the_fallbacks_once(run):
    recs = run[1]
    events = [r for r in recs if r.get("name") == "dispatch_fallback"]
    assert len(events) == 1
    listed = {(f["op"], tuple(f["shape"])) for f in events[0]["data"]["fallbacks"]}
    # the full-rank slots (gamma=1 of 2 layers) of the 64x64 attention and
    # the 64x128 / 128x64 MLP matrices; the rank-4 Newton-Schulz stays legal
    assert listed == {("newton_schulz", (1, 64, 64)), ("newton_schulz", (1, 64, 128)),
                      ("newton_schulz", (1, 128, 64))}
    # the spans and records the run log had before are unchanged
    assert [r["tags"]["kind"] for r in recs if r.get("name") == "step"] == \
        ["refresh", "steady", "steady"]
    assert {r["name"] for r in recs if r["kind"] == "span"} == {"step", "ckpt_save"}


def test_crosscheck_lists_an_oversized_newton_schulz(monkeypatch):
    """The start-up trace of the update lists each dispatched op that falls
    back to jnp, with its shape: here a Newton-Schulz slot wider than the
    kernel allows."""
    from repro.telemetry.instrument import launch_crosscheck

    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    n = dispatch.MAX_NS_DIM + 8
    opt = build_optimizer(OptimizerConfig(name="gum", lr=1e-3, rank=4, gamma=1,
                                          period=3, kernel_impl="pallas"))
    params = {"blocks": {"w": jax.ShapeDtypeStruct((2, n, n), jnp.float32)}}
    xc = launch_crosscheck(opt, params, name="gum")
    assert {"op": "newton_schulz", "shape": [1, n, n], "calls": 1} in xc["fallbacks"]
    assert all(f["op"] != "lowrank_update" for f in xc["fallbacks"])
