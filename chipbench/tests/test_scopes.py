"""Readings by the program's stage names, on synthetic traces."""
import pytest

from chipbench import layers, scopes, trace
from chipbench.metrics import (
    kernel_fallback_ms,
    lowrank_projection_ms,
    newton_schulz_ms,
    optimizer_collective_exposed_ms,
)
from chipbench.trace import Event

HLO = "HloModule jit_train_step, entry_computation_layout={()}\n"
NAMES = {
    "while.1": "jit(train_step)/optimizer/gum/lowrank.inner/newton_schulz.jnp/while",
    "fusion.1": "jit(train_step)/optimizer/gum/lowrank.inner/newton_schulz.jnp/while/body/dot_general",
    "fusion.2": "jit(train_step)/optimizer/gum/vmap(newton_schulz.jnp)/dot_general",
    "gram.3": "jit(train_step)/optimizer/gum/lowrank.inner/newton_schulz.pallas/pallas_call",
    "fusion.4": "jit(train_step)/optimizer/gum/lowrank.inner/lowrank.project/lowrank_update.pallas/pallas_call",
    "fusion.5": "jit(train_step)/optimizer/gum/lowrank.inner/lowrank.back_project/back_project.jnp/dot_general",
    "fusion.6": "jit(train_step)/optimizer/gum/lowrank.projector_probe/dot_general",
    "while.7": "jit(train_step)/loss_and_grads/while",
    "fusion.8": "jit(train_step)/loss_and_grads/transpose(jvp(model))/while/body/dot_general",
    "all-gather.9": "jit(train_step)/loss_and_grads/jvp(model)/dot_general",
    "all-to-all.10": "jit(train_step)/optimizer/gum/lowrank.project/transpose",
}


def ev(name, start, dur):
    return Event(f"%{name} = f32[] fusion()", start, dur)


def make_run(ops_by_chip, steady=2, names=NAMES):
    """A traced run of ``len(ops_by_chip)`` chips whose window is one call of
    the step program over [0, 100)."""
    devices = {f"/device:TPU:{i}": sorted(ops, key=lambda o: o.start)
               for i, ops in enumerate(ops_by_chip)}
    modules = {d: [Event("jit_train_step(1)", 0.0, 100.0)] for d in devices}
    tr = trace.Trace(devices=devices, modules=modules, host=[], window=(0.0, 100.0),
                     op_names=names)
    got = {"hlo_text": HLO, "log": {"steady": [1.0] * steady, "refresh": []}}
    return layers.Run(cell=None, got=got, trace=tr, peak=None, chips=len(devices))


@pytest.mark.parametrize("stack,scope,hit", [
    ("a/vmap(newton_schulz.jnp)/dot", "newton_schulz", True),
    ("a/newton_schulz.pallas/pallas_call", "newton_schulz", True),
    ("a/newton_schulz.pallas/pallas_call", r"[a-z_]+\.jnp", False),
    ("a/back_project.jnp/dot", r"[a-z_]+\.jnp", True),
    ("a/lowrank.projector_probe/dot", r"lowrank\.project", False),
    ("a/lowrank.project/x", r"lowrank\.project", True),
    ("a/my_newton_schulz.jnp/dot", "newton_schulz", False),
])
def test_scope_matches_a_whole_component(stack, scope, hit):
    assert bool(scopes.pattern(scope).search(stack)) is hit


def test_loop_body_counted_once_per_steady_step():
    # while.1 encloses fusion.1: its 10 s are 4 s of its own and 6 s of body
    ops = [ev("while.1", 0.0, 10.0), ev("fusion.1", 2.0, 6.0), ev("fusion.2", 20.0, 2.0),
           ev("gram.3", 30.0, 4.0), ev("fusion.4", 40.0, 1.0), ev("fusion.5", 50.0, 3.0)]
    run = make_run([ops])
    assert newton_schulz_ms.read(run) == pytest.approx(1e3 * (10 + 2 + 4) / 2)
    assert kernel_fallback_ms.read(run) == pytest.approx(1e3 * (10 + 2 + 3) / 2)
    assert lowrank_projection_ms.read(run) == pytest.approx(1e3 * (1 + 3) / 2)


def test_loop_outside_the_scope_keeps_its_own_time():
    # a backward loop (unscoped for these readings) around a projection op
    ops = [ev("while.7", 0.0, 10.0), ev("fusion.4", 1.0, 2.0)]
    assert lowrank_projection_ms.read(make_run([ops], steady=1)) == pytest.approx(2e3)


def test_a_program_without_stage_names_reads_nothing():
    ops = [ev("fusion.1", 0.0, 1.0), ev("all-to-all.10", 2.0, 1.0)]
    run = make_run([ops, ops], names={})
    for metric in (newton_schulz_ms, kernel_fallback_ms, lowrank_projection_ms,
                   optimizer_collective_exposed_ms):
        assert metric.read(run) is None, metric


def test_optimizer_collectives_exposed_averaged_over_chips():
    # chip 0: the optimizer's all-to-all 10..14 half hidden by fusion.4;
    # the forward all-gather 0..4 is not the optimizer's
    chip0 = [ev("all-gather.9", 0.0, 4.0), ev("all-to-all.10", 10.0, 4.0),
             ev("fusion.4", 10.0, 2.0)]
    # chip 1: the all-to-all 10..13 runs alone inside a loop (the loop only
    # encloses other ops, so it hides nothing)
    chip1 = [ev("all-gather.9", 0.0, 4.0), ev("while.7", 9.0, 5.0),
             ev("fusion.8", 9.0, 1.0), ev("all-to-all.10", 10.0, 3.0)]
    run = make_run([chip0, chip1], steady=2)
    assert optimizer_collective_exposed_ms.read(run) == pytest.approx(1e3 * (2 + 3) / 2 / 2)


def test_split_by_innermost_stage():
    ops = [ev("fusion.1", 0.0, 1.0), ev("fusion.4", 1.0, 2.0), ev("fusion.5", 3.0, 3.0),
           ev("fusion.8", 6.0, 4.0)]
    got = scopes.split(make_run([ops], steady=1),
                       ["optimizer", r"lowrank\.inner", r"lowrank\.project",
                        r"lowrank\.back_project"])
    assert got == pytest.approx({r"lowrank\.inner": 1e3, r"lowrank\.project": 2e3,
                                 r"lowrank\.back_project": 3e3, "": 4e3})
