"""The reduction from trace events to per-layer numbers, on synthetic traces."""
import pytest

from chipbench import trace
from chipbench.trace import Event


def ev(name, start, dur):
    return Event(f"%{name} = f32[] fusion()", start, dur)


def test_union_and_busy_share():
    ops = [ev("a", 0.0, 1.0), ev("b", 0.5, 1.0), ev("c", 3.0, 1.0)]
    assert trace.union([(o.start, o.end) for o in ops]) == [(0.0, 1.5), (3.0, 4.0)]
    assert trace.busy_seconds(ops, (0.0, 5.0)) == pytest.approx(2.5)
    assert trace.busy_seconds(ops, (1.0, 3.5)) == pytest.approx(1.0)


def test_self_time_subtracts_nested_ops():
    outer = ev("while.1", 0.0, 10.0)
    inner = [ev("fusion.1", 1.0, 2.0), ev("fusion.2", 4.0, 3.0)]
    got = {trace.instruction(o): t for o, t in trace.self_times([outer] + inner)}
    assert got == pytest.approx({"while.1": 5.0, "fusion.1": 2.0, "fusion.2": 3.0})


HLO = """
  %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, metadata={op_name="jit(train_step)/jvp(model)/dot_general" source_file="m.py"}
  %while.2 = (s32[]) while((s32[]) %t), body=%body, metadata={op_name="jit(train_step)/transpose(jvp(model))/while"}
  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %q), metadata={op_name="jit(train_step)/transpose(jvp(model))/dot_general"}
  %fusion.5 = f32[4]{0} fusion(f32[4]{0} %r), metadata={op_name="jit(train_step)/while/body/dot_general"}
"""


def test_op_names_from_compiled_text():
    names = trace.hlo_op_names(HLO)
    assert names["fusion.7"] == "jit(train_step)/jvp(model)/dot_general"
    assert names["while.2"].endswith("transpose(jvp(model))/while")
    assert trace.instruction(ev("fusion.7", 0.0, 1.0)) == "fusion.7"


@pytest.mark.parametrize("name,want", [
    ("fusion.7", "forward"), ("fusion.3", "backward"), ("fusion.5", "optimizer"),
    ("fusion.99", "optimizer")])
def test_phase_by_name_stack(name, want):
    assert trace.phase(ev(name, 0.0, 1.0), trace.hlo_op_names(HLO)) == want


def test_time_by_phase_counts_self_time_once():
    ops = [ev("while.2", 0.0, 4.0), ev("fusion.3", 1.0, 1.0),
           ev("fusion.7", 5.0, 2.0), ev("fusion.5", 8.0, 1.0)]
    names = trace.hlo_op_names(HLO)
    assert trace.time_by(ops, lambda o: trace.phase(o, names)) == pytest.approx(
        {"backward": 4.0, "forward": 2.0, "optimizer": 1.0})


def test_module_name_of_a_compiled_program():
    text = "HloModule jit_step_fn, is_scheduled=true, entry_computation_layout={()}\n"
    assert trace.module_name(text) == "jit_step_fn"


def test_ops_within_program_spans():
    ops = [ev("a", 0.5, 0.1), ev("b", 1.5, 0.1), ev("c", 2.5, 0.1)]
    spans = [Event("jit_train_step(1)", 0.0, 1.0), Event("jit_train_step(1)", 2.0, 1.0)]
    assert [o.name for o in trace.within(ops, spans)] == [ops[0].name, ops[2].name]


def test_exposed_collective_is_what_compute_does_not_cover():
    ops = [ev("all-gather.1", 0.0, 4.0), ev("fusion.1", 1.0, 1.0),
           ev("all-reduce.2", 5.0, 1.0), ev("fusion.2", 5.5, 2.0)]
    # all-gather exposed 3 of 4, all-reduce 0.5 of 1
    assert trace.exposed_collective_seconds(ops) == pytest.approx(3.5)


def test_a_loop_around_a_collective_does_not_hide_it():
    ops = [ev("while.1", 0.0, 10.0), ev("fusion.1", 0.0, 2.0),
           ev("all-gather.1", 2.0, 2.0), ev("fusion.2", 4.0, 6.0)]
    # the loop encloses the others and runs nothing itself: 2..4 exposed
    assert [o.name for o in trace.innermost(ops)] == [ops[1].name, ops[2].name, ops[3].name]
    assert trace.exposed_collective_seconds(ops) == pytest.approx(2.0)


def test_idle_gaps_named_by_host_span():
    ops = [ev("a", 0.0, 1.0), ev("b", 2.0, 1.0), ev("c", 3.5, 0.5)]
    host = [Event("feed", 1.0, 0.2), Event("block", 1.2, 0.8),
            Event("loss", 3.0, 0.5)]
    gaps = trace.idle_gaps(ops, host, (0.0, 5.0))
    # equal gaps keep their order in time: 1..2 under "block", 4..5 under none
    assert gaps == [["block", pytest.approx(1.0)], ["other", pytest.approx(1.0)],
                    ["loss", pytest.approx(0.5)]]
