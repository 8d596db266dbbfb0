"""The yardstick's arithmetic, at shapes small enough to count by hand."""
import types

import pytest

from chipbench import check, kernels, period, run, work
from chipbench.models import dense
from chipbench.trace import Event

PEAK = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}


def test_tokens_per_s_weighs_the_refresh_once_per_period():
    # period 4: one refresh of 5 s, steady steps of 1 s -> 5 + 3 * 1 = 8 s
    assert period.period_seconds([5.0], [1.0, 1.0], 4) == pytest.approx(8.0)
    assert period.tokens_per_s([5.0], [0.5, 1.5], 4, 100) == pytest.approx(50.0)


def test_tokens_per_s_uses_totals_not_medians():
    # one slow steady step moves the rate: mean of (1, 1, 4) = 2
    assert period.tokens_per_s([2.0], [1.0, 1.0, 4.0], 2, 10) == pytest.approx(5.0)


@pytest.mark.parametrize("step,want", [(0, True), (1, False), (200, True), (399, False)])
def test_refresh_steps(step, want):
    assert period.is_refresh(step, 200) is want


def test_kernel_flops_by_hand():
    # L=2, m=2, n=3: Gram 2*2*4*3 = 48; apply 2*(2*4*3 + 2*6) = 72
    assert work.ns_gram(2, 2, 3) == 48
    assert work.ns_apply(2, 2, 3) == 72
    # L=1, m=2, n=4, r=1: 2*2*4*1 + 3*1*4 = 28
    assert work.lowrank_update(1, 2, 4, 1) == 28
    # the projection alone: 2*2*4*1 = 16
    assert work.project(1, 2, 4, 1) == 16


def test_hbm_bytes_leave_out_on_chip_tensors():
    assert work.hbm_bytes([("f32", (2, 3), True), ("bf16", (4,), True),
                           ("f32", (100, 100), False)]) == 24 + 8


def test_least_seconds_takes_the_binding_bound():
    assert work.least_seconds(1000, 10, PEAK) == pytest.approx(10.0)
    assert work.least_seconds(10, 1000, PEAK) == pytest.approx(100.0)


def test_dense_flops_per_token_by_hand():
    cfg = dict(hidden_size=4, intermediate_size=8, num_attention_heads=2,
               num_key_value_heads=1, num_hidden_layers=2, vocab_size=10)
    # per layer: q,o 2*16 + k,v 2*4*2 + mlp 3*4*8 = 32 + 16 + 96 = 144
    weights = 2 * 144 + 4 * 10
    assert dense.flops_per_token(cfg, 6) == pytest.approx(6 * weights + 6 * 2 * 4 * 6)


def test_held_bytes_counts_the_step_beside_the_allocator():
    mem = types.SimpleNamespace(argument_size_in_bytes=100, output_size_in_bytes=90,
                                alias_size_in_bytes=80, temp_size_in_bytes=50)

    class Chip:
        def __init__(self, peak):
            self.peak = peak

        def memory_stats(self):
            return {"peak_bytes_in_use": self.peak}

    # the step holds 100 + 90 - 80 + 50 = 160 on each chip
    assert run.held_bytes(mem, [Chip(120), Chip(140)]) == (160, 140)
    assert run.held_bytes(mem, [Chip(120), Chip(170)]) == (170, 170)


def test_family_readings_are_the_norms_of_their_stacks():
    first = {"mu:embed": 2.0, "low:a": 3.0, "low:b": 4.0, "full:a": 1.0, "full:b": 0.0,
             "low:c": 5.0, "full:c": 2.0}
    got = check.by_family(first, [["a", "b"], ["c"]])
    assert got == {"mu:embed": 2.0, "low:0": 5.0, "full:0": 1.0, "low:1": 5.0, "full:1": 2.0}


def test_kernel_events_read_their_shapes():
    ev = Event("%lowrank_update_batched.7 = f32[8,128,2560]{2,1,0:T(8,128)S(1)} custom-call("
               "f32[8,2560,128]{2,1,0:T(8,128)S(1)} %copy-done.51, f32[8,2560,2560]{2,1,0:T(8,128)} "
               "%fusion.6, f32[8,128,2560]{2,1,0:T(8,128)S(1)} %fusion.14), "
               "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
               "{f32[8,2560,128]{2,1,0}}", 0.0, 1.0)
    result, operands = kernels.tensors(ev)
    assert result == [("f32", (8, 128, 2560), False)]
    assert [s for _, s, _ in operands] == [(8, 2560, 128), (8, 2560, 2560), (8, 128, 2560)]
    assert [h for _, _, h in operands] == [False, True, False]
    assert kernels.FLOPS["lowrank_update"]["lowrank_update_batched"](
        *(s for _, s, _ in operands)) == work.lowrank_update(8, 2560, 2560, 128)


def test_roofline_sums_least_time_over_kernel_time():
    gram = Event("%gram.3 = f32[1,2,2]{2,1,0:S(1)} custom-call(f32[1,2,3]{2,1,0} %x)", 0.0, 2.0)
    poly = Event("%poly_matmul_axpy.3 = f32[1,2,3]{2,1,0:S(1)} custom-call(f32[1,2,2]{2,1,0:S(1)} "
                 "%a, f32[1,2,3]{2,1,0:S(1)} %x)", 2.0, 2.0)
    other = Event("%fusion.1 = f32[1]{0} fusion()", 4.0, 5.0)

    class Run:
        peak = PEAK

        def step_ops(self):
            return [gram, poly, other]

    # Gram: 24 flops -> 0.24 s, 24 HBM bytes (x) -> 2.4 s;
    # apply: 36 flops -> 0.36 s, no HBM bytes
    assert kernels.roofline(Run(), "newton_schulz") == pytest.approx(100 * 2.76 / 4.0)
    assert kernels.roofline(Run(), "lowrank_update") is None


def test_projection_kernel_events_read_their_shapes():
    ev = Event("%project_batched.3 = f32[2,128,2560]{2,1,0:T(8,128)S(1)} custom-call("
               "f32[2,2560,128]{2,1,0:T(8,128)} %fusion.7, f32[2,2560,6912]{2,1,0:T(8,128)} "
               "%fusion.8), custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
               "{f32[2,2560,128]{2,1,0}, f32[2,2560,6912]{2,1,0}}", 0.0, 1.0)
    result, operands = kernels.tensors(ev)
    assert result == [("f32", (2, 128, 2560), False)]
    assert [s for _, s, _ in operands] == [(2, 2560, 128), (2, 2560, 6912)]
    assert kernels.FLOPS["lowrank_update"]["project_batched"](
        *(s for _, s, _ in operands)) == work.project(2, 2560, 6912, 128)


def test_lowrank_roofline_reads_the_projection_kernel_alone():
    """Where the projection runs ``project_batched`` and no fused update (each
    chip's share of a member on a mesh), the metric reads that kernel."""
    proj = Event("%project_batched.1 = f32[1,1,4]{2,1,0:S(1)} custom-call("
                 "f32[1,2,1]{2,1,0} %p, f32[1,2,4]{2,1,0} %g)", 0.0, 8.0)
    upd = Event("%lowrank_update_batched.2 = f32[1,1,4]{2,1,0:S(1)} custom-call("
                "f32[1,2,1]{2,1,0:S(1)} %p, f32[1,2,4]{2,1,0:S(1)} %g, "
                "f32[1,1,4]{2,1,0:S(1)} %r)", 8.0, 2.0)

    class Run:
        peak = PEAK

        def __init__(self, ops):
            self.ops = ops

        def step_ops(self):
            return self.ops

    # project: 16 flops -> 0.16 s; HBM bytes p 8 + g 32 = 40 -> 4.0 s; over 8 s
    assert kernels.roofline(Run([proj]), "lowrank_update") == pytest.approx(50.0)
    # with the fused update: 28 flops -> 0.28 s, no HBM bytes; (4.0 + 0.28) / 10 s
    assert kernels.roofline(Run([proj, upd]), "lowrank_update") == pytest.approx(42.8)
