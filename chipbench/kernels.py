"""Kernel rooflines: a kernel's events in the trace, the work each event
needs at the shapes it was called with, and the HBM bytes it moves, both read
from the event's HLO (its result and operands, their shapes and layouts; a
layout in memory space 1, ``S(1)``, is on-chip memory and moves no HBM
bytes)."""
from __future__ import annotations

import re

from chipbench import trace, work

_TENSOR = re.compile(r"(f32|bf16|s32|u32|pred)\[([\d,]*)\](\{[^}]*\})?")

# instruction-name prefix of a kernel's events -> flops(operand shapes)
FLOPS = {
    "newton_schulz": {
        "gram": lambda x: work.ns_gram(*x),
        "poly_matmul_axpy": lambda a, x: work.ns_apply(*x),
    },
    # the projection PᵀG: fused with the momentum update, or alone (the
    # full-rank slots' residual, and each chip's share of a member on a mesh)
    "lowrank_update": {
        "lowrank_update_batched": lambda p, g, r: work.lowrank_update(*g, p[-1]),
        "project_batched": lambda p, g: work.project(*g, p[-1]),
    },
}


def tensors(op: trace.Event) -> tuple:
    """(result, operands) of a custom call's event, each a list of
    (dtype, shape, in_hbm)."""
    head, _, rest = op.name.partition("custom-call(")
    args = rest.split("custom_call_target", 1)[0]

    def parse(text):
        return [(dt, tuple(int(d) for d in dims.split(",") if d),
                 "S(1)" not in (layout or ""))
                for dt, dims, layout in _TENSOR.findall(text)]

    return parse(head.split(" = ", 1)[-1]), parse(args)


def roofline(run, kernel: str):
    """Sum over the kernel's events of the least time their work needs,
    over the sum of their device time, in percent (None if none ran)."""
    least = busy = 0.0
    for op in run.step_ops():
        name = trace.instruction(op).split(".")[0]
        if name in FLOPS[kernel]:
            result, operands = tensors(op)
            flops = FLOPS[kernel][name](*(shape for _, shape, _ in operands))
            least += work.least_seconds(flops, work.hbm_bytes(result + operands), run.peak)
            busy += op.dur
    if busy <= 0 or run.peak is None:
        return None
    return 100.0 * least / busy
