"""Per-layer metrics of a traced run.

Each metric of the cell is read by ``metrics/<name>.py``, whose ``read(run)``
returns a number or None (nothing to read: the metric is left out of the
line).  ``run`` is a :class:`Run`: the cell, the host timings of the run, the
reduced trace and the chip's published peaks.
"""
from __future__ import annotations

import dataclasses
import importlib

from chipbench import spec, trace
from chipbench.peaks import peaks

@dataclasses.dataclass
class Run:
    cell: spec.Cell
    got: dict                 # what run.measure returned
    trace: trace.Trace
    peak: dict | None         # published peaks (None off a TPU)
    chips: int

    @property
    def ops(self) -> list:
        """Ops of the first chip inside the traced window."""
        lo, hi = self.trace.window
        ops = next(iter(self.trace.devices.values()), [])
        return [o for o in ops if o.end > lo and o.start < hi]

    def step_ops(self) -> list:
        """Ops that ran inside the compiled train step's programs."""
        mods = next(iter(self.trace.modules.values()), [])
        name = trace.module_name(self.got["hlo_text"])
        return trace.within(self.ops, [m for m in mods if m.name.startswith(name)])

    def model(self):
        return importlib.import_module("chipbench.models." + self.cell.config["reference"])


def read(cell, got: dict, devices) -> tuple:
    """(metrics, busy seconds averaged over the chips, breakdown)."""
    tr = trace.load(got["trace_dir"], trace.hlo_op_names(got["hlo_text"]))
    peak = peaks(devices[0].device_kind) if devices[0].platform == "tpu" else None
    run = Run(cell, got, tr, peak, len(devices))
    metrics = {}
    for m in cell.per_layer:
        value = spec.load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = [trace.busy_seconds(ops, tr.window) for ops in tr.devices.values()]
    by_op = trace.time_by(run.ops, lambda o: " ".join(
        (trace.instruction(o), tr.op_names.get(trace.instruction(o), "")))[:160])
    breakdown = {
        "device_ops": sorted(([k, v] for k, v in by_op.items()), key=lambda x: -x[1])[:10],
        "idle_gaps": trace.idle_gaps(run.ops, tr.host, tr.window),
    }
    return metrics, sum(busy) / max(len(busy), 1), breakdown
