"""What one cell is, read from ``BENCHMARK.json`` and the files it names.

A cell (a ``workloads`` entry) names a configuration and a traffic mix.  The
configuration is ``configs/<name>.json``; its plain reference is
``models/<reference>.py``; the traffic mix is ``traffic/<traffic>.json``; the
optimizer's reference is ``optimizers/<optimizer name>.py``; the limits of the
correctness check are ``limits/<workload>.json``; each per-layer metric is read
by ``metrics/<metric>.py``.  Adding a cell or a metric adds such files and
entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    end_to_end: tuple     # BENCHMARK.json metric entries this cell reports
    per_layer: tuple
    limits: dict          # limits/<workload>.json

    @property
    def tokens_per_step(self) -> int:
        return int(self.traffic["batch"]) * int(self.traffic["seq_len"])


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(has {sorted(entries)})")
    w = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    here = os.path.join(root, "chipbench")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_read_json(os.path.join(root, cfg_entry["file"])),
        traffic=_read_json(os.path.join(here, "traffic", w["traffic"] + ".json")),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, workload)),
        limits=_read_json(os.path.join(here, "limits", workload + ".json")),
    )


def load_module(kind: str, name: str, root: str = ROOT):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
