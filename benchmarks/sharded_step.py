"""ZeRO sharded-step benchmark (PR 9): what sharding the family-stacked
projected state actually buys.

Two counts on the fused gum step over the llama-60m smoke model:

  * per-device optimizer-state bytes vs mesh size (1/2/4/8) — the static
    accountant (:func:`repro.analysis.buffers.per_shard_memory` with
    ``shard_state=True``), AbstractMesh only, no devices.  The shardable
    family leaves must scale ~1/N; the replicated remainder (non-divisible
    families, scalars) is reported so the gap is visible.
  * refresh-boundary gather cost vs mesh size — count, per-shard payload
    and ring wire bytes of the cond-gated all_gathers, from the traced
    schedule (paid once per refresh period, zero in steady state).

Both are counts, not speeds.  The sharded-vs-replicated comparison on real
devices is ``python chip_smoke.py --chips 4`` (four TPU chips, one process).

Emits ``name,us_per_call,derived`` CSV rows and writes
``BENCH_sharded_step.json`` under --out (default results/).  ``--smoke``
keeps one abstract mesh-8 row and skips the JSON.

Usage: PYTHONPATH=src python benchmarks/sharded_step.py [--out DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.analysis.audit import audit_sharded
from repro.core import OptimizerConfig

MESHES = (1, 2, 4, 8)

def abstract_rows(smoke_mode: bool):
    """Per-mesh static rows: per-device state bytes + boundary schedule,
    from the AbstractMesh audit (identical under run.py and standalone)."""
    cfg = OptimizerConfig(name="gum", rank=16, period=10, gamma=1,
                          kernel_impl="jnp", fuse_families=True,
                          shard_state=True)
    rows = {}
    for n in ((8,) if smoke_mode else MESHES):
        t0 = time.time()
        rep = audit_sharded(cfg, mesh_axes=(("data", n),), lower=False)
        us = (time.time() - t0) * 1e6
        mem = rep.summary["per_shard_memory"]
        exp = rep.summary["expected_schedule"]
        wire = rep.summary["wire"]
        gather = exp["boundary_gather"]
        boundary_wire = wire["boundary_bytes"]
        rows[f"mesh{n}"] = {
            "n_shards": n,
            "clean": rep.ok,
            "opt_state_bytes": mem["opt_state_bytes"],
            "opt_state_bytes_per_shard": mem["opt_state_bytes_per_shard"],
            "proj_state_bytes": mem["proj_state_bytes"],
            "proj_state_bytes_per_shard": mem["proj_state_bytes_per_shard"],
            "peak_bytes_per_shard": mem["peak_bytes_per_shard"],
            "boundary_gather_count": gather["count"],
            "boundary_gather_payload_bytes": gather["payload_bytes"],
            "boundary_gather_wire_bytes": boundary_wire,
        }
        derived = ("clean" if rep.ok else "+".join(sorted(rep.codes())))
        derived += (f",opt_bytes_per_shard={mem['opt_state_bytes_per_shard']}"
                    f",boundary_gathers={gather['count']}"
                    f",boundary_wire_bytes={boundary_wire}")
        print(f"sharded_step_state_mesh{n},{us:.0f},{derived}", flush=True)
    return rows


def main() -> None:
    from _smoke import smoke

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results")
    args, _ = ap.parse_known_args()

    print("name,us_per_call,derived")
    state = abstract_rows(smoke())
    if smoke():
        print("# smoke mode: skipping BENCH_sharded_step.json write",
              flush=True)
        return

    # the claim the JSON records: shardable projected state scales ~1/N
    b1 = state["mesh1"]["opt_state_bytes_per_shard"]
    b8 = state["mesh8"]["opt_state_bytes_per_shard"]
    assert b8 < b1, (b1, b8)

    entry = {
        "model": "llama-60m (smoke)",
        "optimizer": "gum fused (rank=16, gamma=1)",
        "per_device_state": state,
    }
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "BENCH_sharded_step.json")
    with open(path, "w") as f:
        json.dump(entry, f, indent=2, default=str)
    print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
