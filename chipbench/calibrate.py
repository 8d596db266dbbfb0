"""Readings that set a cell's limits: the control and the planted faults.

    python3 chipbench/calibrate.py --workload <name> --seeds 1 2 3

At the cell's own size, on each seed, the float32 reference is compared with
(a) the same reference one precision lower (bfloat16 parameters, optimizer
state and matmuls: the control), (b) the reference fed half of each batch
with the mean taken over that half (a planted fault) and, on a cell of more
than one chip, (c) the reference fed one chip's rows alone, as each chip
computes where the exchange between chips is left out.  A step that
returns its state unchanged reads 1 on ``change`` by definition and needs
no run.
Prints one JSON line per seed.  The program's own readings come from the
``checks`` of ``run.py``'s result lines.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), p)
                for p in ("src", "")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import check, reference, run, spec

    cell = spec.load_cell(args.workload)
    devices = run.devices_for(cell.chips, require_tpu=True)
    run.enable_cache()
    abstract = jax.eval_shape(run.build_trainer(cell, devices).model.init,
                              jax.random.PRNGKey(0))
    batch = int(cell.traffic["batch"])
    variants = {"control": {"dtype": jnp.bfloat16}, "half_batch": {"batch_rows": batch // 2}}
    if cell.chips > 1:
        variants["exchange_left_out"] = {"batch_rows": batch // cell.chips}
    for seed in args.seeds:
        ref = reference.run(cell, abstract, seed, devices=devices)
        out = {"seed": seed}
        for name, kw in variants.items():
            read = check.readings(reference.run(cell, abstract, seed, devices=devices, **kw),
                                  ref)
            out[name] = {k: read[k] for k in ("loss", "grad", "change",
                                               "grad_leaf", "change_leaf")}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
