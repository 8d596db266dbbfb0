"""Compile a cell's train step for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 chipbench/compile_check.py <workload> [...]

Builds the cell's Trainer as ``run.py`` does, over as many chips of a
described ``v5e:2x2`` as the cell has, lowers its jitted step on the
abstract weights, optimizer state and batch and compiles it with the TPU
compiler, then prints the compiled program's memory analysis (per chip) as
JSON; then the same for the plain reference's step.  Nothing runs, so it gives no
time.  On this backend the kernel dispatch takes its jnp branch, so the
optimizer's Pallas kernels are absent from the program compiled here.
"""
from __future__ import annotations

import json
import os
import sys

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), p)
                for p in ("src", "")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def memory(workload: str, topology: str = "v5e:2x2") -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import run, spec

    cell = spec.load_cell(workload)
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[:cell.chips]
    trainer = run.build_trainer(cell, devices)
    params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    if cell.chips > 1:  # the mesh's shardings place every argument
        compiled = trainer.lower_step(params).compile()
    else:
        chip = SingleDeviceSharding(devices[0])

        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

        params = on_chip(params)
        opt_state = on_chip(jax.eval_shape(trainer.optimizer.init, params))
        batch = {"tokens": jax.ShapeDtypeStruct(
            (int(cell.traffic["batch"]), int(cell.traffic["seq_len"])), jnp.int32,
            sharding=chip)}
        compiled = trainer._jit_step(params, opt_state).lower(
            params, opt_state, batch).compile()
    return _memory(workload, topology, "step", compiled)


def reference_memory(workload: str, topology: str = "v5e:2x2") -> dict:
    """The same for the plain reference's step, divided over the chips as
    ``run.py`` divides it after the window."""
    import jax
    from jax.experimental import topologies

    from chipbench import reference, run, spec

    cell = spec.load_cell(workload)
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[:cell.chips]
    abstract = jax.eval_shape(run.build_trainer(cell, devices).model.init,
                              jax.random.PRNGKey(0))
    compiled = reference.lower(cell, abstract, devices).compile()
    return _memory(workload, topology, "reference", compiled)


def _memory(workload, topology, program, compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"workload": workload, "topology": topology, "program": program,
           "argument_bytes": m.argument_size_in_bytes,
           "output_bytes": m.output_size_in_bytes,
           "temp_bytes": m.temp_size_in_bytes,
           "alias_bytes": m.alias_size_in_bytes}
    out["resident_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                             - out["alias_bytes"] + out["temp_bytes"])
    return out


if __name__ == "__main__":
    for name in sys.argv[1:]:
        print(json.dumps(memory(name)), flush=True)
        print(json.dumps(reference_memory(name)), flush=True)
