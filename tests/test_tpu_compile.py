"""The dispatched optimizer kernels compiled for a TPU v5e at llama-350m
widths, without a chip: the TPU compiler is installed, and it compiles for a
described ``v5e:2x2`` topology.  This catches tiling and VMEM refusals that
interpret mode cannot see.  Nothing here runs a kernel.

The topology is described inside a module fixture (only one process at a
time may load the TPU library), and the dispatcher is steered to the
compiled path inside each test with ``monkeypatch`` on ``dispatch.backend``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import dispatch, launch_count
from repro.sharding import use_mesh

RANK = 128
HBM_BYTES = 16 * 2**30  # one v5e chip
# llama-350m: attention 1024x1024, MLP in 1024x2736, MLP out 2736x1024
SHAPES = [(1024, 1024), (1024, 2736), (2736, 1024)]
LEAD = 24  # the model's layer stack: one family is one kernel launch
OPS = ["lowrank_update", "project", "back_project", "back_project_epilogue",
       "newton_schulz_projected", "newton_schulz_full"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), ("data",),
                axis_types=(AxisType.Auto,))


def _operands(op, m, n):
    """(fn, operand shapes) of one op on a (LEAD, m, n) family, projected on
    the side GUM picks (left iff m <= n)."""
    side = "left" if m <= n else "right"
    k = m if side == "left" else n
    p, g = (LEAD, k, RANK), (LEAD, m, n)
    s = (LEAD, RANK, n) if side == "left" else (LEAD, m, RANK)
    impl = "pallas"
    return {
        "lowrank_update": (lambda p, g, s: dispatch.lowrank_update(
            p, g, s, 0.95, 1.5, side=side, impl=impl), (p, g, s)),
        "project": (lambda p, g: dispatch.project(
            p, g, side=side, impl=impl), (p, g)),
        "back_project": (lambda p, s: dispatch.back_project(
            p, s, side=side, impl=impl), (p, s)),
        "back_project_epilogue": (lambda p, s, w: dispatch.back_project_epilogue(
            p, s, w=w, scale=-1e-3, decay=-1e-5, side=side, impl=impl),
            (p, s, g)),
        "newton_schulz_projected": (lambda s: dispatch.newton_schulz(
            s, impl=impl), (s,)),
        "newton_schulz_full": (lambda g: dispatch.newton_schulz(
            g, impl=impl), (g,)),
    }[op]


@pytest.mark.parametrize("m,n", SHAPES, ids=[f"{m}x{n}" for m, n in SHAPES])
@pytest.mark.parametrize("op", OPS)
def test_kernel_compiles_for_v5e(op, m, n, one_chip, monkeypatch):
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    fn, shapes = _operands(op, m, n)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    with launch_count.count_fallbacks() as fell_back:
        compiled = jax.jit(fn).lower(*args).compile()
    assert fell_back == []
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


@pytest.mark.parametrize("op", OPS)
def test_kernel_compiles_per_chip_on_v5e_mesh(op, four_chips, monkeypatch):
    """GSPMD refuses to partition a Pallas TPU kernel, so under a mesh the
    dispatcher runs it on each chip's share of the family: with the stack
    split over four chips, each holds a quarter of it and nothing moves."""
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    fn, shapes = _operands(op, 1024, 2736)
    family = NamedSharding(four_chips, P("data"))
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=family)
            for s in shapes]
    with use_mesh(four_chips):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-to-all" not in text
    total = sum(4 * int(np.prod(s)) for s in shapes)
    assert compiled.memory_analysis().argument_size_in_bytes * 4 == total
