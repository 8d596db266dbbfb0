"""The plain references against the program, and the control that the
check must refuse, at a size a CPU test can hold."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import check, inputs, reference, run

from . import tiny

CELLS = {"dense": tiny.dense_cell}


def program_readings(cell, seed):
    """The program's readings through the harness's own path."""
    got = run.measure(cell, seed, 0.0, False, jax.devices()[:1])
    return got["abstract"], got["program"]


@pytest.mark.parametrize("family", sorted(CELLS))
def test_reference_matches_program_in_float32(family):
    cell = CELLS[family](dtype="float32")
    abstract, prog = program_readings(cell, 11)
    read = check.readings(prog, reference.run(cell, abstract, 11))
    # same arithmetic in f32 on the CPU: round-off only
    assert read["loss"] < 1e-5 and read["grad"] < 1e-4 and read["change"] < 1e-3, read


@pytest.mark.parametrize("family", sorted(CELLS))
def test_program_within_the_cells_limits(family):
    cell = CELLS[family]()
    abstract, prog = program_readings(cell, 2 ** 35 + 1)
    ok, table = check.verdict(check.readings(prog, reference.run(cell, abstract, 2 ** 35 + 1)),
                              cell.limits)
    assert ok, table


@pytest.mark.parametrize("family", sorted(CELLS))
def test_bfloat16_control_is_refused(family):
    cell = CELLS[family]()
    abstract = jax.eval_shape(run.build_trainer(cell, [None]).model.init,
                              jax.random.PRNGKey(0))
    ref = reference.run(cell, abstract, 5)
    control = reference.run(cell, abstract, 5, dtype=jnp.bfloat16)
    ok, table = check.verdict(check.readings(control, ref), cell.limits)
    assert not ok, table


def test_seed_wider_than_32_bits_changes_the_inputs():
    a, b = (inputs.seed_key(s) for s in (5, 5 + 2 ** 32))
    assert not bool(jnp.all(a == b))
    with pytest.raises(ValueError):
        inputs.seed_key(-1)
