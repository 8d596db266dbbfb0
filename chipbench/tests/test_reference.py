"""The plain references against the program, and the control that the
check must refuse, at a size a CPU test can hold."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import check, inputs, reference, run
from chipbench.optimizers import gum

from . import tiny

CELLS = {"dense": tiny.dense_cell, "dense-svd": tiny.dense_svd_cell}


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


@pytest.mark.parametrize("side, shape", [("left", (3, 24, 40)), ("right", (3, 40, 24))])
def test_svd_projector_spans_the_leading_singular_subspace(side, shape):
    """On stacked matrices with a clear gap after the rank, the reference's
    projector spans the subspace of ``jnp.linalg.svd``'s leading singular
    vectors on the matrix's short side."""
    rank = 4
    ku, kv, ks, kn = jax.random.split(jax.random.PRNGKey(7), 4)
    L, m, n = shape
    u = jnp.linalg.qr(jax.random.normal(ku, (L, m, m)))[0]
    v = jnp.linalg.qr(jax.random.normal(kv, (L, n, n)))[0]
    k = min(m, n)
    s = jnp.concatenate([4.0 + jax.random.uniform(ks, (L, rank)),
                         jax.random.uniform(kn, (L, k - rank))], axis=-1)
    g = (u[..., :k] * s[:, None, :]) @ jnp.swapaxes(v[..., :k], -1, -2)
    with jax.default_matmul_precision("highest"):
        p = gum.projector("svd", g, rank, None, side)
        short = g if side == "left" else jnp.swapaxes(g, -1, -2)
        want = jnp.linalg.svd(short, full_matrices=False)[0][..., :rank]
        outer = lambda q: q @ jnp.swapaxes(q, -1, -2)
        gap = jnp.linalg.norm(outer(p) - outer(want), ord=2, axis=(-2, -1))
    assert p.shape == (L, k, rank)
    assert float(jnp.max(gap)) <= 1e-4, gap
