"""The kernel dispatch subsystem: impl resolution, registry, padding-aware
ragged-shape parity (Pallas interpret vs jnp reference), optimizer-level
parity with kernel_impl="interpret", and the use_muon_scale wiring.

Everything runs the Pallas kernels through the interpreter (CPU), so the
kernel code itself is exercised on every backend."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply_updates
from repro.core.galore import galore_matrices
from repro.core.gum import gum_matrices
from repro.core.muon import muon_matrices
from repro.core.newton_schulz import muon_scale, newton_schulz
from repro.kernels import KERNEL_REGISTRY, dispatch, get_kernel, ref

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------- resolution


def test_resolve_impl():
    # CPU/GPU CI: auto -> jnp; the interpreter is asked for by name.
    on_tpu = dispatch.backend() == "tpu"
    assert dispatch.resolve_impl("auto") == ("pallas" if on_tpu else "jnp")
    assert dispatch.resolve_impl("xla") == "jnp"
    assert dispatch.resolve_impl("jnp") == "jnp"
    assert dispatch.resolve_impl("interpret") == "interpret"
    with pytest.raises(ValueError):
        dispatch.resolve_impl("cuda")


def test_resolve_pallas_raises_off_tpu(monkeypatch):
    """An explicit "pallas" never degrades to the interpreter: off-TPU it
    raises and names the backend it found."""
    monkeypatch.setattr(dispatch, "backend", lambda: "cpu")
    with pytest.raises(ValueError, match="'cpu'.*interpret"):
        dispatch.resolve_impl("pallas")
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    assert dispatch.resolve_impl("pallas") == "pallas"


def test_registry():
    assert set(KERNEL_REGISTRY) >= {"lowrank_update", "newton_schulz",
                                    "back_project"}
    entry = get_kernel("lowrank_update")
    assert entry.fn is dispatch.lowrank_update
    assert get_kernel("back_project").fn is dispatch.back_project
    with pytest.raises(KeyError):
        get_kernel("nope")


def test_shape_legality_fallback():
    # rank beyond the VMEM bound must fall back to jnp, not fail to compile,
    # and the fallback is recorded with its op and shape
    from repro.kernels import launch_count

    m, n, r = 8, 16, dispatch.MAX_LOWRANK_RANK + 1
    p = jnp.zeros((m, r))
    g = jnp.zeros((m, n))
    assert not dispatch.lowrank_update_supported(p, g, "left")
    with launch_count.count_fallbacks() as fell_back:
        out = dispatch.lowrank_update(p, g, jnp.zeros((r, n)), 0.9, 1.0,
                                      impl="interpret")
        dispatch.lowrank_update(p[:, :4], g, jnp.zeros((4, n)), 0.9, 1.0,
                                impl="interpret")
        dispatch.lowrank_update(p, g, jnp.zeros((r, n)), 0.9, 1.0, impl="jnp")
    assert out.shape == (r, n)
    assert fell_back == [("lowrank_update", (m, n))]
    big = jnp.zeros((dispatch.MAX_NS_DIM + 8, dispatch.MAX_NS_DIM + 8))
    assert not dispatch.newton_schulz_supported(big)


# ------------------------------------------------------------- ragged parity


@pytest.mark.parametrize("m,n,r", [
    (1000, 768, 96),   # the GaLore/GUM production operating point, ragged
    (100, 76, 12),     # nothing divides the default blocks
    (24, 128, 8),      # only n tile-aligned
])
def test_lowrank_update_ragged_left(m, n, r):
    ks = jax.random.split(KEY, 3)
    p = jax.random.normal(ks[0], (m, r))
    g = jax.random.normal(ks[1], (m, n))
    rst = jax.random.normal(ks[2], (r, n))
    out = dispatch.lowrank_update(p, g, rst, 0.95, 4.0 / 3, impl="interpret")
    want = ref.lowrank_update_ref(p, g, rst, 0.95, 4.0 / 3)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


def test_lowrank_update_ragged_right_batched():
    """Right-side projection (m > n) over a stacked (L, m, n) family."""
    L, m, n, r = 3, 76, 40, 12
    ks = jax.random.split(KEY, 3)
    p = jax.random.normal(ks[0], (L, n, r))
    g = jax.random.normal(ks[1], (L, m, n))
    rst = jax.random.normal(ks[2], (L, m, r))
    out = dispatch.lowrank_update(p, g, rst, 0.9, 2.0, side="right",
                                  impl="interpret")
    want = 0.9 * rst + 2.0 * jnp.einsum("lmn,lnr->lmr", g, p)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)


def test_lowrank_update_multi_lead():
    """(L, E, m, n) MoE-style families flatten through the batch grid."""
    lead, m, n, r = (2, 3), 20, 36, 4
    ks = jax.random.split(KEY, 3)
    p = jax.random.normal(ks[0], lead + (m, r))
    g = jax.random.normal(ks[1], lead + (m, n))
    rst = jax.random.normal(ks[2], lead + (r, n))
    out = dispatch.lowrank_update(p, g, rst, 0.5, 1.0, impl="interpret")
    want = 0.5 * rst + jnp.einsum("...mr,...mn->...rn", p, g)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)


def test_project_dispatch_matches_einsum():
    m, n, r = 100, 76, 12
    p = jax.random.normal(KEY, (m, r))
    g = jax.random.normal(jax.random.fold_in(KEY, 1), (m, n))
    out = dispatch.project(p, g, side="left", impl="interpret")
    np.testing.assert_allclose(out, p.T @ g, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("m,n,r", [
    (1000, 768, 96),   # the production operating point, ragged
    (100, 76, 12),
    (24, 128, 8),
])
def test_back_project_ragged_left(m, n, r):
    """The fused back-projection GEMM P @ S through the padding wrappers."""
    p = jax.random.normal(KEY, (m, r))
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (r, n))
    out = dispatch.back_project(p, s, side="left", impl="interpret")
    np.testing.assert_allclose(out, ref.back_project_ref(p, s),
                               atol=2e-4, rtol=2e-4)


def test_back_project_right_batched():
    """Right side S @ Pᵀ over a stacked family, plus shape-legality fallback."""
    L, m, n, r = 3, 76, 40, 12
    p = jax.random.normal(KEY, (L, n, r))
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (L, m, r))
    out = dispatch.back_project(p, s, side="right", impl="interpret")
    want = jnp.einsum("lmr,lnr->lmn", s, p)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)
    # rank beyond the VMEM bound falls back to jnp instead of failing
    r_big = dispatch.MAX_LOWRANK_RANK + 1
    pb = jnp.zeros((8, r_big))
    sb = jnp.zeros((r_big, 16))
    assert not dispatch.back_project_supported(pb, sb, "left")
    assert dispatch.back_project(pb, sb, impl="interpret").shape == (8, 16)


@pytest.mark.parametrize("side", ["left", "right"])
def test_pad_rank_to_parity_ragged_rank(side):
    """Opt-in lane-aligned rank padding (r=96 -> 128) is exact across all
    three dispatched ops."""
    m, n, r = 200, 160, 96
    ks = jax.random.split(KEY, 3)
    if side == "left":
        p = jax.random.normal(ks[0], (m, r))
        rst = jax.random.normal(ks[2], (r, n))
        s = rst
    else:
        p = jax.random.normal(ks[0], (n, r))
        rst = jax.random.normal(ks[2], (m, r))
        s = rst
    g = jax.random.normal(ks[1], (m, n))
    for pad in (0, 128):
        out = dispatch.lowrank_update(p, g, rst, 0.9, 1.5, side=side,
                                      impl="interpret", pad_rank_to=pad)
        want = dispatch.lowrank_update(p, g, rst, 0.9, 1.5, side=side, impl="jnp")
        np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)
        outp = dispatch.project(p, g, side=side, impl="interpret", pad_rank_to=pad)
        np.testing.assert_allclose(
            outp, dispatch.project(p, g, side=side, impl="jnp"),
            atol=2e-4, rtol=2e-4)
        outb = dispatch.back_project(p, s, side=side, impl="interpret",
                                     pad_rank_to=pad)
        np.testing.assert_allclose(
            outb, dispatch.back_project(p, s, side=side, impl="jnp"),
            atol=2e-4, rtol=2e-4)


def test_pad_rank_to_optimizer_parity():
    """An optimizer built with pad_rank_to=128 at a ragged rank matches the
    unpadded kernel path (and the jnp path) trajectory."""
    from repro.core.galore import galore_matrices

    params = {"w": jax.random.normal(KEY, (2, 24, 40)) * 0.1}
    mk = lambda **kw: galore_matrices(1e-2, rank=6, period=3, base="muon",
                                      seed=2, **kw)
    p_ref = _run_traj(mk(kernel_impl="jnp"), params)
    p_pad = _run_traj(mk(kernel_impl="interpret", pad_rank_to=128), params)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_pad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [
    (96, 1000),    # GUM's low-rank NS operand (r, n), ragged n
    (1000, 96),    # transposed path
    (33, 100),
    (3, 40, 28),   # stacked family, m > n
])
def test_newton_schulz_ragged_parity(shape):
    x = jax.random.normal(KEY, shape)
    out = dispatch.newton_schulz(x, impl="interpret")
    want = newton_schulz(x)  # jnp reference
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


def test_core_newton_schulz_impl_arg():
    """core.newton_schulz's documented impl= dispatch reaches the kernels."""
    x = jax.random.normal(KEY, (16, 40))
    np.testing.assert_allclose(
        newton_schulz(x, impl="interpret"), newton_schulz(x, impl="jnp"),
        atol=1e-4, rtol=1e-4,
    )
    # "auto" resolves to the backend default and must always work
    np.testing.assert_allclose(
        newton_schulz(x, impl="auto"), newton_schulz(x, impl="jnp"),
        atol=1e-4, rtol=1e-4,
    )


# ------------------------------------------------------------- optimizer parity


def _quad_loss(p):
    return 0.5 * sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(p))


def _run_traj(opt, params, steps=5):
    st = opt.init(params)
    p = params
    for _ in range(steps):
        g = jax.grad(_quad_loss)(p)
        u, st = opt.update(g, st, p)
        p = apply_updates(p, u)
    return p


PARAMS = {
    "left": jax.random.normal(KEY, (3, 24, 40)) * 0.1,            # m <= n
    "right": jax.random.normal(jax.random.fold_in(KEY, 1), (3, 40, 24)) * 0.1,
}


def test_gum_kernel_impl_pallas_matches_jnp():
    """Acceptance: gum_matrices(kernel_impl="interpret") (the Pallas kernels)
    matches the jnp path within fp32 tolerance, across a projector refresh."""
    mk = lambda impl: gum_matrices(1e-2, rank=6, gamma=1, period=3,
                                   projector="svd", seed=5, kernel_impl=impl)
    p_jnp = _run_traj(mk("jnp"), PARAMS)
    p_pal = _run_traj(mk("interpret"), PARAMS)
    for a, b in zip(jax.tree_util.tree_leaves(p_jnp),
                    jax.tree_util.tree_leaves(p_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("base", ["muon", "sgdm", "adam"])
def test_galore_kernel_impl_pallas_matches_jnp(base):
    mk = lambda impl: galore_matrices(1e-2, rank=6, period=3, projector="svd",
                                      base=base, seed=2, kernel_impl=impl)
    p_jnp = _run_traj(mk("jnp"), PARAMS)
    p_pal = _run_traj(mk("interpret"), PARAMS)
    for a, b in zip(jax.tree_util.tree_leaves(p_jnp),
                    jax.tree_util.tree_leaves(p_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_muon_kernel_impl_pallas_matches_jnp():
    mk = lambda impl: muon_matrices(1e-2, kernel_impl=impl)
    p_jnp = _run_traj(mk("jnp"), PARAMS, steps=3)
    p_pal = _run_traj(mk("interpret"), PARAMS, steps=3)
    for a, b in zip(jax.tree_util.tree_leaves(p_jnp),
                    jax.tree_util.tree_leaves(p_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------- muon_scale


def test_muon_scale_value():
    assert muon_scale((40, 28)) == pytest.approx(math.sqrt(40 / 28))
    assert muon_scale((28, 40)) == 1.0  # wide matrices are not scaled


def test_muon_use_muon_scale_flag():
    """Flag on (default) scales tall-matrix updates by sqrt(m/n); off is the
    raw orthogonalized update.  Both settings must descend."""
    g = jax.tree_util.tree_map(jnp.ones_like, PARAMS)
    on = muon_matrices(1.0, use_muon_scale=True)
    off = muon_matrices(1.0, use_muon_scale=False)
    u_on, _ = on.update(g, on.init(PARAMS), PARAMS)
    u_off, _ = off.update(g, off.init(PARAMS), PARAMS)
    # left family is wide (24x40): scale == 1, identical either way
    np.testing.assert_allclose(u_on["left"], u_off["left"], rtol=1e-6)
    # right family is tall (40x24): exactly sqrt(40/24) between the flags
    np.testing.assert_allclose(
        np.asarray(u_on["right"]),
        np.asarray(u_off["right"]) * math.sqrt(40 / 24), rtol=1e-5,
    )


def test_gum_use_muon_scale_flag():
    """GUM default (False) preserves the seed trajectory; True scales the
    whole family update by the per-family muon_scale factor."""
    mk = lambda flag: gum_matrices(1e-2, rank=4, gamma=0, period=3, seed=3,
                                   use_muon_scale=flag)
    params = {"w": PARAMS["right"]}
    g = jax.tree_util.tree_map(jnp.ones_like, params)
    off = mk(False)
    on = mk(True)
    u_off, _ = off.update(g, off.init(params), params)
    u_on, _ = on.update(g, on.init(params), params)
    np.testing.assert_allclose(
        np.asarray(u_on["w"]),
        np.asarray(u_off["w"]) * muon_scale((40, 24)), rtol=1e-5,
    )
    # both settings still descend on the quadratic
    for flag in (False, True):
        p = _run_traj(mk(flag), params, steps=10)
        assert float(_quad_loss(p)) < float(_quad_loss(params))
