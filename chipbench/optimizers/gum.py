"""Plain reference of GUM (the paper's Algorithm 2) with AdamW elsewhere.

Hidden matrices (stacked ``(L, m, n)`` leaves under ``blocks``, both sides at
least 8, not a conv tap) run GUM; embeddings, head, norms, biases and the
SSM's per-head vectors run AdamW.  For a GUM leaf, every ``period`` steps a
rank-``r`` projector of the shorter side is computed from the gradient and
``gamma`` of its ``L`` blocks are sampled (``q = gamma / L``):

  low-rank, every block:  R <- beta R + c_low Pᵀ G,   U = P NS(R)
  sampled blocks:         F <- beta F + c_full (G - c_comp P Pᵀ G),  U = NS(F)
  W <- W - lr U

with ``(c_low, c_full, c_comp) = (1/(1-q), 1/q, 1)`` ("paper") or
``(1, 1/q, 1-q)`` ("finetune", App. C.1); momenta restart at each refresh.
NS is the quintic Newton–Schulz iteration (3.4445, -4.7750, 2.0315), 5 steps,
on the Frobenius-normalised matrix, transposed so its Gram side is the short
one.  Gradients are clipped to a global norm of ``grad_clip`` first.

Random draws use the keys the algorithm's seed defines: per step
``fold_in(PRNGKey(seed), step)``, per leaf ``fold_in(., leaf index)`` split
into the projector's sketch key and the sampling key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NS_COEFFS = (3.4445, -4.7750, 2.0315)
_VECTOR_NAMES = ("conv_w",)


def is_lowrank(path: str, shape) -> bool:
    name = path.rsplit("/", 1)[-1]
    return (path.startswith("blocks/") and len(shape) >= 3
            and min(shape[-1], shape[-2]) >= 8 and name not in _VECTOR_NAMES
            and not any(k in name for k in ("norm", "scale", "bias")))


def _side(shape):
    return "left" if shape[-2] <= shape[-1] else "right"


def _project(p, g, side):
    return (jnp.einsum("lmr,lmn->lrn", p, g) if side == "left"
            else jnp.einsum("lmn,lnr->lmr", g, p))


def _back(p, s, side):
    return (jnp.einsum("lmr,lrn->lmn", p, s) if side == "left"
            else jnp.einsum("lmr,lnr->lmn", s, p))


def newton_schulz(x, steps=5, eps=1e-7):
    a, b, c = NS_COEFFS
    t = x.shape[-2] > x.shape[-1]
    if t:
        x = jnp.swapaxes(x, -1, -2)
    x = x / (jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=(-2, -1),
                              keepdims=True)).astype(x.dtype) + eps)
    for _ in range(steps):
        gram = x @ jnp.swapaxes(x, -1, -2)
        x = a * x + (b * gram + c * (gram @ gram)) @ x
    return jnp.swapaxes(x, -1, -2) if t else x


def projector(kind, g, rank, key, side, iters=2):
    """(L, s, rank) orthonormal columns spanning G's leading left (or right)
    singular subspace."""
    g = g.astype(jnp.float32)
    if side == "right":
        g = jnp.swapaxes(g, -1, -2)
    if kind == "svd":
        # G = U S Vᵀ gives G Gᵀ = U S² Uᵀ: the leading eigenvectors of the
        # short side's Gram matrix span G's leading left singular subspace
        # (the same subspace in another basis; the check compares norms
        # that no basis changes), and compile in less time than G's SVD
        vecs = jnp.linalg.eigh(g @ jnp.swapaxes(g, -1, -2))[1]
        return vecs[..., ::-1][..., :rank]
    if kind == "subspace":
        y = g @ jax.random.normal(key, g.shape[:-2] + (g.shape[-1], rank), jnp.float32)
        for _ in range(iters):
            y = jnp.linalg.qr(y)[0]
            y = g @ (jnp.swapaxes(g, -1, -2) @ y)
        return jnp.linalg.qr(y)[0]
    raise ValueError(f"no reference for projector {kind!r}")


def _coeffs(opt, L):
    g_f = min(opt["gamma"], L)
    q = g_f / L
    if opt["compensation"] == "finetune":
        return g_f, 1.0, 1.0 / q, 1.0 - q
    return g_f, 1.0 / (1.0 - q), 1.0 / q, 1.0


def init(params: dict, opt: dict, dtype) -> dict:
    state = {}
    for path, p in params.items():
        if is_lowrank(path, p.shape):
            L, m, n = p.shape
            r = min(opt["rank"], m, n)
            g_f = _coeffs(opt, L)[0]
            low = (L, r, n) if _side(p.shape) == "left" else (L, m, r)
            state[path] = {"P": jnp.zeros((L, min(m, n), r), jnp.float32),
                           "R": jnp.zeros(low, dtype),
                           "F": jnp.zeros((g_f, m, n), dtype),
                           "idx": jnp.arange(g_f, dtype=jnp.int32)}
        else:
            state[path] = {"m": jnp.zeros(p.shape, dtype),
                           "v": jnp.zeros(p.shape, dtype)}
    return state


def update(params: dict, grads: dict, state: dict, count, opt: dict,
           dtype, leaf_index: dict):
    """One step (``count`` is 1-based, traced); returns (params, state)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in grads.values()))
    clip = jnp.minimum(1.0, opt["grad_clip"] / (norm + 1e-12))
    refresh = (count - 1) % opt["period"] == 0
    base = jax.random.fold_in(jax.random.PRNGKey(opt["seed"]), count)
    lr, beta = opt["lr"], opt["beta"]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    c = count.astype(jnp.float32)
    new_p, new_s = {}, {}
    for path, p in params.items():
        g = (grads[path].astype(jnp.float32) * clip).astype(dtype)
        st = state[path]
        if "R" not in st:
            m = b1 * st["m"] + (1 - b1) * g
            v = b2 * st["v"] + (1 - b2) * g * g
            u = (m / (1 - b1 ** c)) / (jnp.sqrt(v / (1 - b2 ** c)) + eps)
            new_p[path] = (p - (lr * u).astype(p.dtype)).astype(p.dtype)
            new_s[path] = {"m": m.astype(dtype), "v": v.astype(dtype)}
            continue
        L = p.shape[0]
        side = _side(p.shape)
        r = st["P"].shape[-1]
        g_f, c_low, c_full, c_comp = _coeffs(opt, L)
        k_proj, k_samp = jax.random.split(jax.random.fold_in(base, leaf_index[path]))
        P = jax.lax.cond(
            refresh,
            lambda: projector(opt["projector"], g, r, k_proj, side,
                              opt.get("subspace_iters", 2)),
            lambda: st["P"])
        idx = jnp.where(refresh, jax.random.choice(k_samp, L, (g_f,), replace=False)
                        .astype(jnp.int32), st["idx"])
        Pd = P.astype(dtype)
        R = beta * jnp.where(refresh, 0, st["R"]) + c_low * _project(Pd, g, side)
        u = _back(Pd, newton_schulz(R), side)
        gs, ps = g[idx], Pd[idx]
        resid = gs - c_comp * _back(ps, _project(ps, gs, side), side)
        F = beta * jnp.where(refresh, 0, st["F"]) + c_full * resid
        u = u.at[idx].set(newton_schulz(F))
        new_p[path] = (p - (lr * u).astype(p.dtype)).astype(p.dtype)
        new_s[path] = {"P": P, "R": R.astype(dtype), "F": F.astype(dtype), "idx": idx}
    return new_p, new_s


def first_gradient(state: dict, opt: dict) -> dict:
    """Per state leaf, the norm of what the optimizer kept of the first
    (clipped) gradient: AdamW's first moment ``mu`` and GUM's projected
    momentum ``low`` and full-rank slots ``full``."""
    out = {}
    for path, st in state.items():
        if "R" in st:
            out["low:" + path] = jnp.linalg.norm(st["R"].astype(jnp.float32))
            out["full:" + path] = jnp.linalg.norm(st["F"].astype(jnp.float32))
        else:
            out["mu:" + path] = jnp.linalg.norm(st["m"].astype(jnp.float32))
    return out


def families(params: dict) -> list:
    """GUM leaves grouped by shape, in the order they first occur: how a
    program that stacks its state by shape family numbers its stacks."""
    groups = {}
    for path, p in params.items():
        if is_lowrank(path, p.shape):
            groups.setdefault(tuple(p.shape), []).append(path)
    return list(groups.values())
