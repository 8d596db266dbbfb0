"""Composable optimizer combinators — the paradigm as an API.

The paper's claim is that *layerwise sampling debiases any low-rank
projection mechanism*; GUM is merely the GaLore x Muon instantiation.  This
module makes that claim the API surface (optax-style, zero dependencies):

atomic gradient transforms
    scale_by_momentum   EMA momentum (SGDM direction; Property II holds)
    scale_by_muon       momentum + Newton-Schulz orthogonalization
    scale_by_adam       bias-corrected Adam direction (Property II does NOT
                        hold — documented per-composition)
    add_decayed_weights decoupled weight decay   u + wd * p
    scale_by_lr         -schedule(count) * u     (terminal step of a chain)
    scale_by_factor     constant multiplier (GaLore's alpha)
    clip_by_global_norm global-norm gradient clipping as a chain head

wrapper transforms
    lowrank(inner, ...)           owns ALL projector state: family stacking,
                                  periodic refresh, svd|subspace|random|grass
                                  choice, project / back-project through the
                                  Pallas dispatch layer (repro.kernels) —
                                  runs ``inner`` in the projected space.
                                  ``rank`` may be a per-family RankMap, and
                                  ``rank_policy`` / ``probe_spectrum`` hook
                                  in the adaptive-rank engine
                                  (repro.core.rank_policy)
    layerwise_unbias(base, ...)   the paper's sampling debiasing (gamma
                                  full-rank slots, paper/finetune
                                  compensation) as an independent combinator
    with_fira_residual(base, ...) Fira's norm-scaled out-of-subspace residual
    with_matrix_routing(m, f)     label routing: matrices -> ``m``, the rest
                                  (embeddings/norms/biases) -> ``f``

composition
    chain(*transforms)            sequential application, optax semantics

so the paper's optimizers are one-liners::

    gum = chain(lowrank(layerwise_unbias(scale_by_muon(beta=0.95))),
                add_decayed_weights(wd), scale_by_lr(lr))
    galore_adam = chain(lowrank(scale_by_adam(scale=0.25)),
                        add_decayed_weights(wd), scale_by_lr(lr))
    unbiased_galore_adam = chain(
        lowrank(layerwise_unbias(scale_by_adam(scale=0.25))),
        add_decayed_weights(wd), scale_by_lr(lr))   # a NEW method: no new file

Protocol between ``lowrank`` and the transforms it wraps
--------------------------------------------------------
``lowrank`` hands its inner transform a pytree whose low-rank leaves are
:class:`ProjGrad` objects — *lazy* projected gradients carrying the refreshed
projector, the raw fp32 gradient, the family geometry and the kernel-dispatch
knobs.  (``ProjGrad`` is deliberately NOT a registered pytree node, so
``tree_map`` treats it as an opaque leaf.)  Momentum-style transforms call
``ProjGrad.fused_momentum`` — the single fused Pallas kernel
``R' = beta R + coeff PᵀG`` — while elementwise consumers (Adam) call
``ProjGrad.materialize`` for the projected gradient itself.  A wrapped
transform may return either a projected-space array (``lowrank``
back-projects it through the fused ``back_project`` kernel) or a
:class:`FullUpdate`-wrapped full-shape array (returned as-is — how
``layerwise_unbias`` emits its scatter of sampled full-rank blocks).

At init time the same positions hold :class:`ProjInit` leaves carrying the
projected-space state template plus the :class:`~repro.core.lowrank_common.
FamilyShape`, so wrappers like ``layerwise_unbias`` can size their full-rank
slots without ever seeing real parameters.

Family-stacked fused execution (``fuse_families=True``)
-------------------------------------------------------
By default ``lowrank`` iterates the parameter leaves in Python, issuing one
project / momentum / back-project dispatch per leaf.  With
``fuse_families=True`` it instead computes a static :class:`~repro.core.
family_plan.FamilyPlan` grouping same-signature leaves into stacked
``(L, m, n)`` super-leaves and runs the WHOLE pipeline — projector refresh,
fused project+momentum, inner scale, back-projection — as one batched launch
per shape family.  The inner transform sees one :class:`ProjGrad` per family
whose ``seg`` field carries the member geometry; per-leaf PRNG keys are
stacked (never merged) and ``layerwise_unbias`` samples per *member*, so the
stacked trajectory is bit-identical to the per-leaf one on the jnp path
(tests/test_fused_step.py; at large threaded-GEMM shapes batched-vs-unbatched
reduction order can still round a value differently — observed ≤1 fp32 ulp
over 6 trainer steps on llama-60m, with sampling and projectors exactly
equal).  Optimizer-state layout changes (family lists instead of per-leaf
trees), so the knob is opt-in.

``fused_epilogue=True`` additionally defers the final back-projection into a
:class:`PendingBack` leaf so chain-tail elementwise epilogues (``scale_by_lr``,
``add_decayed_weights``, ``scale_by_factor``) fold into the back-projection
GEMM — one ``back_project_epilogue`` launch per family instead of a GEMM plus
per-leaf elementwise passes.  Not bit-exact (the epilogue redistributes the
multiplications), hence a separate knob.  Scope: it applies to inner
transforms whose output ``lowrank`` back-projects (galore / galore_muon /
golore); inners that emit full-shape :class:`FullUpdate` leaves
(``layerwise_unbias`` — gum/unbiased_galore_adam — and
``with_fira_residual``) already own their back-projection and pass through
unchanged, so the knob is inert there (they still get the stacking win).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as _P

from .api import (
    PyTree,
    Schedule,
    Transform,
    multi_transform,
    schedule_value,
    tree_paths,
)
from .api import clip_by_global_norm as _clip_tree
from .family_plan import (
    build_family_plan,
    member_keys,
    stack_family,
    unstack_family,
)
from .lowrank_common import (
    FamilyShape,
    compute_projectors,
    default_lowrank_filter,
    family_shape,
    gather_blocks,
    lowrank_state_shape,
    proj_shape,
    project as _raw_project,
    scatter_blocks,
    stack_shardable,
)
from .newton_schulz import muon_scale, newton_schulz

_IS_NONE = lambda x: x is None


def _scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)`` (a new
    scope object per call: one object must not be entered twice at once)."""

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


def _dispatch():
    # Lazy: repro.kernels wants repro.core importable first (same convention
    # as lowrank_common).
    from repro.kernels import dispatch

    return dispatch


# ---------------------------------------------------------------------------
# Leaf protocol objects (opaque leaves — intentionally not pytree nodes)
# ---------------------------------------------------------------------------


class ProjInit:
    """Init-time stand-in for a low-rank leaf inside :func:`lowrank`.

    ``low`` is a ShapeDtypeStruct of the projected-space state — transforms
    allocate momenta with ``jnp.zeros_like(leaf.low)`` via
    :func:`_zeros_momentum`; ``fs`` carries the full family geometry.  Under
    family stacking ``seg`` carries the member geometry (None per-leaf)."""

    __slots__ = ("fs", "low", "seg")

    def __init__(self, fs: FamilyShape, low, seg=None):
        self.fs = fs
        self.low = low
        self.seg = seg


class ProjGrad:
    """Lazy projected gradient leaf handed to transforms inside ``lowrank``."""

    __slots__ = ("p", "g", "fs", "kernel_impl", "pad_rank_to", "coeff",
                 "reset", "refresh", "key", "seg")

    def __init__(self, p, g, fs, kernel_impl, pad_rank_to=0, coeff=1.0,
                 reset=None, refresh=False, key=None, seg=None):
        self.p = p                      # (*lead, s, r) refreshed projector
        self.g = g                      # (*lead, m, n) raw fp32 gradient
        self.fs = fs                    # FamilyShape (static)
        self.kernel_impl = kernel_impl
        self.pad_rank_to = pad_rank_to
        self.coeff = coeff              # static float on the projected grad
        self.reset = reset              # traced bool: zero momenta first (or None)
        self.refresh = refresh          # traced bool period boundary (False = external)
        self.key = key                  # sampling key; (members, 2) when stacked
        self.seg = seg                  # StackSeg under family stacking (or None)

    def with_coeff(self, coeff: float) -> "ProjGrad":
        return ProjGrad(self.p, self.g, self.fs, self.kernel_impl,
                        self.pad_rank_to, coeff, self.reset, self.refresh,
                        self.key, self.seg)

    def apply_reset(self, x):
        """Zero a momentum buffer at the period boundary (no-op if the
        wrapping ``lowrank`` was built with ``reset_on_refresh=False``)."""
        if self.reset is None:
            return x
        return jnp.where(self.reset, jnp.zeros_like(x), x)

    def materialize(self):
        """The projected gradient PᵀG / G P through the dispatch layer
        (coeff NOT applied — elementwise consumers fold it in themselves)."""
        with jax.named_scope("lowrank.project"):
            return _dispatch().project(
                self.p, self.g, side=self.fs.side, impl=self.kernel_impl,
                pad_rank_to=self.pad_rank_to,
            )

    def fused_momentum(self, mu, beta: float):
        """``beta * mu + coeff * PᵀG`` via the single fused Pallas kernel —
        the per-step hot loop of every momentum-based low-rank optimizer."""
        with jax.named_scope("lowrank.project"):
            return _dispatch().lowrank_update(
                self.p, self.g, self.apply_reset(mu), beta, self.coeff,
                side=self.fs.side, impl=self.kernel_impl,
                pad_rank_to=self.pad_rank_to,
            )

    def back(self, s):
        """Back-project a projected-space array to full shape."""
        with jax.named_scope("lowrank.back_project"):
            return _dispatch().back_project(
                self.p, s, side=self.fs.side, impl=self.kernel_impl,
                pad_rank_to=self.pad_rank_to,
            )


class FullUpdate:
    """Marker a lowrank-inner transform returns for a leaf that is ALREADY in
    full (m, n) space and must not be back-projected again."""

    __slots__ = ("u",)

    def __init__(self, u):
        self.u = u


class RefreshMsg:
    """Per-leaf message for the external-refresh hook (see ``lowrank``).
    Under family stacking, one message per family: ``key`` is the stacked
    ``(members, 2)`` per-member sampling keys and ``seg`` the geometry."""

    __slots__ = ("fs", "key", "seg")

    def __init__(self, fs: FamilyShape, key, seg=None):
        self.fs = fs
        self.key = key
        self.seg = seg


class PendingBack:
    """Lazy scale-and-back-project epilogue leaf (``fused_epilogue=True``).

    Represents ``scale * back_project(p, s) + decay * W`` without
    materializing the full-shape update.  Protocol-aware tail transforms fold
    their elementwise epilogues into the two scalars (``scale_by_lr`` and
    ``scale_by_factor`` via :meth:`scaled`, ``add_decayed_weights`` via
    :meth:`decayed`); ``scale_by_lr`` — the terminal stage of every chain —
    then materializes the whole tree through
    :func:`repro.kernels.dispatch.back_project_epilogue`, ONE fused launch per
    family stack (the GEMM result never round-trips HBM before the epilogue).

    Under family stacking all member leaves share one ``(p, s, w)`` payload;
    ``member`` selects this leaf's slice after the grouped materialization.
    Grouped materialization reads the fold scalars from the first member, so
    chain tails must apply leaf-uniform scalars — which every built-in tail
    transform does.  A chain that ends without ``scale_by_lr`` still works
    when ``update`` and ``apply_updates`` are traced together (the usual
    train-step shape): :func:`repro.core.api.apply_updates` materializes
    stray PendingBack leaves one by one (correct, just unfused).  A
    PendingBack leaf is NOT a JAX type, so it cannot cross a jit boundary on
    its own — jitting ``opt.update`` alone with such a chain raises
    TypeError at the output; end the chain with ``scale_by_lr`` (or call
    :func:`materialize_pending`) before returning updates across a
    boundary."""

    __slots__ = ("p", "s", "w", "fs", "kernel_impl", "pad_rank_to",
                 "scale", "decay", "member", "members", "member_lead")

    def __init__(self, p, s, w, fs, kernel_impl, pad_rank_to, scale=1.0,
                 decay=0.0, member=None, members=1, member_lead=()):
        self.p = p                      # projector, possibly family-stacked
        self.s = s                      # projected-space update (payload key)
        self.w = w                      # params (for the decay term), stacked
        self.fs = fs
        self.kernel_impl = kernel_impl
        self.pad_rank_to = pad_rank_to
        self.scale = scale              # float | traced scalar
        self.decay = decay              # float | traced scalar
        self.member = member            # None = unstacked leaf
        self.members = members
        self.member_lead = member_lead

    def _replace(self, scale, decay) -> "PendingBack":
        return PendingBack(self.p, self.s, self.w, self.fs, self.kernel_impl,
                           self.pad_rank_to, scale, decay, self.member,
                           self.members, self.member_lead)

    def scaled(self, f) -> "PendingBack":
        # keep a never-decayed leaf's 0.0 static so materialization can skip
        # the W operand entirely
        zero = isinstance(self.decay, float) and self.decay == 0.0
        return self._replace(f * self.scale, 0.0 if zero else f * self.decay)

    def decayed(self, wd: float) -> "PendingBack":
        return self._replace(self.scale, self.decay + wd)

    def _use_w(self) -> bool:
        return not (isinstance(self.decay, float) and self.decay == 0.0)

    def _w_stack(self):
        """Resolve the (possibly thunked) stacked-params operand."""
        return self.w() if callable(self.w) else self.w

    def _resolved_impl(self) -> str:
        return _dispatch().resolve_impl(self.kernel_impl)

    def _materialize_stack(self):
        """The full (possibly stacked) ``(*lead, m, n)`` update through the
        fused ``back_project_epilogue`` kernel (Pallas/interpret path)."""
        use_w = self._use_w()
        return _dispatch().back_project_epilogue(
            self.p, self.s, w=(self._w_stack() if use_w else None),
            scale=self.scale, decay=self.decay, side=self.fs.side,
            impl=self.kernel_impl, pad_rank_to=self.pad_rank_to,
        )

    def _jnp_epilogue_slice(self, full, w):
        """Slice-then-scale epilogue for the jnp path: ``full`` is the
        UNSCALED back-projection of the whole stack; the scale/decay apply
        AFTER the member slice (see :func:`materialize_pending` for why that
        ordering wins on CPU)."""
        u = self.scale * _member_slice(full, self)
        if self._use_w():
            u = u + self.decay * _member_slice(w, self).astype(jnp.float32)
        return u

    def _jnp_full(self):
        """Unit-scale epilogue call (XLA folds the 1.0): the unscaled
        back-projection of the whole stack, recorded as the epilogue op."""
        return _dispatch().back_project_epilogue(
            self.p, self.s, side=self.fs.side, impl="jnp",
            pad_rank_to=self.pad_rank_to,
        )

    def materialize_update(self):
        """Materialize THIS leaf only (the ungrouped fallback used by
        ``apply_updates``; grouped chains go through
        :func:`materialize_pending` instead)."""
        with jax.named_scope("lowrank.back_project"):
            if self._resolved_impl() == "jnp":
                return self._jnp_epilogue_slice(
                    self._jnp_full(), self._w_stack() if self._use_w() else None
                )
            return _member_slice(self._materialize_stack(), self)


def _member_slice(stacked, leaf: PendingBack):
    """This leaf's ``(*member_lead, m, n)`` slice of a family-stacked array
    (identity for unstacked leaves)."""
    if leaf.member is None:
        return stacked
    parts = stacked.reshape((leaf.members,) + leaf.member_lead
                            + stacked.shape[-2:])
    return parts[leaf.member]


_is_pending = lambda x: x is None or isinstance(x, PendingBack)


def materialize_pending(updates: PyTree) -> PyTree:
    """Materialize every :class:`PendingBack` leaf, grouping the members of
    each family stack into a single ``back_project_epilogue`` launch.  No-op
    on trees without pending leaves.

    On the Pallas path the scale/decay epilogue rides inside the kernel (the
    GEMM tile never leaves VMEM).  On the jnp reference path the epilogue is
    deliberately applied AFTER the per-member slicing instead: pre-scaling
    the stack materializes an extra full-size intermediate that XLA CPU
    cannot fuse away, whereas a scalar multiply on each slice fuses into the
    slice's consumer — measured ~30% faster on the write-back."""
    leaves, treedef = jax.tree_util.tree_flatten(updates, is_leaf=_is_pending)
    if not any(isinstance(x, PendingBack) for x in leaves):
        return updates
    with jax.named_scope("lowrank.back_project"):
        groups: dict[int, list[int]] = {}
        for pos, leaf in enumerate(leaves):
            if isinstance(leaf, PendingBack):
                groups.setdefault(id(leaf.s), []).append(pos)
        out = list(leaves)
        for positions in groups.values():
            head = leaves[positions[0]]
            if head._resolved_impl() == "jnp":
                full = head._jnp_full()
                w = head._w_stack() if any(
                    leaves[p]._use_w() for p in positions
                ) else None
                for pos in positions:
                    out[pos] = leaves[pos]._jnp_epilogue_slice(full, w)
                continue
            full = head._materialize_stack()
            for pos in positions:
                out[pos] = _member_slice(full, leaves[pos])
    return jax.tree_util.tree_unflatten(treedef, out)


def _zeros_momentum(leaf):
    if leaf is None:
        return None
    if isinstance(leaf, ProjInit):
        leaf = leaf.low
    return jnp.zeros(leaf.shape, jnp.float32)


def _reset_floats(tree: PyTree, refresh) -> PyTree:
    """Zero every inexact array leaf when ``refresh`` is true (ints — counts,
    indices — pass through untouched)."""

    def one(x):
        if x is None or not hasattr(x, "dtype"):
            return x
        if not jnp.issubdtype(x.dtype, jnp.inexact):
            return x
        return jnp.where(refresh, jnp.zeros_like(x), x)

    return jax.tree_util.tree_map(one, tree, is_leaf=_IS_NONE)


def _transpose(flat: PyTree, n: int) -> tuple:
    is_tup = lambda x: isinstance(x, tuple) and len(x) == n
    return tuple(
        jax.tree_util.tree_map(lambda t, i=i: t[i], flat, is_leaf=is_tup)
        for i in range(n)
    )


# ---------------------------------------------------------------------------
# chain
# ---------------------------------------------------------------------------


def chain_info(t: Transform) -> dict:
    """Static composition metadata for a combinator-built transform.

    Every combinator in this module attaches a ``chain_info`` dict to its
    update function — ``{"kind": <combinator name>, ...}``, nesting through
    ``stages`` (chain), ``inner`` (lowrank / layerwise_unbias /
    with_fira_residual) and ``branches`` (multi_transform) — so the static
    analyzer (:mod:`repro.analysis`) can walk the composition without
    executing anything.  Transforms built outside this module read as
    ``{"kind": "opaque"}`` and are treated as unmodelable."""
    info = getattr(t.update, "chain_info", None) if t is not None else None
    return dict(info) if info else {"kind": "opaque"}


def chain(*transforms: Transform) -> Transform:
    """Sequentially compose gradient transforms (optax semantics): each
    transform maps (updates, state, params) -> (updates, state); state is the
    tuple of inner states.

    A chain whose FIRST transform speaks the lowrank leaf protocol (e.g.
    ``chain(layerwise_unbias(...), scale_by_factor(...))``) forwards that
    transform's ``wants_sample_key`` / ``refresh_state`` hooks, so such a
    chain can itself be the inner transform of :func:`lowrank`."""

    def init(params: PyTree) -> tuple:
        return tuple(t.init(params) for t in transforms)

    def update(updates: PyTree, state: tuple, params: PyTree):
        new_states = []
        for t, s in zip(transforms, state):
            updates, ns = t.update(updates, s, params)
            new_states.append(ns)
        return updates, tuple(new_states)

    if transforms and getattr(transforms[0].update, "wants_sample_key", False):
        update.wants_sample_key = True
    if transforms and getattr(transforms[0].update, "wants_params", False):
        update.wants_params = True
    head_refresh = transforms and getattr(transforms[0].update, "refresh_state", None)
    if head_refresh:
        def refresh_state(state, msgs, refresh_now):
            return (head_refresh(state[0], msgs, refresh_now),) + tuple(state[1:])

        update.refresh_state = refresh_state

    update.chain_info = {
        "kind": "chain", "stages": [chain_info(t) for t in transforms],
    }
    return Transform(init, update)


# ---------------------------------------------------------------------------
# atomic transforms
# ---------------------------------------------------------------------------


def scale_by_momentum(beta: float = 0.9, use_muon_scale: bool = False) -> Transform:
    """EMA momentum direction ``mu' = beta mu + g`` (Property-II compliant).
    On :class:`ProjGrad` leaves the update runs through the fused low-rank
    kernel.  ``use_muon_scale`` applies Muon's sqrt(max(1, m/n)) factor —
    only meaningful as the GUM ``base="sgdm"`` variant's scaling."""

    def init(params: PyTree) -> PyTree:
        return jax.tree_util.tree_map(_zeros_momentum, params, is_leaf=_IS_NONE)

    def update(updates: PyTree, mu: PyTree, params: PyTree):
        def upd(g, m, p):
            if g is None:
                return (None, None)
            if isinstance(g, ProjGrad):
                m2 = g.fused_momentum(m, beta)
                o = m2
                if use_muon_scale:
                    o = muon_scale((g.fs.m, g.fs.n)) * o
                return (o, m2)
            m2 = beta * m + g.astype(jnp.float32)
            o = m2
            if use_muon_scale:
                shape = p.shape if p is not None else g.shape
                o = muon_scale(shape) * o
            return (o, m2)

        flat = jax.tree_util.tree_map(upd, updates, mu, params, is_leaf=_IS_NONE)
        out, new_mu = _transpose(flat, 2)
        return out, new_mu

    update.chain_info = {"kind": "scale_by_momentum", "beta": beta}
    return Transform(init, update)


def scale_by_muon(
    beta: float = 0.95,
    ns_steps: int = 5,
    nesterov: bool = False,
    use_muon_scale: bool = False,
    kernel_impl: str = "auto",
) -> Transform:
    """Momentum + Newton-Schulz orthogonalization (the Muon direction).

    Full-rank leaves get plain EMA momentum (+ optional Nesterov); ProjGrad
    leaves run the fused low-rank momentum kernel, then NS in the projected
    space (Property II: NS(P X) = P NS(X) makes this exact)."""

    def init(params: PyTree) -> PyTree:
        return jax.tree_util.tree_map(_zeros_momentum, params, is_leaf=_IS_NONE)

    def update(updates: PyTree, mu: PyTree, params: PyTree):
        def upd(g, m, p):
            if g is None:
                return (None, None)
            if isinstance(g, ProjGrad):
                if nesterov:
                    r_g = g.materialize()
                    if g.coeff != 1.0:
                        r_g = g.coeff * r_g
                    m2 = beta * g.apply_reset(m) + r_g
                    mom = beta * m2 + r_g
                else:
                    m2 = g.fused_momentum(m, beta)
                    mom = m2
                o = newton_schulz(mom, steps=ns_steps, impl=kernel_impl)
                if use_muon_scale:
                    o = muon_scale((g.fs.m, g.fs.n)) * o
                return (o, m2)
            g32 = g.astype(jnp.float32)
            m2 = beta * m + g32
            mom = beta * m2 + g32 if nesterov else m2
            o = newton_schulz(mom, steps=ns_steps, impl=kernel_impl)
            if use_muon_scale:
                shape = p.shape if p is not None else g.shape
                o = muon_scale(shape) * o
            return (o, m2)

        flat = jax.tree_util.tree_map(upd, updates, mu, params, is_leaf=_IS_NONE)
        out, new_mu = _transpose(flat, 2)
        return out, new_mu

    update.chain_info = {"kind": "scale_by_muon", "beta": beta,
                         "ns_steps": ns_steps, "nesterov": nesterov}
    return Transform(init, update)


class ScaleByAdamState(NamedTuple):
    count: jax.Array
    mu: PyTree
    nu: PyTree


def scale_by_adam(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    scale: float = 1.0,
) -> Transform:
    """Bias-corrected Adam direction, optionally pre-scaled (GaLore's alpha).

    Property II does NOT hold for Adam: inside ``lowrank`` this reproduces
    GaLore's (biased) semantics, and inside ``layerwise_unbias`` the
    *gradient estimate* is debiased even though the update is not exactly
    full Adam in expectation (the AdaRankGrad-style extension)."""

    def init(params: PyTree) -> ScaleByAdamState:
        zeros = lambda: jax.tree_util.tree_map(
            _zeros_momentum, params, is_leaf=_IS_NONE
        )
        return ScaleByAdamState(
            count=jnp.zeros((), jnp.int32), mu=zeros(), nu=zeros()
        )

    def update(updates: PyTree, state: ScaleByAdamState, params: PyTree):
        count = state.count + 1
        c = count.astype(jnp.float32)
        bc1 = 1.0 - b1 ** c
        bc2 = 1.0 - b2 ** c

        def upd(g, m, v, p):
            if g is None:
                return (None, None, None)
            if isinstance(g, ProjGrad):
                g32 = g.materialize()
                if g.coeff != 1.0:
                    g32 = g.coeff * g32
                m = g.apply_reset(m)
                v = g.apply_reset(v)
            else:
                g32 = g.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * g32
            v2 = b2 * v + (1 - b2) * jnp.square(g32)
            s = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            if scale != 1.0:
                s = scale * s
            return (s, m2, v2)

        flat = jax.tree_util.tree_map(
            upd, updates, state.mu, state.nu, params, is_leaf=_IS_NONE
        )
        out, mu, nu = _transpose(flat, 3)
        return out, ScaleByAdamState(count=count, mu=mu, nu=nu)

    update.chain_info = {"kind": "scale_by_adam", "scale": scale}
    return Transform(init, update)


def add_decayed_weights(weight_decay: float = 0.0) -> Transform:
    """Decoupled weight decay ``u + wd * p`` (apply before scale_by_lr)."""

    def init(params: PyTree):
        return ()

    def update(updates: PyTree, state, params: PyTree):
        if weight_decay == 0.0:
            return updates, ()

        def one(u, p):
            if u is None:
                return None
            if isinstance(u, PendingBack):
                return u.decayed(weight_decay)
            return u + weight_decay * p.astype(jnp.float32)

        out = jax.tree_util.tree_map(one, updates, params, is_leaf=_IS_NONE)
        return out, ()

    update.chain_info = {"kind": "add_decayed_weights",
                         "weight_decay": weight_decay}
    return Transform(init, update)


class ScaleByLrState(NamedTuple):
    count: jax.Array


def scale_by_lr(lr: Schedule) -> Transform:
    """Terminal step: ``-schedule(count) * u`` (updates are *added* to
    params, so the minus sign lives here)."""

    def init(params: PyTree) -> ScaleByLrState:
        return ScaleByLrState(count=jnp.zeros((), jnp.int32))

    def update(updates: PyTree, state: ScaleByLrState, params: PyTree):
        count = state.count + 1
        step = schedule_value(lr, count)

        def one(u):
            if u is None:
                return None
            if isinstance(u, PendingBack):
                return u.scaled(-step)
            return (-step) * u

        out = jax.tree_util.tree_map(one, updates, is_leaf=_IS_NONE)
        # Terminal stage of every chain: materialize deferred epilogues here,
        # one fused launch per family stack.
        out = materialize_pending(out)
        return out, ScaleByLrState(count=count)

    update.chain_info = {"kind": "scale_by_lr"}
    return Transform(init, update)


def scale_by_factor(factor: float) -> Transform:
    """Constant multiplier (GaLore/Fira's alpha applied outside the base).
    Protocol-aware, so it also composes INSIDE lowrank(): ProjGrad leaves
    scale lazily through their coeff, FullUpdate leaves through the payload."""

    def init(params: PyTree):
        return ()

    def update(updates: PyTree, state, params: PyTree):
        def one(u):
            if u is None:
                return None
            if isinstance(u, ProjGrad):
                return u.with_coeff(factor * u.coeff)
            if isinstance(u, FullUpdate):
                return FullUpdate(factor * u.u)
            if isinstance(u, PendingBack):
                return u.scaled(factor)
            return factor * u

        out = jax.tree_util.tree_map(one, updates, is_leaf=_IS_NONE)
        return out, ()

    update.chain_info = {"kind": "scale_by_factor", "factor": factor}
    return Transform(init, update)


def clip_by_global_norm(max_norm: float) -> Transform:
    """Global-norm gradient clipping as a chain head (the transform twin of
    :func:`repro.core.api.clip_by_global_norm`)."""

    def init(params: PyTree):
        return ()

    def update(updates: PyTree, state, params: PyTree):
        return _clip_tree(materialize_pending(updates), max_norm), ()

    update.chain_info = {"kind": "clip_by_global_norm"}
    return Transform(init, update)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def with_matrix_routing(
    matrix: Transform,
    fallback: Transform,
    *,
    matrix_filter: Callable[[str, jax.Array], bool] = default_lowrank_filter,
    matrix_label: str = "matrix",
    fallback_label: str = "adamw",
) -> Transform:
    """Route hidden-matrix leaves to ``matrix`` and everything else
    (embeddings / head / norms / biases / routers) to ``fallback`` — the
    label plumbing every paper optimizer previously re-implemented."""

    def label_fn(params: PyTree) -> PyTree:
        paths = tree_paths(params)
        return jax.tree_util.tree_map(
            lambda path, p: matrix_label if matrix_filter(path, p) else fallback_label,
            paths, params,
        )

    return multi_transform({matrix_label: matrix, fallback_label: fallback}, label_fn)


# ---------------------------------------------------------------------------
# ZeRO-style family-state sharding context
# ---------------------------------------------------------------------------

_FAMILY_SHARDING = threading.local()


@contextlib.contextmanager
def family_sharding(mesh, axis: str):
    """Declare that family-stacked low-rank state (projectors + projected
    moments) is partitioned on mesh ``axis`` along the stack dimension.

    Entered by the step builders (``launch.shardmap_fsdp`` /
    ``train.Trainer``) around ``optimizer.update`` at *trace* time; the fused
    path reads it via :func:`active_family_sharding` and routes each
    shardable family's projector refresh through a shard-local
    ``all_gather → SVD → slice`` (the ColossalAI ``distributed_galore``
    schedule) so the new projectors are born sharded.  Steady-state family
    math is leading-axis elementwise/batched and needs no collectives — GSPMD
    partitions it from the in/out shardings alone.  ``mesh`` may be a
    concrete :class:`jax.sharding.Mesh` or an ``AbstractMesh`` (the
    collective auditor traces device-free)."""
    prev = getattr(_FAMILY_SHARDING, "ctx", None)
    _FAMILY_SHARDING.ctx = (mesh, axis)
    try:
        yield
    finally:
        _FAMILY_SHARDING.ctx = prev


def active_family_sharding():
    """The active ``(mesh, axis)`` family-sharding declaration, or None."""
    return getattr(_FAMILY_SHARDING, "ctx", None)


def family_shard_count(shard_ctx) -> int:
    """Shard count of a ``(mesh, axis)`` context (1 when ctx is None)."""
    if shard_ctx is None:
        return 1
    mesh, axis = shard_ctx
    return int(mesh.shape[axis])


# ---------------------------------------------------------------------------
# lowrank — the projection wrapper
# ---------------------------------------------------------------------------


class LowRankState(NamedTuple):
    count: jax.Array
    projs: PyTree   # per-leaf projector (*lead, s, r) arrays (None elsewhere)
    inner: PyTree   # the wrapped transform's state (projected space)
    # Spectrum probes (``probe_spectrum=True``, None otherwise): per leaf /
    # family a dict {"sv2": (r,) squared singular values of PᵀG summed over
    # blocks, "g2": () total ||G||_F², "mn": (2,) family shape} captured at
    # each refresh — the raw material of the spectral() rank policy
    # (repro.core.rank_policy reads them host-side via gather_probes).
    probes: PyTree = None


def _spectrum_probe(p, g32, fs: FamilyShape):
    """Squared singular values of the projected gradient sketch ``PᵀG``
    (via the r x r Gram eigenvalues — no extra SVD), summed over stacked
    blocks and sorted descending, plus the total gradient energy.  Reuses
    the projector the refresh just computed, so the probe costs one thin
    GEMM + an r x r eigh per refresh."""
    s = _dispatch().project(p, g32, side=fs.side, impl="jnp")
    if fs.side == "left":
        gram = jnp.einsum("...ab,...cb->...ac", s, s)
    else:
        gram = jnp.einsum("...ba,...bc->...ac", s, s)
    ev = jnp.maximum(jnp.linalg.eigvalsh(gram), 0.0)     # (*lead, r)
    sv2 = jnp.sum(ev.reshape((-1, ev.shape[-1])), axis=0)
    sv2 = jnp.flip(jnp.sort(sv2))
    return {"sv2": sv2, "g2": jnp.sum(jnp.square(g32)),
            "mn": jnp.asarray((fs.m, fs.n), jnp.int32)}


def _probe_zeros(fs: FamilyShape, telemetry: bool = False):
    pr = {"sv2": jnp.zeros((fs.rank,), jnp.float32),
          "g2": jnp.zeros((), jnp.float32),
          "mn": jnp.asarray((fs.m, fs.n), jnp.int32)}
    if telemetry:
        pr["drift"] = jnp.zeros((), jnp.float32)
        pr["bias"] = jnp.zeros((), jnp.float32)
        pr["bias_step"] = jnp.zeros((), jnp.int32)
    return pr


def _subspace_drift(p_old, p_new):
    """How far the refreshed subspace moved: ``1 − mean squared overlap``
    of the two orthonormal projector stacks via the r×r cross-Gram
    ``P_oldᵀ P_new`` (0 = unchanged span, →1 = orthogonal).  Uses the raw
    einsum (not the dispatch layer) so telemetry never perturbs launch
    counts.  The very first refresh compares against the zero-initialised
    projector and therefore reads 1."""
    r = p_new.shape[-1]
    blocks = 1
    for d in p_new.shape[:-2]:
        blocks *= d
    gram = jnp.einsum("...sr,...sq->...rq", p_old.astype(jnp.float32),
                      p_new.astype(jnp.float32))
    overlap = jnp.sum(jnp.square(gram)) / (r * blocks)
    return jnp.clip(1.0 - overlap, 0.0, 1.0)


def _bias_residual(p, g32, side):
    """Fraction of this step's gradient energy OUTSIDE the current subspace,
    ``1 − ‖PᵀG‖²/‖G‖²`` — the live per-step counterpart of the offline
    bias-residual benchmark (zero iff the projection loses nothing).  Raw
    einsum again: launch-count neutral."""
    s = _raw_project(p, g32, side)
    g2 = jnp.sum(jnp.square(g32))
    return jnp.clip(1.0 - jnp.sum(jnp.square(s)) / jnp.maximum(g2, 1e-30),
                    0.0, 1.0)


def lowrank(
    inner: Transform,
    *,
    rank=128,
    period: int = 200,
    projector: str = "svd",
    seed: int = 0,
    subspace_iters: int = 2,
    reset_on_refresh: bool = False,
    external_refresh: bool = False,
    kernel_impl: str = "auto",
    pad_rank_to: int = 0,
    fuse_families: bool = False,
    fused_epilogue: bool = False,
    rank_policy=None,
    probe_spectrum: bool = False,
    telemetry: bool = False,
) -> Transform:
    """Run ``inner`` inside a periodically-refreshed low-rank subspace.

    Owns everything projection-related: per-family GaLore-side choice,
    projector computation (``svd | subspace | random | grass | rsvd``) every
    ``period`` steps, project / back-project through the Pallas dispatch
    layer (``kernel_impl``, opt-in ``pad_rank_to`` lane alignment), and the
    ProjGrad/FullUpdate leaf protocol described in the module docstring.

    ``reset_on_refresh`` zeroes the inner momenta at each period boundary
    (GUM always does; GaLore only with ``reset_on_update``).

    ``external_refresh=True`` skips the in-update refresh entirely; callers
    drive it through the attached ``update.refresh(grads, state, params)``
    hook instead (the projected-space gradient-accumulation path, which must
    refresh against a raw microbatch gradient *before* projecting).

    ``fuse_families=True`` executes the whole pipeline family-stacked — one
    batched launch per shape family instead of one per leaf (see the module
    docstring); trajectory-identical to the per-leaf path but with a
    different (family-list) state layout.  ``fused_epilogue=True``
    additionally defers the back-projection into :class:`PendingBack` leaves
    so chain tails fold into the GEMM.

    ``rank`` accepts an int or a per-shape :class:`~repro.core.rank_policy.
    RankMap`; ``rank_policy`` (a :class:`~repro.core.rank_policy.RankPolicy`)
    supplies the initial map and, for policies that need them, turns on
    ``probe_spectrum`` — storing per-family spectrum probes in
    ``LowRankState.probes`` at each refresh so a host-side
    :class:`~repro.core.rank_policy.RankPolicyController` can adapt the rank
    over training (rank is a *shape* in JAX, so the change itself happens
    outside jit via ``migrate_opt_state`` + a rebuild at the new map).

    ``telemetry=True`` (implies ``probe_spectrum``) additionally stores, in
    the same probe dicts: projector drift since the previous refresh
    (captured inside the refresh cond), and a per-step bias-residual
    estimate on one round-robin-sampled family (``lax.switch`` — only the
    selected family's thin GEMM executes each step).  The probes are
    write-only from the update's point of view — the parameter trajectory
    is bit-exact with ``telemetry=False`` — and add zero state leaves when
    off.  Host-side readout lives in :mod:`repro.telemetry.instrument`."""
    if telemetry:
        probe_spectrum = True
    if rank_policy is not None:
        probe_spectrum = probe_spectrum or bool(
            getattr(rank_policy, "wants_probes", False))
        if isinstance(rank, int):
            rank = rank_policy.initial_map(rank)
    wants_key = bool(getattr(inner.update, "wants_sample_key", False))
    inner_refresh_state = getattr(inner.update, "refresh_state", None)

    def _leaf_key(base_key, i):
        k = jax.random.fold_in(base_key, i)
        if wants_key:
            k_proj, k_samp = jax.random.split(k)
            return k_proj, k_samp
        return k, None

    def _family_keys(fam, base_key):
        """Stacked per-member (key_proj, key_samp) — bit-identical to
        ``_leaf_key`` per member."""
        keys = member_keys(fam, base_key)              # (M, 2)
        if wants_key:
            ks = jax.vmap(jax.random.split)(keys)      # (M, 2, 2)
            return ks[:, 0], ks[:, 1]
        return keys, None

    def _stacked_projectors(fam, g_stack, keys_proj):
        """Refresh a whole family: vmap ``compute_projectors`` over members
        (vmap is semantics-preserving per element, so each member's projector
        — including its RNG draws — matches the per-leaf path bit-for-bit),
        batching the SVD/QR linear algebra across the stack."""
        mfs = fam.member_fs
        g_mem = g_stack.reshape((fam.seg.members,) + mfs.lead + (mfs.m, mfs.n))
        p_mem = jax.vmap(
            lambda g, k: compute_projectors(
                projector, g, mfs.rank, k, mfs.side, subspace_iters
            )
        )(g_mem, keys_proj)
        return p_mem.reshape((fam.fs.L,) + p_mem.shape[1 + len(mfs.lead):])

    def _sharded_projectors(fam, g_stack, keys_proj, shard_ctx):
        """Sharded refresh of one family under :func:`family_sharding`.

        The stacked gradient arrives partitioned on its leading (stack) axis;
        each shard re-materializes the FULL stacked gradient with one
        ``all_gather`` (the only boundary collective — the count the schedule
        auditor asserts), computes every member's projector exactly as the
        replicated path would (same gradient, same keys → bit-identical
        values), and keeps only its own slice: the refreshed projectors are
        born sharded, no second collective to redistribute them."""
        mesh, axis = shard_ctx
        loc = fam.fs.L // family_shard_count(shard_ctx)

        def body(g_loc, keys):
            g_full = jax.lax.all_gather(g_loc, axis, axis=0, tiled=True)
            p_full = _stacked_projectors(fam, g_full, keys)
            k = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice_in_dim(p_full, k * loc, loc, axis=0)

        return jax.shard_map(
            body, mesh=mesh, in_specs=(_P(axis), _P()), out_specs=_P(axis),
            check_vma=False,
        )(g_stack, keys_proj)

    def _refresh_projectors(fam, g_stack, keys_proj):
        """Dispatch one family's projector refresh: sharded when a
        family-sharding context is active and the stack divides the axis,
        replicated otherwise (the non-divisible fallback keeps auditor
        expectation and runtime consistent — both count only divisible
        families as gathered)."""
        shard_ctx = active_family_sharding()
        if shard_ctx is not None \
                and stack_shardable(fam.fs.L, family_shard_count(shard_ctx)):
            return _sharded_projectors(fam, g_stack, keys_proj, shard_ctx)
        return _stacked_projectors(fam, g_stack, keys_proj)

    def _probe_fresh(p_new, p_old, g32, fs, old_probe):
        """Refresh-boundary probe: the spectrum sketch, plus (telemetry)
        projector drift vs the outgoing subspace and the carried-over bias
        fields — runs inside the refresh cond, so it costs nothing on
        steady steps."""
        pr = _spectrum_probe(p_new, g32, fs)
        if telemetry:
            pr["drift"] = _subspace_drift(p_old, p_new)
            pr["bias"] = old_probe["bias"]
            pr["bias_step"] = old_probe["bias_step"]
        return pr

    def _sample_bias(count, sites, probes):
        """Round-robin bias-residual sampling: ``sites`` is a list of
        (probe-index, projector, grad, side); one site's residual is
        measured per step via ``lax.switch`` (only the selected branch
        executes) and written into its probe dict.  Mutates ``probes`` in
        place; the update path never reads these fields, so the parameter
        trajectory is untouched."""
        if not sites:
            return
        sel = (count - 1) % len(sites)
        branches = [
            (lambda _, p=p, g=g, s=s: _bias_residual(p, g, s))
            for (_pi, p, g, s) in sites
        ]
        bias_val = jax.lax.switch(sel, branches, None)
        for k, (pi, _p, _g, _s) in enumerate(sites):
            pr = dict(probes[pi])
            hit = sel == k
            pr["bias"] = jnp.where(hit, bias_val, pr["bias"])
            pr["bias_step"] = jnp.where(hit, count, pr["bias_step"])
            probes[pi] = pr

    def _plan_leaves(params, grads=None):
        """Flatten params (and optionally grads up to them) and build the
        family plan.  Grad/param trees must mask together in fused mode."""
        leaves, treedef = jax.tree_util.tree_flatten(params, is_leaf=_IS_NONE)
        plan = build_family_plan(leaves, rank)
        g_leaves = None
        if grads is not None:
            g_leaves = treedef.flatten_up_to(grads)
            for fam in plan.families:
                for i in fam.members:
                    if g_leaves[i] is None:
                        raise ValueError(
                            "fuse_families=True requires gradient leaves to "
                            "mask together with param leaves (param at flat "
                            f"index {i} has no gradient)"
                        )
        return leaves, treedef, plan, g_leaves

    def init_fused(params: PyTree) -> LowRankState:
        leaves, _, plan, _ = _plan_leaves(params)
        projs = [jnp.zeros(proj_shape(fam.fs), jnp.float32)
                 for fam in plan.families]
        tmpls = [
            ProjInit(
                fam.fs,
                jax.ShapeDtypeStruct(lowrank_state_shape(fam.fs), jnp.float32),
                seg=fam.seg,
            )
            for fam in plan.families
        ]
        probes = ([_probe_zeros(fam.fs, telemetry) for fam in plan.families]
                  if probe_spectrum else None)
        return LowRankState(
            count=jnp.zeros((), jnp.int32), projs=projs,
            inner=inner.init(tmpls), probes=probes,
        )

    def update_fused(updates: PyTree, state: LowRankState, params: PyTree):
        count = state.count + 1
        refresh = (count - 1) % period == 0
        base_key = jax.random.fold_in(jax.random.PRNGKey(seed), count)

        leaves, treedef, plan, g_leaves = _plan_leaves(params, updates)

        # Stacking the params costs a concat per family per step; only pay it
        # when the inner transform actually reads them (layerwise_unbias
        # gathers full-rank param blocks; the scale_by_* bases only use
        # shapes, which ProjGrad.fs already carries).
        inner_wants_params = bool(getattr(inner.update, "wants_params", False))
        fam_msgs, fam_projs, fam_params, fam_probes = [], [], [], []
        for fi, fam in enumerate(plan.families):
            with jax.named_scope("lowrank.project"):
                g32 = stack_family(
                    fam, [g if g is None else g.astype(jnp.float32)
                          for g in g_leaves]
                )
            keys_proj, keys_samp = _family_keys(fam, base_key)
            if external_refresh:
                p_proj = state.projs[fi]
            else:
                with jax.named_scope("lowrank.refresh"):
                    p_proj = jax.lax.cond(
                        refresh,
                        lambda _, fam=fam, g32=g32, kp=keys_proj:
                            _refresh_projectors(fam, g32, kp),
                        lambda _, fi=fi: state.projs[fi],
                        None,
                    )
                    if probe_spectrum:
                        fam_probes.append(jax.lax.cond(
                            refresh,
                            lambda _, p=p_proj, g=g32, fam=fam, fi=fi:
                                _probe_fresh(p, state.projs[fi], g, fam.fs,
                                             state.probes[fi]),
                            lambda _, fi=fi: state.probes[fi],
                            None,
                        ))
            fam_msgs.append(ProjGrad(
                p=p_proj, g=g32, fs=fam.fs, kernel_impl=kernel_impl,
                pad_rank_to=pad_rank_to, coeff=1.0,
                reset=(refresh if (reset_on_refresh and not external_refresh) else None),
                refresh=(False if external_refresh else refresh),
                key=keys_samp, seg=fam.seg,
            ))
            fam_projs.append(p_proj)
            with jax.named_scope("lowrank.project"):
                fam_params.append(
                    stack_family(fam, leaves) if inner_wants_params else None
                )

        if telemetry and not external_refresh:
            _sample_bias(
                count,
                [(fi, m.p, m.g, fam.fs.side)
                 for fi, (m, fam) in enumerate(zip(fam_msgs, plan.families))],
                fam_probes,
            )

        with jax.named_scope("lowrank.inner"):
            inner_out, new_inner = inner.update(fam_msgs, state.inner, fam_params)

        out_leaves = [None] * plan.n_leaves
        for fam, msg, o, w in zip(plan.families, fam_msgs, inner_out, fam_params):
            if isinstance(o, FullUpdate):
                with jax.named_scope("lowrank.back_project"):
                    parts = unstack_family(fam, o.u)
                for i, part in zip(fam.members, parts):
                    out_leaves[i] = part
            elif fused_epilogue:
                if w is None:
                    w = lambda fam=fam: stack_family(fam, leaves)
                for j, i in enumerate(fam.members):
                    out_leaves[i] = PendingBack(
                        p=msg.p, s=o, w=w, fs=fam.fs,
                        kernel_impl=kernel_impl, pad_rank_to=pad_rank_to,
                        member=j, members=fam.seg.members,
                        member_lead=fam.member_fs.lead,
                    )
            else:
                parts = msg.back(o)
                with jax.named_scope("lowrank.back_project"):
                    parts = unstack_family(fam, parts)
                for i, part in zip(fam.members, parts):
                    out_leaves[i] = part

        return (
            jax.tree_util.tree_unflatten(treedef, out_leaves),
            LowRankState(
                count=count, projs=fam_projs, inner=new_inner,
                probes=(fam_probes if (probe_spectrum and not external_refresh)
                        else state.probes),
            ),
        )

    @_scoped("lowrank.refresh")
    def refresh_fused(grads: PyTree, state: LowRankState, params: PyTree) -> LowRankState:
        count = state.count + 1
        refresh_now = (count - 1) % period == 0
        base_key = jax.random.fold_in(jax.random.PRNGKey(seed), count)

        _, _, plan, g_leaves = _plan_leaves(params, grads)

        new_projs, msgs, new_probes = [], [], []
        for fi, fam in enumerate(plan.families):
            g32 = stack_family(
                fam, [g if g is None else g.astype(jnp.float32)
                      for g in g_leaves]
            )
            keys_proj, keys_samp = _family_keys(fam, base_key)
            p_new = jax.lax.cond(
                refresh_now,
                lambda _, fam=fam, g32=g32, kp=keys_proj:
                    _refresh_projectors(fam, g32, kp),
                lambda _, fi=fi: state.projs[fi],
                None,
            )
            new_projs.append(p_new)
            if probe_spectrum:
                new_probes.append(jax.lax.cond(
                    refresh_now,
                    lambda _, p=p_new, g=g32, fam=fam, fi=fi:
                        _probe_fresh(p, state.projs[fi], g, fam.fs,
                                     state.probes[fi]),
                    lambda _, fi=fi: state.probes[fi],
                    None,
                ))
            msgs.append(RefreshMsg(fs=fam.fs, key=keys_samp, seg=fam.seg))

        if inner_refresh_state is not None:
            new_inner = inner_refresh_state(state.inner, msgs, refresh_now)
        elif reset_on_refresh:
            new_inner = _reset_floats(state.inner, refresh_now)
        else:
            new_inner = state.inner
        return LowRankState(
            count=state.count, projs=new_projs, inner=new_inner,
            probes=(new_probes if probe_spectrum else state.probes),
        )

    def init(params: PyTree) -> LowRankState:
        def init_leaf(p):
            if p is None:
                return (None, None)
            fs = family_shape(p, rank)
            proj = jnp.zeros(proj_shape(fs), jnp.float32)
            tmpl = ProjInit(
                fs, jax.ShapeDtypeStruct(lowrank_state_shape(fs), jnp.float32)
            )
            return (proj, tmpl)

        flat = jax.tree_util.tree_map(init_leaf, params, is_leaf=_IS_NONE)
        projs, tmpls = _transpose(flat, 2)
        probes = None
        if probe_spectrum:
            probes = jax.tree_util.tree_map(
                lambda p: None if p is None
                else _probe_zeros(family_shape(p, rank), telemetry),
                params, is_leaf=_IS_NONE,
            )
        return LowRankState(
            count=jnp.zeros((), jnp.int32), projs=projs,
            inner=inner.init(tmpls), probes=probes,
        )

    def update(updates: PyTree, state: LowRankState, params: PyTree):
        count = state.count + 1
        refresh = (count - 1) % period == 0
        base_key = jax.random.fold_in(jax.random.PRNGKey(seed), count)

        leaves, treedef = jax.tree_util.tree_flatten(params, is_leaf=_IS_NONE)
        g_leaves = treedef.flatten_up_to(updates)
        p_leaves = treedef.flatten_up_to(state.projs)
        pr_leaves = (treedef.flatten_up_to(state.probes)
                     if probe_spectrum else None)

        msg_leaves, proj_leaves, probe_leaves, lr_sites = [], [], [], []
        for i, (g, proj, p) in enumerate(zip(g_leaves, p_leaves, leaves)):
            if g is None or p is None:
                msg_leaves.append(None)
                proj_leaves.append(proj)
                if probe_spectrum:
                    probe_leaves.append(pr_leaves[i])
                continue
            fs = family_shape(p, rank)
            key_proj, key_samp = _leaf_key(base_key, i)
            with jax.named_scope("lowrank.project"):
                g32 = g.astype(jnp.float32)
            if external_refresh:
                p_proj = proj
            else:
                with jax.named_scope("lowrank.refresh"):
                    p_proj = jax.lax.cond(
                        refresh,
                        lambda _: compute_projectors(
                            projector, g32, fs.rank, key_proj, fs.side,
                            subspace_iters
                        ),
                        lambda _: proj,
                        None,
                    )
            if probe_spectrum:
                if external_refresh:
                    probe_leaves.append(pr_leaves[i])
                else:
                    with jax.named_scope("lowrank.refresh"):
                        probe_leaves.append(jax.lax.cond(
                            refresh,
                            lambda _, p=p_proj, old=proj, g=g32, fs=fs, i=i:
                                _probe_fresh(p, old, g, fs, pr_leaves[i]),
                            lambda _, i=i: pr_leaves[i],
                            None,
                        ))
                    lr_sites.append((i, p_proj, g32, fs.side))
            msg_leaves.append(ProjGrad(
                p=p_proj, g=g32, fs=fs, kernel_impl=kernel_impl,
                pad_rank_to=pad_rank_to, coeff=1.0,
                reset=(refresh if (reset_on_refresh and not external_refresh) else None),
                refresh=(False if external_refresh else refresh),
                key=key_samp,
            ))
            proj_leaves.append(p_proj)

        if telemetry and not external_refresh:
            _sample_bias(count, lr_sites, probe_leaves)

        inner_updates = jax.tree_util.tree_unflatten(treedef, msg_leaves)
        with jax.named_scope("lowrank.inner"):
            inner_out, new_inner = inner.update(inner_updates, state.inner, params)

        out_leaves = []
        for msg, o, p in zip(msg_leaves, treedef.flatten_up_to(inner_out), leaves):
            if msg is None or o is None:
                out_leaves.append(None)
            elif isinstance(o, FullUpdate):
                out_leaves.append(o.u)
            elif fused_epilogue:
                out_leaves.append(PendingBack(
                    p=msg.p, s=o, w=p, fs=msg.fs, kernel_impl=kernel_impl,
                    pad_rank_to=pad_rank_to,
                ))
            else:
                out_leaves.append(msg.back(o))

        return (
            jax.tree_util.tree_unflatten(treedef, out_leaves),
            LowRankState(
                count=count,
                projs=jax.tree_util.tree_unflatten(treedef, proj_leaves),
                inner=new_inner,
                probes=(jax.tree_util.tree_unflatten(treedef, probe_leaves)
                        if probe_spectrum else None),
            ),
        )

    @_scoped("lowrank.refresh")
    def refresh(grads: PyTree, state: LowRankState, params: PyTree) -> LowRankState:
        """External period-boundary refresh against raw gradients: recompute
        projectors, resample the inner transform's block assignments, zero
        momenta — leaving ``count`` untouched (the subsequent ``update`` on
        the same step sees fresh state and, in external mode, never
        refreshes itself).  Key derivation matches the in-update path
        exactly, so trajectories are identical either way."""
        count = state.count + 1
        refresh_now = (count - 1) % period == 0
        base_key = jax.random.fold_in(jax.random.PRNGKey(seed), count)

        leaves, treedef = jax.tree_util.tree_flatten(params, is_leaf=_IS_NONE)
        g_leaves = treedef.flatten_up_to(grads)
        p_leaves = treedef.flatten_up_to(state.projs)
        pr_leaves = (treedef.flatten_up_to(state.probes)
                     if probe_spectrum else None)

        new_projs, msgs, new_probes = [], [], []
        for i, (g, proj, p) in enumerate(zip(g_leaves, p_leaves, leaves)):
            if g is None or p is None or proj is None:
                new_projs.append(proj)
                msgs.append(None)
                if probe_spectrum:
                    new_probes.append(pr_leaves[i])
                continue
            fs = family_shape(p, rank)
            key_proj, key_samp = _leaf_key(base_key, i)
            g32 = g.astype(jnp.float32)
            p_new = jax.lax.cond(
                refresh_now,
                lambda _: compute_projectors(
                    projector, g32, fs.rank, key_proj, fs.side, subspace_iters
                ),
                lambda _: proj,
                None,
            )
            new_projs.append(p_new)
            if probe_spectrum:
                new_probes.append(jax.lax.cond(
                    refresh_now,
                    lambda _, p=p_new, old=proj, g=g32, fs=fs, i=i:
                        _probe_fresh(p, old, g, fs, pr_leaves[i]),
                    lambda _, i=i: pr_leaves[i],
                    None,
                ))
            msgs.append(RefreshMsg(fs=fs, key=key_samp))

        msgs_tree = jax.tree_util.tree_unflatten(treedef, msgs)
        if inner_refresh_state is not None:
            new_inner = inner_refresh_state(state.inner, msgs_tree, refresh_now)
        elif reset_on_refresh:
            new_inner = _reset_floats(state.inner, refresh_now)
        else:
            new_inner = state.inner
        return LowRankState(
            count=state.count,
            projs=jax.tree_util.tree_unflatten(treedef, new_projs),
            inner=new_inner,
            probes=(jax.tree_util.tree_unflatten(treedef, new_probes)
                    if probe_spectrum else None),
        )

    info = {
        "kind": "lowrank", "inner": chain_info(inner), "rank": rank,
        "period": period, "projector": projector,
        "kernel_impl": kernel_impl, "pad_rank_to": pad_rank_to,
        "fuse_families": fuse_families, "fused_epilogue": fused_epilogue,
        "external_refresh": external_refresh, "rank_policy": rank_policy,
        "probe_spectrum": probe_spectrum, "telemetry": telemetry,
    }
    if fuse_families:
        update_fused.refresh = refresh_fused
        update_fused.chain_info = info
        return Transform(init_fused, update_fused)
    update.refresh = refresh
    update.chain_info = info
    return Transform(init, update)


# ---------------------------------------------------------------------------
# layerwise_unbias — the paper's debiasing, as a combinator
# ---------------------------------------------------------------------------


class LayerwiseUnbiasState(NamedTuple):
    low: PyTree    # base state over the projected-space leaves
    full: PyTree   # base state over the (gamma, m, n) full-rank slots
    idx: PyTree    # per-leaf (gamma,) int32 slot -> block assignment


def layerwise_unbias(
    base: Transform,
    *,
    gamma: int = 2,
    compensation: str = "paper",
) -> Transform:
    """Layerwise-sampling debiasing (Lemma 1) around ANY base transform.

    Per period, a fixed count ``gamma`` of blocks per family runs the base
    on the *compensated full-rank* gradient (``gamma`` static slots,
    resampled at each projector refresh); the rest run it on the scaled
    projected gradient.  Coefficients per ``compensation``:

      paper    : c_low = 1/(1-q),  c_full = 1/q,  c_comp = 1
      finetune : c_low = 1,        c_full = 1/q,  c_comp = 1-q   (App. C.1)

    Must be composed inside :func:`lowrank` (it consumes the ProjGrad
    protocol and sizes its slots from the ProjInit templates).  With a
    Property-II base (scale_by_muon / scale_by_momentum) the expected update
    equals the full-rank base update — this is GUM; with scale_by_adam the
    *gradient estimate* is unbiased (the new unbiased GaLore-Adam)."""
    if compensation not in ("paper", "finetune"):
        raise ValueError(f"unknown compensation: {compensation}")

    def _coeffs(fs: FamilyShape, seg=None):
        # Under family stacking the sampling unit is the MEMBER leaf (q =
        # gamma / member_L, uniform across the stack by plan construction),
        # exactly as in the per-leaf path.
        L_eff = seg.member_L if seg is not None else fs.L
        g_f = min(gamma, L_eff)
        q = g_f / L_eff
        if q >= 1.0:
            c_low = 0.0  # low branch fully overwritten by the scatter
        elif compensation == "finetune":
            c_low = 1.0
        else:
            c_low = 1.0 / max(1.0 - q, 1e-12)
        c_comp = (1.0 - q) if compensation == "finetune" else 1.0
        c_full = (1.0 / q) if g_f > 0 else 0.0
        return g_f, q, c_low, c_comp, c_full

    def _member_sample(keys, members: int, member_L: int, g_f: int):
        """Stacked resampling: each member draws ``g_f`` of its own
        ``member_L`` blocks with its own key (bit-identical to the per-leaf
        ``jax.random.choice`` under vmap), offset to global stack indices."""
        fresh = jax.vmap(
            lambda k: jax.random.choice(k, member_L, (g_f,), replace=False)
        )(keys).astype(jnp.int32)
        offs = (jnp.arange(members, dtype=jnp.int32) * member_L)[:, None]
        return (fresh + offs).reshape(-1)

    _is_tmpl = lambda x: x is None or isinstance(x, ProjInit)

    def init(params: PyTree) -> LayerwiseUnbiasState:
        def full_tmpl(t):
            if t is None:
                return None
            if not isinstance(t, ProjInit):
                raise TypeError(
                    "layerwise_unbias must be composed inside lowrank() "
                    f"(init saw a {type(t).__name__} leaf, expected ProjInit)"
                )
            g_f, *_ = _coeffs(t.fs, t.seg)
            if g_f == 0:
                return None
            slots = (t.seg.members if t.seg is not None else 1) * g_f
            return jax.ShapeDtypeStruct((slots, t.fs.m, t.fs.n), jnp.float32)

        def idx0(t):
            if t is None:
                return None
            g_f, *_ = _coeffs(t.fs, t.seg)
            if g_f == 0:
                return None
            if t.seg is not None:
                offs = (jnp.arange(t.seg.members, dtype=jnp.int32)
                        * t.seg.member_L)[:, None]
                return (jnp.arange(g_f, dtype=jnp.int32)[None, :]
                        + offs).reshape(-1)
            return jnp.arange(g_f, dtype=jnp.int32)

        def low_tmpl(t):
            # q >= 1 (gamma covers every block): the scatter overwrites the
            # whole family, so the low branch carries no state and does no
            # work for this leaf (mirrors the monoliths' `if q < 1` guard).
            if t is None:
                return None
            g_f, q, *_ = _coeffs(t.fs, t.seg)
            if q >= 1.0:
                return None
            return t

        fulls = jax.tree_util.tree_map(full_tmpl, params, is_leaf=_is_tmpl)
        lows = jax.tree_util.tree_map(low_tmpl, params, is_leaf=_is_tmpl)
        idx = jax.tree_util.tree_map(idx0, params, is_leaf=_is_tmpl)
        return LayerwiseUnbiasState(
            low=base.init(lows), full=base.init(fulls), idx=idx
        )

    _is_pg = lambda x: x is None or isinstance(x, ProjGrad)

    def update(updates: PyTree, state: LayerwiseUnbiasState, params: PyTree):
        g_leaves, treedef = jax.tree_util.tree_flatten(updates, is_leaf=_is_pg)
        idx_leaves = treedef.flatten_up_to(state.idx)
        param_leaves = treedef.flatten_up_to(params)
        d = _dispatch()

        low_upds, new_idx, full_upds, full_params = [], [], [], []
        refresh_any = False
        # The gamma full-rank slots: sample, gather, base update, scatter.
        with jax.named_scope("unbias.full_slots"):
            for g, idx, p in zip(g_leaves, idx_leaves, param_leaves):
                if g is None:
                    low_upds.append(None)
                    new_idx.append(None)
                    full_upds.append(None)
                    full_params.append(None)
                    continue
                if not isinstance(g, ProjGrad):
                    raise TypeError(
                        "layerwise_unbias must be composed inside lowrank() "
                        f"(got a {type(g).__name__} leaf)"
                    )
                fs = g.fs
                g_f, q, c_low, c_comp, c_full = _coeffs(fs, g.seg)
                # q >= 1: no low branch at all (state is None too — see init)
                low_upds.append(g.with_coeff(c_low) if q < 1.0 else None)
                if g_f == 0:
                    new_idx.append(None)
                    full_upds.append(None)
                    full_params.append(None)
                    continue
                if g.refresh is False:  # static: external-refresh mode
                    idx2 = idx
                else:
                    refresh_any = g.refresh
                    if g.seg is not None:
                        fresh = _member_sample(
                            g.key, g.seg.members, g.seg.member_L, g_f
                        )
                    else:
                        fresh = jax.random.choice(
                            g.key, fs.L, (g_f,), replace=False
                        ).astype(jnp.int32)
                    idx2 = jnp.where(g.refresh, fresh, idx)
                new_idx.append(idx2)
                g_s = gather_blocks(g.g, idx2, fs)        # (gamma, m, n)
                p_s = gather_blocks(g.p, idx2, fs)        # (gamma, s, r)
                pptg = d.back_project(
                    p_s,
                    d.project(p_s, g_s, side=fs.side, impl=g.kernel_impl,
                              pad_rank_to=g.pad_rank_to),
                    side=fs.side, impl=g.kernel_impl, pad_rank_to=g.pad_rank_to,
                )
                resid = g_s - c_comp * pptg
                full_upds.append(c_full * resid)
                full_params.append(gather_blocks(p, idx2, fs))

        low_out, new_low = base.update(
            jax.tree_util.tree_unflatten(treedef, low_upds), state.low, params
        )
        with jax.named_scope("unbias.full_slots"):
            # Slot -> block assignments change at the boundary, so the slots'
            # base momenta always reset there (independent of reset_on_refresh).
            full_state = state.full
            if refresh_any is not False:
                full_state = _reset_floats(state.full, refresh_any)
            full_out, new_full = base.update(
                jax.tree_util.tree_unflatten(treedef, full_upds),
                full_state,
                jax.tree_util.tree_unflatten(treedef, full_params),
            )

        lo_leaves = treedef.flatten_up_to(low_out)
        fo_leaves = treedef.flatten_up_to(full_out)
        outs = []
        for g, lo, fo, idx2 in zip(g_leaves, lo_leaves, fo_leaves, new_idx):
            if g is None:
                outs.append(None)
                continue
            fs = g.fs
            g_f, q, *_ = _coeffs(fs, g.seg)
            if q < 1.0:
                u = g.back(lo)
            else:
                u = jnp.zeros(fs.lead + (fs.m, fs.n), jnp.float32)
            if g_f > 0:
                with jax.named_scope("unbias.full_slots"):
                    u = scatter_blocks(u, idx2, fo, fs)
            outs.append(FullUpdate(u))

        return (
            jax.tree_util.tree_unflatten(treedef, outs),
            LayerwiseUnbiasState(
                low=new_low,
                full=new_full,
                idx=jax.tree_util.tree_unflatten(treedef, new_idx),
            ),
        )

    _is_msg = lambda x: x is None or isinstance(x, RefreshMsg)

    def refresh_state(state: LayerwiseUnbiasState, msgs: PyTree, refresh_now):
        """External-refresh hook (driven by ``lowrank``'s refresh): resample
        slot assignments and zero both branches' momenta."""
        msg_leaves, treedef = jax.tree_util.tree_flatten(msgs, is_leaf=_is_msg)
        idx_leaves = treedef.flatten_up_to(state.idx)
        new_idx = []
        for msg, idx in zip(msg_leaves, idx_leaves):
            if msg is None or idx is None:
                new_idx.append(idx)
                continue
            if msg.seg is not None:
                g_f = int(idx.shape[0]) // msg.seg.members
                fresh = _member_sample(
                    msg.key, msg.seg.members, msg.seg.member_L, g_f
                )
            else:
                g_f = int(idx.shape[0])
                fresh = jax.random.choice(
                    msg.key, msg.fs.L, (g_f,), replace=False
                ).astype(jnp.int32)
            new_idx.append(jnp.where(refresh_now, fresh, idx))
        return LayerwiseUnbiasState(
            low=_reset_floats(state.low, refresh_now),
            full=_reset_floats(state.full, refresh_now),
            idx=jax.tree_util.tree_unflatten(treedef, new_idx),
        )

    update.wants_sample_key = True
    update.wants_params = True
    update.refresh_state = refresh_state
    update.chain_info = {"kind": "layerwise_unbias", "inner": chain_info(base),
                         "gamma": gamma, "compensation": compensation}
    return Transform(init, update)


# ---------------------------------------------------------------------------
# with_fira_residual — Fira's out-of-subspace residual, as a combinator
# ---------------------------------------------------------------------------


class FiraResidualState(NamedTuple):
    inner: PyTree
    prev_norm: PyTree  # per-leaf (*lead,) norm-growth-limiter memory


def with_fira_residual(
    base: Transform,
    *,
    limiter: float = 1.01,
    eps: float = 1e-8,
) -> Transform:
    """Fira (Chen et al., 2024): add back the gradient component OUTSIDE the
    projected subspace, scaled per block by phi = ||s|| / ||PᵀG|| (s = the
    base's projected-space update), with the norm-growth limiter.  Must be
    composed inside :func:`lowrank`; no unbiasedness guarantee (the paper's
    point of comparison)."""
    _is_tmpl = lambda x: x is None or isinstance(x, ProjInit)
    _is_pg = lambda x: x is None or isinstance(x, ProjGrad)

    def init(params: PyTree) -> FiraResidualState:
        def pn(t):
            return None if t is None else jnp.zeros(t.fs.lead, jnp.float32)

        return FiraResidualState(
            inner=base.init(params),
            prev_norm=jax.tree_util.tree_map(pn, params, is_leaf=_is_tmpl),
        )

    def update(updates: PyTree, state: FiraResidualState, params: PyTree):
        g_leaves, treedef = jax.tree_util.tree_flatten(updates, is_leaf=_is_pg)

        r_gs, reset = [], None
        for g in g_leaves:
            if g is None:
                r_gs.append(None)
                continue
            if not isinstance(g, ProjGrad):
                raise TypeError("with_fira_residual must be composed inside lowrank()")
            reset = g.reset if g.reset is not None else reset
            r_gs.append(g.materialize())

        # The base consumes plain arrays here, so lowrank's ProjGrad.reset
        # never reaches it — honor reset_on_refresh ourselves (keeps the
        # in-update and external-refresh paths trajectory-identical).
        inner_state, prev_norm = state.inner, state.prev_norm
        if reset is not None:
            inner_state = _reset_floats(inner_state, reset)
            prev_norm = _reset_floats(prev_norm, reset)
        state = FiraResidualState(inner=inner_state, prev_norm=prev_norm)

        s_out, new_inner = base.update(
            jax.tree_util.tree_unflatten(treedef, r_gs), state.inner, params
        )

        s_leaves = treedef.flatten_up_to(s_out)
        pn_leaves = treedef.flatten_up_to(state.prev_norm)
        outs, new_pn = [], []
        for g, r_g, s, prev in zip(g_leaves, r_gs, s_leaves, pn_leaves):
            if g is None:
                outs.append(None)
                new_pn.append(prev)
                continue
            resid = g.g - g.back(r_g)
            s_norm = jnp.linalg.norm(s, axis=(-2, -1))
            rg_norm = jnp.linalg.norm(r_g, axis=(-2, -1))
            phi = s_norm / (rg_norm + eps)
            scaled = phi[..., None, None] * resid

            rnorm = jnp.linalg.norm(scaled, axis=(-2, -1))
            cap = jnp.where(prev > 0, limiter * prev, rnorm)
            shrink = jnp.minimum(1.0, cap / (rnorm + eps))
            scaled = scaled * shrink[..., None, None]
            new_pn.append(rnorm * shrink)

            outs.append(FullUpdate(g.back(s) + scaled))

        return (
            jax.tree_util.tree_unflatten(treedef, outs),
            FiraResidualState(
                inner=new_inner,
                prev_norm=jax.tree_util.tree_unflatten(treedef, new_pn),
            ),
        )

    if getattr(base.update, "wants_params", False):
        update.wants_params = True
    update.chain_info = {"kind": "with_fira_residual",
                         "inner": chain_info(base)}
    return Transform(init, update)


# ---------------------------------------------------------------------------
# state introspection
# ---------------------------------------------------------------------------


def find_lowrank_states(state: PyTree) -> list[LowRankState]:
    """Every :class:`LowRankState` inside an optimizer state (benchmarks and
    tests read projectors through this instead of guessing chain indices)."""
    found: list[LowRankState] = []

    def walk(s):
        if isinstance(s, LowRankState):
            found.append(s)
            return
        if isinstance(s, tuple):
            for c in s:
                walk(c)
        elif isinstance(s, dict):
            for c in s.values():
                walk(c)

    walk(state)
    return found
