"""Host-side gatherers over the live optimizer state.

``lowrank(telemetry=True)`` stores its in-jit measurements inside the
existing spectrum-probe dicts (``LowRankState.probes``) — this module reads
them out between steps and turns them into bus metrics:

  * :func:`lowrank_family_metrics` — per shape family: captured-energy
    fraction at rank r (sum of the top-r squared singular values of PᵀG over
    total gradient energy), projector drift since the previous refresh
    (1 − mean subspace overlap via the r×r Gram), the sampled per-step bias
    residual (1 − ‖PᵀG‖²/‖G‖²) with the step it was sampled at, and the
    current rank.
  * :class:`GammaSlotTracker` — the layerwise-unbias gamma-slot sampling
    distribution: which blocks the debiasing currently runs full-rank, plus
    cumulative per-block visit counts across refreshes (the paper's
    uniform-knowledge claim made observable).

Everything here is read-only over the state and runs on the host at
refresh-boundary cadence — nothing is traced, nothing recompiles.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

PyTree = Any


def _is_probe(x) -> bool:
    return isinstance(x, dict) and "sv2" in x and "g2" in x


def lowrank_family_metrics(opt_state: PyTree) -> list[dict]:
    """Per-(m, n) family telemetry read from the probe dicts; one record per
    shape family, averaged over same-shape leaves on the per-leaf path.
    Keys ``drift`` / ``bias`` / ``bias_step`` appear only when the state was
    built with ``lowrank(telemetry=True)``; energy/rank work with plain
    ``probe_spectrum=True`` probes too.  Empty list when no probes exist."""
    from repro.core.combinators import find_lowrank_states

    acc: dict[tuple[int, int], dict] = {}
    for st in find_lowrank_states(opt_state):
        if st.probes is None:
            continue
        for pr in jax.tree_util.tree_leaves(st.probes, is_leaf=_is_probe):
            if not _is_probe(pr):
                continue
            host = {k: np.asarray(jax.device_get(v)) for k, v in pr.items()}
            mn = (int(host["mn"][0]), int(host["mn"][1]))
            sv2 = host["sv2"].astype(np.float64)
            cur = acc.setdefault(mn, {
                "m": mn[0], "n": mn[1], "rank": int(sv2.shape[0]),
                "sv2_sum": 0.0, "g2": 0.0, "leaves": 0,
                "drift": 0.0, "bias": 0.0, "bias_step": -1,
                "has_telemetry": False,
            })
            cur["sv2_sum"] += float(sv2.sum())
            cur["g2"] += float(host["g2"])
            cur["leaves"] += 1
            if "drift" in host:
                cur["has_telemetry"] = True
                cur["drift"] += float(host["drift"])
                cur["bias"] += float(host["bias"])
                cur["bias_step"] = max(cur["bias_step"],
                                       int(host["bias_step"]))

    out = []
    for mn in sorted(acc):
        cur = acc[mn]
        n_leaves = max(cur["leaves"], 1)
        rec = {
            "family": f"{mn[0]}x{mn[1]}",
            "m": cur["m"], "n": cur["n"], "rank": cur["rank"],
            "energy": (cur["sv2_sum"] / cur["g2"]) if cur["g2"] > 0 else 0.0,
        }
        if cur["has_telemetry"]:
            rec["drift"] = cur["drift"] / n_leaves
            rec["bias"] = cur["bias"] / n_leaves
            rec["bias_step"] = cur["bias_step"]
        out.append(rec)
    return out


def find_unbias_states(state: PyTree) -> list:
    """Every :class:`~repro.core.combinators.LayerwiseUnbiasState` inside an
    optimizer state (they live *inside* LowRankState.inner, which the plain
    tuple walk passes through)."""
    from repro.core.combinators import LayerwiseUnbiasState

    found: list = []

    def walk(s):
        if isinstance(s, LayerwiseUnbiasState):
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


class GammaSlotTracker:
    """Cumulative histogram of layerwise-unbias gamma-slot assignments.

    Call :meth:`observe` at refresh boundaries; it reads the current
    slot→block index arrays out of every ``LayerwiseUnbiasState`` and folds
    them into per-leaf visit counts.  The returned records expose both the
    live assignment and the cumulative distribution (min/max/mean visits per
    block), so a skewed sampler — blocks that never take their full-rank
    turn — is visible in one event."""

    def __init__(self):
        # (unbias-state index, idx-leaf index) -> np.ndarray of visit counts
        self.counts: dict[tuple[int, int], np.ndarray] = {}
        self.observations = 0

    def observe(self, opt_state: PyTree) -> list[dict]:
        records = []
        states = find_unbias_states(opt_state)
        if not states:
            return records
        self.observations += 1
        for si, st in enumerate(states):
            idx_leaves = [l for l in jax.tree_util.tree_leaves(st.idx)
                          if l is not None]
            for li, idx in enumerate(idx_leaves):
                slots = np.asarray(jax.device_get(idx)).astype(int).ravel()
                key = (si, li)
                hist = self.counts.get(key)
                size = int(slots.max()) + 1 if slots.size else 0
                if hist is None or hist.shape[0] < size:
                    grown = np.zeros(max(size, 1), dtype=np.int64)
                    if hist is not None:
                        grown[: hist.shape[0]] = hist
                    hist = grown
                    self.counts[key] = hist
                np.add.at(hist, slots, 1)
                records.append({
                    "leaf": li,
                    "slots": [int(s) for s in slots],
                    "visits_min": int(hist.min()),
                    "visits_max": int(hist.max()),
                    "visits_mean": round(float(hist.mean()), 3),
                })
        return records


def launch_crosscheck(transform, params, *, name: str = "optimizer") -> dict:
    """Runtime launch-counter cross-check: trace the live transform's update
    through the dispatch layer's launch counter and diff the recorded counts
    against the closed-form model from :mod:`repro.analysis.launch_model`
    (PR 6) — the static auditor's contract asserted again on the *actual*
    chain about to train, as a telemetry event instead of a hard failure.
    Returns ``{expected, traced, ok, unmodeled, fallbacks}``; ``ok`` is False
    when the counts diverge or the model could not account for a stage
    (RA303).  ``fallbacks`` lists each ``{op, shape, calls}`` the same trace
    sent to the jnp reference because the kernel refuses the shape."""
    from repro.analysis.launch_model import expected_launches
    from repro.kernels import launch_count

    expected, findings = expected_launches(transform, params, name=name)
    state = jax.eval_shape(transform.init, params)
    with launch_count.count_launches() as counts, \
            launch_count.count_fallbacks() as fallbacks:
        jax.make_jaxpr(
            lambda g, s, w: transform.update(g, s, w))(params, state, params)
    traced = dict(counts)
    calls: dict = {}
    for op_shape in fallbacks:
        calls[op_shape] = calls.get(op_shape, 0) + 1
    return {
        "expected": expected,
        "traced": traced,
        "ok": not findings and traced == expected,
        "unmodeled": [f.code for f in findings],
        "fallbacks": [{"op": op, "shape": list(shape), "calls": n}
                      for (op, shape), n in sorted(calls.items())],
    }
