"""The comparison that decides ``correct``.

Three numbers, each held to a limit of its own (``limits/<workload>.json``):

* ``loss``: over the first steps, the largest gap between the program's loss
  and the reference's, as a share of the reference's;
* ``grad``: the first gradient as the optimizer keeps it after one step (per
  state leaf: AdamW's first moment, GUM's projected momentum and full-rank
  slots, or per family stack where the program stacks GUM's state by
  shape), the worst leaf's gap of norms;
* ``change``: each parameter leaf's change after the steps, the worst leaf's
  gap of norms.

A gap of norms is ``|a - b| / max(b, median of the reference's leaves)``.
Leaves whose reference gradient is under a thousandth of the median leaf's
(a key's bias under softmax) move by round-off alone and are left out of
``change``.
"""
from __future__ import annotations

import math
import statistics

import jax

NEGLIGIBLE_GRAD = 1e-3


def program_first_gradient(opt_state) -> dict:
    """The program's counterparts of the reference's ``first`` readings,
    found by the state's field names (``mu`` of Adam, ``low`` and ``full``
    of the layerwise-unbiased low-rank state)."""
    out = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        names = [getattr(k, "name", None) for k in kp]
        for field in ("mu", "low", "full"):
            if field in names:
                rest = kp[names.index(field) + 1:]
                path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                                for k in rest)
                out[field + ":" + path] = leaf
    return out


def by_family(first: dict, families: list) -> dict:
    """The reference's per-leaf ``low`` and ``full`` readings as the norms
    of the family stacks a program keeps when it stacks its state by shape
    (stack ``i`` holds the leaves ``families[i]``)."""
    out = {k: v for k, v in first.items() if not k.startswith(("low:", "full:"))}
    for i, members in enumerate(families):
        for field in ("low", "full"):
            out[f"{field}:{i}"] = math.sqrt(sum(first[f"{field}:{p}"] ** 2 for p in members))
    return out


def _gaps(prog: dict, ref: dict, keys) -> dict:
    keys = list(keys)
    missing = [k for k in keys if k not in prog]
    if missing:
        raise KeyError(f"the program has no reading for {missing[:4]}")
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}


def readings(prog: dict, ref: dict) -> dict:
    """The three compared numbers (and the worst leaves, for the record)."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    first = ref["first"]
    if any(k.startswith("low:") and k[4:].isdigit() for k in prog["first"]):
        first = by_family(first, ref["families"])
    grad = _gaps(prog["first"], first, first)
    med_raw = statistics.median(ref["raw"].values())
    moved = [k for k in ref["change"] if ref["raw"][k] >= NEGLIGIBLE_GRAD * med_raw]
    change = _gaps(prog["change"], ref["change"], moved)
    worst_g = max(grad, key=grad.get)
    worst_c = max(change, key=change.get)
    return {"loss": loss, "grad": grad[worst_g], "change": change[worst_c],
            "grad_leaf": worst_g, "change_leaf": worst_c,
            "left_out": sorted(set(ref["change"]) - set(moved))}


def verdict(read: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: [reading, limit]}) over the numbers the cell's
    limits name (a number with no reading that separates sound runs from
    the control and the faults has no limit and is not compared)."""
    table = {k: [read[k], limits[k]] for k in ("loss", "grad", "change") if k in limits}
    ok = all(v == v and v <= lim for v, lim in table.values())  # NaN fails
    return ok, table
