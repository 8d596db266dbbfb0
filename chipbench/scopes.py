"""Device time of the compiled train step by the program's stage names.

The program names its stages with ``jax.named_scope``; each op's name stack
is in the compiled step's metadata (``trace.hlo_op_names``).  An op belongs
to a scope when a component of its name stack is the scope itself, or the
scope wrapped by a transform: ``.../newton_schulz.pallas/...`` and
``vmap(newton_schulz.jnp)`` both belong to ``newton_schulz``.  A program
without the names matches nothing, and every reading here is then None.
"""
from __future__ import annotations

import re

from chipbench import trace


def pattern(*scopes: str) -> re.Pattern:
    """Matches a name stack holding any of ``scopes`` (regular expressions
    for one component, such as ``[a-z_]+\\.jnp``)."""
    return re.compile(r"(^|[/(])(%s)([)./]|$)" % "|".join(scopes))


def under(op: trace.Event, names: dict, pat: re.Pattern) -> bool:
    return bool(pat.search(names.get(trace.instruction(op), "")))


def ms_per_step(run, *scopes: str):
    """Self time of the step's ops under any of ``scopes`` on the first
    chip, in milliseconds per steady step of the window; None when no op
    matched.  Self time (``trace.self_times`` over all of the step's ops)
    counts the body of a loop once, under the body's own names."""
    pat, names = pattern(*scopes), run.trace.op_names
    steps = len(run.got["log"]["steady"])
    hits = [t for o, t in trace.self_times(run.step_ops()) if under(o, names, pat)]
    if not hits or not steps:
        return None
    return 1e3 * sum(hits) / steps


def split(run, scopes) -> dict:
    """Milliseconds per steady step of the step's ops by the innermost of
    ``scopes`` in each op's name stack (``""`` for none): with ``scopes``
    the stages of one layer, the layer's split by stage."""
    pats = [(s, pattern(s)) for s in scopes]
    names, steps = run.trace.op_names, max(len(run.got["log"]["steady"]), 1)

    def innermost(op):
        stack = names.get(trace.instruction(op), "")
        found = [(m.start(), s) for s, p in pats for m in p.finditer(stack)]
        return max(found)[1] if found else ""

    return {k: 1e3 * v / steps
            for k, v in trace.time_by(run.step_ops(), innermost).items()}
