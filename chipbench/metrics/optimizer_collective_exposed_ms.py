"""Per steady step, the milliseconds of collectives under the program's
``optimizer`` stage during which no other operation runs on the same chip,
averaged over the chips (the part of ``collective_exposed_ms`` the
optimizer issues)."""
from chipbench import scopes, trace


def read(run):
    steps = len(run.got["log"]["steady"])
    pat, names = scopes.pattern("optimizer"), run.trace.op_names
    lo, hi = run.trace.window
    chips = [[o for o in ops if o.end > lo and o.start < hi
              and (not trace.is_collective(o) or scopes.under(o, names, pat))]
             for ops in run.trace.devices.values()]
    if not steps or not any(trace.is_collective(o) for ops in chips for o in ops):
        return None
    exposed = [trace.exposed_collective_seconds(ops) for ops in chips]
    return 1e3 * sum(exposed) / len(exposed) / steps
