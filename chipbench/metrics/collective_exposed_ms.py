"""Per steady step, the milliseconds of collective operations during which
no other operation runs on the same chip, averaged over the chips."""
from chipbench import trace


def read(run):
    steps = len(run.got["log"]["steady"])
    lo, hi = run.trace.window
    chips = [[o for o in ops if o.end > lo and o.start < hi]
             for ops in run.trace.devices.values()]
    if not steps or not any(trace.is_collective(o) for ops in chips for o in ops):
        return None
    exposed = [trace.exposed_collective_seconds(ops) for ops in chips]
    return 1e3 * sum(exposed) / len(exposed) / steps
