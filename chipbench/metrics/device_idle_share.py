"""Share of the traced window in which no operation ran on the device."""
from chipbench import trace


def read(run):
    if not run.ops:
        return None
    lo, hi = run.trace.window
    return 100.0 * (1.0 - trace.busy_seconds(run.ops, run.trace.window) / (hi - lo))
