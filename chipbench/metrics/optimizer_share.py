"""Device time of the optimizer update over the device time of the train
step, by the profiler's name stack (forward under jvp, backward under
transpose, optimizer the rest of the step)."""
from chipbench import trace


def read(run):
    by = trace.time_by(run.step_ops(), lambda o: trace.phase(o, run.trace.op_names))
    total = sum(by.values())
    return 100.0 * by.get("optimizer", 0.0) / total if total else None
