"""The harness on a data mesh of four CPU devices: the sharded program
agrees with the reference, the control is refused, and each fault a
four-chip cell can have makes ``correct`` false.  JAX has to see four
devices from its start, so the cases run in one process of their own."""
import json
import os
import subprocess
import sys

import pytest

from . import tiny

SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
import jax, jax.numpy as jnp
from chipbench import check, reference, run
from chipbench.tests import tiny

devices = jax.devices()[:4]
out = {}
cell = tiny.sharded_cell(dtype="float32")
got = run.measure(cell, 11, 0.0, False, devices)
out["float32"] = check.readings(got["program"], reference.run(cell, got["abstract"], 11,
                                                              devices=devices))
cell = tiny.sharded_cell()
ref = reference.run(cell, got["abstract"], 5, devices=devices)
control = reference.run(cell, got["abstract"], 5, dtype=jnp.bfloat16, devices=devices)
out["control"] = check.verdict(check.readings(control, ref), cell.limits)[0]
faults = {"sound": None, "state_unchanged": tiny.state_unchanged,
          "half_batch": tiny.rows_of(2), "exchange_left_out": tiny.rows_of(4)}
for name, fault in faults.items():
    rc = run.main(tiny.run_args(), cell=cell, require_tpu=False, wrap_step=fault)
    out[name] = rc
print("RESULTS " + json.dumps(out, default=str))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", f"ROOT = {tiny.ROOT!r}\n" + SCRIPT],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.splitlines()
    out = json.loads(next(x for x in lines if x.startswith("RESULTS "))[8:])
    out["lines"] = [json.loads(x) for x in lines if x.startswith("{")]
    return out


def test_sharded_program_matches_reference_in_float32(results):
    read = results["float32"]
    assert read["loss"] < 1e-5 and read["grad"] < 1e-4 and read["change"] < 1e-3, read


def test_bfloat16_control_is_refused_on_the_mesh(results):
    assert results["control"] is False


@pytest.mark.parametrize("case, correct", [
    ("sound", True), ("state_unchanged", False), ("half_batch", False),
    ("exchange_left_out", False)])
def test_run_on_the_mesh(results, case, correct):
    names = ["sound", "state_unchanged", "half_batch", "exchange_left_out"]
    line = results["lines"][names.index(case)]
    assert results[case] == 0 and line["correct"] is correct, line["checks"]
    assert line["device"]["count"] == 4
