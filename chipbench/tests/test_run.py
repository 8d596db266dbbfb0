"""run.py end to end on the CPU: no chip means no result; with the look for
a chip skipped, a broken step makes ``correct`` false."""
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import run

from . import tiny


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py"), "--workload",
         "qwen1.5-4b.finetune", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_and_names_the_platform():
    p = _run_py(tiny.ROOT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and p.stdout.strip() == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    """In a directory with only BENCHMARK.json and chipbench/, past the look
    for a chip, the run finds no program and prints no result."""
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.'); from chipbench import run; "
            "sys.exit(run.main(['--workload', 'qwen1.5-4b.finetune', '--seed', '1', "
            "'--seconds', '1'], require_tpu=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "repro" in p.stderr


def test_sound_run_prints_the_contract_line(capsys):
    assert run.main(tiny.run_args(trace=1), cell=tiny.dense_cell(), require_tpu=False) == 0
    res = tiny.result(capsys)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["device"]["window_s"] > 0 and "busy_s" in res["device"]
    assert {"refresh_stall_s", "opt_state_gib"} <= set(res["metrics"])


def test_setup_compiles_once_and_drops_the_first_call(capsys, monkeypatch):
    """Before the window the step is compiled once and called once, then
    for the check's three steps and the warm-up; the first call (made slow
    here) is not the refresh sample."""
    from repro.train import Trainer

    slow, lowered, calls = 2.0, [], []
    lower_step = Trainer.lower_step
    monkeypatch.setattr(Trainer, "lower_step",
                        lambda self, *a, **k: lowered.append(1) or lower_step(self, *a, **k))

    def counted(step):
        def call(*args):
            calls.append(1)
            if len(calls) == 1:
                time.sleep(slow)
            return step(*args)
        return call

    cell = tiny.dense_cell()
    assert run.main(tiny.run_args(trace=1), cell=cell, require_tpu=False,
                    wrap_step=counted) == 0
    res = tiny.result(capsys)
    assert len(lowered) == 1
    assert len(calls) - res["attempted"] == 1 + 3 + cell.traffic["warmup_steps"]
    assert res["metrics"]["refresh_stall_s"]["value"] < slow / 2


@pytest.mark.parametrize("fault", [tiny.state_unchanged, tiny.rows_of(2)],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", [tiny.dense_cell], ids=["dense"])
def test_broken_step_is_not_correct(fault, cell, capsys):
    assert run.main(tiny.run_args(), cell=cell(), require_tpu=False, wrap_step=fault) == 0
    assert tiny.result(capsys)["correct"] is False
