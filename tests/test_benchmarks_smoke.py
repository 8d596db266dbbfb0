"""CI guard for the benchmark harness: ``benchmarks/run.py --smoke`` must
execute EVERY suite end-to-end (1-2 steps, no timing claims, no result-JSON
writes).  Before this test existed the harness itself had bit-rotted — the
suite imports were broken under the documented invocation and nothing
noticed.  Runs the harness once as a subprocess, exactly as a user would."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_run_py_smoke_executes_all_suites(tmp_path):
    # (subprocess timeout=520 is the watchdog; pytest-timeout isn't vendored)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"), "--smoke"],
        cwd=tmp_path,  # NOT the repo root: smoke must not depend on cwd
        env=env, capture_output=True, text=True, timeout=520,
    )
    assert res.returncode == 0, res.stderr[-4000:]
    out = res.stdout
    assert "# smoke run complete" in out
    # every registered suite announced itself (run.py prints to stderr)
    for suite in ("synthetic_counterexample", "memory_table", "pretrain_proxy",
                  "bias_residual", "stable_rank", "roofline_report",
                  "optimizer_api", "fused_step", "rank_policy",
                  "audit_matrix", "resilience", "sharded_step"):
        assert f"# --- {suite} ---" in res.stderr, suite
    # the fused-step suite produced its rows, including launch counts
    assert "fusedstep_gum_stacked" in out
    assert "launches=" in out
    # the resilience suite measured the monitor and checksum costs
    assert "resilience_step_monitor_on" in out
    assert "resilience_save_crc" in out
    # the audit-matrix suite audited its smoke cells clean
    assert "audit_gum," in out and ",clean" in out
    # ...including the sharded collective-schedule cell (AbstractMesh trace,
    # so it runs identically with however many devices the runner has)
    assert "audit_sharded_gum_mesh8," in out
    assert "steady_wire_bytes=" in out
    # the ZeRO sharded-step suite reported its per-device state row
    assert "sharded_step_state_mesh8," in out
    assert "opt_bytes_per_shard=" in out
    # registered suites all have their result JSONs committed, and every
    # suite is declared in exactly one of RESULT_JSON / NO_RESULT_JSON
    assert "WARNING: suite" not in res.stderr
    # no result JSONs written in smoke mode (cwd is a scratch dir anyway)
    assert "# wrote" not in out

