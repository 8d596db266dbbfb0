"""End-to-end behaviour: synthetic counterexample (paper Fig. 1), trainer
fault tolerance (resume-exactness, NaN guard, straggler monitor), and the
sharded train step (subprocess with 8 fake devices)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- paper Fig. 1 counterexample


def test_synthetic_counterexample_fig1():
    sys.path.insert(0, REPO)
    from benchmarks.synthetic_counterexample import make_problem, run

    prob = make_problem()
    steps = 800
    l_muon = run(prob, "muon", steps=steps)[-1]
    l_galore = run(prob, "galore_muon", steps=steps, rank=12)[-1]
    l_gum = run(prob, "gum", steps=steps, rank=2, q=0.5)[-1]
    # GaLore-Muon stalls far from the optimum; GUM converges near Muon.
    assert l_galore > 5.0, l_galore
    assert abs(l_gum) < 0.5, l_gum
    assert abs(l_muon) < 0.5, l_muon
    assert l_galore > 10 * max(abs(l_gum), 1e-3)


# ------------------------------------------------- trainer fault tolerance


def _train(tmpdir, steps, resume=True, seed=0):
    from repro.configs import RunConfig, get_smoke
    from repro.core import OptimizerConfig
    from repro.data import DataConfig
    from repro.models import build_model
    from repro.train import Trainer

    cfg = get_smoke("llama-60m")
    model = build_model(cfg)
    trainer = Trainer(
        model,
        OptimizerConfig(name="gum", lr=1e-3, rank=4, gamma=1, period=3),
        RunConfig(steps=steps, ckpt_dir=tmpdir, ckpt_every=4, log_every=0,
                  resume=resume, seed=seed),
        DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=2, seed=seed),
    )
    return trainer


def test_compile_cache_dir_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache sits at
    the fixed <checkout>/.jax_cache (the same path on every run)."""
    from repro.launch import devices

    assert os.path.isfile(os.path.join(devices.CHECKOUT, "src", "repro",
                                       "launch", "devices.py"))
    # the entry points leave the CPU backend (the test suite) uncached
    assert devices.enable_compile_cache() is None
    assert (jax.config.jax_compilation_cache_dir
            == os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/elsewhere")
    assert devices.compile_cache_dir() == "/cache/elsewhere"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert devices.compile_cache_dir() == os.path.join(devices.CHECKOUT,
                                                       ".jax_cache")


@pytest.mark.slow
def test_trainer_resume_exact(tmp_path):
    """train(12) straight == train(8) + crash + resume to 12 — exact same
    final params (counter-based data + deterministic optimizer)."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    t1 = _train(d1, 12)
    r1 = t1.train()
    t2 = _train(d2, 8)
    t2.train()
    t3 = _train(d2, 12)  # resumes from step 8 checkpoint
    r3 = t3.train()
    assert r3.resumed_from == 8

    from repro.checkpoint import CheckpointManager

    like = t1.init_state()
    a, _ = CheckpointManager(d1).restore(12, like)
    b, _ = CheckpointManager(d2).restore(12, like)
    fa, fb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


def test_nan_guard_skips_update():
    from repro.configs import get_smoke
    from repro.core import OptimizerConfig, build_optimizer
    from repro.launch.steps import make_train_step
    from repro.models import build_model

    cfg = get_smoke("llama-60m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = build_optimizer(OptimizerConfig(name="adamw", lr=1e-3))
    st = opt.init(params)
    step = jax.jit(make_train_step(model, opt, grad_clip=1.0))

    bad = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    # poison the embedding -> NaN loss
    poisoned = jax.tree_util.tree_map(lambda x: x, params)
    poisoned["embed"]["embed"] = poisoned["embed"]["embed"].at[0, 0].set(jnp.nan)
    new_params, _, metrics = step(poisoned, st, bad)
    assert not bool(metrics["update_applied"])
    # params unchanged (still poisoned but not *further* changed)
    np.testing.assert_array_equal(
        np.asarray(new_params["final_norm"]["norm_scale"]),
        np.asarray(params["final_norm"]["norm_scale"]),
    )


def test_straggler_monitor():
    from repro.train import StepTimeMonitor

    mon = StepTimeMonitor(window=50, z=3.0, min_samples=5)
    for i in range(20):
        assert not mon.record(i, 0.1 + 0.001 * (i % 3))
    assert mon.record(20, 1.5)  # 15x the mean -> flagged
    assert mon.flagged and mon.flagged[0][0] == 20


# ------------------------------------------------- sharded step (8 devices)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device(tmp_path):
    """pjit on a (2,4) debug mesh must produce the same loss/params as the
    unsharded step (same inputs, same seed)."""
    script = """
from repro.launch.devices import force_host_device_count
force_host_device_count(8)  # shared helper: preserves other XLA_FLAGS
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke
from repro.configs.base import ShapeConfig
from repro.core import OptimizerConfig, build_optimizer
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import batch_shardings, batch_struct, make_train_step
from repro.models import build_model
from repro.sharding import named_sharding_tree, opt_state_sharding, use_mesh

cfg = get_smoke("qwen1.5-4b")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = build_optimizer(OptimizerConfig(name="gum", lr=1e-2, rank=4, gamma=1, period=2, projector="svd"))
st = opt.init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab)
step = make_train_step(model, opt, grad_clip=1.0)

p1, s1, m1 = jax.jit(step)(params, st, {"tokens": tokens})

mesh = make_debug_mesh((2, 4), ("data", "model"))
psh = named_sharding_tree(params, mesh)
osh = opt_state_sharding(st, mesh)
shape = ShapeConfig("t", 64, 8, "train")
bsh = batch_shardings(cfg, shape, mesh)
with use_mesh(mesh):
    p2, s2, m2 = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None))(params, st, {"tokens": tokens})

np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-3)
print("SHARDED_OK")
"""
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=REPO, timeout=600)
    assert "SHARDED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-4000:]


def test_dryrun_cell_smoke():
    """One real dry-run cell end-to-end in a subprocess (512 fake devices,
    16x16 mesh): lower + compile must succeed and report roofline terms."""
    script = """
import json, tempfile, os
from repro.launch.devices import force_host_device_count
force_host_device_count(512, verify=False)  # shared helper
from repro.launch.dryrun import run_cell
res = run_cell("mamba2-370m", "decode_32k", multi_pod=False)
assert res["status"] == "ok", res
assert res["roofline"]["flops"] > 0
assert res["roofline"]["collective_bytes"] >= 0
print("DRYRUN_OK", res["roofline"]["bottleneck"])
"""
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=REPO, timeout=600)
    assert "DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-4000:]
