"""The sharded GUM state in the params' FSDP layout (``shard_state`` in the
Trainer's GSPMD step).

One subprocess (host forced to 4 CPU devices) builds a small dense model
with square attention (``wq``/``wk``/``wv`` shard rows, ``wo`` columns: one
mixed family) and a rectangular SwiGLU MLP (``w_in``/``w_gate`` shard rows,
left side; ``w_out`` shards columns, right side) over ``data=4``, and
reports:

  * the collectives of the compiled step that serve ``lowrank.project`` /
    ``lowrank.back_project`` outside the refresh: a steady step must move
    rank-r data only, never a member's full-size gradient or update;
  * the trajectory of the step with ``shard_state`` on against off, through
    two projector refreshes;
  * the state's shardings, the shard_map step's, and the Trainer's start-up
    record of each family's projector layout.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
from repro.launch.devices import force_host_device_count
force_host_device_count(4)
import json, re, sys, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.configs import RunConfig, get_smoke
from repro.core import OptimizerConfig, find_lowrank_states
from repro.data import DataConfig, build_stream
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.sharding import family_state_sharding, use_mesh
from repro.telemetry.bus import read_jsonl
from repro.train import Trainer

cfg = get_smoke("llama-60m").replace(n_layers=8)
model = build_model(cfg)
mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
tmp = tempfile.mkdtemp()
out = {}

def trainer(name, shard, **kw):
    return Trainer(model, OptimizerConfig(
        name=name, lr=1e-2, rank=4, gamma=4, period=3, projector="svd",
        fuse_families=True, shard_state=shard),
        RunConfig(steps=1, ckpt_dir=f"{tmp}/{name}{shard}", ckpt_every=0,
                  log_every=0, resume=False, grad_clip=1.0),
        DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8), mesh=mesh,
        **kw)

abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
full = {path: int(np.prod(x.shape)) for path, x in zip(
    ("wk", "wo", "wq", "wv", "w_gate", "w_in", "w_out"),
    [abstract["blocks"]["attn"][k] for k in ("wk", "wo", "wq", "wv")]
    + [abstract["blocks"]["mlp"][k] for k in ("w_gate", "w_in", "w_out")])}
out["member_elems"] = min(full.values())

# 1) collectives of the steady step that serve projection/back-projection
t = trainer("gum", True)
hlo = t.lower_step(abstract).compile().as_text()
colls = []
for line in hlo.splitlines():
    m = re.search(r"= (.*?) (all-gather|all-to-all|collective-permute)"
                  r"(-start)?\(", line)
    name = re.search(r'op_name="([^"]*)"', line)
    if not m or not name:
        continue
    stack = name.group(1)
    if "lowrank.refresh" in stack or not re.search(
            r"lowrank\.(project|back_project)", stack):
        continue
    elems = max(int(np.prod([int(d) for d in dims.split(",") if d]))
                for dims in re.findall(r"\[([\d,]*)\]", m.group(1)))
    colls.append([m.group(2), elems, stack])
out["collectives"] = colls

# 2) the step with the state sharded against the same step without
def trajectory(name, shard, steps=7):
    t = trainer(name, shard)
    params, opt = t.init_state()
    step = t.lower_step(params, opt).compile()
    stream = build_stream(t.data_cfg)
    losses = []
    with use_mesh(mesh):
        for k in range(steps):  # period 3: refreshes at steps 0, 3 and 6
            params, opt, m = step(params, opt,
                                  {"tokens": jnp.asarray(stream.batch_at(k))})
            losses.append(float(m["loss"]))
    return losses, jax.device_get(params), opt

out["equiv"] = {}
for name in ("gum", "galore_muon"):
    ls, ps, state = trajectory(name, True)
    lr, pr, _ = trajectory(name, False)
    gaps = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))
                         / (1e-4 + 1e-3 * np.abs(np.asarray(b)))))
            for a, b in zip(jax.tree_util.tree_leaves(ps),
                            jax.tree_util.tree_leaves(pr))]
    out["equiv"][name] = {"sharded": ls, "unsharded": lr,
                          "param_gap": max(gaps)}
    if name == "gum":
        st = find_lowrank_states(state)[0]
        out["live"] = {
            "projs": [[list(p.shape), str(p.sharding.spec)] for p in st.projs],
            "low": [str(x.sharding.spec) for x in st.inner.low],
            "full": [str(x.sharding.spec) for x in st.inner.full],
        }

# 3) the shard_map step's rule, and the Trainer's start-up record
opt_abs = jax.eval_shape(t.optimizer.init, abstract)
st = find_lowrank_states(family_state_sharding(opt_abs, mesh, "data"))[0]
out["shardmap"] = [str(x.spec) for x in jax.tree_util.tree_leaves(
    (st.projs, st.inner.low, st.inner.full))]
t = trainer("gum", True, telemetry="stdout=0", events_out=f"{tmp}/ev.jsonl")
t.train()
out["events"] = [r for r in read_jsonl(f"{tmp}/ev.jsonl")
                 if r.get("kind") == "event" and r.get("name") == "family_layout"]
print("REPORT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=REPO, timeout=600,
    )
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("REPORT ")]
    assert lines, r.stdout[-3000:] + r.stderr[-4000:]
    return json.loads(lines[-1][len("REPORT "):])


def test_steady_step_moves_no_full_size_gradient_or_update(report):
    """Outside the refresh, projection and back-projection gather or
    exchange rank-r arrays only: none as large as one member's gradient
    (the smallest member here, an 8x64x64 attention leaf)."""
    colls = report["collectives"]
    assert colls, "no collective serves the projection: the state is not sharded"
    big = [c for c in colls if c[1] >= report["member_elems"]]
    assert not big, big


@pytest.mark.parametrize("name", ["gum", "galore_muon"])
def test_sharded_state_matches_unsharded_trainer_step(report, name):
    """The GSPMD step with the family state sharded in the members' layout
    trains as the same step with the state unsharded, through refreshes at
    steps 0, 3 and 6: the math is the same up to f32 summation order."""
    eq = report["equiv"][name]
    assert len(eq["sharded"]) == 7
    for a, b in zip(eq["sharded"], eq["unsharded"]):
        assert abs(a - b) <= 1e-5 * abs(b), (eq["sharded"], eq["unsharded"])
    # |a - b| <= 1e-4 + 1e-3 |b| on every parameter
    assert eq["param_gap"] <= 1.0, eq["param_gap"]


def test_projector_follows_member_layout(report):
    """The live state after the steps: each family's projector ``(L, s, r)``
    shards its ``s`` dim — rows for the attention family (``wq``/``wk``/
    ``wv`` shard rows, ``wo`` columns) and the row-sharded MLP-in family,
    columns (``n``) for the right-side ``w_out`` — while the projected
    moments and the full-rank slots keep the stack dim."""
    live = report["live"]
    assert live["projs"] == [
        [[32, 64, 4], "PartitionSpec(None, 'data', None)"],
        [[16, 64, 4], "PartitionSpec(None, 'data', None)"],
        [[8, 64, 4], "PartitionSpec(None, 'data', None)"],
    ], live["projs"]
    assert live["low"] == ["PartitionSpec('data',)"] * 3, live["low"]
    assert live["full"] == ["PartitionSpec('data',)"] * 3, live["full"]


def test_shardmap_step_keeps_the_stack_rule(report):
    """The shard_map step replicates its params, so every family-stacked
    leaf of its state (projectors, projected moments, full-rank slots)
    stays sharded on the stack dim."""
    specs = report["shardmap"]
    assert specs == ["PartitionSpec('data',)"] * 9, specs


def test_start_up_event_lists_one_layout_per_family(report):
    events = report["events"]
    assert len(events) == 1, events
    rows = events[0]["data"]["layouts"]
    assert [(r["family"], r["projector"], r["members"]) for r in rows] == [
        ("64x64r4x4", "m", "mnmm"),
        ("64x128r4x2", "m", "mm"),
        ("128x64r4x1", "n", "n"),
    ], rows
