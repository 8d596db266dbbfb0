"""Drive the plain reference through a cell's first steps.

The reference takes the weights and tokens that ``inputs`` makes from the
seed (never the program's), its model from ``models/<reference>.py`` and its
optimizer from ``optimizers/<name>.py``, and computes in float32 at the
``highest`` matmul precision.  ``dtype=bfloat16`` gives the lower-precision
control: the same reference with parameters, optimizer state and matmuls one
precision below what the configuration states.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

from chipbench import inputs


def _flat(tree) -> dict:
    return dict(zip(inputs.tree_paths(tree), jax.tree_util.tree_leaves(tree)))


def _placed(tree, shardings):
    return tree if shardings is None else jax.lax.with_sharding_constraint(tree, shardings)


def change_norms(params, make_params, key) -> dict:
    """Per leaf ``|params - params_0|``, with ``params_0`` made again from
    the seed (nothing else holds the initial weights)."""
    p0 = _flat(make_params(key))
    return {k: jnp.linalg.norm(v.astype(jnp.float32) - p0[k].astype(jnp.float32))
            for k, v in _flat(params).items()}


def spread(tree, devices):
    """Shardings that divide every leaf of ``tree`` over ``devices`` along
    its longest axis that they divide (a stack of layers keeps its leading
    axis whole); None on one chip."""
    if devices is None or len(devices) < 2:
        return None
    mesh = jax.sharding.Mesh(list(devices), ("x",))
    n = len(devices)

    def one(path, x):
        lead = 1 if path.startswith("blocks/") and x.ndim > 2 else 0
        axes = [a for a in range(lead, x.ndim) if x.shape[a] % n == 0]
        spec = [None] * x.ndim
        if axes:
            spec[max(axes, key=lambda a: x.shape[a])] = "x"
        return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec))

    paths = inputs.tree_paths(tree)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(treedef, [one(p, x) for p, x in zip(paths, leaves)])


def _program(cell, abstract_params, dtype, devices):
    """The reference's jitted step and what it needs around it."""
    model = importlib.import_module("chipbench.models." + cell.config["reference"])
    opt_cfg = dict(cell.traffic["optimizer"], grad_clip=cell.traffic["grad_clip"])
    optim = importlib.import_module("chipbench.optimizers." + opt_cfg["name"])
    treedef = jax.tree_util.tree_structure(abstract_params)
    paths = inputs.tree_paths(abstract_params)
    index = {p: i for i, p in enumerate(paths)}
    make_params = inputs.make_params_fn(abstract_params,
                                        None if dtype == jnp.float32 else dtype)
    make_tokens = inputs.make_tokens_fn(cell.traffic, int(cell.config["vocab_size"]))

    def loss_fn(flat, tokens):
        nested = jax.tree_util.tree_unflatten(treedef, [flat[p] for p in paths])
        return model.loss(nested, tokens, cell.config, dtype)

    flat_abs = jax.eval_shape(lambda k: _flat(make_params(k)), jax.random.PRNGKey(0))
    state_abs = jax.eval_shape(lambda f: optim.init(f, opt_cfg, dtype), flat_abs)
    flat_sh, state_sh = spread(flat_abs, devices), spread(state_abs, devices)
    out_sh = None if flat_sh is None else (flat_sh, state_sh, None, None, None)

    @functools.partial(jax.jit, donate_argnums=(0, 1), out_shardings=out_sh)
    def step(flat, state, count, key, s, keep):
        tokens = make_tokens(key, s)
        # rows past ``keep`` repeat the first ones: the mean is theirs alone
        tokens = tokens[jnp.arange(tokens.shape[0]) % keep]
        loss, grads = jax.value_and_grad(loss_fn)(flat, tokens)
        raw = {k: jnp.linalg.norm(g.astype(jnp.float32)) for k, g in grads.items()}
        flat, state = optim.update(flat, grads, state, count, opt_cfg, dtype, index)
        return flat, state, loss, raw, optim.first_gradient(state, opt_cfg)

    return dict(step=step, make_params=make_params, optim=optim, opt_cfg=opt_cfg,
                flat_abs=flat_abs, state_abs=state_abs, flat_sh=flat_sh,
                state_sh=state_sh,
                precision="highest" if dtype == jnp.float32 else "default")


def lower(cell, abstract_params, devices, dtype=jnp.float32):
    """The reference's step lowered on shapes alone, placed over ``devices``
    (described chips will do): what a compile for the chip needs."""
    pr = _program(cell, abstract_params, dtype, devices)

    def place(tree, sh):
        if sh is None:
            sh = jax.tree_util.tree_map(
                lambda _: jax.sharding.SingleDeviceSharding(devices[0]), tree)
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, sh)

    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    key = jax.eval_shape(lambda: inputs.stream_key(0, 0))
    with jax.default_matmul_precision(pr["precision"]):
        return pr["step"].lower(place(pr["flat_abs"], pr["flat_sh"]),
                                place(pr["state_abs"], pr["state_sh"]), i32, key, i32, i32)


def run(cell, abstract_params, seed: int, steps: int = 3, dtype=jnp.float32,
        batch_rows=None, devices=None, phases=None) -> dict:
    """Losses of ``steps`` steps, the first gradient as the optimizer keeps
    it, the raw first gradient per leaf, and each leaf's change after the
    last step.  ``phases`` (``run.Phases``) is told where each phase of the
    reference ends.  ``batch_rows`` keeps only the first rows of every batch (a planted
    fault: part of the batch left out, the mean taken over the rest; one
    program serves every ``batch_rows``).  On more than one chip the
    weights and state are divided over ``devices`` so that they fit; the
    arithmetic is that of one chip."""
    pr = _program(cell, abstract_params, dtype, devices)
    keep = jnp.int32(batch_rows or cell.traffic["batch"])
    make_params, optim, opt_cfg = pr["make_params"], pr["optim"], pr["opt_cfg"]
    pkey = inputs.stream_key(seed, inputs.PARAMS_STREAM)
    tkey = inputs.stream_key(seed, inputs.TOKENS_STREAM)
    mark = phases.mark if phases is not None else (lambda name: None)
    with jax.default_matmul_precision(pr["precision"]):
        flat = jax.jit(lambda k: _placed(_flat(make_params(k)), pr["flat_sh"]))(pkey)
        state = jax.jit(lambda f: optim.init(f, opt_cfg, dtype),
                        out_shardings=pr["state_sh"])(flat)
        jax.block_until_ready(state)
        mark("reference_make")
        step = pr["step"].lower(flat, state, jnp.int32(1), tkey, jnp.int32(0),
                                keep).compile()
        mark("reference_compile")
        losses, raw, first = [], None, None
        for s in range(steps):
            flat, state, loss, r, f = step(flat, state, jnp.int32(s + 1), tkey,
                                           jnp.int32(s), keep)
            losses.append(float(loss))
            if s == 0:
                raw, first = jax.device_get(r), jax.device_get(f)
        del state
        mark("reference_steps")
        change = jax.device_get(jax.jit(lambda f, k: change_norms(
            f, lambda key: _placed(_flat(make_params(key)), pr["flat_sh"]), k))(flat, pkey))
        mark("reference_change")
    return {"losses": losses, "first": {k: float(v) for k, v in first.items()},
            "families": optim.families(pr["flat_abs"]),
            "raw": {k: float(v) for k, v in raw.items()},
            "change": {k: float(v) for k, v in change.items()}}
