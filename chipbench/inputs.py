"""Weights and token batches, made on the device from ``--seed``.

Both the program and the plain reference take their weights and tokens from
here, so neither takes anything the other made.  The weights follow the
program's parameter tree (its shapes, read from ``jax.eval_shape``), with
values set by the rules below; one jitted call makes the whole tree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PARAMS_STREAM, TOKENS_STREAM = 0, 1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits)."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def stream_key(seed: int, stream: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), stream)


def _leaf_value(key, path: str, shape, dtype):
    """Initial value of one parameter leaf, by its name and shape."""
    name = path.rsplit("/", 1)[-1]
    if len(shape) - (1 if path.startswith("blocks/") else 0) <= 1:
        if "scale" in name or name == "skip_d":
            return jnp.ones(shape, dtype)
        if name == "a_log":  # A = -exp(a_log) in [-16, -1], as Mamba-2 draws it
            u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
            return jnp.log(u).astype(dtype)
        return jnp.zeros(shape, dtype)  # biases, dt bias
    if name in ("embed", "lm_head"):
        std = 0.02
    elif name == "conv_w":
        std = 0.1
    else:
        std = 1.0 / math.sqrt(shape[-2])
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def tree_paths(tree) -> list[str]:
    out = []
    for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append("/".join(str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
                            for k in kp))
    return out


def make_params_fn(abstract_params, dtype=None):
    """``key -> params`` with the structure and shapes of ``abstract_params``
    (``dtype`` overrides every leaf's type: the lower-precision control)."""
    leaves, treedef = jax.tree_util.tree_flatten(abstract_params)
    paths = tree_paths(abstract_params)

    def make(key):
        vals = [_leaf_value(jax.random.fold_in(key, i), p, x.shape,
                            dtype or x.dtype)
                for i, (p, x) in enumerate(zip(paths, leaves))]
        return jax.tree_util.tree_unflatten(treedef, vals)

    return make


def make_tokens_fn(traffic: dict, n_ids: int):
    """``(key, step) -> (batch, seq_len) int32`` ids, uniform over ``n_ids``:
    the traffic mix's one generator.  Every step's rows differ."""
    if traffic.get("tokens", "uniform") != "uniform":
        raise ValueError(f"unknown token mix {traffic['tokens']!r}")
    shape = (int(traffic["batch"]), int(traffic["seq_len"]))

    def make(key, step):
        return jax.random.randint(jax.random.fold_in(key, step), shape, 0,
                                  n_ids, jnp.int32)

    return make
