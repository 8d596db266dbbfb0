"""Cells at a size a CPU test can hold, with the real cells' limits, and
the faults a test plants under the timed step."""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp

from chipbench import spec

OPT = dict(name="gum", lr=1e-3, rank=8, gamma=1, period=200, beta=0.95,
           ns_steps=5, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0, seed=0)


def dense_cell(dtype="bfloat16", d=128) -> spec.Cell:
    top = dict(hidden_size=d, intermediate_size=3 * d, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=3, vocab_size=96,
               rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=False,
               reference="dense")
    prog = dict(name="tiny", family="dense", n_layers=3, d_model=d, n_heads=4,
                n_kv_heads=4, head_dim=d // 4, d_ff=3 * d, vocab=96, act="swiglu",
                qkv_bias=True, rope="rope", rope_theta=10000.0, dtype=dtype)
    opt = dict(OPT, projector="subspace", compensation="finetune")
    return _cell("qwen1.5-4b.finetune", dict(top, program=prog), 64, opt)


def dense_svd_cell(dtype="bfloat16", d=128) -> spec.Cell:
    """The dense cell with the SVD refresh and the paper's compensation."""
    cell = dense_cell(dtype, d)
    opt = dict(cell.traffic["optimizer"], projector="svd", compensation="paper")
    return dataclasses.replace(cell, traffic=dict(cell.traffic, optimizer=opt))


def sharded_cell(dtype="bfloat16", d=128, chips=4) -> spec.Cell:
    """The dense cell over a data mesh of ``chips`` devices, with the
    family-stacked GUM state sharded over it."""
    cell = dense_cell(dtype, d)
    opt = dict(cell.traffic["optimizer"], fuse_families=True, shard_state=True)
    traffic = dict(cell.traffic, optimizer=opt, mesh={"data": chips})
    with open(os.path.join(spec.HERE, "limits", "qwen1.5-4b-x4.finetune-sharded.json")) as f:
        limits = json.load(f)
    return dataclasses.replace(cell, name="qwen1.5-4b-x4.finetune-sharded", chips=chips,
                               traffic=traffic, limits=limits)


def _cell(workload, config, seq, opt) -> spec.Cell:
    """A tiny cell held to ``limits/<workload>.json`` and reporting every
    metric ``BENCHMARK.json`` names."""
    bench = spec.load_benchmark()
    with open(os.path.join(spec.HERE, "limits", workload + ".json")) as f:
        limits = json.load(f)
    traffic = dict(seq_len=seq, batch=4, tokens="uniform", grad_clip=1.0,
                   warmup_steps=1, optimizer=opt)
    return spec.Cell(workload, 1, config, traffic, tuple(bench["end_to_end"]),
                     tuple(bench["per_layer"]), limits)


def run_args(seed=2 ** 33 + 7, seconds=1.0, trace=0):
    return ["--workload", "tiny", "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]


def result(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


ROOT = spec.ROOT
RUN = os.path.join(ROOT, "chipbench", "run.py")


def state_unchanged(step):
    """The step returns the state it was given."""
    def broken(params, opt_state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        _, _, metrics = step(params, opt_state, batch)  # donates its inputs
        return (*kept, metrics)
    return broken


def rows_of(parts):
    """The step sees only the first ``1/parts`` of each batch (its rows
    repeated): with ``parts=2`` half of the batch is left out; with ``parts``
    the number of chips, each chip's own rows alone, as where the exchange
    between chips is left out."""
    def fault(step):
        def broken(params, opt_state, batch):
            tokens = batch["tokens"]
            kept = tokens[: tokens.shape[0] // parts]
            tokens = jax.device_put(jnp.concatenate([kept] * parts), tokens.sharding)
            return step(params, opt_state, {"tokens": tokens})
        return broken
    return fault
