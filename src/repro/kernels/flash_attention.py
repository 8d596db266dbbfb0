"""Flash attention (causal, GQA) as a Pallas TPU kernel.

TPU-native design (FlashAttention's insight re-tiled for VMEM/MXU, not a CUDA
port): the grid is (batch, q_heads, q_blocks, kv_blocks) with the kv axis
innermost and sequential ("arbitrary"); running max / denominator / output
accumulator live in VMEM scratch that persists across kv-grid steps, so HBM
traffic is one pass over K/V per q block and one write of O.  Block shapes
should be multiples of (8, 128) on real TPU; interpret mode (tests) accepts
any shape.

GQA is expressed in the BlockSpec index maps: the kv block for query head h
is head ``h // (H // KV)`` — no materialized K/V repetition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, block_q: int, block_kv: int, seq_q: int, seq_kv: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + (seq_kv - seq_q)  # causal row offset for short q
    k_start = ki * block_kv

    if causal:
        # Skip kv blocks that are fully masked for this q block.
        run = k_start <= q_start + block_q - 1
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (bkv, d)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = (q @ k.T) * scale                      # (bq, bkv)

        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + p @ v
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q (B, S, H, D); k/v (B, T, KV, D); returns (B, S, H, D)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    group = H // KV
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    assert S % block_q == 0 and T % block_kv == 0, "pad seq to block multiples"

    grid = (B, H, S // block_q, T // block_kv)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
        seq_q=S, seq_kv=T,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, D), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, block_kv, 1, D), lambda b, h, qi, ki: (b, ki, h // group, 0)),
            pl.BlockSpec((1, block_kv, 1, D), lambda b, h, qi, ki: (b, ki, h // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, D), lambda b, h, qi, ki: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )(q, k, v)
