"""Plain float32 reference of a dense decoder (Qwen1.5 / Qwen2 layout).

RMSNorm, rotary attention with QKV bias and a causal softmax, SwiGLU MLP,
untied LM head, mean next-token cross-entropy.  Written from the published
description with nothing of the program imported; it reads the program's
parameter names only to find the weights the harness made.  Rotary embedding
turns interleaved pairs (dims 2i, 2i+1), the program's convention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x (B, S, H, hd): rotate pairs (2i, 2i+1) by position * theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _layer(cfg, dtype):
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    eps = cfg["rms_norm_eps"]

    def layer(x, bp):
        B, S, D = x.shape
        a = bp["attn"]
        h = _rms(x, bp["ln1"]["norm_scale"], eps)
        q = (h @ a["wq"].astype(dtype) + a["bias_q"].astype(dtype)).reshape(B, S, H, hd)
        k = (h @ a["wk"].astype(dtype) + a["bias_k"].astype(dtype)).reshape(B, S, KV, hd)
        v = (h @ a["wv"].astype(dtype) + a["bias_v"].astype(dtype)).reshape(B, S, KV, hd)
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * hd ** -0.5
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", p.astype(dtype), v).reshape(B, S, H * hd)
        x = x + o @ a["wo"].astype(dtype)
        m = bp["mlp"]
        h = _rms(x, bp["ln2"]["norm_scale"], eps)
        u = jax.nn.silu(h @ m["w_gate"].astype(dtype)) * (h @ m["w_in"].astype(dtype))
        return x + u @ m["w_out"].astype(dtype), None

    return layer


def hidden(params, tokens, cfg, dtype=jnp.float32):
    x = params["embed"]["embed"].astype(dtype)[tokens]
    x, _ = jax.lax.scan(jax.checkpoint(_layer(cfg, dtype)), x, params["blocks"])
    return _rms(x, params["final_norm"]["norm_scale"], cfg["rms_norm_eps"])


def head(params, cfg):
    if cfg.get("tie_word_embeddings"):
        return params["embed"]["embed"].T
    return params["embed"]["lm_head"]


def loss(params, tokens, cfg, dtype=jnp.float32):
    return lm_loss(hidden(params, tokens, cfg, dtype), head(params, cfg), tokens)


def lm_loss(x, w, tokens):
    """Mean next-token cross-entropy, one batch row at a time (the logits of
    a whole batch need not fit)."""
    B, S = tokens.shape

    @jax.checkpoint
    def row(args):
        xb, tb = args
        logits = (xb[:-1] @ w.astype(xb.dtype)).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, tb[1:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    return jnp.sum(jax.lax.map(row, (x, tokens))) / (B * (S - 1))


def flops_per_token(cfg, seq_len: int) -> float:
    """Operations one token needs in the forward and backward passes: 6 per
    weight of every matrix product (the head included, the embedding lookup
    not), plus causal attention's two products, 6 * (S/2) * d per layer
    averaged over positions.  Recomputation is not counted."""
    d, ff, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * d * 2 + d * kv * 2 + 3 * d * ff
    weights = L * per_layer + d * cfg["vocab_size"]
    return 6.0 * weights + 6.0 * L * d * seq_len
