"""Shared neural-net layers (pure functions over param pytrees).

Everything is written in chunk-flow-friendly style: explicit einsums,
softmax/masking built from primitives that the AutoChunk dimflow rules can
trace (iota-based masks hoist cleanly), no nested jit.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(x, w, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * w + b).astype(x.dtype)


def apply_norm(cfg, x, p):
    with jax.named_scope("norm"):
        if cfg.norm == "layernorm":
            return layernorm(x, p["w"], p["b"])
        return rmsnorm(x, p["w"])


def norm_params(cfg, key, d):
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), cfg.jdtype), "b": jnp.zeros((d,), cfg.jdtype)}
    return {"w": jnp.zeros((d,), cfg.jdtype)}


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    if theta <= 0.0:
        return x
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA; full / causal / sliding-window; shared by all archs)
# --------------------------------------------------------------------------

def attention_scores_mask(
    q_pos, kv_pos, *, causal: bool, window: Optional[int]
):
    """Boolean mask (q_len, kv_len): True = attend."""
    dq = q_pos[:, None]
    dk = kv_pos[None, :]
    mask = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), dtype=bool)
    if causal:
        mask = mask & (dk <= dq)
    if window is not None:
        mask = mask & (dq - dk < window)
    return mask


def gqa_attention(
    q, k, v, *,
    q_pos, kv_pos,
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid=None,
):
    """q: (B,Sq,H,hd); k,v: (B,Skv,Kv,hd).  Returns (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Kv = k.shape[2]
    G = H // Kv
    scale = 1.0 / math.sqrt(hd)
    with jax.named_scope("attention"):
        qg = q.reshape(B, Sq, Kv, G, hd)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        mask = attention_scores_mask(q_pos, kv_pos, causal=causal,
                                     window=window)
        if kv_valid is not None:
            mask = mask & kv_valid[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        a = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", a, v.astype(jnp.float32))
        return out.reshape(B, Sq, H, hd).astype(q.dtype)


def attn_params(cfg, key, *, d=None, n_heads=None, n_kv=None, hd=None):
    d = d or cfg.d_model
    n_heads = n_heads or cfg.n_heads
    n_kv = n_kv or cfg.n_kv_heads
    hd = hd or cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "wq": (jax.random.normal(k1, (d, n_heads * hd)) * s).astype(cfg.jdtype),
        "wk": (jax.random.normal(k2, (d, n_kv * hd)) * s).astype(cfg.jdtype),
        "wv": (jax.random.normal(k3, (d, n_kv * hd)) * s).astype(cfg.jdtype),
        "wo": (jax.random.normal(k4, (n_heads * hd, d)) * s).astype(cfg.jdtype),
    }


def attn_project_qkv(cfg, p, x, positions, *, n_heads=None, n_kv=None, hd=None):
    n_heads = n_heads or cfg.n_heads
    n_kv = n_kv or cfg.n_kv_heads
    hd = hd or cfg.hd
    B, S, _ = x.shape
    with jax.named_scope("qkv"):
        q = (x @ p["wq"]).reshape(B, S, n_heads, hd)
        k = (x @ p["wk"]).reshape(B, S, n_kv, hd)
        v = (x @ p["wv"]).reshape(B, S, n_kv, hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        return q, k, v


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_params(cfg, key, *, d=None, f=None, act=None):
    d = d or cfg.d_model
    f = f or cfg.d_ff
    act = act or cfg.act
    k1, k2 = jax.random.split(key)
    s = 1.0 / math.sqrt(d)
    gated = act in ("swiglu", "geglu")
    win = jax.random.normal(k1, (d, 2 * f if gated else f)) * s
    wout = jax.random.normal(k2, (f, d)) / math.sqrt(f)
    return {"w_in": win.astype(cfg.jdtype), "w_out": wout.astype(cfg.jdtype)}


def mlp(cfg, p, x, act=None):
    act = act or cfg.act
    with jax.named_scope("mlp"):
        h = x @ p["w_in"]
        if act == "swiglu":
            u, g = jnp.split(h, 2, axis=-1)
            h = u * jax.nn.silu(g)
        elif act == "geglu":
            u, g = jnp.split(h, 2, axis=-1)
            h = u * jax.nn.gelu(g)
        else:
            h = jax.nn.gelu(h)
        return h @ p["w_out"]


# --------------------------------------------------------------------------
# Embedding / head
# --------------------------------------------------------------------------

def embed_params(cfg, key):
    s = 1.0 / math.sqrt(cfg.d_model)
    vp = cfg.vocab_padded
    p = {"embedding": (jax.random.normal(key, (vp, cfg.d_model)) * s).astype(cfg.jdtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = (
            jax.random.normal(jax.random.fold_in(key, 1), (cfg.d_model, vp)) * s
        ).astype(cfg.jdtype)
    return p


def embed(cfg, p, tokens):
    return jnp.take(p["embedding"], tokens, axis=0)


def unembed(cfg, p, h):
    with jax.named_scope("unembed"):
        logits = h @ (p["embedding"].T if cfg.tie_embeddings else p["lm_head"])
        if cfg.vocab_padded != cfg.vocab_size:
            pad = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
            logits = jnp.where(pad, -1e30, logits.astype(jnp.float32)).astype(logits.dtype)
        return logits
