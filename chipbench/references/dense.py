"""Plain reference of a dense GQA decoder, in float32, one layer at a time.

It follows the layer equations the configuration files state, and
imports nothing of the program:

    x = x + Wo . attn(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
    x = x + Wout . (up * silu(gate)),  [up | gate] = Win . n2(x)
    n(x) = x / rms(x) * (1 + w)  (eps 1e-6);  logits = n_f(x) . Wlm

RoPE rotates the two halves of each head (theta from the file), attention
is causal with scale 1/sqrt(head_dim), and query head h reads KV head
h // (n_heads / n_kv_heads).  The weights are drawn again from the seed
(``weights.layer``), rounded to the served dtype and widened to float32,
so nothing the program made is read.

``mode="f32"`` is the reference: every matmul in float32 at highest
precision.  ``mode="fp8"`` is the control: every matmul's operands are
rounded to float8 (e4m3) with one absmax scale per tensor, the nearest
precision below the configuration's bfloat16.

Memory stays within one chip at a 32k context: attention runs in query
blocks of 256 against the keys their group of blocks can see, the MLP in
row blocks, and the unembedding only at the positions asked for.
Sequences are padded at the end to a multiple of 512 rows.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights as W

HIGHEST = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 256
ROW_BLOCK = 2048
LOGIT_BLOCK = 256


def _qdq(x):
    scale = jnp.max(jnp.abs(x)) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(mode, spec, a, b):
    if mode == "fp8":
        a, b = _qdq(a), _qdq(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _served(x, dtype):
    return jax.tree.map(lambda v: v.astype(dtype).astype(jnp.float32), x)


def _norm(x, w):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + 1e-6) * (1.0 + w)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(n, b):
    return -(-n // b)


def _groups(T: int) -> int:
    """How many groups of query blocks a causal pass of T rows takes: up
    to 8, each a whole number of blocks."""
    blocks = T // Q_BLOCK
    return max(g for g in range(1, 9) if blocks % g == 0)


def _row_map(fn, x, block):
    """fn over row blocks of x (T, ...); T is padded up to a block."""
    T = x.shape[0]
    nb = _blocks(T, block)
    xp = jnp.pad(x, [(0, nb * block - T)] + [(0, 0)] * (x.ndim - 1))
    y = lax.map(fn, xp.reshape((nb, block) + x.shape[1:]))
    return y.reshape((nb * block,) + y.shape[2:])[:T]


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(frozen, mode, dtype, key, x):
    s = dict(frozen)
    lw = _served(W.layer(s, key), dtype)
    T = x.shape[0]
    H, Kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    G = H // Kv
    pos = jnp.arange(T, dtype=jnp.int32)
    h = _norm(x, lw["ln1"]["w"])
    q = _mm(mode, "td,de->te", h, lw["attn"]["wq"]).reshape(T, H, hd)
    k = _mm(mode, "td,de->te", h, lw["attn"]["wk"]).reshape(T, Kv, hd)
    v = _mm(mode, "td,de->te", h, lw["attn"]["wv"]).reshape(T, Kv, hd)
    q = _rope(q, pos, s["rope_theta"]).reshape(T, Kv, G, hd)
    k = _rope(k, pos, s["rope_theta"])

    def attend(blk, keys):
        qb, pb = blk
        kk, vv = k[:keys], v[:keys]
        logits = _mm(mode, "bkgd,tkd->kgbt", qb, kk) / math.sqrt(hd)
        keep = pb[:, None] >= pos[None, :keys]
        logits = jnp.where(keep[None, None], logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return _mm(mode, "kgbt,tkd->bkgd", p, vv)

    # causal: query rows of group g need only the keys up to its end, so
    # the groups skip most of the masked half of the logits
    groups = _groups(T)
    rows = T // groups
    o = jnp.concatenate([
        lax.map(partial(attend, keys=(g + 1) * rows),
                (q[g * rows:(g + 1) * rows].reshape(-1, Q_BLOCK, Kv, G, hd),
                 pos[g * rows:(g + 1) * rows].reshape(-1, Q_BLOCK)))
        .reshape(rows, H * hd)
        for g in range(groups)])
    x = x + _mm(mode, "te,ed->td", o, lw["attn"]["wo"])

    def mlp(rows):
        h = _norm(rows, lw["ln2"]["w"])
        a = _mm(mode, "td,df->tf", h, lw["mlp"]["w_in"])
        if s["act"] == "swiglu":
            up, gate = jnp.split(a, 2, axis=-1)
            a = up * jax.nn.silu(gate)
        elif s["act"] == "geglu":
            up, gate = jnp.split(a, 2, axis=-1)
            a = up * jax.nn.gelu(gate)
        else:
            a = jax.nn.gelu(a)
        return rows + _mm(mode, "tf,fd->td", a, lw["mlp"]["w_out"])

    return _row_map(mlp, x, ROW_BLOCK)


@partial(jax.jit, static_argnums=(0, 1))
def _embed(frozen, dtype, base, tokens):
    table = _served(W.embedding(dict(frozen), base), dtype)
    return jnp.take(table, tokens, axis=0)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _logit_stats(frozen, mode, dtype, base, h, lookup):
    """Per row of h (P, d): largest logit, its token, and the logits of
    the tokens in ``lookup`` (P, k)."""
    s = dict(frozen)
    head = _served(W.lm_head(s, base), dtype)
    w = _served(W.final_norm(s, base), dtype)

    def one(blk):
        hb, lb = blk
        logits = _mm(mode, "pd,dv->pv", _norm(hb, w), head)
        return (jnp.max(logits, -1), jnp.argmax(logits, -1),
                jnp.take_along_axis(logits, lb, axis=1))

    P = h.shape[0]
    nb = _blocks(P, LOGIT_BLOCK)
    hp = jnp.pad(h, ((0, nb * LOGIT_BLOCK - P), (0, 0)))
    lp = jnp.pad(lookup, ((0, nb * LOGIT_BLOCK - P), (0, 0)))
    best, top, got = lax.map(one, (hp.reshape(nb, LOGIT_BLOCK, -1),
                                   lp.reshape(nb, LOGIT_BLOCK, -1)))
    return (best.reshape(-1)[:P], top.reshape(-1)[:P],
            got.reshape(nb * LOGIT_BLOCK, -1)[:P])


@partial(jax.jit, static_argnums=(0, 1, 2))
def _full_logits(frozen, mode, dtype, base, h):
    s = dict(frozen)
    head = _served(W.lm_head(s, base), dtype)
    w = _served(W.final_norm(s, base), dtype)
    return _mm(mode, "pd,dv->pv", _norm(h, w), head)


def _frozen(sizes):
    return tuple(sorted(sizes.items()))


def hidden(sizes: dict, seed: int, tokens, *, mode: str = "f32",
           dtype: str = "bfloat16", bucket: int = 512):
    """Final hidden states (T, d) float32 of one sequence of token ids.

    The sequence is padded at its end to a multiple of ``bucket`` so that
    lengths share compiled programs; causality keeps the padding out of
    every real position.
    """
    frozen = _frozen(sizes)
    tokens = np.asarray(tokens, np.int32)
    T = len(tokens)
    Tp = _blocks(T, bucket) * bucket
    base = W.base_key(seed)
    x = _embed(frozen, dtype, base, jnp.asarray(np.pad(tokens, (0, Tp - T))))
    for i in range(sizes["n_layers"]):
        x = _layer(frozen, mode, dtype, W.layer_key(base, i), x)
    return x[:T]


def last_logits(sizes: dict, seed: int, tokens, *, mode: str = "f32",
                dtype: str = "bfloat16") -> np.ndarray:
    """Next-token logits (vocab_size,) float32 after ``tokens``."""
    h = hidden(sizes, seed, tokens, mode=mode, dtype=dtype)
    out = _full_logits(_frozen(sizes), mode, dtype, W.base_key(seed), h[-1:])
    return np.asarray(out[0], np.float32)


def position_stats(sizes: dict, seed: int, tokens, positions, lookup, *,
                   mode: str = "f32", dtype: str = "bfloat16"):
    """At each of ``positions`` of ``tokens``: the largest next-token logit,
    its token, and the logits of ``lookup[i]`` (a list of token ids per
    position).  Returns numpy arrays (best (P,), top (P,), got (P, k))."""
    h = hidden(sizes, seed, tokens, mode=mode, dtype=dtype)
    pos = jnp.asarray(np.asarray(positions, np.int32))
    best, top, got = _logit_stats(
        _frozen(sizes), mode, dtype, W.base_key(seed), h[pos],
        jnp.asarray(np.asarray(lookup, np.int32)))
    return np.asarray(best), np.asarray(top), np.asarray(got)
