"""The dense GQA decoder: its weights, its plain reference in float32,
one layer at a time, and its operation counts.

A configuration file whose ``"reference"`` is ``dense`` is run with this
module (``bench.reference``): ``make`` builds the program's parameter
tree, ``last_logits`` and ``position_stats`` are the reference that
decides ``correct``, and the counts (``prefill_flops``,
``prefill_attention``, ``tokens_flops``, ``unembed_flops``,
``paged_step``, from ``flops.py``) are what the readers divide by.

Weights (``weights.py``'s hash of each element's place): uniform with the
standard deviation of the usual initialisation, 1/sqrt(fan_in) for
matrices and 0.1 for norm scales (applied as ``1 + w``).  ``layer(sizes,
weights.layer_key(weights.base_key(seed), i))`` gives the values that
``make`` put in layer ``i``, so the reference draws one layer again, bit
for bit, without the rest.  The vocabulary rows beyond ``vocab_size``
that the program's layout pads to are zero.

The reference follows the layer equations the configuration files state,
and imports nothing of the program:

    x = x + Wo . attn(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
    x = x + Wout . (up * silu(gate)),  [up | gate] = Win . n2(x)
    n(x) = x / rms(x) * (1 + w)  (eps 1e-6);  logits = n_f(x) . Wlm

RoPE rotates the two halves of each head (theta from the file), attention
is causal with scale 1/sqrt(head_dim), and query head h reads KV head
h // (n_heads / n_kv_heads).  The weights are drawn again from the seed
(``layer``), rounded to the served dtype and widened to float32, so
nothing the program made is read.

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
# the counts that the readers divide by: the dense ones of flops.py
from flops import paged_step, prefill_attention, tokens_flops, unembed_flops
from flops import prefill as prefill_flops

HIGHEST = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 256
ROW_BLOCK = 2048
LOGIT_BLOCK = 256
# tags of the leaves outside the layer stack (layers use 0..L-1)
EMBED, LM_HEAD, FINAL_NORM = 1 << 20, (1 << 20) + 1, (1 << 20) + 2


def gated(sizes: dict) -> bool:
    return sizes["act"] in ("swiglu", "geglu")


def layer(sizes: dict, key) -> dict:
    """One layer's leaves in float32, in the program's layout: q/k/v/o
    projections ``x @ w``, and an MLP whose ``w_in`` holds the up half and
    then the gate half."""
    d, hd, f = sizes["d_model"], sizes["head_dim"], sizes["d_ff"]
    H, Kv = sizes["n_heads"], sizes["n_kv_heads"]
    k = [W.fold(key, j) for j in range(8)]
    return {
        "ln1": {"w": W.uniform(k[0], (d,), 0.1)},
        "ln2": {"w": W.uniform(k[1], (d,), 0.1)},
        "attn": {
            "wq": W.uniform(k[2], (d, H * hd), 1 / math.sqrt(d)),
            "wk": W.uniform(k[3], (d, Kv * hd), 1 / math.sqrt(d)),
            "wv": W.uniform(k[4], (d, Kv * hd), 1 / math.sqrt(d)),
            "wo": W.uniform(k[5], (H * hd, d), 1 / math.sqrt(H * hd)),
        },
        "mlp": {
            "w_in": W.uniform(k[6], (d, (2 if gated(sizes) else 1) * f),
                              1 / math.sqrt(d)),
            "w_out": W.uniform(k[7], (f, d), 1 / math.sqrt(f)),
        },
    }


def embedding(sizes: dict, base):
    return W.uniform(W.fold(base, EMBED),
                     (sizes["vocab_size"], sizes["d_model"]),
                     1 / math.sqrt(sizes["d_model"]))


def lm_head(sizes: dict, base):
    return W.uniform(W.fold(base, LM_HEAD),
                     (sizes["d_model"], sizes["vocab_size"]),
                     1 / math.sqrt(sizes["d_model"]))


def final_norm(sizes: dict, base):
    return W.uniform(W.fold(base, FINAL_NORM), (sizes["d_model"],), 0.1)


@partial(jax.jit, static_argnums=(0, 2, 3))
def _make(frozen, base, vocab_rows: int, dtype: str):
    sizes = dict(frozen)
    pad = vocab_rows - sizes["vocab_size"]
    keys = jax.vmap(lambda i: W.layer_key(base, i))(
        jnp.arange(sizes["n_layers"], dtype=jnp.uint32))
    blocks = jax.vmap(lambda k: jax.tree.map(lambda x: x.astype(dtype),
                                             layer(sizes, k)))(keys)
    return {
        "embed": {
            "embedding": jnp.pad(embedding(sizes, base).astype(dtype),
                                 ((0, pad), (0, 0))),
            "lm_head": jnp.pad(lm_head(sizes, base).astype(dtype),
                               ((0, 0), (0, pad))),
        },
        "final_norm": {"w": final_norm(sizes, base).astype(dtype)},
        "blocks": blocks,
    }


def make(sizes: dict, seed: int, spec):
    """All weights in one jitted call, in the dtype and padded layout of
    ``spec``, the program's parameter shapes (``ShapeDtypeStruct`` tree).

    Raises where the program expects another tree, shape or dtype.
    """
    vocab_rows = spec["embed"]["embedding"].shape[0]
    dtype = str(spec["embed"]["embedding"].dtype)
    frozen, base = W.freeze(sizes), W.base_key(seed)
    W.check(jax.eval_shape(lambda b: _make(frozen, b, vocab_rows, dtype),
                           base), spec)
    return _make(frozen, base, vocab_rows, dtype)


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
    lw = _served(layer(s, key), dtype)
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
    table = _served(embedding(dict(frozen), base), dtype)
    return jnp.take(table, tokens, axis=0)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _logit_stats(frozen, mode, dtype, base, h, lookup):
    """Per row of h (P, d): largest logit, its token, and the logits of
    the tokens in ``lookup`` (P, k)."""
    s = dict(frozen)
    head = _served(lm_head(s, base), dtype)
    w = _served(final_norm(s, base), dtype)

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
    head = _served(lm_head(s, base), dtype)
    w = _served(final_norm(s, base), dtype)
    return _mm(mode, "pd,dv->pv", _norm(h, w), head)


def hidden(sizes: dict, seed: int, tokens, *, mode: str = "f32",
           dtype: str = "bfloat16", bucket: int = 512):
    """Final hidden states (T, d) float32 of one sequence of token ids.

    The sequence is padded at its end to a multiple of ``bucket`` so that
    lengths share compiled programs; causality keeps the padding out of
    every real position.
    """
    frozen = W.freeze(sizes)
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
    out = _full_logits(W.freeze(sizes), mode, dtype, W.base_key(seed),
                       h[-1:])
    return np.asarray(out[0], np.float32)


def position_stats(sizes: dict, seed: int, tokens, positions, lookup, *,
                   mode: str = "f32", dtype: str = "bfloat16"):
    """At each of ``positions`` of ``tokens``: the largest next-token logit,
    its token, and the logits of ``lookup[i]`` (a list of token ids per
    position).  Returns numpy arrays (best (P,), top (P,), got (P, k))."""
    h = hidden(sizes, seed, tokens, mode=mode, dtype=dtype)
    pos = jnp.asarray(np.asarray(positions, np.int32))
    best, top, got = _logit_stats(
        W.freeze(sizes), mode, dtype, W.base_key(seed), h[pos],
        jnp.asarray(np.asarray(lookup, np.int32)))
    return np.asarray(best), np.asarray(top), np.asarray(got)
