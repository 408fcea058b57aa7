"""Seeded weights of a dense GQA decoder, made on the device.

Each value is a hash of its place: the seed, the layer, the leaf and the
element's flat index, mixed by integer arithmetic alone, then scaled once.
So the reference can draw one layer again, bit for bit, without the rest:
``layer(sizes, layer_key(base_key(seed), i))`` gives the values that
``make`` put in layer ``i``, and the whole model costs one pass of integer
work on the device rather than a normal sampler per element.

Values are uniform with the standard deviation of the usual
initialisation: 1/sqrt(fan_in) for matrices, 0.1 for norm scales (applied
as ``1 + w``).  The vocabulary rows beyond ``vocab_size`` that the
program's layout pads to are zero.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# tags of the leaves outside the layer stack (layers use 0..L-1)
EMBED, LM_HEAD, FINAL_NORM = 1 << 20, (1 << 20) + 1, (1 << 20) + 2
_M32 = 0xFFFFFFFF


def _mix(x):
    """A 32-bit integer hash (lowbias32); ``x`` is uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _mix_host(x: int) -> int:
    x &= _M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & _M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & _M32
    return x ^ (x >> 16)


def base_key(seed: int):
    """A uint32 key from any whole number (more than 32 bits allowed)."""
    seed = int(seed) & (2**64 - 1)
    return jnp.uint32(_mix_host(_mix_host(seed >> 32) ^ (seed & _M32)))


def fold(key, tag):
    """The key of a part (a layer, a leaf) of what ``key`` keys."""
    return _mix(jnp.asarray(key, jnp.uint32) * jnp.uint32(0x9E3779B9)
                + jnp.asarray(tag, jnp.uint32))


layer_key = fold


def uniform(key, shape, std):
    """Values of ``shape`` with mean 0 and standard deviation ``std``.

    Odd integers in (-2**24, 2**24) are exact in float32, so the one
    rounding is the final multiplication, the same in every program.
    """
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in range(len(shape) - 1, -1, -1):
        idx = idx + lax.broadcasted_iota(jnp.uint32, shape, ax) \
            * jnp.uint32(stride & _M32)
        stride *= shape[ax]
    h = _mix(_mix(idx ^ jnp.asarray(key, jnp.uint32)) + jnp.asarray(
        key, jnp.uint32))
    odd = (h >> 8).astype(jnp.int32) * 2 + 1 - (1 << 24)
    return odd.astype(jnp.float32) * jnp.float32(std * math.sqrt(3)
                                                  / (1 << 24))


def gated(sizes: dict) -> bool:
    return sizes["act"] in ("swiglu", "geglu")


def layer(sizes: dict, key) -> dict:
    """One layer's leaves in float32, in the program's layout: q/k/v/o
    projections ``x @ w``, and an MLP whose ``w_in`` holds the up half and
    then the gate half."""
    d, hd, f = sizes["d_model"], sizes["head_dim"], sizes["d_ff"]
    H, Kv = sizes["n_heads"], sizes["n_kv_heads"]
    k = [fold(key, j) for j in range(8)]
    return {
        "ln1": {"w": uniform(k[0], (d,), 0.1)},
        "ln2": {"w": uniform(k[1], (d,), 0.1)},
        "attn": {
            "wq": uniform(k[2], (d, H * hd), 1 / math.sqrt(d)),
            "wk": uniform(k[3], (d, Kv * hd), 1 / math.sqrt(d)),
            "wv": uniform(k[4], (d, Kv * hd), 1 / math.sqrt(d)),
            "wo": uniform(k[5], (H * hd, d), 1 / math.sqrt(H * hd)),
        },
        "mlp": {
            "w_in": uniform(k[6], (d, (2 if gated(sizes) else 1) * f),
                            1 / math.sqrt(d)),
            "w_out": uniform(k[7], (f, d), 1 / math.sqrt(f)),
        },
    }


def embedding(sizes: dict, base):
    return uniform(fold(base, EMBED), (sizes["vocab_size"], sizes["d_model"]),
                   1 / math.sqrt(sizes["d_model"]))


def lm_head(sizes: dict, base):
    return uniform(fold(base, LM_HEAD),
                   (sizes["d_model"], sizes["vocab_size"]),
                   1 / math.sqrt(sizes["d_model"]))


def final_norm(sizes: dict, base):
    return uniform(fold(base, FINAL_NORM), (sizes["d_model"],), 0.1)


def _freeze(sizes: dict):
    return tuple(sorted(sizes.items()))


@partial(jax.jit, static_argnums=(0, 2, 3))
def _make(frozen, base, vocab_rows: int, dtype: str):
    sizes = dict(frozen)
    pad = vocab_rows - sizes["vocab_size"]
    keys = jax.vmap(lambda i: layer_key(base, i))(
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
    base = base_key(seed)
    want = jax.eval_shape(lambda b: _make(_freeze(sizes), b, vocab_rows,
                                          dtype), base)
    if jax.tree.structure(want) != jax.tree.structure(spec):
        raise ValueError(f"the program's parameter tree differs: "
                         f"{jax.tree.structure(spec)} != "
                         f"{jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(spec)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"the program's parameter {b.shape} {b.dtype}"
                             f" differs from the benchmark's {a.shape}"
                             f" {a.dtype}")
    return _make(_freeze(sizes), base, vocab_rows, dtype)
