"""Seeded weights, made on the device: the parts no architecture owns.

Each value is a hash of its place: the seed, the layer, the leaf and the
element's flat index, mixed by integer arithmetic alone, then scaled once.
So a reference can draw one layer again, bit for bit, without the rest,
and a whole model costs one pass of integer work on the device rather
than a normal sampler per element.  Each architecture module
(``references/<name>.py``) lays out its own tree from ``uniform`` under
keys from ``base_key``, ``layer_key`` and ``fold``, and checks it against
the program's with ``check``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

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


def freeze(sizes: dict):
    """``sizes`` as a hashable static argument of a jitted function."""
    return tuple(sorted(sizes.items()))


def check(want, spec) -> None:
    """Raise where ``want``, the tree an architecture module would make
    (``ShapeDtypeStruct`` leaves), differs from ``spec``, the program's."""
    if jax.tree.structure(want) != jax.tree.structure(spec):
        raise ValueError(f"the program's parameter tree differs: "
                         f"{jax.tree.structure(spec)} != "
                         f"{jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(spec)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"the program's parameter {b.shape} {b.dtype}"
                             f" differs from the benchmark's {a.shape}"
                             f" {a.dtype}")
