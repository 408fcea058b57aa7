"""Flash-style chunked attention Pallas kernel (TPU target).

This is the TPU-native realization of the paper's fused-attention baseline
(Rabe & Staats / FlashAttention): the KV sequence is streamed through VMEM
in blocks with an online-softmax accumulator, so the (Sq, Skv) logits matrix
never materializes in HBM.  Where AutoChunk chunks at the *graph* level
(lax.scan over slices), this kernel chunks at the *memory-hierarchy* level
(HBM -> VMEM BlockSpecs); Fig. 6 of the paper composes the two.

Two masking paths:

- :func:`computed_attention` — causal / sliding-window predicates computed
  from block indices *inside* the kernel.  No mask array exists anywhere
  (not in HBM, not even as a streamed block), and kv blocks that the
  predicate fully masks are skipped via ``pl.when`` before any compute or
  softmax update.  The query offset into kv coordinates is a scalar-prefetch
  operand, so a chunked caller can pass the loop-dependent chunk start
  without retracing.
- :func:`masked_attention` — an explicit (Nm, Sq, Skv) boolean mask streamed
  block-by-block.  This is the fallback for arbitrary masks; it pays O(S²)
  mask memory and exists for exactly the masks positions cannot express.

Grid: (batch*heads, q_blocks, kv_blocks) — kv innermost so the VMEM scratch
accumulator carries across kv steps; output is written on the last kv step.
Block shapes default to (128, head_dim) and are rounded to legal divisors
via :mod:`repro.kernels.tiling` (the autotuner shares the same filter).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import legal_block

NEG_INF = -1e30


def _computed_attn_kernel(
    off_ref,                                   # scalar prefetch: (1,) int32
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window, bq: int, bkv: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # kv-coordinate of query row 0; dynamic so chunked callers can feed the
    # loop-dependent chunk start without retracing
    off = off_ref[0]

    # block-level early skip: when the predicate masks the *entire*
    # (bq, bkv) tile, skip the matmul and the softmax update outright —
    # the accumulators carry through untouched
    live = jnp.bool_(True)
    if causal:
        # smallest kpos in block > largest qpos in block -> fully masked
        live = live & (ki * bkv <= off + qi * bq + (bq - 1))
    if window is not None:
        # largest kpos in block < smallest qpos - (window-1) -> fully masked
        live = live & (ki * bkv + (bkv - 1) >= off + qi * bq - (window - 1))

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)      # (bq, hd)
        k = k_ref[0].astype(jnp.float32)      # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)      # (bkv, hd)
        s = q @ k.T * scale                    # (bq, bkv)

        qpos = off + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask = mask & (kpos <= qpos)
        if window is not None:
            mask = mask & (qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                    # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                 # (bq, bkv)
        alpha = jnp.exp(m_prev - m_new)        # (bq, 1)
        l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def computed_attention(
    q, k, v, *,
    scale: float,
    causal: bool = True,
    window=None,
    q_offset=None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
):
    """Flat fused attention with a position-computed mask.

    ``q``: (N, Sq, hd); ``k``/``v``: (N, Skv, hd).  The causal/window
    predicate is evaluated from block indices inside the kernel — no
    (Sq, Skv) boolean array is ever built, and fully-masked kv blocks are
    skipped before any FLOPs.  ``q_offset`` is the kv-coordinate of query
    row 0 (scalar, may be traced); it defaults to ``Skv - Sq``, i.e.
    queries right-aligned to the kv sequence.  Kernel dispatch passes the
    chunk-loop start here so each chunk masks against absolute positions.
    """
    N, Sq, hd = q.shape
    Skv = k.shape[1]
    bq = legal_block(Sq, block_q)
    bkv = legal_block(Skv, block_kv)
    if q_offset is None:
        q_offset = Skv - Sq
    off = jnp.asarray(q_offset, jnp.int32).reshape((1,))

    grid = (N, Sq // bq, Skv // bkv)
    kernel = functools.partial(
        _computed_attn_kernel,
        scale=scale, causal=causal, window=window, bq=bq, bkv=bkv,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, hd), lambda b, qi, ki, off: (b, qi, 0)),
                pl.BlockSpec((1, bkv, hd), lambda b, qi, ki, off: (b, ki, 0)),
                pl.BlockSpec((1, bkv, hd), lambda b, qi, ki, off: (b, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), lambda b, qi, ki, off: (b, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, hd), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, Sq, hd), q.dtype),
        interpret=interpret, name="computed_attention",
    )(off, q, k, v)


def _masked_attn_kernel(
    q_ref, k_ref, v_ref, mask_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, scale: float,
):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)          # (bkv, hd)
    v = v_ref[0].astype(jnp.float32)          # (bkv, hd)
    s = q @ k.T * scale                        # (bq, bkv)
    s = jnp.where(mask_ref[0], s, NEG_INF)

    m_prev = m_ref[...]                        # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                     # (bq, bkv)
    alpha = jnp.exp(m_prev - m_new)            # (bq, 1)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def masked_attention(
    q, k, v, mask, *,
    scale: float,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
):
    """Flat fused attention with an explicit boolean mask (kernel dispatch).

    ``q``: (N, Sq, hd); ``k``/``v``: (N, Skv, hd); ``mask``: (Nm, Sq, Skv)
    with Nm in {1, N} (True = attend).  This is the fallback the graph-level
    kernel-dispatch pass lowers matched softmax-attention loop bodies onto
    when the mask cannot be classified as causal/sliding-window: masking
    stays fully general at the cost of the O(Sq*Skv) mask buffer, the
    (Sq, Skv) logits never materialize in HBM, and the online-softmax
    accumulator reproduces exp/sum/div semantics of the scan body exactly
    (masked logits pinned at -1e30 on both paths).  Position-expressible
    masks should go through :func:`computed_attention` instead.
    """
    N, Sq, hd = q.shape
    Skv = k.shape[1]
    Nm = mask.shape[0]
    bq = legal_block(Sq, block_q)
    bkv = legal_block(Skv, block_kv)
    assert Nm in (1, N), (Nm, N)

    grid = (N, Sq // bq, Skv // bkv)
    kernel = functools.partial(_masked_attn_kernel, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec(
                (1, bq, bkv),
                (lambda b, qi, ki: (b, qi, ki))
                if Nm > 1
                else (lambda b, qi, ki: (0, qi, ki)),
            ),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="masked_attention",
    )(q, k, v, mask)


def chunked_attention(
    q, k, v, *,
    causal: bool = True,
    window=None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
):
    """q: (B,Sq,H,hd); k,v: (B,Skv,H,hd) -> (B,Sq,H,hd).

    Routes through :func:`computed_attention` (queries right-aligned to
    kv), so the mask is position-computed and fully-masked kv blocks are
    skipped.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(hd)

    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, Skv, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, Skv, hd)

    out = computed_attention(
        qf, kf, vf,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return jnp.moveaxis(out.reshape(B, H, Sq, hd), 1, 2)
