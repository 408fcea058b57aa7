"""Ragged paged flash attention Pallas kernel (TPU target).

The serving rewrite stores KV in a paged pool (``serving.kv_pool``): fixed
``page_size`` pages with a fused head-interleaved layout ``[K0,V0,K1,V1,..]``
on the head axis, one page table per sequence.  This kernel attends a ragged
batch of query rows against that pool *in place* — no gather of pages into a
dense per-sequence cache ever happens in HBM:

* the **page table is the index map**: the KV BlockSpec resolves grid step
  ``(s, ki)`` to physical page ``page_table[s, ki]`` through scalar prefetch
  (``pltpu.PrefetchScalarGridSpec``), so the DMA engine walks each
  sequence's logical pages directly;
* the batch is **ragged**: row ``s`` carries ``q_lens[s]`` query tokens
  (1 for decode rows, a planner-sized chunk for prefill rows — both kinds
  coexist in one mixed step) against ``kv_lens[s]`` context tokens;
* attention is **causal within each sequence**: query ``i`` of row ``s``
  sits at absolute position ``kv_lens[s] - q_lens[s] + i`` and attends to
  positions ``<=`` its own.

Grid: ``(S, q_blocks, max_pages)`` with the page index innermost, so the
online-softmax accumulator carries across a sequence's pages in VMEM
scratch.  Pages past ``ceil(kv_len / page_size)`` and pages wholly after a
query block's last position are skipped (``pl.when``); their page-table
entries are clamped to a valid physical page so the prefetch never reads out
of bounds.

The pure-jnp oracle is :func:`repro.kernels.ref.paged_attention_ref`; the
public ragged wrapper (``cu_q_lens``/``cu_kv_lens`` descriptors) is
:func:`repro.kernels.ops.paged_attention`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import VMEM_BUDGET, legal_block, paged_vmem_bytes

NEG_INF = -1e30


def interleave_kv(k, v):
    """Fuse K/V into the pool's head-interleaved layout.

    ``k``/``v``: (..., Kv, hd)  ->  (..., 2*Kv, hd) ordered [K0,V0,K1,V1,..].
    """
    Kv, hd = k.shape[-2:]
    stacked = jnp.stack([k, v], axis=-2)          # (..., Kv, 2, hd)
    return stacked.reshape(*k.shape[:-2], 2 * Kv, hd)


def split_kv(pages):
    """Inverse of :func:`interleave_kv`: (..., 2*Kv, hd) -> k, v."""
    two_kv, hd = pages.shape[-2:]
    kv = pages.reshape(*pages.shape[:-2], two_kv // 2, 2, hd)
    return kv[..., 0, :], kv[..., 1, :]


def _paged_attn_kernel(
    # scalar-prefetch refs
    pt_ref, ql_ref, kl_ref,
    # tensor refs: q, pages_per_step kv page blocks, output, then scratch
    q_ref, *rest,
    scale: float, page_size: int, block_q: int, group: int, n_kv_heads: int,
    pages_per_step: int,
):
    kv_refs = rest[:pages_per_step]
    o_ref = rest[pages_per_step]
    acc_ref, m_ref, l_ref = rest[pages_per_step + 1:]

    s = pl.program_id(0)
    qb = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_len = ql_ref[s]
    kv_len = kl_ref[s]
    # absolute position of this query block's first token
    q0 = kv_len - q_len + qb * block_q
    rows = block_q * group
    live_q = qb * block_q < q_len

    # unrolled over the step's pages: each logical page gets its own guarded
    # online-softmax update, so one grid step drains ``pages_per_step``
    # already-prefetched page DMAs (the autotuner picks the step width)
    for t in range(pages_per_step):
        kv_ref = kv_refs[t]
        start = (ki * pages_per_step + t) * page_size

        # skip pages past the context and pages wholly after the block's
        # last query (causal)
        @pl.when(live_q & (start < kv_len) & (start <= q0 + block_q - 1))
        def _accumulate(kv_ref=kv_ref, start=start):
            # row r of a head holds query r // group, so the causal test
            # kpos <= q0 + r // group is r >= group * (kpos - q0)
            kpos = start + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            r = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0)
            mask = (r >= group * (kpos - q0)) & (kpos < kv_len)
            for h in range(n_kv_heads):
                q = q_ref[0, 0, h].astype(jnp.float32)             # (rows, hd)
                k = kv_ref[0, :, 2 * h, :].astype(jnp.float32)     # (ps, hd)
                v = kv_ref[0, :, 2 * h + 1, :].astype(jnp.float32)
                logits = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ()))) * scale        # (rows, ps)
                logits = jnp.where(mask, logits, NEG_INF)
                m_prev = m_ref[h]                                  # (rows, 1)
                m_new = jnp.maximum(
                    m_prev, jnp.max(logits, axis=-1, keepdims=True))
                p = jnp.exp(logits - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + p @ v
                m_ref[h] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_attention_blocked(
    q, kv_pages, page_table, q_lens, kv_lens, *,
    scale: float | None = None,
    pages_per_step: int = 1,
    block_q: int = 128,
    interpret: bool = False,
):
    """Ragged paged attention over per-sequence-blocked queries.

    ``q``: (S, q_max, H, hd) — row ``s`` holds ``q_lens[s]`` real tokens
    (left-aligned; the tail is padding whose output is garbage and must be
    discarded by the caller).  ``kv_pages``: (P, page_size, 2*Kv, hd) in the
    interleaved [K0,V0,..] layout.  ``page_table``: (S, max_pages) int32 —
    logical page ``j`` of row ``s`` lives in physical page
    ``page_table[s, j]`` (entries past the row's page count may be any valid
    physical index; they are skipped).  ``kv_lens[s]`` counts the row's
    total context *including* its own q tokens, which must already be
    written into the pool.  Returns (S, q_max, H, hd).

    The GQA grouping happens outside the kernel: q is laid out per KV head
    as ``(S, q_blocks, Kv, block_q * G, hd)``, so inside the kernel every
    head is one 2-D ``(block_q * G, hd) x (page_size, hd)`` dot against its
    K and V rows of the page.  The query axis is blocked in the grid, and
    ``block_q`` shrinks until a grid step fits the VMEM budget
    (:func:`repro.kernels.tiling.paged_vmem_bytes`), so prefill chunks of
    any width keep VMEM bounded.

    ``pages_per_step`` widens the inner grid step: the kernel takes that
    many page-table-indexed KV operands per step (each its own prefetched
    DMA block) and drains them in an unrolled guarded loop — more page
    copies in flight per grid step, less grid overhead per page.  The
    autotuner searches this width.
    """
    S, q_max, H, hd = q.shape
    P, page_size, two_kv, _ = kv_pages.shape
    Kv = two_kv // 2
    if H % Kv:
        raise ValueError(f"{H} query heads do not group over {Kv} KV heads")
    G = H // Kv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    max_pages = page_table.shape[1]
    pages_per_step = max(1, min(int(pages_per_step), max_pages))
    n_steps = -(-max_pages // pages_per_step)  # ceil
    itemsize = jnp.dtype(q.dtype).itemsize
    # the q rows are the whole block axis, so any divisor of q_max is legal
    bq = legal_block(
        q_max, block_q, align=1,
        fits=lambda b: paged_vmem_bytes(
            b * G, Kv, hd, page_size, pages_per_step, itemsize
        ) <= VMEM_BUDGET,
    )
    nqb = q_max // bq
    rows = bq * G

    # inactive page-table entries may be uninitialized: clamp so the
    # prefetched index map always names a physical page
    page_table = jnp.clip(page_table.astype(jnp.int32), 0, P - 1)
    q_lens = q_lens.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)

    # (S, q_max, H, hd) -> (S, nqb, Kv, bq * G, hd); query head kv * G + g
    # at block position i sits in row i * G + g of KV head kv
    qg = q.reshape(S, nqb, bq, Kv, G, hd).transpose(0, 1, 3, 2, 4, 5)
    qg = qg.reshape(S, nqb, Kv, rows, hd)

    kernel = functools.partial(
        _paged_attn_kernel,
        scale=float(scale), page_size=page_size, block_q=bq, group=G,
        n_kv_heads=Kv, pages_per_step=pages_per_step,
    )

    def _kv_spec(t):
        # logical page of sub-step t; clamped past max_pages (the tail of a
        # non-dividing step width) — those reads are skipped in the kernel
        return pl.BlockSpec(
            (1, page_size, two_kv, hd),
            lambda s, qb, ki, pt, ql, kl, t=t: (
                pt[s, jnp.minimum(ki * pages_per_step + t, max_pages - 1)],
                0, 0, 0,
            ),
        )

    q_spec = pl.BlockSpec(
        (1, 1, Kv, rows, hd), lambda s, qb, ki, pt, ql, kl: (s, qb, 0, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, nqb, n_steps),
        in_specs=[q_spec, *[_kv_spec(t) for t in range(pages_per_step)]],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Kv, rows, hd), jnp.float32),
            pltpu.VMEM((Kv, rows, 1), jnp.float32),
            pltpu.VMEM((Kv, rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(page_table, q_lens, kv_lens, qg, *([kv_pages] * pages_per_step))
    out = out.reshape(S, nqb, Kv, bq, G, hd).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(S, q_max, H, hd)
