"""Public jit'd wrappers for the Pallas kernels.

On a CPU the kernels run in interpret mode (``interpret=True`` executes
the kernel body in Python for correctness); on TPU the same call compiles
to Mosaic.  ``INTERPRET`` follows the backend, resolved on first use (not
at import, so importing this module does not initialize a backend), and
the ``AUTOCHUNK_PALLAS_INTERPRET`` env var overrides the detection ("1"
forces interpret mode — the CPU test suites set this; "0" forces compiled
Mosaic).  A chip run checks that ``INTERPRET`` is false: a kernel in
interpret mode on a TPU is a fault, not a fallback.
GQA inputs are expanded to full heads before the attention kernel (the
kernel itself is head-uniform).
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from .chunked_attention import chunked_attention as _attn
from .chunked_attention import computed_attention as _computed_attn
from .chunked_attention import masked_attention as _masked_attn
from .chunked_ffn import chunked_ffn as _ffn
from .paged_attention import paged_attention_blocked as _paged_attn
from .rglru_scan import rglru_scan as _rglru
from .ssd_scan import ssd_scan as _ssd
from .tiling import legal_block


_INTERPRET_RESOLVED: "bool | None" = None


def _resolve_interpret() -> bool:
    """Read the env override, then fall back to backend detection."""
    env = os.environ.get("AUTOCHUNK_PALLAS_INTERPRET", "")
    if env in ("1", "true"):
        return True
    if env in ("0", "false"):
        return False
    return jax.default_backend() != "tpu"


def interpret_default() -> bool:
    """Resolve interpret mode, memoized for the process lifetime.

    The env var is read (and the backend probed) exactly once — dispatch
    paths can call this freely without a per-call ``os.environ`` read.
    Tests that need a different mode use :func:`set_interpret` explicitly
    instead of mutating the environment mid-process.
    """
    global _INTERPRET_RESOLVED
    if _INTERPRET_RESOLVED is None:
        _INTERPRET_RESOLVED = _resolve_interpret()
    return _INTERPRET_RESOLVED


def set_interpret(value: "bool | None") -> bool:
    """Explicit override for tests: True/False forces the mode, None drops
    back to lazy env/backend resolution.  Returns the now-active mode.
    Call it before the first use of a kernel wrapper — already-traced jit
    entries keep the mode they were traced with."""
    global _INTERPRET_RESOLVED
    _INTERPRET_RESOLVED = value
    return interpret_default()


def __getattr__(name):
    # ``ops.INTERPRET``: the resolved mode, computed on first access
    if name == "INTERPRET":
        return interpret_default()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _stream_block(size: int, block: int, buffer_depth: int) -> int:
    """Legal block for the *streamed* axis at a given DMA buffer depth.

    Pallas double-buffers every streamed input block by construction; depth 4
    ("quad buffering", sglang-jax's ``test_quad_buffering`` trick) is realized
    by halving the streamed block so twice as many half-size blocks are in
    flight — same VMEM high-water mark, finer DMA granularity, more
    compute/copy overlap on shapes where the copy dominates.
    """
    if buffer_depth >= 4:
        block = max(block // 2, 1)
    return legal_block(size, block)


def _expand_gqa(k, H):
    Kv = k.shape[2]
    if Kv == H:
        return k
    return jnp.repeat(k, H // Kv, axis=2)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_kv",
                                   "buffer_depth"))
def attention(q, k, v, *, causal=True, window=None, block_q=128, block_kv=128,
              buffer_depth=2):
    """GQA-aware fused attention.  q: (B,Sq,H,hd); k,v: (B,Skv,Kv,hd)."""
    H = q.shape[2]
    k = _expand_gqa(k, H)
    v = _expand_gqa(v, H)
    bq = legal_block(q.shape[1], block_q)
    bkv = _stream_block(k.shape[1], block_kv, buffer_depth)
    return _attn(
        q, k, v, causal=causal, window=window,
        block_q=bq, block_kv=bkv, interpret=interpret_default(),
    )


@partial(jax.jit, static_argnames=("block_s", "block_f", "buffer_depth"))
def swiglu_ffn(x, w_gate, w_up, w_down, *, block_s=128, block_f=512,
               buffer_depth=2):
    # chunked_ffn legalizes the tiles and shrinks the d_ff block to fit VMEM
    if buffer_depth >= 4:
        block_f = max(block_f // 2, 1)
    return _ffn(x, w_gate, w_up, w_down, block_s=block_s, block_f=block_f,
                interpret=interpret_default())


@partial(jax.jit, static_argnames=("scale", "block_q", "block_kv",
                                   "buffer_depth"))
def masked_attention(q, k, v, mask, *, scale, block_q=128, block_kv=128,
                     buffer_depth=2):
    """Flat masked fused attention — the arbitrary-mask dispatch target.

    ``q``: (N, Sq, hd); ``k``/``v``: (N, Skv, hd); ``mask``: (Nm, Sq, Skv)
    boolean, Nm in {1, N}.  Block sizes round to legal divisors of the
    (possibly odd, chunk-loop-sized) sequence extents.
    """
    bq = legal_block(q.shape[1], block_q)
    bkv = _stream_block(k.shape[1], block_kv, buffer_depth)
    return _masked_attn(
        q, k, v, mask, scale=scale,
        block_q=bq, block_kv=bkv, interpret=interpret_default(),
    )


@partial(jax.jit, static_argnames=("scale", "causal", "window", "block_q",
                                   "block_kv", "buffer_depth"))
def computed_attention(q, k, v, q_offset=None, *, scale, causal=True,
                       window=None, block_q=128, block_kv=128,
                       buffer_depth=2):
    """Flat fused attention, mask computed from positions — the preferred
    dispatch target for causal / sliding-window sites.

    ``q``: (N, Sq, hd); ``k``/``v``: (N, Skv, hd).  No mask array exists at
    any level (the predicate lives in the kernel), and fully-masked kv
    blocks are skipped.  ``q_offset`` — kv-coordinate of q row 0 — may be a
    traced scalar (the chunk-loop start), so one trace serves every chunk.
    """
    bq = legal_block(q.shape[1], block_q)
    bkv = _stream_block(k.shape[1], block_kv, buffer_depth)
    return _computed_attn(
        q, k, v, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=bq, block_kv=bkv, interpret=interpret_default(),
    )


@partial(jax.jit, static_argnames=("scale", "q_max", "pages_per_step"))
def paged_attention(q, kv_pages, page_table, cu_q_lens, cu_kv_lens, *,
                    scale=None, q_max=None, pages_per_step=1):
    """Ragged paged flash attention — the paged serving path's core op.

    ``q``: (T, H, hd) — every sequence's new query tokens concatenated
    (decode rows contribute 1 token, prefill rows a planner-sized chunk);
    ``kv_pages``: (P, page_size, 2*Kv, hd) pool in the fused
    head-interleaved [K0,V0,K1,V1,..] layout; ``page_table``:
    (S, max_pages) int32; ``cu_q_lens``/``cu_kv_lens``: (S+1,) cumulative
    ragged descriptors (kv lens count context *including* the new q tokens,
    already written into the pool).  Causal within each sequence.  Returns
    (T, H, hd).

    ``q_max`` (static) bounds the longest per-sequence q run; it defaults
    to T (always safe).  The wrapper blocks the ragged batch per sequence,
    runs the page-table-indexed kernel, and re-flattens.
    """
    T, H, hd = q.shape
    S = cu_q_lens.shape[0] - 1
    if q_max is None:
        q_max = T
    q_lens = jnp.diff(cu_q_lens.astype(jnp.int32))
    kv_lens = jnp.diff(cu_kv_lens.astype(jnp.int32))
    # ragged-flat -> per-sequence blocks (q padding only; KV stays paged)
    idx = cu_q_lens[:-1, None].astype(jnp.int32) + jnp.arange(q_max)[None, :]
    valid = jnp.arange(q_max)[None, :] < q_lens[:, None]
    qb = jnp.take(q, jnp.clip(idx, 0, T - 1), axis=0)        # (S, q_max, H, hd)
    out_b = _paged_attn(
        qb, kv_pages, page_table, q_lens, kv_lens,
        scale=scale, pages_per_step=pages_per_step, interpret=interpret_default(),
    )
    # scatter back to the flat layout; padded rows land in a dump slot
    flat_idx = jnp.where(valid, idx, T).reshape(-1)
    out = jnp.zeros((T + 1, H, hd), q.dtype).at[flat_idx].set(
        out_b.reshape(S * q_max, H, hd)
    )
    return out[:T]


@partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A, B, C, *, chunk=128):
    s = x.shape[1]
    q = min(chunk, s)
    while s % q:
        q //= 2
    return _ssd(x, dt, A, B, C, chunk=max(q, 1), interpret=interpret_default())


@partial(jax.jit, static_argnames=("chunk",))
def rglru(a, b, *, chunk=256):
    s = a.shape[1]
    q = min(chunk, s)
    while s % q:
        q //= 2
    return _rglru(a, b, chunk=max(q, 1), interpret=interpret_default())
