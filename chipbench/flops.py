"""Operations and bytes the algorithm needs, computed from shapes.

These count the work a computation requires, never what one kernel
happens to execute: causal attention counts only the (query, key) pairs
the mask keeps, and Q, K, V and O are each read or written once at their
GQA sizes.  A kernel that skips masked blocks, or reads K/V once per KV
head, therefore gains honestly, and every implementation is held to the
same count.

``attended_pairs`` and ``attention`` serve every architecture.  The rest
are the dense GQA decoder's counts, which ``references/dense.py`` gives
the readers; another architecture's module gives its own.  ``sizes`` is
a configuration file's ``sizes`` mapping (see ``configs/*.json``):
n_layers, d_model, n_heads, n_kv_heads, head_dim, d_ff, vocab_size, act.
"""
from __future__ import annotations

BF16 = 2


def attended_pairs(q_len: int, kv_len: int, causal: bool = True) -> int:
    """(query, key) pairs of ``q_len`` queries at the last positions of a
    ``kv_len`` context.  Causal: query ``j`` sees ``kv_len - q_len + j + 1``
    keys."""
    if not causal:
        return q_len * kv_len
    return q_len * (kv_len - q_len) + q_len * (q_len + 1) // 2


def attention(*, n_heads: int, n_kv_heads: int, head_dim: int, q_len: int,
              kv_len: int, causal: bool = True, itemsize: int = BF16):
    """(flops, bytes) of one attention call over all heads.

    FLOPs: ``Q K^T`` and ``P V`` each take 2 * head_dim per pair and head.
    Bytes: Q and O at ``n_heads``, K and V at ``n_kv_heads``, once each.
    """
    pairs = attended_pairs(q_len, kv_len, causal)
    flops = 4 * n_heads * head_dim * pairs
    nbytes = itemsize * head_dim * (2 * n_heads * q_len
                                    + 2 * n_kv_heads * kv_len)
    return flops, nbytes


def paged_step(sizes: dict, rows, itemsize: int = BF16):
    """(flops, bytes) of the paged kernel over one step of every layer.

    ``rows``: (q_len, kv_len) of each scheduled sequence, kv_len counting
    this step's tokens.
    """
    flops = nbytes = 0
    for q_len, kv_len in rows:
        f, b = attention(n_heads=sizes["n_heads"],
                         n_kv_heads=sizes["n_kv_heads"],
                         head_dim=sizes["head_dim"], q_len=q_len,
                         kv_len=kv_len, itemsize=itemsize)
        flops += f
        nbytes += b
    return flops * sizes["n_layers"], nbytes * sizes["n_layers"]


def matmul_params(sizes: dict) -> int:
    """Weights of the layer stack that multiply every token (no embedding,
    no unembedding, no norms)."""
    d, hd = sizes["d_model"], sizes["head_dim"]
    attn = d * hd * (2 * sizes["n_heads"] + 2 * sizes["n_kv_heads"])
    gated = sizes["act"] in ("swiglu", "geglu")
    mlp = d * sizes["d_ff"] * (3 if gated else 2)
    return sizes["n_layers"] * (attn + mlp)


def unembed_flops(sizes: dict) -> int:
    return 2 * sizes["d_model"] * sizes["vocab_size"]


def tokens_flops(sizes: dict, q_len: int, kv_len: int, *,
                 unembedded: int, causal: bool = True) -> int:
    """Model FLOPs of ``q_len`` new tokens at the end of a ``kv_len``
    context: the matmuls of every layer, attention at each token's
    position, and the unembedding of ``unembedded`` positions."""
    attn, _ = attention(n_heads=sizes["n_heads"],
                        n_kv_heads=sizes["n_kv_heads"],
                        head_dim=sizes["head_dim"], q_len=q_len,
                        kv_len=kv_len, causal=causal)
    return (2 * matmul_params(sizes) * q_len + sizes["n_layers"] * attn
            + unembedded * unembed_flops(sizes))


def prefill(sizes: dict, seq_len: int, causal: bool = True) -> int:
    """Model FLOPs of one prefill of ``seq_len`` tokens that unembeds only
    the last position."""
    return tokens_flops(sizes, seq_len, seq_len, unembedded=1,
                        causal=causal)


def prefill_attention(sizes: dict, seq_len: int):
    """(flops, bytes) of causal self-attention over one prompt, all layers."""
    f, b = attention(n_heads=sizes["n_heads"], n_kv_heads=sizes["n_kv_heads"],
                     head_dim=sizes["head_dim"], q_len=seq_len,
                     kv_len=seq_len)
    return f * sizes["n_layers"], b * sizes["n_layers"]
