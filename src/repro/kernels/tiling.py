"""Shared tile-legality and VMEM rules for the Pallas kernels and the autotuner.

TPU tiling constraints (Mosaic): the last two dims of every block must be
divisible by 8 (sublanes) and 128 (lanes) respectively, or equal the whole
axis.  Pallas additionally requires a block to divide the axis it tiles
(the grid is ``size // block`` with per-block index maps; a non-dividing
block would read out of bounds).

Every kernel also has to fit its blocks, their DMA double buffers and its
scratch into the scoped VMEM limit (16 MiB by default on v5e).  The VMEM
models below are the one estimate of that footprint: the kernels' default
tile choice and the autotuner's candidate filter both read them, so a
default tile is never one the tuner would reject, and vice versa.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

# f32 sublane count — the alignment unit for second-to-last block dims.
SUBLANE = 8
# lane count — the alignment unit for last block dims.
LANE = 128

# Mosaic leaves headroom for its own spills; tiles are planned into the
# first three quarters of the 16 MiB scoped VMEM limit.
VMEM_BUDGET = int(16 * 1024 * 1024 * 0.75)


def is_legal_block(total: int, block: int, *, align: int = SUBLANE) -> bool:
    """True when ``block`` legally tiles an axis of extent ``total``.

    Legal means: divides ``total`` (Pallas grid requirement) AND is either
    ``align``-aligned or the whole axis.
    """
    if not 0 < block <= total:
        return False
    if total % block:
        return False
    return block % align == 0 or block == total


def legal_block(
    total: int,
    want: int,
    *,
    align: int = SUBLANE,
    fits: Optional[Callable[[int], bool]] = None,
) -> int:
    """Largest legal block <= ``want`` for an axis of extent ``total``.

    Returns the largest ``align``-aligned divisor of ``total`` that is at
    most ``want`` (and, with ``fits``, for which ``fits(block)`` holds).
    When there is none — odd extents, tiny axes — the block is the whole
    axis, which Mosaic always accepts.  A smaller unaligned divisor is
    never returned: Mosaic refuses it.
    """
    total = int(total)
    want = max(1, min(int(want), total))
    for b in range(want - want % align, 0, -align):
        if total % b == 0 and (fits is None or fits(b)):
            return b
    return total


def legal_candidates(
    total: int, grid: Sequence[int], *, align: int = SUBLANE
) -> List[int]:
    """Distinct legal blocks nearest each requested grid point, ascending.

    This is the autotuner's legality filter: the same rounding the manual
    kernel paths apply, so every candidate the tuner times is a block the
    kernel would actually accept.
    """
    out: List[int] = []
    for want in grid:
        b = legal_block(total, want, align=align)
        if b not in out:
            out.append(b)
    return sorted(out)


def ffn_vmem_bytes(bs: int, bf: int, d: int, itemsize: int) -> int:
    """Scoped VMEM of one ``chunked_ffn`` grid step.

    Double-buffered x and output blocks ``(bs, d)``, double-buffered gate,
    up and down weight blocks ``(d, bf)``, the f32 accumulator ``(bs, d)``
    and the f32 gate/up tiles ``(bs, bf)``.
    """
    io = 2 * (2 * bs * d + 3 * d * bf) * itemsize
    return io + 4 * (bs * d + 2 * bs * bf)


def paged_vmem_bytes(rows: int, n_kv: int, hd: int, page_size: int,
                     pages_per_step: int, itemsize: int) -> int:
    """Scoped VMEM of one ``paged_attention_blocked`` grid step.

    ``rows`` is query positions per block times the GQA group.  Counts the
    double-buffered query and output blocks ``(n_kv, rows, hd)``, the f32
    accumulator, the lane-padded f32 running max and sum, the
    double-buffered page blocks, and one head's f32 logits tile.
    """
    qo = 2 * 2 * n_kv * rows * hd * itemsize
    acc = 4 * n_kv * rows * hd
    ml = 2 * 4 * n_kv * rows * LANE
    pages = 2 * pages_per_step * page_size * 2 * n_kv * hd * itemsize
    tile = 4 * rows * (max(page_size, LANE) + 2 * hd)
    return qo + acc + ml + pages + tile
