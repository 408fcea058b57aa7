"""Chunked SwiGLU FFN Pallas kernel.

The d_ff intermediate ((S, f) gate/up activations) is the second-largest
activation in a transformer block after attention logits — the paper's Fig. 4
shows exactly this two-peak profile.  This kernel tiles the intermediate over
(sequence block x d_ff block) so only a (bs, bf) tile of the gate/up
activations ever exists in VMEM, accumulating partial down-projections into a
VMEM scratch across the f-blocks.

Grid: (s_blocks, f_blocks) — f innermost, accumulator carried in scratch.
The d_ff block is the lane dim of the gate/up weight blocks, so it is a
multiple of 128 (or the whole d_ff), and it shrinks until the step fits the
VMEM budget (:func:`repro.kernels.tiling.ffn_vmem_bytes`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import LANE, VMEM_BUDGET, ffn_vmem_bytes, legal_block


def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref):
    fi = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)        # (bs, d)
    g = x @ wg_ref[...].astype(jnp.float32)   # (bs, bf)
    u = x @ wu_ref[...].astype(jnp.float32)
    h = (g * jax.nn.sigmoid(g)) * u           # silu(g) * u
    acc_ref[...] += h @ wd_ref[...].astype(jnp.float32)  # (bs, d)

    @pl.when(fi == nf - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def ffn_blocks(S: int, d: int, f: int, itemsize: int,
               block_s: int = 128, block_f: int = 512):
    """Legal ``(bs, bf)`` tiles at most the wanted ones that fit VMEM."""
    bs = legal_block(S, block_s)
    bf = legal_block(
        f, block_f, align=LANE,
        fits=lambda b: ffn_vmem_bytes(bs, b, d, itemsize) <= VMEM_BUDGET,
    )
    return bs, bf


def chunked_ffn(
    x, w_gate, w_up, w_down, *,
    block_s: int = 128,
    block_f: int = 512,
    interpret: bool = False,
):
    """x: (S, d); w_gate/w_up: (d, f); w_down: (f, d) -> (S, d)."""
    S, d = x.shape
    f = w_gate.shape[1]
    bs, bf = ffn_blocks(S, d, f, jnp.dtype(w_gate.dtype).itemsize,
                        block_s, block_f)
    grid = (S // bs, f // bf)
    return pl.pallas_call(
        _ffn_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, d), lambda si, fi: (si, 0)),
            pl.BlockSpec((d, bf), lambda si, fi: (0, fi)),
            pl.BlockSpec((d, bf), lambda si, fi: (0, fi)),
            pl.BlockSpec((bf, d), lambda si, fi: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((bs, d), lambda si, fi: (si, 0)),
        out_shape=jax.ShapeDtypeStruct((S, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs, d), jnp.float32)],
        interpret=interpret,
        name="chunked_ffn",
    )(x, w_gate, w_up, w_down)
