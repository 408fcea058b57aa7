"""Pallas kernel dispatch: swap chunk-loop bodies for fused kernels.

The paper's Fig. 6 shows graph-level chunking *composing* with fused
kernels rather than competing with them.  This pass realizes that on the
lowering backend: after :func:`~repro.core.lowering.apply_chunk` has spliced
a region into a structured ``chunk_loop`` node, the node's body equations
are pattern-matched against two shapes the fused Pallas kernels in
``repro.kernels.ops`` implement —

* **softmax attention** — ``dot_general -> (scale/mask/transpose) ->
  softmax -> dot_general``, any operand order / GQA grouping / batch
  layout, with an arbitrary boolean mask (causal, sliding-window,
  padding...).  When the mask resolves to a *constant band* — causal and
  sliding-window masks are affine position algebra, read symbolically from
  the mask's producing chain (``_symbolic_band``) —
  the site dispatches onto :func:`repro.kernels.ops.computed_attention`:
  the predicate is recomputed from block indices inside the kernel, no
  ``(Sq, Skv)`` mask array exists at any level, and fully-masked kv blocks
  are skipped via ``pl.when``.  Arbitrary masks keep
  :func:`repro.kernels.ops.masked_attention`, which streams the mask
  through VMEM blocks alongside K/V.
* **SwiGLU FFN** — ``dot -> split -> silu -> mul -> dot`` (fused ``w_in``)
  or ``dot/dot -> silu -> mul -> dot`` (separate gate/up weights).
  Dispatched onto :func:`repro.kernels.ops.swiglu_ffn`: the ``(c, d_ff)``
  gate/up activations exist only as VMEM tiles.

A match replaces the interior equations with one
:class:`~repro.core.lowering.KernelDispatch` record (the scan loop itself
stays — graph-level chunking and kernel-level tiling compose); non-matching
bodies keep the generic scan codegen.  ``annotate_candidates`` runs the
same matcher during chunk *selection* so kernelizable candidates charge the
VMEM-tile body peak instead of the full chunk-slice peak — and
computed-mask candidates stop charging mask bytes entirely.

``dispatch_graph`` also hosts the autotune hook: with ``autotune=True`` it
collects the matched kernel sites' shapes and runs
:func:`repro.kernels.autotune.tune_sites` once, then threads the winning
tile sizes / DMA buffer depth into every builder.  The caller persists the
returned :class:`~repro.kernels.autotune.KernelTuning` in the plan (schema
v4) so warm replays skip the pass (``autotune_passes == 0``).

Counters: ``kernel_dispatch_hits`` / ``kernel_dispatch_misses`` /
``kernel_dispatch_errors`` / ``kernel_dispatch_computed_mask`` in
``core.stats`` make dispatch coverage observable in serve logs; an error is
a matcher or validation that raised, whose body fell back to scan codegen.
``kernel_dispatch_band.symbolic`` / ``.evaluated`` say which classifier
found each computed-mask dispatch's band.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from . import stats
from .graph import Graph, Var, is_var
from .lowering import (
    LOOP_INDEX,
    ChunkLoopEqn,
    KernelDispatch,
    is_chunk_loop,
    refresh_node,
    validate_body,
)
from .search import ChunkCandidate

_PASS = ("convert_element_type", "stop_gradient")

# Default VMEM block caps of the dispatch targets (see kernels.ops): the
# dispatch-aware cost model charges these tiles instead of chunk slices.
# Autotuning may shrink/grow the runtime blocks; the selection-time model
# keeps the defaults (selection happens before tuning runs).
_BLOCK = 128
_BLOCK_F = 512

# tuning kwargs each ops wrapper accepts (KernelTuning.kernel_kwargs keys)
_ATTN_TILE = ("block_q", "block_kv", "buffer_depth")
_FFN_TILE = ("block_s", "block_f", "buffer_depth")


@dataclass
class _BodyCtx:
    """A loop body viewed as a mini-graph (candidate or chunk_loop node)."""

    eqns: List[Any]
    producer: Dict[Var, int] = field(default_factory=dict)
    consumers: Dict[Var, List[int]] = field(default_factory=dict)
    escapes: Set[Var] = field(default_factory=set)
    var_dim: Dict[Var, int] = field(default_factory=dict)
    # producers of vars defined OUTSIDE the body (prefix/hoisted equations):
    # followed read-only, e.g. to resolve a hoisted -1e30 mask constant
    outer: Dict[Var, Any] = field(default_factory=dict)
    # the graph's const bindings: masks built from concrete positions
    # (jnp.arange/tril comparisons) constant-fold at trace time and land
    # here — the computed-mask classifier reads them directly
    consts: Dict[Var, Any] = field(default_factory=dict)

    def __post_init__(self):
        for i, eqn in enumerate(self.eqns):
            for ov in eqn.outvars:
                if is_var(ov):
                    self.producer[ov] = i
            for iv in eqn.invars:
                if is_var(iv):
                    self.consumers.setdefault(iv, []).append(i)


def _outer_producers(g: Optional[Graph]) -> Dict[Var, Any]:
    if g is None:
        return {}
    out: Dict[Var, Any] = {}
    for eqn in g.eqns:
        for ov in eqn.outvars:
            if is_var(ov):
                out[ov] = eqn
    return out


def _ctx_from_node(
    node: ChunkLoopEqn, g: Optional[Graph] = None, outer=None
) -> _BodyCtx:
    return _BodyCtx(
        eqns=list(node.params["body"]),
        escapes=set(node.outvars),
        var_dim=dict(node.params["var_dim"]),
        outer=_outer_producers(g) if outer is None else outer,
        consts=g.consts if g is not None else {},
    )


def _ctx_from_candidate(g: Graph, cand: ChunkCandidate, outer=None) -> _BodyCtx:
    eqns = [g.eqns[i] for i in cand.in_loop]
    region = set(cand.in_loop)
    escapes: Set[Var] = set(cand.loop_out)
    for i in cand.in_loop:
        for ov in g.eqns[i].outvars:
            if not is_var(ov):
                continue
            if any(c not in region for c in g.consumers.get(ov, [])):
                escapes.add(ov)
    return _BodyCtx(
        eqns=eqns, escapes=escapes, var_dim=dict(cand.var_dim),
        outer=_outer_producers(g) if outer is None else outer,
        consts=g.consts,
    )


@dataclass
class Match:
    """One recognized fused-kernel site inside a loop body."""

    kind: str
    interior: Set[int]          # body positions the kernel replaces
    at: int                     # body position of the root eqn
    root: Var
    reads: Tuple[Var, ...]
    builder: Any                # fn(env, kw) -> value for root
    tile_bytes: int
    # site shapes for the autotuner + bookkeeping (mask variant, which
    # shape fields are chunk-scaled)
    meta: Dict[str, Any] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def _scalar_lit(atom) -> Optional[float]:
    if is_var(atom):
        return None
    val = getattr(atom, "val", None)
    if val is None:
        return None
    if getattr(val, "shape", ()) not in ((), (1,)):
        return None
    try:
        return float(val)
    except (TypeError, ValueError):
        return None


def _producer_eqn(ctx: _BodyCtx, atom):
    if not is_var(atom):
        return None, None
    i = ctx.producer.get(atom)
    if i is None:
        return None, None
    return i, ctx.eqns[i]


def _is_neg_const(ctx: _BodyCtx, atom) -> bool:
    """True when atom is (a broadcast of) a scalar <= -1e15.

    The scalar's broadcast/convert chain may have been hoisted out of the
    loop, so producers outside the body are followed too (read-only).
    """
    for _ in range(6):
        _, e = _producer_eqn(ctx, atom)
        if e is None and is_var(atom):
            e = ctx.outer.get(atom)
        if e is not None and e.primitive.name in (
            "broadcast_in_dim", "convert_element_type",
        ):
            atom = e.invars[0]
            continue
        break
    v = _scalar_lit(atom)
    return v is not None and v <= -1e15


# prims whose full-shape value is row/col-consistent with the chunked
# runtime value (elementwise + structural ops that never *generate*
# positions — an in-body iota would count 0..c-1 per chunk while the
# full-shape eval counts 0..S-1, so position generators are only trusted
# from OUTER producers, whose params are never chunk-adjusted)
_MASK_EVAL_ANY = frozenset({
    "broadcast_in_dim", "convert_element_type", "stop_gradient",
    "transpose", "not", "and", "or", "xor",
    "le", "lt", "ge", "gt", "eq", "ne",
    "add", "sub", "mul", "min", "max",
})
_MASK_EVAL_OUTER = _MASK_EVAL_ANY | {"iota", "reshape"}
_MASK_EVAL_LIMIT = 1 << 26  # elements per intermediate (64M = 8192^2)

_NP_BINOPS = {
    "le": np.less_equal, "lt": np.less, "ge": np.greater_equal,
    "gt": np.greater, "eq": np.equal, "ne": np.not_equal,
    "add": np.add, "sub": np.subtract, "mul": np.multiply,
    "min": np.minimum, "max": np.maximum,
    "and": np.logical_and, "or": np.logical_or, "xor": np.logical_xor,
}


def _concrete_mask_value(
    ctx: _BodyCtx, v, depth: int = 0, why: Optional[List[str]] = None
) -> Optional[np.ndarray]:
    """Concrete FULL-shape value behind a mask var, or None.

    The fallback of the computed-mask classification: it runs only where
    :func:`_symbolic_band` has no answer (a mask that is position algebra
    but not a half-plane conjunction on ``j - a``: holes, ``or``, ``ne``,
    ``min``/``max``...), because its cost and memory grow with Sq*Skv.

    Position masks are built from ``jnp.arange`` comparisons: the arange is
    an ``iota`` equation in the graph (usually outside the loop, its output
    sliced in) and the comparison chain sits in the body.  This evaluates
    that chain with numpy at the vars' *aval* shapes — avals are never
    chunk-adjusted, so the result is the full (Sq, Skv) mask even when the
    body's eqn params were shrunk to chunk size.  Anything outside the
    whitelisted position algebra (gathers, data-dependent masks...) returns
    None and keeps the boolean-mask kernel.  An intermediate larger than
    ``_MASK_EVAL_LIMIT`` elements also returns None, and appends
    ``"mask_eval_limit"`` to ``why`` when a list is given.
    """
    if not is_var(v):
        val = getattr(v, "val", None)
        return np.asarray(val) if val is not None else None
    if v in ctx.consts:
        return np.asarray(ctx.consts[v])
    if depth > 24:
        return None
    shape = tuple(v.aval.shape)
    if _prod(shape) > _MASK_EVAL_LIMIT:
        if why is not None:
            why.append("mask_eval_limit")
        return None
    i = ctx.producer.get(v)
    if i is not None:
        e, allowed = ctx.eqns[i], _MASK_EVAL_ANY
    else:
        e, allowed = ctx.outer.get(v), _MASK_EVAL_OUTER
    if e is None:
        return None
    nm = e.primitive.name
    if nm not in allowed:
        return None
    if nm == "iota":
        dim = int(e.params["dimension"])
        base = np.arange(shape[dim], dtype=np.dtype(v.aval.dtype))
        base = base.reshape(
            [shape[dim] if d == dim else 1 for d in range(len(shape))]
        )
        return np.broadcast_to(base, shape)
    ins = [_concrete_mask_value(ctx, iv, depth + 1, why) for iv in e.invars]
    if any(x is None for x in ins):
        return None
    if nm == "broadcast_in_dim":
        bd = e.params["broadcast_dimensions"]
        news = [1] * len(shape)
        for j, d in enumerate(bd):
            news[d] = ins[0].shape[j]
        return np.broadcast_to(ins[0].reshape(news), shape)
    if nm == "transpose":
        return np.transpose(ins[0], e.params["permutation"])
    if nm == "reshape":
        return ins[0].reshape(shape)
    if nm in ("convert_element_type", "stop_gradient"):
        return ins[0].astype(np.dtype(v.aval.dtype))
    if nm == "not":
        return np.logical_not(ins[0])
    op = _NP_BINOPS.get(nm)
    if op is None or len(ins) != 2:
        return None
    return op(ins[0], ins[1])


def _band_params(mask: np.ndarray) -> Optional[Tuple[int, int]]:
    """(U, L) such that mask[a, j] == (j - a <= U) and (a - j <= L).

    Exact-reconstruction check: anything that is not a contiguous
    causal/sliding-window band (padding masks, block-sparse patterns)
    returns None and keeps the boolean-mask kernel.  Reads the mask that
    :func:`_concrete_mask_value` materialises, so it runs only where
    :func:`_symbolic_band` has no answer; the two agree wherever both
    answer.
    """
    sq, skv = mask.shape
    counts = mask.sum(axis=1)
    if (counts == 0).any():
        return None
    idx = np.arange(sq)
    first = mask.argmax(axis=1)
    last = skv - 1 - mask[:, ::-1].argmax(axis=1)
    if not (counts == last - first + 1).all():
        return None  # a row with holes is not a band
    u = int((last - idx).max())
    low = int((idx - first).max())
    if not (
        (first == np.maximum(idx - low, 0)).all()
        and (last == np.minimum(idx + u, skv - 1)).all()
    ):
        return None
    return u, low


# ---------------------------------------------------------------------------
# Symbolic band classification
# ---------------------------------------------------------------------------
#
# A causal or sliding-window mask is affine position algebra: each integer
# intermediate of its chain is ``const + sum_d coef[d] * index[d]`` over the
# intermediate's own axes, and each bool intermediate is a conjunction of
# half-planes ``form >= 0``.  Carrying those forms through the producers
# _concrete_mask_value walks classifies the band exactly, in time and memory
# that do not depend on Sq or Skv.


@dataclass(frozen=True)
class _Form:
    """Integer value ``const + sum_d coef[d] * index[d]`` over a var's axes;
    an axis of size 1 carries coefficient 0."""

    coef: Tuple[int, ...]
    const: int

    def span(self, shape) -> Tuple[int, int]:
        """Least and greatest value over the var's index box."""
        lo = hi = self.const
        for c, n in zip(self.coef, shape):
            lo += min(0, c * (n - 1))
            hi += max(0, c * (n - 1))
        return lo, hi


@dataclass(frozen=True)
class _Pred:
    """Bool value: the conjunction of the half-planes ``form >= 0`` (none:
    everywhere true), or everywhere false."""

    halves: Tuple[_Form, ...] = ()
    false: bool = False


def _exact_in(dtype, span: Tuple[int, int]) -> bool:
    """True when every integer in ``span`` is exact in ``dtype``: the
    forms then give the very values the program computes."""
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return int(info.min) <= span[0] and span[1] <= int(info.max)
    if jnp.issubdtype(dtype, jnp.floating):
        lim = 2 ** (int(jnp.finfo(dtype).nmant) + 1)
        return -lim <= span[0] and span[1] <= lim
    return False


def _remap(x, src: Sequence[Optional[int]], shape):
    """``x`` over new axes: new axis o reads old axis ``src[o]`` (None: an
    axis ``x`` does not vary along)."""
    def one(f: _Form) -> _Form:
        coef = tuple(
            0 if s is None or n == 1 else f.coef[s] for s, n in zip(src, shape)
        )
        return _Form(coef, f.const)

    if isinstance(x, _Form):
        return one(x)
    return _Pred(tuple(one(h) for h in x.halves), x.false)


def _conj(halves: Sequence[_Form], shape) -> _Pred:
    """The conjunction of ``halves``, with each half-plane that holds on the
    whole index box dropped, and everywhere false if one holds nowhere."""
    keep = []
    for h in halves:
        lo, hi = h.span(shape)
        if hi < 0:
            return _Pred(false=True)
        if lo < 0:
            keep.append(h)
    return _Pred(tuple(keep))


def _sym_leaf(val):
    """A scalar literal or const as a rank-0 form or predicate, or None."""
    if np.size(val) != 1:  # .size: a large const is never copied
        return None
    arr = np.asarray(val)
    x = arr.reshape(()).item()
    if arr.dtype == np.bool_:
        return _Pred(false=not x)
    if jnp.issubdtype(arr.dtype, jnp.integer) or (
        jnp.issubdtype(arr.dtype, jnp.floating) and float(x).is_integer()
    ):
        return _Form((), int(x))
    return None


def _sym_mask(ctx: _BodyCtx, v, depth: int = 0):
    """:class:`_Form` or :class:`_Pred` of ``v`` at its aval shape, or None
    where the value is not affine position algebra.

    Walks the producers :func:`_concrete_mask_value` walks, under the same
    rule that position generators (``iota``, ``reshape``) are trusted only
    outside the body, and builds no array.
    """
    if not is_var(v):
        val = getattr(v, "val", None)
        return None if val is None else _sym_leaf(val)
    if v in ctx.consts:
        return _sym_leaf(ctx.consts[v])
    if depth > 24:
        return None
    shape = tuple(v.aval.shape)
    dtype = v.aval.dtype
    i = ctx.producer.get(v)
    if i is not None:
        e, allowed = ctx.eqns[i], _MASK_EVAL_ANY
    else:
        e, allowed = ctx.outer.get(v), _MASK_EVAL_OUTER
    if e is None or e.primitive.name not in allowed:
        return None
    nm = e.primitive.name
    if nm == "iota":
        dim = int(e.params["dimension"])
        f = _remap(_Form((1,), 0), [0 if d == dim else None
                                    for d in range(len(shape))], shape)
        return f if _exact_in(dtype, f.span(shape)) else None
    ins = [_sym_mask(ctx, iv, depth + 1) for iv in e.invars]
    if any(x is None for x in ins):
        return None
    if nm == "broadcast_in_dim":
        src: List[Optional[int]] = [None] * len(shape)
        for j, d in enumerate(e.params["broadcast_dimensions"]):
            src[d] = j
        return _remap(ins[0], src, shape)
    if nm == "transpose":
        return _remap(ins[0], list(e.params["permutation"]), shape)
    if nm == "reshape":
        # only a reshape that adds or drops unit dims keeps the axes apart
        in_shape = tuple(e.invars[0].aval.shape)
        ins_nu = [d for d, n in enumerate(in_shape) if n != 1]
        out_nu = [d for d, n in enumerate(shape) if n != 1]
        if e.params.get("dimensions") is not None or (
            [in_shape[d] for d in ins_nu] != [shape[d] for d in out_nu]
        ):
            return None
        src = [None] * len(shape)
        for o, s in zip(out_nu, ins_nu):
            src[o] = s
        return _remap(ins[0], src, shape)
    is_bool = dtype == np.bool_
    if nm in ("convert_element_type", "stop_gradient"):
        x = ins[0]
        if isinstance(x, _Pred) != is_bool:
            return None
        if isinstance(x, _Form) and not _exact_in(dtype, x.span(shape)):
            return None
        return x
    # elementwise: a rank-0 operand (a scalar literal) broadcasts
    lifted = []
    for x in ins:
        if isinstance(x, _Form):
            rank = len(x.coef)
        else:
            rank = len(x.halves[0].coef) if x.halves else 0
        if rank == 0:
            x = _remap(x, [None] * len(shape), shape)
        elif rank != len(shape):
            return None
        lifted.append(x)
    if is_bool:
        if not all(isinstance(x, _Pred) for x in lifted):
            return _sym_compare(nm, lifted, shape)
        if nm == "not":
            (x,) = lifted
            if x.false or not x.halves:
                return _Pred(false=not x.false)
            if len(x.halves) != 1:
                return None
            h = x.halves[0]
            return _conj([_Form(tuple(-c for c in h.coef), -h.const - 1)],
                         shape)
        if nm == "and":
            a, b = lifted
            if a.false or b.false:
                return _Pred(false=True)
            return _conj(a.halves + b.halves, shape)
        return None
    if len(lifted) != 2 or not all(isinstance(x, _Form) for x in lifted):
        return None
    a, b = lifted
    if nm in ("add", "sub"):
        sgn = 1 if nm == "add" else -1
        f = _Form(tuple(p + sgn * q for p, q in zip(a.coef, b.coef)),
                  a.const + sgn * b.const)
    elif nm == "mul" and (not any(a.coef) or not any(b.coef)):
        k, x = (a.const, b) if not any(a.coef) else (b.const, a)
        f = _Form(tuple(k * c for c in x.coef), k * x.const)
    else:
        return None
    return f if _exact_in(dtype, f.span(shape)) else None


def _sym_compare(nm: str, ins, shape) -> Optional[_Pred]:
    """``le``/``lt``/``ge``/``gt`` of two integer forms as a half-plane."""
    if len(ins) != 2 or not all(isinstance(x, _Form) for x in ins):
        return None
    a, b = ins
    if nm in ("le", "lt"):
        a, b = b, a  # x <= y  is  y - x >= 0
    elif nm not in ("ge", "gt"):
        return None
    off = 1 if nm in ("lt", "gt") else 0
    diff = _Form(tuple(p - q for p, q in zip(a.coef, b.coef)),
                 a.const - b.const - off)
    return _conj([diff], shape)


def _symbolic_band(
    ctx: _BodyCtx, m_var, flip: bool, invert: bool, sq: int, skv: int
) -> Optional[Tuple[int, int]]:
    """The ``(U, L)`` :func:`_band_params` returns on the materialised mask,
    read from the mask's position algebra alone; None where the algebra
    gives no answer, and the numpy evaluation then decides.

    Each half-plane of the mask's predicate must read ``k*(j - a) + c >= 0``
    over the q index ``a`` and the kv index ``j``: it bounds ``d = j - a``
    on one side, in integer arithmetic.  Their intersection is an interval
    of ``d``, clipped to the ``d`` the (sq, skv) box holds; ``invert``
    takes its complement where that is one interval.  A row left empty
    returns None, as ``_band_params`` does.
    """
    shape = tuple(m_var.aval.shape)
    if m_var.aval.dtype != np.bool_ or len(shape) != 2:
        return None
    qa, ka = (1, 0) if flip else (0, 1)
    if (shape[qa], shape[ka]) != (sq, skv) or min(sq, skv) < 1:
        return None
    p = _sym_mask(ctx, m_var)
    if not isinstance(p, _Pred):
        return None
    d_lo, d_hi = -(sq - 1), skv - 1
    lo, hi = d_lo, d_hi
    if p.false:
        lo, hi = d_hi, d_lo - 1
    for h in p.halves:
        ca, cj = h.coef[qa], h.coef[ka]
        # a size-1 axis has index 0: its coefficient is free
        if sq == 1:
            ca = -cj
        if skv == 1:
            cj = -ca
        if cj != -ca or cj == 0:
            return None  # depends on a or j alone, or on both unevenly
        if cj > 0:
            lo = max(lo, -(h.const // cj))
        else:
            hi = min(hi, h.const // -cj)
    if invert:
        if lo > hi:
            lo, hi = d_lo, d_hi
        elif lo == d_lo and hi == d_hi:
            lo, hi = d_hi, d_lo - 1
        elif lo == d_lo:
            lo, hi = hi + 1, d_hi
        elif hi == d_hi:
            lo, hi = d_lo, lo - 1
        else:
            return None  # two intervals of d: rows with a hole
    if lo > hi or hi < 0 or lo > skv - sq:
        return None  # some row attends to nothing
    return hi, -lo


def _interior_is_private(ctx: _BodyCtx, interior: Set[int], at: int) -> bool:
    """No interior intermediate may be read outside the match."""
    for i in interior:
        if i == at:
            continue
        for ov in ctx.eqns[i].outvars:
            if not is_var(ov):
                continue
            if ov in ctx.escapes:
                return False
            if any(c not in interior for c in ctx.consumers.get(ov, [])):
                return False
    return True


def _prod(xs) -> int:
    return int(math.prod(xs)) if xs else 1


def _tile_kwargs(kw: Dict[str, Any], keys: Tuple[str, ...]) -> Dict[str, Any]:
    return {k: kw[k] for k in keys if k in kw}


# ---------------------------------------------------------------------------
# Attention matcher
# ---------------------------------------------------------------------------

def _try_attention(
    ctx: _BodyCtx, i_div: int, mask_mode: str = "auto"
) -> Optional[Match]:
    eqns = ctx.eqns
    div = eqns[i_div]
    num, den = div.invars
    if not (is_var(num) and is_var(den)):
        return None
    interior: Set[int] = {i_div}

    # denominator: broadcast(reduce_sum(num, axes=(ax,)))
    i_b, be = _producer_eqn(ctx, den)
    if be is None or be.primitive.name != "broadcast_in_dim":
        return None
    i_rs, rs = _producer_eqn(ctx, be.invars[0])
    if rs is None or rs.primitive.name != "reduce_sum":
        return None
    if rs.invars[0] is not num or len(rs.params["axes"]) != 1:
        return None
    ax = rs.params["axes"][0]
    interior |= {i_b, i_rs}

    # numerator: exp(sub(x, running-max-of-x))
    i_exp, ex = _producer_eqn(ctx, num)
    if ex is None or ex.primitive.name != "exp":
        return None
    i_sub, sb = _producer_eqn(ctx, ex.invars[0])
    if sb is None or sb.primitive.name != "sub":
        return None
    x = sb.invars[0]
    interior |= {i_exp, i_sub}
    cur = sb.invars[1]
    saw_rmax = False
    for _ in range(6):
        i_c, ce = _producer_eqn(ctx, cur)
        if ce is None:
            return None
        nm = ce.primitive.name
        if nm in _PASS or nm == "broadcast_in_dim":
            interior.add(i_c)
            cur = ce.invars[0]
            continue
        if nm == "max":  # jnp.max(..., initial=-inf) companion
            vs = [a for a in ce.invars if is_var(a)]
            lits = [a for a in ce.invars if not is_var(a)]
            if len(vs) != 1 or any(_scalar_lit(a) is None for a in lits):
                return None
            interior.add(i_c)
            cur = vs[0]
            continue
        if nm == "reduce_max":
            if ce.invars[0] is not x or tuple(ce.params["axes"]) != (ax,):
                return None
            interior.add(i_c)
            saw_rmax = True
        break
    if not saw_rmax:
        return None

    # backward from the softmax input to the scores dot_general, collecting
    # scale factors, the mask select, and the dim permutation
    scale = 1.0
    hops: List[Tuple[int, Any]] = []
    mask_var = None
    mask_hop = -1
    mask_invert = False
    cur = x
    dg1 = dg1_i = None
    for _ in range(8):
        i_c, ce = _producer_eqn(ctx, cur)
        if ce is None:
            return None
        nm = ce.primitive.name
        if nm == "dot_general":
            dg1_i, dg1 = i_c, ce
            break
        hops.append((i_c, ce))
        if nm in _PASS or nm == "transpose":
            cur = ce.invars[0]
            continue
        if nm == "mul":
            a, b = ce.invars
            s, nxt = _scalar_lit(b), a
            if s is None:
                s, nxt = _scalar_lit(a), b
            if s is None or s <= 0 or not is_var(nxt):
                return None
            scale *= s
            cur = nxt
            continue
        if nm == "div":  # logits / sqrt(hd): scalar denominator only
            a, b = ce.invars
            s = _scalar_lit(b)
            if s is None or s <= 0 or not is_var(a):
                return None
            scale /= s
            cur = a
            continue
        if nm == "select_n":
            if mask_var is not None or len(ce.invars) != 3:
                return None
            pred, c0, c1 = ce.invars
            if not is_var(pred):
                return None
            # select_n(pred, on_false, on_true): jnp.where(m, x, y) lowers
            # to select_n(m, y, x).  When the -inf constant sits on the
            # TRUE branch the model uses the True-means-MASKED convention
            # (jnp.where(pad, -1e30, scores)) and the kernel mask — whose
            # convention is True-means-attend — must be negated.
            if is_var(c1) and _is_neg_const(ctx, c0):
                cur = c1
                mask_invert = False
            elif is_var(c0) and _is_neg_const(ctx, c1):
                cur = c0
                mask_invert = True
            else:
                return None
            mask_var, mask_hop = pred, len(hops) - 1
            continue
        return None
    if dg1 is None or mask_var is None:
        return None
    interior.add(dg1_i)
    interior.update(i for i, _ in hops)

    # forward dim maps: var coords -> dg1 output coords
    out_rank = len(dg1.outvars[0].aval.shape)
    cmap = list(range(out_rank))
    mask_map = None
    for hop_i, (_, ce) in enumerate(reversed(hops)):
        orig_pos = len(hops) - 1 - hop_i
        if ce.primitive.name == "transpose":
            perm = ce.params["permutation"]
            cmap = [cmap[perm[j]] for j in range(len(perm))]
        if orig_pos == mask_hop:
            mask_map = list(cmap)  # select_n output coords at this point
    if mask_map is None:
        mask_map = list(cmap)
    xmap = list(cmap)  # x (and p) coords -> dg1 out coords

    # classify dg1 dims
    (lc, rc), (lb, rb) = dg1.params["dimension_numbers"]
    if len(lc) != 1 or len(rc) != 1:
        return None
    lhs, rhs = dg1.invars
    if not (is_var(lhs) and is_var(rhs)) or lhs is rhs:
        return None
    nb = len(lb)
    lhs_free = [
        d for d in range(len(lhs.aval.shape)) if d not in lb and d != lc[0]
    ]
    rhs_free = [
        d for d in range(len(rhs.aval.shape)) if d not in rb and d != rc[0]
    ]
    owner: Dict[int, Tuple[str, int]] = {}
    for j, d in enumerate(lhs_free):
        owner[nb + j] = ("l", d)
    for j, d in enumerate(rhs_free):
        owner[nb + len(lhs_free) + j] = ("r", d)
    kv_out = xmap[ax]
    if kv_out not in owner:
        return None
    k_side, k_seq = owner[kv_out]
    k_var, k_batch = (lhs, lb) if k_side == "l" else (rhs, rb)
    k_free = lhs_free if k_side == "l" else rhs_free
    if len(k_free) != 1:
        return None
    q_side = "r" if k_side == "l" else "l"
    q_var, q_batch = (rhs, rb) if k_side == "l" else (lhs, lb)
    q_free = rhs_free if k_side == "l" else lhs_free
    q_contract = rc[0] if q_side == "r" else lc[0]
    dq = ctx.var_dim.get(q_var)
    if dq is None or dq not in q_free:
        return None
    group_dims = [d for d in q_free if d != dq]
    q_out = next(c for c, (s, d) in owner.items() if s == q_side and d == dq)
    group_out = {
        next(c for c, (s, d2) in owner.items() if s == q_side and d2 == d): gi
        for gi, d in enumerate(group_dims)
    }

    # forward from p (the div output) to the output dot_general
    p_var = div.outvars[0]
    cur, pmap = p_var, list(xmap)
    dg2 = dg2_i = None
    for _ in range(4):
        if cur in ctx.escapes:
            return None
        cons = ctx.consumers.get(cur, [])
        if len(cons) != 1:
            return None
        ce = eqns[cons[0]]
        nm = ce.primitive.name
        if nm in _PASS:
            interior.add(cons[0])
            cur = ce.outvars[0]
            continue
        if nm == "transpose":
            perm = ce.params["permutation"]
            pmap = [pmap[perm[j]] for j in range(len(perm))]
            interior.add(cons[0])
            cur = ce.outvars[0]
            continue
        if nm == "dot_general":
            dg2_i, dg2 = cons[0], ce
        break
    if dg2 is None:
        return None
    interior.add(dg2_i)

    (lc2, rc2), (lb2, rb2) = dg2.params["dimension_numbers"]
    if len(lc2) != 1 or len(rc2) != 1:
        return None
    if dg2.invars[0] is cur:
        p_b, v_b, p_c, v_c = lb2, rb2, lc2[0], rc2[0]
        v_var, p_first = dg2.invars[1], True
    elif dg2.invars[1] is cur:
        p_b, v_b, p_c, v_c = rb2, lb2, rc2[0], lc2[0]
        v_var, p_first = dg2.invars[0], False
    else:
        return None
    if not is_var(v_var) or v_var is cur:
        return None
    if pmap[p_c] != kv_out or len(p_b) != nb:
        return None
    i_ts = []
    for t in range(nb):
        c0 = pmap[p_b[t]]
        if c0 >= nb:
            return None
        i_ts.append(c0)
    if sorted(i_ts) != list(range(nb)):
        return None
    v_free = [
        d for d in range(len(v_var.aval.shape)) if d not in v_b and d != v_c
    ]
    if len(v_free) != 1:
        return None
    p_free = [
        d for d in range(len(cur.aval.shape)) if d not in p_b and d != p_c
    ]
    if sorted(pmap[d] for d in p_free) != sorted([q_out] + list(group_out)):
        return None
    root = dg2.outvars[0]
    if not _interior_is_private(ctx, interior, dg2_i):
        return None

    # --- canonicalization metadata (all shapes resolved at call time) ------
    ng = len(group_dims)
    q_perm = list(q_batch) + group_dims + [dq, q_contract]
    k_contract = lc[0] if k_side == "l" else rc[0]
    k_perm = list(k_batch) + [k_seq, k_contract]
    # v batch dims ordered to follow dg1 batch order
    v_by_dg1 = [0] * nb
    for t in range(nb):
        v_by_dg1[i_ts[t]] = v_b[t]
    v_perm = v_by_dg1 + [v_c, v_free[0]]

    # mask: strip in-body broadcasts down to a (q, kv) 2-D mask if possible
    m_var, m_map = mask_var, list(mask_map)
    while True:
        _, pe = _producer_eqn(ctx, m_var)
        if pe is None or pe.primitive.name != "broadcast_in_dim":
            break
        inner = pe.invars[0]
        if not is_var(inner):
            break
        bd = pe.params["broadcast_dimensions"]
        new_map = [m_map[bd[j]] for j in range(len(inner.aval.shape))]
        if q_out in new_map and kv_out in new_map:
            m_var, m_map = inner, new_map
            continue
        break
    if len(m_map) == 2 and set(m_map) == {q_out, kv_out}:
        mask_shape = "2d"
        mask_flip = m_map[0] == kv_out
        mask_perm = None
    else:
        mask_shape = "full"
        mask_flip = False
        m_var, m_map = mask_var, list(mask_map)
        targets = (
            list(range(nb))
            + sorted(group_out, key=lambda c: group_out[c])
            + [q_out, kv_out]
        )
        if sorted(m_map) != sorted(targets):
            return None
        mask_perm = [m_map.index(t) for t in targets]

    # dg2 output layout: canonical index per output position
    canon_of_out_coord = {i: i for i in range(nb)}
    canon_of_out_coord.update({c: nb + gi for c, gi in group_out.items()})
    canon_of_out_coord[q_out] = nb + ng
    hdv_canon = nb + ng + 1
    p_labels = [canon_of_out_coord[pmap[d]] for d in p_free]
    batch_labels = [i_ts[t] for t in range(nb)]
    if p_first:
        out_axes = batch_labels + p_labels + [hdv_canon]
    else:
        out_axes = batch_labels + [hdv_canon] + p_labels

    sq_full = int(q_var.aval.shape[dq])
    skv_full = int(k_var.aval.shape[k_seq])
    hd_sz = int(q_var.aval.shape[q_contract])

    # --- computed-mask classification --------------------------------------
    # A 2-D mask whose value is a contiguous band (causal / sliding-window)
    # is replayed inside the kernel from block indices: no mask array is
    # read, so the mask drops out of ``reads`` and its producing chain dies
    # with it.  Requirements: the mask must be position algebra, be chunked
    # along its q axis (each chunk sees rows [i*c, i*c + c) of the full
    # band — the builder rebuilds the global row offset from the loop
    # index), and K must not be chunked along kv (column positions must
    # stay global).  The band is read symbolically first
    # (``_symbolic_band``: affine forms over the mask's two position
    # indices, at a cost independent of Sq and Skv).  Only a mask that
    # algebra gives no answer for is evaluated with numpy at full shape
    # (``_concrete_mask_value`` + ``_band_params``), whose cost grows with
    # Sq*Skv and is therefore capped by ``_MASK_EVAL_LIMIT``.
    # ``band_source`` says which of the two classified the band (counted,
    # ``kernel_dispatch_band.*``); ``bool_reason`` names the requirement a
    # bool-mask dispatch failed (``kernel_dispatch_bool_mask.*``).
    band = None
    band_source = None
    bool_reason = None
    if mask_mode == "bool":
        bool_reason = "mask_mode_bool"
    elif mask_shape != "2d":
        bool_reason = "mask_not_2d"
    elif ctx.var_dim.get(m_var) != m_map.index(q_out):
        bool_reason = "mask_not_chunked_on_q"
    elif ctx.var_dim.get(k_var) == k_seq:
        bool_reason = "kv_chunked"
    else:
        band_source = "symbolic"
        band = _symbolic_band(
            ctx, m_var, mask_flip, mask_invert, sq_full, skv_full
        )
    if band is None and bool_reason is None:
        band_source = "evaluated"
        why: List[str] = []
        mval = _concrete_mask_value(ctx, m_var, why=why)
        if mval is None:
            bool_reason = why[0] if why else "mask_not_position_algebra"
        else:
            if mval.ndim == 2 and mval.dtype == np.bool_:
                m2 = mval.T if mask_flip else mval
                if mask_invert:
                    m2 = ~m2
                if m2.shape == (sq_full, skv_full):
                    band = _band_params(m2)
            if band is None:
                bool_reason = "mask_not_band"

    root_dtype = root.aval.dtype
    scale_f = float(scale)

    def _canon_qkv(env):
        q = jnp.transpose(env[q_var], q_perm)
        k = jnp.transpose(env[k_var], k_perm)
        v = jnp.transpose(env[v_var], v_perm)
        bsh = q.shape[:nb]
        gsh = q.shape[nb : nb + ng]
        cq, hd = q.shape[-2], q.shape[-1]
        skv, hdv = k.shape[-2], v.shape[-1]
        nbatch, g = _prod(bsh), _prod(gsh)
        qf = q.reshape(nbatch * g, cq, hd)
        kf = k.reshape(nbatch, skv, hd)
        vf = v.reshape(nbatch, skv, hdv)
        if g != 1:
            kf = jnp.broadcast_to(
                kf[:, None], (nbatch, g, skv, hd)
            ).reshape(nbatch * g, skv, hd)
            vf = jnp.broadcast_to(
                vf[:, None], (nbatch, g, skv, hdv)
            ).reshape(nbatch * g, skv, hdv)
        return qf, kf, vf, (bsh, gsh, cq, hdv)

    def _restore(out, shp):
        bsh, gsh, cq, hdv = shp
        out = out.reshape(tuple(bsh) + tuple(gsh) + (cq, hdv))
        return jnp.transpose(out, out_axes).astype(root_dtype)

    if band is not None:
        band_u, band_l = band
        causal_flag = band_u < skv_full - 1
        win = (band_u + band_l + 1) if band_l < sq_full - 1 else None

        def builder(env, kw):
            from repro.kernels import ops

            qf, kf, vf, shp = _canon_qkv(env)
            # global kv-coordinate of this chunk's q row 0: the chunk
            # start (clamped exactly like _slice_chunk clamps the slice)
            # shifted by the band's upper diagonal
            c_, ext_ = int(kw["c"]), int(kw["ext"])
            start = jnp.minimum(
                jnp.asarray(env[LOOP_INDEX], jnp.int32) * c_, ext_ - c_
            )
            out = ops.computed_attention(
                qf, kf, vf, start + band_u, scale=scale_f,
                causal=causal_flag, window=win,
                **_tile_kwargs(kw, _ATTN_TILE),
            )
            return _restore(out, shp)

        reads = (q_var, k_var, v_var)
        # no mask tile: the predicate is registers-only inside the kernel
        tile = 4 * _BLOCK * _BLOCK + 12 * _BLOCK * max(hd_sz, 1)
        mask_variant = "computed"
    else:

        def builder(env, kw):
            from repro.kernels import ops

            qf, kf, vf, shp = _canon_qkv(env)
            cq, skv = qf.shape[1], kf.shape[1]
            m = env[m_var]
            if mask_invert:
                m = jnp.logical_not(m)
            if mask_shape == "2d":
                mm = (jnp.transpose(m) if mask_flip else m)[None]
            else:
                mm = jnp.transpose(m, mask_perm).reshape(-1, cq, skv)
            out = ops.masked_attention(
                qf, kf, vf, mm, scale=scale_f,
                **_tile_kwargs(kw, _ATTN_TILE),
            )
            return _restore(out, shp)

        reads = (q_var, k_var, v_var, m_var)
        # logits tile + streamed bool mask tile + q/k/v rows
        tile = (
            4 * _BLOCK * _BLOCK
            + _BLOCK * _BLOCK
            + 12 * _BLOCK * max(hd_sz, 1)
        )
        mask_variant = "bool"

    n_site = _prod([q_var.aval.shape[d] for d in q_batch]) * _prod(
        [q_var.aval.shape[d] for d in group_dims]
    )
    meta = {
        "mask": mask_variant,
        "band": band_source if band is not None else None,
        "bool_reason": bool_reason,
        "site": {
            "kind": "attention", "n": n_site, "sq": sq_full,
            "skv": skv_full, "hd": hd_sz,
        },
        # shape fields that scale with the chunk size (dq is the chunked
        # dim by construction; kv only when K itself is chunked)
        "chunk_adjust": dict(
            [("sq", sq_full)]
            + ([("skv", skv_full)] if ctx.var_dim.get(k_var) == k_seq else [])
        ),
    }
    return Match(
        kind="attention",
        interior=interior,
        at=dg2_i,
        root=root,
        reads=reads,
        builder=builder,
        tile_bytes=tile,
        meta=meta,
    )


# ---------------------------------------------------------------------------
# SwiGLU matcher
# ---------------------------------------------------------------------------

def _plain_matmul(eqn) -> bool:
    """x @ w with w rank-2: contract (last(x), 0), no batch dims."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    if lb or rb or len(lc) != 1 or len(rc) != 1:
        return False
    lhs, rhs = eqn.invars
    if not (is_var(lhs) and is_var(rhs)):
        return False
    return (
        len(rhs.aval.shape) == 2
        and rc[0] == 0
        and lc[0] == len(lhs.aval.shape) - 1
    )


def _try_swiglu(ctx: _BodyCtx, i_dg3: int) -> Optional[Match]:
    eqns = ctx.eqns
    dg3 = eqns[i_dg3]
    if not _plain_matmul(dg3):
        return None
    h, wd_var = dg3.invars
    interior: Set[int] = {i_dg3}
    i_m2, m2 = _producer_eqn(ctx, h)
    if m2 is None or m2.primitive.name != "mul":
        return None
    interior.add(i_m2)

    def silu_of(atom):
        """If atom == g * logistic(g), return (g, interior ids)."""
        i_m1, m1 = _producer_eqn(ctx, atom)
        if m1 is None or m1.primitive.name != "mul":
            return None
        a, b = m1.invars
        for g_at, lg in ((a, b), (b, a)):
            i_lg, le = _producer_eqn(ctx, lg)
            if (
                le is not None
                and le.primitive.name == "logistic"
                and le.invars[0] is g_at
            ):
                return g_at, {i_m1, i_lg}
        return None

    a, b = m2.invars
    got = silu_of(a)
    u_var = b
    if got is None:
        got = silu_of(b)
        u_var = a
    if got is None or not is_var(u_var):
        return None
    g_var, silu_ids = got
    interior |= silu_ids

    # where do g and u come from?
    i_g, ge = _producer_eqn(ctx, g_var)
    i_u, ue = _producer_eqn(ctx, u_var)
    if ge is None or ue is None:
        return None
    wg_slice = wu_slice = None
    if ge.primitive.name == "split" and ge is ue:
        # fused w_in form, one split eqn: u, g = split(x @ w_in, 2, axis=-1)
        rank = len(ge.invars[0].aval.shape)
        if int(ge.params["axis"]) % rank != rank - 1:
            return None
        i_h0, h0 = _producer_eqn(ctx, ge.invars[0])
        if h0 is None or h0.primitive.name != "dot_general" \
                or not _plain_matmul(h0):
            return None
        x_var, w_in = h0.invars
        bounds = np.cumsum([0] + [int(n) for n in ge.params["sizes"]])
        gi = list(ge.outvars).index(g_var)
        ui = list(ge.outvars).index(u_var)
        wg_slice = (int(bounds[gi]), int(bounds[gi + 1]))
        wu_slice = (int(bounds[ui]), int(bounds[ui + 1]))
        wg_var = wu_var = w_in
        interior |= {i_g, i_h0}
    elif ge.primitive.name == "slice" and ue.primitive.name == "slice":
        # fused w_in form written as two slices:
        # u, g = h[..., :f], h[..., f:] with h = x @ w_in
        i_h0g, h0g = _producer_eqn(ctx, ge.invars[0])
        i_h0u, h0u = _producer_eqn(ctx, ue.invars[0])
        if h0g is not h0u or h0g is None:
            return None
        if h0g.primitive.name != "dot_general" or not _plain_matmul(h0g):
            return None
        x_var, w_in = h0g.invars
        rank = len(ge.outvars[0].aval.shape)
        for sl in (ge, ue):
            # params may carry chunk-adjusted limits; the "full along every
            # dim but the last" test must use the (unadjusted) avals
            st = sl.params["start_indices"]
            strides = sl.params["strides"] or (1,) * rank
            inn = sl.invars[0].aval.shape
            out = sl.outvars[0].aval.shape
            if any(s != 1 for s in strides):
                return None
            for d in range(rank - 1):
                if st[d] != 0 or out[d] != inn[d]:
                    return None
        wg_slice = (int(ge.params["start_indices"][-1]),
                    int(ge.params["limit_indices"][-1]))
        wu_slice = (int(ue.params["start_indices"][-1]),
                    int(ue.params["limit_indices"][-1]))
        wg_var = wu_var = w_in
        interior |= {i_g, i_u, i_h0g}
    elif ge.primitive.name == "dot_general" and ue.primitive.name == "dot_general":
        # separate-weights form: silu(x @ wg) * (x @ wu)
        if not (_plain_matmul(ge) and _plain_matmul(ue)):
            return None
        if ge.invars[0] is not ue.invars[0]:
            return None
        x_var, wg_var = ge.invars
        wu_var = ue.invars[1]
        interior |= {i_g, i_u}
    else:
        return None

    if not is_var(x_var):
        return None
    dx = ctx.var_dim.get(x_var)
    if dx is None or dx == len(x_var.aval.shape) - 1:
        return None
    root = dg3.outvars[0]
    if not _interior_is_private(ctx, interior, i_dg3):
        return None

    root_dtype = root.aval.dtype
    reads = tuple({x_var, wg_var, wu_var, wd_var})

    def builder(env, kw):
        from repro.kernels import ops

        x = env[x_var]
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if wg_slice is not None:
            w_in = env[wg_var]
            wg = w_in[:, wg_slice[0] : wg_slice[1]]
            wu = w_in[:, wu_slice[0] : wu_slice[1]]
        else:
            wg, wu = env[wg_var], env[wu_var]
        wd = env[wd_var]
        out = ops.swiglu_ffn(x2, wg, wu, wd, **_tile_kwargs(kw, _FFN_TILE))
        return out.reshape(tuple(lead) + (wd.shape[1],)).astype(root_dtype)

    d_sz = int(x_var.aval.shape[-1])
    if wg_slice is not None:
        f_sz = int(wg_slice[1] - wg_slice[0])
    else:
        f_sz = int(wg_var.aval.shape[1])
    s_full = _prod(x_var.aval.shape[:-1])
    tile = 4 * (_BLOCK * _BLOCK_F + 2 * _BLOCK * max(d_sz, 1))
    meta = {
        "site": {"kind": "swiglu", "s": s_full, "d": d_sz, "f": f_sz,
                 "itemsize": int(np.dtype(x_var.aval.dtype).itemsize)},
        # s is the flattened leading-dim product: it scales by c/extent of
        # the chunked dim rather than collapsing to c
        "chunk_adjust": {"s": int(x_var.aval.shape[dx])},
    }
    return Match(
        kind="swiglu",
        interior=interior,
        at=i_dg3,
        root=root,
        reads=reads,
        builder=builder,
        tile_bytes=tile,
        meta=meta,
    )


# ---------------------------------------------------------------------------
# Body matching + the pass entry points
# ---------------------------------------------------------------------------

def match_body(ctx: _BodyCtx, mask_mode: str = "auto") -> List[Match]:
    """All non-overlapping fused-kernel matches in one loop body."""
    found: List[Match] = []
    used: Set[int] = set()
    for i, eqn in enumerate(ctx.eqns):
        name = eqn.primitive.name
        m = None
        if name == "div":
            m = _try_attention(ctx, i, mask_mode)
        elif name == "dot_general":
            m = _try_swiglu(ctx, i)
        if m is None:
            continue
        if m.interior & used:
            continue
        used |= m.interior
        found.append(m)
    return found


def _dead_after(ctx: _BodyCtx, skip: Set[int], protected: Set[Var]) -> Set[int]:
    """Body eqns whose outputs become unread once ``skip`` is removed."""
    dead = set(skip)
    changed = True
    while changed:
        changed = False
        for i in range(len(ctx.eqns) - 1, -1, -1):
            if i in dead:
                continue
            ovs = [ov for ov in ctx.eqns[i].outvars if is_var(ov)]
            if any(ov in ctx.escapes or ov in protected for ov in ovs):
                continue
            if all(
                all(c in dead for c in ctx.consumers.get(ov, []))
                for ov in ovs
            ):
                dead.add(i)
                changed = True
    return dead


def _prune_node_inputs(node: ChunkLoopEqn) -> bool:
    """Drop sliced/captured inputs nothing in the dispatched body reads.

    After a computed-mask dispatch the mask var has no consumers left (its
    select chain is skipped and it is not in any record's ``reads``):
    removing it from the node's inputs stops the scan from slicing an
    O(Sq*Skv) array per iteration — and lets graph-level DCE delete the
    chain that built it.
    """
    p = node.params
    if not p["dispatches"]:
        return False
    skip = set().union(*(d.skip for d in p["dispatches"]))
    fire = {d.at for d in p["dispatches"]}
    needed: Set[Var] = set()
    for i, eqn in enumerate(p["body"]):
        if i in skip or i in fire:
            continue
        needed.update(iv for iv in eqn.invars if is_var(iv))
    for d in p["dispatches"]:
        needed.update(d.reads)
    new_sliced = [sv for sv in p["sliced"] if sv[0] in needed]
    new_captured = [v for v in p["captured"] if v in needed]
    if (
        len(new_sliced) == len(p["sliced"])
        and len(new_captured) == len(p["captured"])
    ):
        return False
    if not new_sliced:
        return False  # keep at least one sliced input driving the loop
    p["sliced"] = new_sliced
    p["captured"] = new_captured
    node.invars = [v for v, _ in new_sliced] + list(new_captured)
    return True


def dispatch_node(
    node: ChunkLoopEqn,
    g: Optional[Graph] = None,
    outer=None,
    *,
    tuning=None,
    mask_mode: str = "auto",
) -> int:
    """Try to dispatch one chunk-loop node; returns the number of matches.

    ``tuning`` (a :class:`repro.kernels.autotune.KernelTuning`) supplies the
    tile/buffer kwargs baked into each dispatch record; ``mask_mode='bool'``
    disables the computed-mask path (every mask streams as a bool array).
    """
    try:
        ctx = _ctx_from_node(node, g, outer)
        matches = match_body(ctx, mask_mode)
    except Exception:
        # dispatch must never break a compilable plan: an exotic body that
        # trips the matcher falls back to generic scan codegen, counted
        # apart from bodies that simply do not match
        refresh_node(node)
        stats.bump("kernel_dispatch_errors")
        return 0
    if not matches:
        refresh_node(node)  # drop any dispatch-aware body_peak cap
        stats.bump("kernel_dispatch_misses")
        return 0
    protected = {v for m in matches for v in m.reads} | {m.root for m in matches}
    skip0 = {i for m in matches for i in m.interior if i != m.at}
    at_set = {m.at for m in matches}
    skip_all = _dead_after(ctx, skip0 | at_set, protected) - at_set
    base_kw = {
        "c": int(node.params["c"]),
        "ext": int(node.params["chunk_extent"]),
    }
    records = []
    for j, m in enumerate(matches):
        own = set(m.interior) - {m.at}
        if j == 0:  # fold the globally-dead eqns into the first record
            own |= skip_all - {i for mm in matches for i in mm.interior} - at_set
        kw = dict(base_kw)
        if tuning is not None:
            kw.update(tuning.kernel_kwargs(m.kind))
        records.append(
            KernelDispatch(
                skip=frozenset(own),
                at=m.at,
                root=m.root,
                reads=tuple(m.reads),
                fn=(lambda env, _b=m.builder, _kw=kw: _b(env, _kw)),
                kind=m.kind,
            )
        )
    saved = (
        node.params["dispatches"],
        list(node.params["sliced"]),
        list(node.params["captured"]),
        list(node.invars),
    )
    node.params["dispatches"] = tuple(records)
    try:
        validate_body(node)
        if _prune_node_inputs(node):
            validate_body(node)
    except Exception:
        # dispatch must never break a compilable plan: revert to scan codegen
        node.params["dispatches"] = saved[0]
        node.params["sliced"] = saved[1]
        node.params["captured"] = saved[2]
        node.invars = saved[3]
        refresh_node(node)
        stats.bump("kernel_dispatch_errors")
        return 0
    refresh_node(node)
    stats.bump("kernel_dispatch_hits", len(records))
    attention = [m for m in matches if m.kind == "attention"]
    if attention:
        stats.bump("kernel_dispatch_attention", len(attention))
    n_computed = sum(1 for m in attention if m.meta["mask"] == "computed")
    if n_computed:
        stats.bump("kernel_dispatch_computed_mask", n_computed)
    for m in attention:
        if m.meta["band"] is not None:
            stats.bump(f"kernel_dispatch_band.{m.meta['band']}")
        if m.meta["bool_reason"] is not None:
            stats.bump(f"kernel_dispatch_bool_mask.{m.meta['bool_reason']}")
    return len(records)


def _node_sites(node: ChunkLoopEqn, matches: Sequence[Match]) -> List[Dict]:
    """Autotune site descriptors for one node's matches, at chunk shapes."""
    c = int(node.params["c"])
    sites: List[Dict] = []
    for m in matches:
        site = dict(m.meta.get("site") or {})
        if not site:
            continue
        for fld, ext in (m.meta.get("chunk_adjust") or {}).items():
            if fld == "s":
                site["s"] = max(1, (int(site["s"]) // max(int(ext), 1)) * c)
            else:
                site[fld] = c
        sites.append(site)
    return sites


def _prune_graph(g: Graph) -> Graph:
    """Fixpoint DCE after dispatch.

    Node-input pruning can orphan whole prefix chains — e.g. the eqns that
    built a boolean mask a computed-mask dispatch no longer reads.  Drops
    eqns with no remaining consumers (chunk-loop nodes and graph outputs
    stay) and const bindings nothing references; rebuilding the
    :class:`Graph` recomputes the producer/consumer indices.
    """
    eqns = list(g.eqns)
    out_set = {v for v in g.outvars if is_var(v)}
    while True:
        consumed: Set[Var] = set(out_set)
        for eqn in eqns:
            consumed.update(iv for iv in eqn.invars if is_var(iv))
        keep = [
            e for e in eqns
            if is_chunk_loop(e)
            or any(is_var(ov) and ov in consumed for ov in e.outvars)
        ]
        if len(keep) == len(eqns):
            break
        eqns = keep
    consumed = set(out_set)
    for eqn in eqns:
        consumed.update(iv for iv in eqn.invars if is_var(iv))
    consts = {v: val for v, val in g.consts.items() if v in consumed}
    return Graph(
        invars=list(g.invars),
        outvars=list(g.outvars),
        eqns=eqns,
        consts=consts,
        weight_invars=set(g.weight_invars),
    )


def dispatch_graph(
    g: Graph,
    *,
    tuning=None,
    autotune: bool = False,
    mask_mode: str = "auto",
):
    """Run kernel dispatch over every chunk-loop node of a rewritten graph.

    Returns ``(graph, tuning)``.  With ``autotune=True`` and no ``tuning``
    given, the matched sites' shapes are collected first and
    :func:`repro.kernels.autotune.tune_sites` picks the tile sizes / DMA
    buffer depth baked into the dispatch records; the caller persists the
    returned tuning in the plan so warm replays pass it back instead
    (``autotune_passes == 0`` on replay).  The returned graph has dead
    equations pruned — a computed-mask dispatch leaves the chain that built
    the boolean mask unconsumed, and this is where it is deleted.
    """
    outer = _outer_producers(g)
    nodes = [e for e in g.eqns if is_chunk_loop(e)]
    if autotune and tuning is None and nodes:
        sites: List[Dict] = []
        for node in nodes:
            try:
                ms = match_body(_ctx_from_node(node, g, outer), mask_mode)
            except Exception:
                stats.bump("kernel_dispatch_errors")
                ms = []
            sites.extend(_node_sites(node, ms))
        if sites:
            from ..kernels import autotune as _autotune
            from ..kernels import ops as _ops

            tuning = _autotune.tune_sites(
                sites, interpret=_ops.interpret_default()
            )
    dispatched = 0
    for node in nodes:
        dispatched += dispatch_node(
            node, g, outer, tuning=tuning, mask_mode=mask_mode
        )
    if dispatched:
        g = _prune_graph(g)
    return g, tuning


def annotate_candidates(
    g: Graph, cands: Sequence[ChunkCandidate], mask_mode: str = "auto"
) -> None:
    """Dispatch-aware selection: mark kernelizable candidates.

    Sets ``kernel_tile_bytes`` on every candidate whose body matches a fused
    kernel, so the cost model charges the VMEM-tile body peak instead of
    the full chunk-slice peak (see ``ChunkCandidate.chunked_body_peak``).
    Computed-mask matches charge no mask bytes at all — the predicate
    never materializes.
    """
    outer = _outer_producers(g)
    for cand in cands:
        try:
            matches = match_body(
                _ctx_from_candidate(g, cand, outer), mask_mode
            )
        except Exception:
            stats.bump("kernel_dispatch_errors")
            continue
        if matches:
            cand.kernel_tile_bytes = sum(m.tile_bytes for m in matches)
