"""Chunk-flow dimension propagation rules (backward, per primitive).

The paper's *chunk flow* (§3.3) is a path of a chunk dimension through
consecutive graph nodes.  Here a rule answers, for one equation and one
(output, dim) pair:

    "If I want this output sliced along ``out_dim``, what do I need from the
     inputs?"

The answer, per input, is either
  * an integer dim  — the input must be sliced along that dim, or
  * ``FULL``        — the whole input is needed for every chunk (paper's
                      non-chunkable inputs X^nc), or
the rule returns ``None`` ( = BREAK): the primitive cannot produce chunked
output along that dim from slices (contractions along the dim, reshapes that
merge it, data-dependent ops, ...).  A broken equation may still be *hoisted*
out of the loop by the search pass when its inputs are chunk-invariant.

These play the role vmap's batching rules play for the forward direction —
but run in reverse, establishing the paper's Output-Alignment rule
constructively: slicing is only propagated where slice-then-compute equals
compute-then-slice.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Union

FULL = "full"
InDim = Union[int, str]  # int dim or FULL

_RULES = {}


def register(*names):
    def deco(fn):
        for n in names:
            _RULES[n] = fn
        return fn

    return deco


def propagate(eqn, out_idx: int, out_dim: int) -> Optional[Dict[int, InDim]]:
    """Map (output out_idx sliced along out_dim) -> required input dims.

    Returns {invar_index: dim|FULL} covering *all* inputs, or None (BREAK).
    """
    rule = _RULES.get(eqn.primitive.name)
    if rule is None:
        return None
    try:
        return rule(eqn, out_idx, out_dim)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Elementwise ops: every same-shaped input slices along the same dim;
# scalars ride along whole.
# ---------------------------------------------------------------------------
_ELEMENTWISE = [
    "add", "sub", "mul", "div", "pow", "rem", "max", "min", "atan2",
    "nextafter", "and", "or", "xor", "shift_left",
    "shift_right_logical", "shift_right_arithmetic",
    "eq", "ne", "ge", "gt", "le", "lt",
    "exp", "exp2", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan",
    "asin", "acos", "atan", "sinh", "cosh", "asinh", "acosh", "atanh",
    "sqrt", "rsqrt", "cbrt", "logistic", "erf", "erfc", "erf_inv",
    "abs", "neg", "sign", "floor", "ceil", "round", "is_finite", "not",
    "integer_pow", "real", "imag", "conj", "square",
    "convert_element_type", "bitcast_convert_type", "copy",
    "stop_gradient", "clamp", "select_n", "nan_to_num", "population_count",
    "reduce_precision",
]


@register(*_ELEMENTWISE)
def _elementwise(eqn, out_idx, out_dim):
    # jax.lax binary ops permit numpy-style broadcasting; align trailing dims.
    out = eqn.outvars[out_idx].aval
    res = {}
    for i, iv in enumerate(eqn.invars):
        shp = getattr(iv.aval, "shape", ())
        if len(shp) == 0:
            res[i] = FULL
            continue
        j = out_dim - (len(out.shape) - len(shp))
        if j < 0:
            res[i] = FULL
        elif shp[j] == out.shape[out_dim]:
            res[i] = j
        elif shp[j] == 1:
            res[i] = FULL
        else:
            return None
    return res


@register("broadcast_in_dim")
def _broadcast(eqn, out_idx, out_dim):
    bdims = eqn.params["broadcast_dimensions"]
    out = eqn.outvars[0].aval
    inv = eqn.invars[0].aval
    if out_dim in bdims:
        i = list(bdims).index(out_dim)
        if inv.shape[i] == out.shape[out_dim]:
            return {0: i}
    # broadcast along out_dim: every chunk reuses the whole (tiny) input
    return {0: FULL}


@register("transpose")
def _transpose(eqn, out_idx, out_dim):
    perm = eqn.params["permutation"]
    return {0: perm[out_dim]}


@register("reshape")
def _reshape(eqn, out_idx, out_dim):
    if eqn.params.get("dimensions") is not None:
        return None
    out = eqn.outvars[0].aval.shape
    inn = eqn.invars[0].aval.shape
    # Prefix-product rule: slicing commutes with a row-major reshape iff the
    # element-count before the dim and the dim's own extent both match.
    pre_out = math.prod(out[:out_dim])
    for d in range(len(inn)):
        if math.prod(inn[:d]) == pre_out and inn[d] == out[out_dim]:
            return {0: d}
    return None


@register("squeeze")
def _squeeze(eqn, out_idx, out_dim):
    removed = set(eqn.params["dimensions"])
    kept = [d for d in range(len(eqn.invars[0].aval.shape)) if d not in removed]
    return {0: kept[out_dim]}


@register("expand_dims")
def _expand_dims(eqn, out_idx, out_dim):
    added = set(eqn.params["dimensions"])
    if out_dim in added:
        return None
    shift = sum(1 for d in added if d < out_dim)
    return {0: out_dim - shift}


@register("dot_general")
def _dot_general(eqn, out_idx, out_dim):
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    rhs = eqn.invars[1].aval
    nb = len(lb)
    lhs_free = [d for d in range(len(lhs.shape)) if d not in lc and d not in lb]
    rhs_free = [d for d in range(len(rhs.shape)) if d not in rc and d not in rb]
    if out_dim < nb:
        return {0: lb[out_dim], 1: rb[out_dim]}
    if out_dim < nb + len(lhs_free):
        return {0: lhs_free[out_dim - nb], 1: FULL}
    return {0: FULL, 1: rhs_free[out_dim - nb - len(lhs_free)]}


@register(
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
)
def _reduce(eqn, out_idx, out_dim):
    axes = set(eqn.params["axes"])
    inn = eqn.invars[0].aval.shape
    kept = [d for d in range(len(inn)) if d not in axes]
    return {0: kept[out_dim]}


@register("cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp")
def _cumulative(eqn, out_idx, out_dim):
    if out_dim == eqn.params["axis"]:
        return None
    return {0: out_dim}


@register("concatenate")
def _concat(eqn, out_idx, out_dim):
    if out_dim == eqn.params["dimension"]:
        return None
    return {i: out_dim for i in range(len(eqn.invars))}


@register("split")
def _split(eqn, out_idx, out_dim):
    # every piece keeps the input's extent off the split axis
    if out_dim == eqn.params["axis"]:
        return None
    return {0: out_dim}


@register("slice")
def _slice(eqn, out_idx, out_dim):
    p = eqn.params
    inn = eqn.invars[0].aval.shape
    strides = p["strides"] or (1,) * len(inn)
    if (
        p["start_indices"][out_dim] == 0
        and p["limit_indices"][out_dim] == inn[out_dim]
        and strides[out_dim] == 1
    ):
        return {0: out_dim}
    return None


@register("rev")
def _rev(eqn, out_idx, out_dim):
    if out_dim in eqn.params["dimensions"]:
        return None
    return {0: out_dim}


@register("dynamic_slice")
def _dynamic_slice(eqn, out_idx, out_dim):
    operand = eqn.invars[0].aval
    out = eqn.outvars[0].aval
    if out.shape[out_dim] != operand.shape[out_dim]:
        return None
    res = {0: out_dim}
    for i in range(1, len(eqn.invars)):
        res[i] = FULL
    return res


@register("dynamic_update_slice")
def _dus(eqn, out_idx, out_dim):
    operand = eqn.invars[0].aval
    update = eqn.invars[1].aval
    if update.shape[out_dim] != operand.shape[out_dim]:
        return None
    res = {0: out_dim, 1: out_dim}
    for i in range(2, len(eqn.invars)):
        res[i] = FULL
    return res


@register("pad")
def _pad(eqn, out_idx, out_dim):
    lo, hi, interior = eqn.params["padding_config"][out_dim]
    if lo == 0 and hi == 0 and interior == 0:
        return {0: out_dim, 1: FULL}
    return None


@register("gather")
def _gather(eqn, out_idx, out_dim):
    dn = eqn.params["dimension_numbers"]
    if out_dim in dn.offset_dims:
        return None
    out_rank = len(eqn.outvars[0].aval.shape)
    batch_out = [d for d in range(out_rank) if d not in dn.offset_dims]
    k = batch_out.index(out_dim)
    idx_aval = eqn.invars[1].aval
    # index_vector_dim == rank(indices) means implicit trailing vector dim
    if k >= len(idx_aval.shape):
        return None
    return {0: FULL, 1: k}


@register("iota")
def _iota(eqn, out_idx, out_dim):
    # No inputs: chunks would need offset iotas.  BREAK — the search pass
    # hoists iotas (compute once, slice per chunk), which is always legal.
    return None


@register("sort")
def _sort(eqn, out_idx, out_dim):
    if out_dim == eqn.params["dimension"]:
        return None
    return {i: out_dim for i in range(len(eqn.invars))}


@register("top_k")
def _top_k(eqn, out_idx, out_dim):
    out = eqn.outvars[0].aval
    if out_dim == len(out.shape) - 1:
        return None
    return {0: out_dim}
