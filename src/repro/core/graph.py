"""Jaxpr graph wrapper shared by all AutoChunk compiler passes.

AutoChunk operates on JAX's intermediate representation (jaxprs) the way the
paper operates on PyTorch FX graphs.  A :class:`Graph` is a flattened view of
a traced function: a list of equations in program order, the (flat) input and
output atoms, plus bookkeeping about which inputs are *weights* (parameter
memory) versus *activations* (the thing AutoChunk optimizes).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
from jax import tree_util
from jax.extend import core as jex_core

Var = jex_core.Var
Literal = jex_core.Literal
JaxprEqn = Any

# Call-like primitives that we inline so the pass pipeline sees a flat graph.
_CALL_PRIM_NAMES = {
    "jit",   # nested jax.jit / jnp internal wrappers
    "closed_call",
    "custom_jvp_call",
    "custom_vjp_call",
    "custom_vjp_call_jaxpr",
    "remat2",
    "checkpoint",
}


def fresh_var(aval) -> Var:
    """A new Var of abstract type ``aval``."""
    return Var(aval)


def aval_bytes(aval) -> int:
    """Bytes occupied by a value of this abstract type."""
    try:
        return int(aval.size) * aval.dtype.itemsize
    except Exception:  # tokens, abstract refs, ...
        return 0


def atom_bytes(atom) -> int:
    return aval_bytes(atom.aval)


def is_var(atom) -> bool:
    return isinstance(atom, Var)


def _inner_closed_jaxpr(eqn) -> Optional[jex_core.ClosedJaxpr]:
    p = eqn.params
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in p:
            inner = p[key]
            if isinstance(inner, jex_core.ClosedJaxpr):
                return inner
            if hasattr(inner, "eqns"):  # raw Jaxpr
                return jex_core.ClosedJaxpr(inner, ())
    return None


def _flatten_jaxpr(jaxpr, consts, const_env: Dict[Var, Any], arg_atoms):
    """Inline all call-like eqns, rewriting every defined var to a FRESH Var.

    jit caches inner jaxprs, so the SAME jaxpr object (and its Var objects)
    can appear at several call sites; per-call-site renaming keeps the flat
    graph SSA.  Returns (eqns, resolved_out_atoms).
    """
    sub: Dict[Var, Any] = {}
    for cv, cval in zip(jaxpr.constvars, consts):
        const_env[cv] = cval
    for iv, atom in zip(jaxpr.invars, arg_atoms):
        sub[iv] = atom

    def resolve(a):
        if isinstance(a, Var) and a in sub:
            return sub[a]
        return a  # literal, constvar, or top-level var

    out: List[JaxprEqn] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _CALL_PRIM_NAMES:
            inner = _inner_closed_jaxpr(eqn)
            if inner is not None:
                args = [resolve(a) for a in eqn.invars]
                sub_eqns, inner_outs = _flatten_jaxpr(
                    inner.jaxpr, inner.consts, const_env, args
                )
                out.extend(sub_eqns)
                for ov, res in zip(eqn.outvars, inner_outs):
                    sub[ov] = res
                continue
        new_invars = [resolve(a) for a in eqn.invars]
        new_outvars = []
        for v in eqn.outvars:
            nv = fresh_var(v.aval)
            sub[v] = nv
            new_outvars.append(nv)
        out.append(eqn.replace(invars=new_invars, outvars=new_outvars))
    return out, [resolve(a) for a in jaxpr.outvars]


@dataclass
class Graph:
    """Flat computation graph for one traced function."""

    invars: List[Var]
    outvars: List[Any]  # atoms (Var or Literal)
    eqns: List[JaxprEqn]
    consts: Dict[Var, Any]
    weight_invars: Set[Var] = field(default_factory=set)

    # -- derived indices ---------------------------------------------------
    def __post_init__(self):
        self.producer: Dict[Var, int] = {}
        self.consumers: Dict[Var, List[int]] = {}
        for i, eqn in enumerate(self.eqns):
            for ov in eqn.outvars:
                if isinstance(ov, Var):
                    self.producer[ov] = i
            for iv in eqn.invars:
                if isinstance(iv, Var):
                    self.consumers.setdefault(iv, []).append(i)
        self.out_set: Set[Var] = {v for v in self.outvars if isinstance(v, Var)}
        self.last_use: Dict[Var, int] = {}
        n = len(self.eqns)
        for v, cs in self.consumers.items():
            self.last_use[v] = max(cs)
        for v in self.out_set:
            self.last_use[v] = n  # live until the end

    # ----------------------------------------------------------------------
    def var_bytes(self, atom) -> int:
        return atom_bytes(atom)

    def eqn_out_bytes(self, i: int) -> int:
        return sum(atom_bytes(ov) for ov in self.eqns[i].outvars)

    def intermediate_vars(self) -> Set[Var]:
        inv = set(self.invars) | set(self.consts)
        return {
            ov
            for eqn in self.eqns
            for ov in eqn.outvars
            if isinstance(ov, Var) and ov not in inv
        }


def trace(
    fn: Callable,
    example_args: Sequence[Any],
    weight_argnums: Sequence[int] = (0,),
) -> Tuple[Graph, Any]:
    """Trace ``fn(*example_args)`` to a :class:`Graph`.

    Returns (graph, out_tree).  ``example_args`` may be ShapeDtypeStructs —
    nothing is materialized.
    """
    from . import stats

    stats.bump("trace_calls")
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    out_tree = tree_util.tree_structure(out_shape)
    jaxpr = closed.jaxpr
    const_env: Dict[Var, Any] = {}
    eqns, outvars = _flatten_jaxpr(
        jaxpr, closed.consts, const_env, list(jaxpr.invars)
    )

    # figure out which flat invars correspond to weight args
    flat_counts = [len(tree_util.tree_leaves(a)) for a in example_args]
    weight_set: Set[Var] = set()
    pos = 0
    for argi, cnt in enumerate(flat_counts):
        if argi in weight_argnums:
            weight_set.update(jaxpr.invars[pos : pos + cnt])
        pos += cnt

    g = Graph(
        invars=list(jaxpr.invars),
        outvars=list(outvars),
        eqns=eqns,
        consts=const_env,
        weight_invars=weight_set,
    )
    g.closed_jaxpr = closed  # the unflattened ClosedJaxpr (Planned.lower())
    return g, out_tree


# ---------------------------------------------------------------------------
# FLOP model (used by the chunk-selection cost function and the benchmarks)
# ---------------------------------------------------------------------------

def eqn_flops(eqn) -> float:
    """Cheap analytic FLOP estimate for one equation."""
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, rc), _ = eqn.params["dimension_numbers"]
        out = eqn.outvars[0].aval
        k = 1
        lhs = eqn.invars[0].aval
        for d in lc:
            k *= lhs.shape[d]
        return 2.0 * out.size * k
    if name in ("conv_general_dilated",):
        out = eqn.outvars[0].aval
        lhs, rhs = (iv.aval for iv in eqn.invars[:2])
        return 2.0 * out.size * (rhs.size / max(rhs.shape[-1], 1))
    if name == "scan":
        body = eqn.params["jaxpr"]
        inner = sum(eqn_flops(e) for e in body.jaxpr.eqns)
        return inner * eqn.params["length"]
    if name == "chunk_loop":
        # core.lowering structured loop: body eqns keep full-extent avals,
        # so their summed flops already equal the total across iterations
        return sum(eqn_flops(e) for e in eqn.params["body"])
    if name.startswith("reduce_") or name in ("argmax", "argmin"):
        return float(eqn.invars[0].aval.size)
    # elementwise-ish default: one op per output element
    return float(sum(ov.aval.size for ov in eqn.outvars if hasattr(ov, "aval")))


def graph_flops(g: Graph, lo: int = 0, hi: Optional[int] = None) -> float:
    hi = len(g.eqns) if hi is None else hi
    return sum(eqn_flops(e) for e in g.eqns[lo:hi])


def dim_stride(shape: Sequence[int], dim: int) -> int:
    """Row-major stride (in elements) of ``dim``."""
    s = 1
    for d in shape[dim + 1 :]:
        s *= d
    return s
