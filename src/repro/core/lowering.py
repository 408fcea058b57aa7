"""Jaxpr-native lowering backend: chunk stages as graph rewrites, one emit.

The original codegen wrapped each applied chunk stage in a new Python
interpreter closure (``build_chunked_fn``) and re-traced between stages, so a
K-stage plan cost K nested interpreters and K+1 traces.  This module is the
replacement back end:

* :func:`apply_chunk` rewrites a :class:`~repro.core.graph.Graph` *in place*
  (structurally — a new node list over the same vars): the chunked region
  ``[s, e]`` is spliced into ``prefix -> hoisted -> ChunkLoopEqn -> suffix``,
  where :class:`ChunkLoopEqn` is a structured loop node carrying the adjusted
  body equations.  Applying a multi-stage plan is K successive rewrites on
  one graph — no tracing, no nesting.
* :func:`emit` turns the final rewritten graph into a single flat callable
  (``jax.core.jaxpr_as_fun``-style evaluation: prefix/hoisted/suffix nodes
  interpret directly, each ``ChunkLoopEqn`` becomes one ``lax.scan``), so
  the trace count of a compile is independent of the stage count — observable
  via the ``lowering_emits`` / ``trace_calls`` counters in ``core.stats``.

``ChunkLoopEqn`` quacks like a ``JaxprEqn`` (``primitive.name``, ``invars``,
``outvars``, ``params``) so every existing pass — estimation, chunk search,
selection, plan serialization — runs on rewritten graphs unchanged; dimflow
has no rule for ``chunk_loop``, which makes applied loops opaque to later
stages exactly like a re-traced ``scan`` equation was.

The ``kernel_dispatch`` pass (see ``core.kernel_dispatch``) may attach
:class:`KernelDispatch` records to a loop node, swapping part of the scan
body for a fused Pallas kernel at evaluation time.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, FrozenSet, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend import source_info_util

from . import stats
from .graph import Graph, Var, atom_bytes, is_var
from .search import ChunkCandidate


class LoweringError(RuntimeError):
    """A candidate's loop body does not abstract-evaluate at chunk shapes."""


# ---------------------------------------------------------------------------
# The structured loop node
# ---------------------------------------------------------------------------

class _ChunkLoopPrimitive:
    """Stand-in primitive so ChunkLoopEqn duck-types as a JaxprEqn."""

    name = "chunk_loop"
    multiple_results = True

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "chunk_loop"


CHUNK_LOOP = _ChunkLoopPrimitive()


class _LoopIndexSentinel:
    """Env key under which a chunk loop binds its (traced) iteration index.

    Kernel-dispatch builders that compute masks from absolute positions need
    the chunk's start offset at runtime; ``benv[LOOP_INDEX]`` is the scan's
    int32 iteration counter (``validate_body`` binds a zero so dispatched
    bodies abstract-eval cleanly).
    """

    __slots__ = ()

    def __repr__(self) -> str:
        return "<loop_index>"


LOOP_INDEX = _LoopIndexSentinel()


@dataclass(frozen=True)
class KernelDispatch:
    """One fused-kernel substitution inside a chunk-loop body.

    ``skip``  body-eqn positions replaced by the kernel (never evaluated)
    ``at``    body position of the match root — the kernel fires here
    ``root``  the var the kernel's result is bound to
    ``reads`` body/captured vars the kernel closure reads (protected from
              dead-code elimination)
    ``fn``    ``fn(env) -> value``: computes ``root`` from the environment
    ``kind``  ``'attention'`` / ``'swiglu'`` (observability)
    """

    skip: FrozenSet[int]
    at: int
    root: Var
    reads: Tuple[Var, ...]
    fn: Callable[[Dict[Var, Any]], Any]
    kind: str = "?"


class ChunkLoopEqn:
    """A chunked region lowered to a structured loop node.

    params:
      ``body``         adjusted in-loop equations (chunk-sized semantics)
      ``sliced``       [(var, dim)] inputs sliced per chunk
      ``captured``     vars (incl. consts) the body reads whole
      ``out_dims``     chunk dim per outvar (reassembly axis)
      ``var_dim``      var -> chunk-dim assignment over the body flow
      ``n_chunks``     requested chunk count
      ``c``            per-chunk slice extent (ceil)
      ``n_iters``      actual loop trips
      ``chunk_extent`` full extent of the chunked dim
      ``body_peak``    modeled per-iteration live bytes (estimation pass)
      ``dispatches``   KernelDispatch records (kernel_dispatch pass)
    """

    primitive = CHUNK_LOOP

    def __init__(self, invars: List[Any], outvars: List[Var], params: Dict[str, Any]):
        self.invars = invars
        self.outvars = outvars
        self.params = params

    def __repr__(self) -> str:
        p = self.params
        return (
            f"chunk_loop[n={p['n_chunks']} c={p['c']} ext={p['chunk_extent']}"
            f" body={len(p['body'])} dispatch={len(p['dispatches'])}]"
        )


def is_chunk_loop(eqn) -> bool:
    return isinstance(eqn, ChunkLoopEqn)


# ---------------------------------------------------------------------------
# Equation evaluation (shared with codegen's legacy path)
# ---------------------------------------------------------------------------

def _slice_chunk(x, dim: int, i, c: int):
    """Dynamic slice of chunk i (size c) along dim; clamps the last chunk."""
    return lax.dynamic_slice_in_dim(x, i * c, c, axis=dim)


def _write_chunk(buf, val, dim: int, i, c: int):
    return lax.dynamic_update_slice_in_dim(buf, val, i * c, axis=dim)


def _scope_of(eqn):
    """The name stack ``eqn`` was traced under, below the current one."""
    return source_info_util.current_name_stack() + eqn.source_info.name_stack


def eval_eqns(eqns, env: Dict[Var, Any]) -> None:
    """Interpret equations (including chunk_loop nodes) against ``env``.

    Each equation is bound under the source line and name stack it was
    traced with, as ``jax.core.eval_jaxpr`` does, so the emitted program's
    ops keep the model's scopes and source lines in their metadata.
    """
    for eqn in eqns:
        if isinstance(eqn, ChunkLoopEqn):
            _eval_chunk_loop(eqn, env)
            continue
        invals = [env[iv] if is_var(iv) else iv.val for iv in eqn.invars]
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        with source_info_util.user_context(eqn.source_info.traceback,
                                           name_stack=_scope_of(eqn)):
            ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
        outs = ans if eqn.primitive.multiple_results else [ans]
        for ov, o in zip(eqn.outvars, outs):
            env[ov] = o


def _eval_body(body, benv: Dict[Var, Any], dispatches: Sequence[KernelDispatch]):
    """Evaluate a loop body, substituting fused kernels where dispatched.

    A kernel runs under the name stack of the equation it replaces but not
    under its source line: its ops keep the kernel's own ``pallas_call``
    line, by which the benchmark names kernels.
    """
    if not dispatches:
        eval_eqns(body, benv)
        return
    skip = set().union(*(d.skip for d in dispatches))
    fire = {d.at: d for d in dispatches}
    for i, eqn in enumerate(body):
        d = fire.get(i)
        if d is not None:
            with source_info_util.set_name_stack(_scope_of(eqn)):
                benv[d.root] = d.fn(benv)
            continue
        if i in skip:
            continue
        eval_eqns([eqn], benv)


def _eval_chunk_loop(node: ChunkLoopEqn, env: Dict[Var, Any]) -> None:
    p = node.params
    c, n_iters = p["c"], p["n_iters"]
    sliced = p["sliced"]
    captured = {v: env[v] for v in p["captured"]}
    sliced_full = [env[v] for v, _ in sliced]
    out_dims = p["out_dims"]
    # output buffers are written chunk-by-chunk inside the scan; inputs are
    # sliced in-body (no stacked copies).  dynamic_slice/update clamp the
    # final start index, so a non-divisible chunk count re-covers the tail
    # exactly (chunk outputs are pure functions of their input slices).
    # The loop and its chunk I/O carry scopes of their own, by which a
    # device trace tells the cost of chunking from the model's work.
    with jax.named_scope("autochunk"):
        with jax.named_scope("autochunk.io"):
            bufs0 = tuple(
                jnp.zeros(v.aval.shape, v.aval.dtype) for v in node.outvars
            )

        def scan_body(bufs, i):
            benv: Dict[Var, Any] = dict(captured)
            benv[LOOP_INDEX] = i
            with jax.named_scope("autochunk.io"):
                for (v, d), full in zip(sliced, sliced_full):
                    benv[v] = _slice_chunk(full, d, i, c)
            _eval_body(p["body"], benv, p["dispatches"])
            with jax.named_scope("autochunk.io"):
                bufs = tuple(
                    _write_chunk(buf, benv[v], d, i, c)
                    for buf, v, d in zip(bufs, node.outvars, out_dims)
                )
            return bufs, None

        bufs, _ = lax.scan(scan_body, bufs0, jnp.arange(n_iters))
    for v, y in zip(node.outvars, bufs):
        env[v] = y


# ---------------------------------------------------------------------------
# The rewrite
# ---------------------------------------------------------------------------

def _adjust_eqn_params(eqn, var_dim: Dict[Var, int], ext: int, c: int):
    """Shrink static shape params of an in-loop equation to chunk size ``c``.

    Primitives like broadcast_in_dim / reshape / slice bake their output
    shapes into eqn.params at trace time; inside the chunk loop the chunked
    dim has extent ``c``, so those params must be rewritten — for *every*
    assigned outvar dim (an eqn can carry several chunked outputs).
    Primitives without shape params re-derive output shapes from their
    (sliced) inputs and need no adjustment.
    """
    out_dims = [
        var_dim[ov] for ov in eqn.outvars if is_var(ov) and ov in var_dim
    ]
    if not out_dims:
        return eqn

    def shrink(size: int) -> int:
        return c if size == ext else size

    def shrink_at(key: str, p: Dict[str, Any]) -> None:
        vals = list(p[key])
        for d in out_dims:
            vals[d] = shrink(vals[d])
        p[key] = tuple(vals)

    name = eqn.primitive.name
    p = dict(eqn.params)
    if name in ("broadcast_in_dim", "iota"):
        shrink_at("shape", p)
        return eqn.replace(params=p)
    if name == "reshape":
        shrink_at("new_sizes", p)
        return eqn.replace(params=p)
    if name == "slice":
        shrink_at("limit_indices", p)
        return eqn.replace(params=p)
    if name == "dynamic_slice":
        shrink_at("slice_sizes", p)
        return eqn.replace(params=p)
    return eqn


def _body_peak_bytes(node: ChunkLoopEqn) -> int:
    """Modeled live HBM bytes while one loop iteration runs.

    Mirrors what the estimation pass would report on a re-trace of the same
    loop: per-chunk input slices + chunk-scaled body intermediates, plus the
    full output buffers the final dynamic_update_slice writes into.
    """
    p = node.params
    c, var_dim = p["c"], p["var_dim"]
    body = p["body"]
    skip = set().union(*(d.skip for d in p["dispatches"])) if p["dispatches"] else set()
    roots = {d.at: d.root for d in p["dispatches"]}

    def nbytes(v) -> int:
        b = atom_bytes(v)
        d = var_dim.get(v)
        if d is not None and v.aval.shape:
            b = int(b * c / max(v.aval.shape[d], 1))
        return b

    last: Dict[Var, int] = {}
    for i, eqn in enumerate(body):
        if i in skip:
            continue
        for iv in eqn.invars:
            if is_var(iv):
                last[iv] = i
    for d in p["dispatches"]:
        # the kernel closure reads its inputs at the match root even though
        # their consuming eqns are skipped — keep them live until then
        for v in d.reads:
            last[v] = max(last.get(v, -1), d.at)
    out_set = set(node.outvars)
    live_set = {v for v, _ in p["sliced"]}
    live = sum(nbytes(v) for v in live_set)
    peak = live
    for i, eqn in enumerate(body):
        if i in roots:
            born = [roots[i]]
        elif i in skip:
            continue
        else:
            born = [ov for ov in eqn.outvars if is_var(ov)]
        for ov in born:
            if ov not in live_set:
                live_set.add(ov)
                live += nbytes(ov)
        peak = max(peak, live)
        dead = [
            v for v in live_set if last.get(v, -1) <= i and v not in out_set
        ]
        for v in dead:
            live_set.remove(v)
            live -= nbytes(v)
    # the reassembly writes: full output buffers co-resident with the last
    # live chunk values (the traced scan shows the same dus-born buffers)
    peak = max(peak, live + sum(atom_bytes(v) for v in node.outvars))
    return peak


def validate_body(node: ChunkLoopEqn) -> None:
    """Abstract-eval the loop body at chunk shapes; raise LoweringError.

    This replaces the legacy backend's per-candidate full re-trace as the
    legality check: a candidate whose adjusted body cannot produce
    chunk-shaped outputs (missed shape param, dtype drift) is rejected
    before it ever reaches the emitted program.
    """
    p = node.params
    sliced_vars = [v for v, _ in p["sliced"]]
    order = sliced_vars + list(p["captured"])

    def run(*vals):
        benv = dict(zip(order, vals))
        benv[LOOP_INDEX] = jnp.zeros((), jnp.int32)
        _eval_body(p["body"], benv, p["dispatches"])
        return tuple(benv[v] for v in node.outvars)

    specs = []
    for v, d in p["sliced"]:
        shp = list(v.aval.shape)
        shp[d] = p["c"]
        specs.append(jax.ShapeDtypeStruct(tuple(shp), v.aval.dtype))
    for v in p["captured"]:
        specs.append(jax.ShapeDtypeStruct(tuple(v.aval.shape), v.aval.dtype))
    try:
        outs = jax.eval_shape(run, *specs)
    except Exception as e:
        raise LoweringError(f"loop body failed abstract eval: {e!r}") from e
    for v, d, o in zip(node.outvars, p["out_dims"], outs):
        want = list(v.aval.shape)
        want[d] = p["c"]
        if tuple(o.shape) != tuple(want) or jnp.dtype(o.dtype) != jnp.dtype(
            v.aval.dtype
        ):
            raise LoweringError(
                f"loop body output mismatch: got {o.shape}/{o.dtype},"
                f" want {tuple(want)}/{v.aval.dtype}"
            )
    node.params["validated"] = True


def validate_pending(g: Graph) -> None:
    """Validate every not-yet-validated chunk_loop node in ``g``.

    The search scores beam candidates on unvalidated rewrites (estimation
    needs no legality proof) and calls this only on the winner — one
    abstract body eval per applied stage instead of one per beam entry.
    """
    for eqn in g.eqns:
        if is_chunk_loop(eqn) and not eqn.params.get("validated"):
            validate_body(eqn)


def make_chunk_loop(g: Graph, cand: ChunkCandidate, n_chunks: int) -> ChunkLoopEqn:
    """Build the structured loop node for one candidate (no validation)."""
    ext = cand.chunk_extent
    n = int(n_chunks)
    c = -(-ext // n)             # ceil: per-chunk slice extent
    n_iters = -(-ext // c)       # actual loop trips (== n when divisible)
    body = [
        _adjust_eqn_params(g.eqns[i], cand.var_dim, ext, c) for i in cand.in_loop
    ]
    sliced_set = {v for v, _ in cand.sliced_in}
    consts_used: List[Var] = []
    seen = set(sliced_set) | set(cand.full_in)
    for eqn in body:
        for iv in eqn.invars:
            if is_var(iv) and iv in g.consts and iv not in seen:
                seen.add(iv)
                consts_used.append(iv)
    captured = list(cand.full_in) + consts_used
    node = ChunkLoopEqn(
        invars=[v for v, _ in cand.sliced_in] + captured,
        outvars=list(cand.loop_out),
        params={
            "body": body,
            "sliced": list(cand.sliced_in),
            "captured": captured,
            "out_dims": [cand.var_dim[v] for v in cand.loop_out],
            "var_dim": dict(cand.var_dim),
            "n_chunks": n,
            "c": c,
            "n_iters": n_iters,
            "chunk_extent": ext,
            "dispatches": (),
            "body_peak": 0,
            "validated": False,
        },
    )
    node.params["body_peak"] = _body_peak_bytes(node)
    if getattr(cand, "kernel_tile_bytes", 0):
        # dispatch-aware selection marked this body as kernelizable: cap the
        # modeled body peak at the VMEM-tile bound so the beam's acceptance
        # estimate agrees with the choose_n estimate that picked n.  The
        # actual dispatch pass recomputes body_peak from the real skip sets
        # (refresh_node), and the final verification re-trace stays truthful.
        node.params["body_peak"] = min(
            node.params["body_peak"], int(cand.kernel_tile_bytes)
        )
    return node


def refresh_node(node: ChunkLoopEqn) -> None:
    """Recompute derived params after a dispatch mutated the node."""
    node.params["body_peak"] = _body_peak_bytes(node)


def apply_chunk(
    g: Graph, cand: ChunkCandidate, n_chunks: int, *, validate: bool = True
) -> Graph:
    """Rewrite ``g`` so that candidate ``cand`` executes as a chunk loop.

    Returns a new :class:`Graph` over the *same* vars: prefix equations,
    then the hoisted (chunk-invariant) equations, then one
    :class:`ChunkLoopEqn`, then the suffix.  Pure data-structure rewrite —
    no tracing; applying a K-stage plan is K calls on one graph.
    """
    stats.bump("lowering_rewrites")
    node = make_chunk_loop(g, cand, n_chunks)
    if validate:
        validate_body(node)
    nodes = (
        list(g.eqns[: cand.s])
        + [g.eqns[i] for i in cand.hoisted]
        + [node]
        + list(g.eqns[cand.e + 1 :])
    )
    return Graph(
        invars=list(g.invars),
        outvars=list(g.outvars),
        eqns=nodes,
        consts=dict(g.consts),
        weight_invars=set(g.weight_invars),
    )


# ---------------------------------------------------------------------------
# Emission
# ---------------------------------------------------------------------------

def emit(g: Graph) -> Callable[..., Tuple[Any, ...]]:
    """Emit the rewritten graph as ONE flat callable.

    The callable evaluates the node list directly (each chunk_loop node as a
    ``lax.scan``), so jitting or tracing it costs a single pass regardless
    of how many chunk stages the graph carries.
    """
    stats.bump("lowering_emits")
    consts = dict(g.consts)
    invars = list(g.invars)
    outvars = list(g.outvars)
    nodes = list(g.eqns)

    def fn(*flat_args):
        env: Dict[Var, Any] = dict(consts)
        env.update(zip(invars, flat_args))
        eval_eqns(nodes, env)
        return tuple(env[ov] if is_var(ov) else ov.val for ov in outvars)

    return fn


# ---------------------------------------------------------------------------
# Padded calls (canonical-shape bucket executables)
# ---------------------------------------------------------------------------
#
# ``ChunkConfig.canonical_bucket_exec`` compiles ONE executable per shape
# bucket, at the bucket's canonical (boundary) shape.  Every other length in
# the bucket is served by the wrapper below: right-pad inputs with zeros up
# to the canonical shape, call the canonical executable (same input
# signature every time, so zero traces and zero XLA compiles), then slice
# outputs back to the true shapes.
#
# Semantics contract: the wrapped function must be *length-masked* — real
# output positions must not depend on padded buffer content.  That holds
# when attention masks / position logic are computed from a true-length or
# position argument that passes through unpadded (scalars and sub-min_dim
# axes are never padded), exactly like a serving decode step masked by its
# position counter.  The padded output rows/columns are garbage and are
# sliced off; everything kept is bitwise what the unpadded executable would
# have produced under the same mask.


def pad_to_shape(x, shape: Sequence[int]):
    """Right-pad ``x`` with zeros up to ``shape`` (no-op when equal)."""
    target = tuple(int(s) for s in shape)
    x = jnp.asarray(x)
    if tuple(x.shape) == target:
        return x
    if len(target) != x.ndim or any(t < s for s, t in zip(x.shape, target)):
        raise ValueError(
            f"cannot pad shape {tuple(x.shape)} up to {target}"
        )
    pads = [(0, t - s, 0) for s, t in zip(x.shape, target)]
    return lax.pad(x, jnp.zeros((), x.dtype), pads)


def slice_to_shape(y, shape: Sequence[int]):
    """Slice ``y`` back down to ``shape`` (no-op when equal)."""
    target = tuple(int(s) for s in shape)
    if tuple(y.shape) == target:
        return y
    if len(target) != y.ndim or any(t > s for s, t in zip(y.shape, target)):
        raise ValueError(
            f"cannot slice shape {tuple(y.shape)} down to {target}"
        )
    return y[tuple(slice(0, t) for t in target)]


def emit_padded_call(fn: Callable, arg_specs, out_specs) -> Callable:
    """Wrap a canonical-shape callable with the pad/unpad protocol.

    ``fn``         callable compiled at the bucket's canonical shapes
                   (original pytree signature)
    ``arg_specs``  pytree of ``ShapeDtypeStruct`` giving the canonical input
                   shapes ``fn`` was compiled at
    ``out_specs``  pytree of ``ShapeDtypeStruct`` giving the TRUE output
                   shapes for the caller's actual input shapes (from
                   ``jax.eval_shape`` at the true shapes — abstract only,
                   never an XLA compile)

    The returned callable takes true-shape args, pads each leaf up to its
    canonical spec, invokes ``fn`` (whose jit signature therefore never
    changes inside a bucket), and slices every output leaf down to its true
    spec.  Dim provenance is exact: outputs are cut to the shapes the
    function genuinely produces at the true input shapes, so an output axis
    that merely *coincides* with a padded extent is never mis-sliced.
    """
    from jax import tree_util

    flat_specs, spec_tree = tree_util.tree_flatten(arg_specs)

    def padded_call(*args):
        leaves, in_tree = tree_util.tree_flatten(tuple(args))
        if in_tree != spec_tree or len(leaves) != len(flat_specs):
            raise ValueError(
                "padded call arg structure does not match the canonical"
                " executable's signature"
            )
        stats.bump("padded_calls")
        padded = [
            pad_to_shape(x, s.shape) for x, s in zip(leaves, flat_specs)
        ]
        out = fn(*tree_util.tree_unflatten(in_tree, padded))
        return jax.tree.map(
            lambda y, sp: slice_to_shape(y, sp.shape), out, out_specs
        )

    return padded_call
