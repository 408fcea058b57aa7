"""Staged AOT compilation: ``ChunkedFunction`` -> Traced -> Planned -> Compiled.

The paper describes AutoChunk as a compiler with distinct passes (estimate ->
chunk search -> chunk selection -> codegen).  This module makes each pass a
first-class stage object, mirroring ``jax.jit``'s AOT surface
(``.trace()/.lower()/.compile()``):

    cf = autochunk(fn, ChunkConfig(budget_ratio=0.4))
    traced   = cf.trace(*specs)     # jaxpr graph + memory profile
    planned  = traced.search()      # chunk search + selection -> ChunkPlan
    compiled = planned.compile()    # codegen (+ the plan's wrapped callable)
    y = compiled(*args)

Each stage is independently reusable and cacheable: ``Traced`` carries the
graph and baseline memory profile, ``Planned`` carries the serializable
:class:`~repro.core.plan.ChunkPlan` (inspectable and persistable before any
execution), ``CompiledFunction`` the runnable result.  Calling a
``ChunkedFunction`` directly compiles lazily per input shape.

Shape-bucketed plan reuse: when the ``ChunkedFunction`` has a
:class:`~repro.core.config.ShapeBucketer` (the default), a plan searched at
one shape is *replayed* — rescaled chunk extents, zero search/selection
passes — for every other shape in the same bucket.  ``core.stats`` counters
(``search_passes``, ``plan_bucket_hits``) make that contract observable.
"""
from __future__ import annotations

import functools
import hashlib
import json
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
from jax import tree_util

from . import stats
from ..obs import accuracy as obs_accuracy
from ..obs.tracing import span
from .codegen import build_fn_from_plan
from .config import ChunkConfig, ShapeBucketer
from .estimation import MemoryProfile, estimate_memory
from .graph import Graph, trace
from .kernel_dispatch import dispatch_graph
from .lowering import apply_chunk, emit, emit_padded_call, validate_pending
from .plan import ChunkPlan, PlanApplyError, PlanStage, as_plan_cache, plan_cache_key
from .search import search_chunks
from .selection import rank_candidates

_DEFAULT_BUCKETER = object()  # sentinel: "use a fresh default ShapeBucketer"


# ---------------------------------------------------------------------------
# Result records (shared with the legacy one-shot API)
# ---------------------------------------------------------------------------

@dataclass
class StageRecord:
    stage: int
    region: Tuple[int, int]
    n_chunks: int
    chunk_extent: int
    n_loop_eqns: int
    n_hoisted: int
    cost: float
    peak_before: int
    peak_after: int


@dataclass
class AutoChunkResult:
    """A chunked callable plus the full compilation report."""

    fn: Callable                      # original signature
    flat_fn: Callable                 # flat leaves -> flat leaves
    plan: List[StageRecord]
    baseline_peak: int
    final_peak: int
    budget_bytes: int
    io_bytes: int
    weight_bytes: int
    elapsed_s: float = 0.0
    plan_stages: List[PlanStage] = field(default_factory=list)
    from_cache: bool = False
    cache_key: Optional[str] = None
    tuning: Optional[Dict[str, Any]] = None  # autotuned kernel configs (v4)
    # predicted-vs-measured activation peak (repro.obs.accuracy), attached
    # by Planned.compile(): the search-time analytic prediction next to the
    # emitted program's live-set watermark
    accuracy: Optional[obs_accuracy.PlanAccuracy] = None

    def to_chunk_plan(self) -> ChunkPlan:
        """Detach the compilation into a serializable :class:`ChunkPlan`."""
        return ChunkPlan(
            cache_key=self.cache_key or "",
            budget_bytes=self.budget_bytes,
            baseline_peak=self.baseline_peak,
            final_peak=self.final_peak,
            stages=list(self.plan_stages),
            meta={
                "io_bytes": self.io_bytes,
                "weight_bytes": self.weight_bytes,
                "compile_s": round(self.elapsed_s, 3),
            },
            tuning=dict(self.tuning) if self.tuning else None,
        )

    @property
    def reduction(self) -> float:
        if self.baseline_peak == 0:
            return 0.0
        return 1.0 - self.final_peak / self.baseline_peak

    def report(self) -> str:
        lines = [
            "AutoChunk plan:",
            f"  baseline peak activation: {self.baseline_peak/2**20:.2f} MiB",
            f"  budget:                   {self.budget_bytes/2**20:.2f} MiB",
            f"  final peak activation:    {self.final_peak/2**20:.2f} MiB"
            f"  ({self.reduction*100:.1f}% reduction)",
            f"  io bytes: {self.io_bytes/2**20:.2f} MiB,"
            f" weights: {self.weight_bytes/2**20:.2f} MiB",
            f"  compile time: {self.elapsed_s:.2f}s, stages: {len(self.plan)}"
            + (" [from cache]" if self.from_cache else ""),
        ]
        for r in self.plan:
            lines.append(
                f"    stage {r.stage}: region [{r.region[0]},{r.region[1]}]"
                f" n={r.n_chunks} (extent {r.chunk_extent})"
                f" loop_eqns={r.n_loop_eqns} hoisted={r.n_hoisted}"
                f" peak {r.peak_before/2**20:.1f} -> {r.peak_after/2**20:.1f} MiB"
                f" cost={r.cost:.3f}"
            )
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Shared plumbing
# ---------------------------------------------------------------------------

def _progress_metric(prof: MemoryProfile):
    """Lexicographic progress: peak, #equations at >=99% of peak, then the
    mass of the top-8 live sets.  Repeated layer stacks tie on raw peak, so
    a stage that flattens one of several equal peaks must still count as
    progress (the next stage attacks the remaining ones)."""
    peak = prof.peak_bytes
    near = sum(1 for b in prof.per_eqn_bytes if b >= 0.99 * peak)
    top = sum(sorted(prof.per_eqn_bytes)[-8:])
    return (peak, near, top)


def _flatten_spec(example_args: Sequence[Any], weight_argnums: Sequence[int]):
    flat, in_tree = tree_util.tree_flatten(tuple(example_args))
    counts = [len(tree_util.tree_leaves(a)) for a in example_args]
    weight_flat: List[int] = []
    pos = 0
    for i, c in enumerate(counts):
        if i in weight_argnums:
            weight_flat.extend(range(pos, pos + c))
        pos += c
    return flat, in_tree, weight_flat


def _leaf_aval(x) -> Tuple[Tuple[int, ...], str]:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return tuple(int(s) for s in x.shape), str(x.dtype)
    import numpy as np

    arr = np.asarray(x)
    return tuple(arr.shape), str(arr.dtype)


def _package_result(
    *,
    fn: Callable,
    out_tree_box: List[Any],
    plan: List[StageRecord],
    plan_stages: List[PlanStage],
    baseline_peak: int,
    final_peak: int,
    budget_bytes: int,
    io_bytes: int,
    weight_bytes: int,
    elapsed_s: float,
    from_cache: bool = False,
    cache_key: Optional[str] = None,
    tuning: Optional[Dict[str, Any]] = None,
) -> AutoChunkResult:
    """Wrap a flat callable back into the original pytree signature."""
    final_flat = fn

    def wrapped(*args):
        leaves, _ = tree_util.tree_flatten(tuple(args))
        out_leaves = final_flat(*leaves)
        return tree_util.tree_unflatten(out_tree_box[0], list(out_leaves))

    return AutoChunkResult(
        fn=wrapped,
        flat_fn=final_flat,
        plan=plan,
        baseline_peak=baseline_peak,
        final_peak=final_peak,
        budget_bytes=budget_bytes,
        io_bytes=io_bytes,
        weight_bytes=weight_bytes,
        elapsed_s=elapsed_s,
        plan_stages=plan_stages,
        from_cache=from_cache,
        cache_key=cache_key,
        tuning=tuning,
    )


# ---------------------------------------------------------------------------
# The search pipeline (the paper's chunk-search + chunk-selection passes)
# ---------------------------------------------------------------------------

def _search_loop(
    g: Graph,
    prof: MemoryProfile,
    budget_bytes: int,
    config: ChunkConfig,
):
    """Greedy staged search with beam verification (paper Alg. 1 driver).

    Each accepted stage is a pure graph rewrite
    (:func:`~repro.core.lowering.apply_chunk`) verified by re-estimating the
    rewritten graph — no tracing happens anywhere in the search, so the
    compile's trace count stays independent of the stage count.
    """
    kd = config.resolve_kernel_dispatch()
    records: List[StageRecord] = []
    pstages: List[PlanStage] = []
    for stage in range(config.max_stages):
        if prof.peak_bytes <= budget_bytes:
            break
        cands = search_chunks(
            g, prof, window=config.window, allow_hoist=config.allow_hoist,
            dim_blocklist=frozenset(config.dim_blocklist),
        )
        with span("compile.select", stage=stage, candidates=len(cands)):
            ranked = rank_candidates(
                g, prof, cands, budget_bytes, config.hyper, kernel_dispatch=kd,
                mask_mode=config.mask_mode,
            )
        if config.verbose:
            print(
                f"[autochunk] stage {stage}: peak={prof.peak_bytes/2**20:.1f}MiB"
                f" budget={budget_bytes/2**20:.1f}MiB candidates={len(ranked)}"
            )
        # DP-with-beam: rewrite the top-`beam` candidates (no tracing),
        # re-estimate, keep the best (meets-budget, lowest cost, lowest
        # estimated peak).  Only the winner pays the abstract body eval;
        # a validation failure falls through to the next-best rewrite.
        cur_metric = _progress_metric(prof)
        verified = []
        for cand, n, est, cost in ranked[: config.beam]:
            try:
                g2 = apply_chunk(g, cand, n, validate=False)
                prof2 = estimate_memory(g2, mesh_spec=config.mesh_spec)
            except Exception:
                continue
            big_gain = prof2.peak_bytes < prof.peak_bytes * (1.0 - config.min_gain)
            if not big_gain and _progress_metric(prof2) >= cur_metric:
                continue  # no peak gain and no structural progress
            over = prof2.peak_bytes > budget_bytes
            key = (
                (over, cost, prof2.peak_bytes)
                if not over
                else (over,) + _progress_metric(prof2) + (cost,)
            )
            verified.append((key, cand, n, cost, g2, prof2))
        applied = None
        for key, cand, n, cost, g2, prof2 in sorted(verified, key=lambda t: t[0]):
            try:
                validate_pending(g2)
            except Exception:
                continue
            applied = (cand, n, cost, g2, prof2)
            break
        if applied is None:
            break
        cand, n, cost, g2, prof2 = applied
        records.append(
            StageRecord(
                stage=stage,
                region=(cand.s, cand.e),
                n_chunks=n,
                chunk_extent=cand.chunk_extent,
                n_loop_eqns=len(cand.in_loop),
                n_hoisted=len(cand.hoisted),
                cost=cost,
                peak_before=prof.peak_bytes,
                peak_after=prof2.peak_bytes,
            )
        )
        pstages.append(
            PlanStage.from_candidate(
                g, cand, n, cost=cost,
                peak_before=prof.peak_bytes, peak_after=prof2.peak_bytes,
            )
        )
        g, prof = g2, prof2
    return g, prof, records, pstages


def _search_with_anneal(g0, prof0, budget_bytes, config):
    """Search, then budget-anneal: the analytic per-stage estimate is
    optimistic for loose budgets, so a missed target retries the whole
    pipeline against a tighter internal budget and keeps whichever plan
    estimates lower."""
    g, prof, records, pstages = _search_loop(g0, prof0, budget_bytes, config)
    if prof.peak_bytes > budget_bytes and config.anneal > 0 and pstages:
        retry = _search_with_anneal(
            g0, prof0,
            max(budget_bytes // 2, 1),
            config.with_(anneal=config.anneal - 1),
        )
        if retry[1].peak_bytes < prof.peak_bytes:
            return retry
    return g, prof, records, pstages


# ---------------------------------------------------------------------------
# Stage objects
# ---------------------------------------------------------------------------

class Traced:
    """Stage 1: traced graph + baseline memory profile (the estimate pass).

    Produced by :meth:`ChunkedFunction.trace`; nothing is materialized —
    example args may be arrays or ``ShapeDtypeStruct``s.
    """

    def __init__(self, cf: "ChunkedFunction", example_args: Sequence[Any]):
        self.cf = cf
        config = cf.config
        self._t0 = time.perf_counter()
        self.flat_args, self.in_tree, self.weight_flat = _flatten_spec(
            example_args, config.weight_argnums
        )
        self.out_tree_box: List[Any] = [None]
        in_tree, out_tree_box, fn = self.in_tree, self.out_tree_box, cf.fn

        def flat_fn(*leaves):
            args = tree_util.tree_unflatten(in_tree, leaves)
            out = fn(*args)
            out_leaves, out_tree = tree_util.tree_flatten(out)
            out_tree_box[0] = out_tree
            return tuple(out_leaves)

        self.flat_fn = flat_fn
        with span("compile.trace", leaves=len(self.flat_args)):
            self.graph, _ = trace(
                flat_fn, self.flat_args, weight_argnums=self.weight_flat
            )
        with span("compile.estimate"):
            self.profile: MemoryProfile = estimate_memory(
                self.graph, mesh_spec=config.mesh_spec
            )
        self.baseline_peak: int = self.profile.peak_bytes
        self.budget_bytes: int = config.resolve_budget(self.baseline_peak)

    # -- inspection ---------------------------------------------------------
    @property
    def memory_profile(self) -> MemoryProfile:
        return self.profile

    def cache_key(self) -> str:
        """Exact structural plan-cache key for this trace + config."""
        config = self.cf.config
        return plan_cache_key(
            self.graph, self.budget_bytes, config.hyper, config.search_knobs()
        )

    def bucket_key(self) -> Optional[str]:
        """Shape-bucket key (None when bucketing is disabled)."""
        bucketer = self.cf.bucketer
        if bucketer is None:
            return None
        fn = self.cf.fn
        doc = {
            "fn": f"{getattr(fn, '__module__', '?')}."
                  f"{getattr(fn, '__qualname__', repr(fn))}",
            "tree": str(self.in_tree),
            "weights": list(self.weight_flat),
            "sig": [
                [list(bucketer.bucket_shape(shape)), dtype]
                for shape, dtype in map(_leaf_aval, self.flat_args)
            ],
            "config": self.cf.config.cache_token(),
        }
        blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(blob.encode()).hexdigest()

    # -- stage transition ---------------------------------------------------
    def search(self) -> "Planned":
        """Run chunk search + selection (or replay a cached/bucketed plan).

        Lookup order: exact structural key in the plan cache, then the
        shape bucket (same function + config, a *similar* shape).  Either
        hit replays with zero search/selection passes; replay failures fall
        through to the cold pipeline.
        """
        cf, config = self.cf, self.cf.config
        cache, ckey = cf.cache, self.cache_key()

        if cache is not None:
            saved = cache.get(ckey)
            if saved is not None:
                stats.bump("plan_cache_hits")
                planned = self._replay(saved, rescale=False)
                if planned is not None:
                    return planned
            else:
                stats.bump("plan_cache_misses")

        bkey = self.bucket_key()
        if bkey is not None:
            saved = cf._bucket_plans.get(bkey)
            if saved is None and cache is not None:
                saved = cache.get_bucket(bkey)
            planned = (
                self._replay(saved, rescale=True) if saved is not None else None
            )
            if planned is not None:
                # a hit is only a hit once the replay validated — failed or
                # rejected replays fall through to the search and count as
                # misses, so "bucket hit" always implies zero search passes
                stats.bump("plan_bucket_hits")
                cf.counters["bucket_hits"] += 1
                if cache is not None:  # exact-hit next time at this shape
                    cache.put(ckey, planned.plan)
                return planned
            stats.bump("plan_bucket_misses")
            cf.counters["bucket_misses"] += 1

        with span("compile.search", budget_bytes=self.budget_bytes):
            lowered, prof, records, pstages = _search_with_anneal(
                self.graph, self.profile, self.budget_bytes, config,
            )
        # single-lowering emission: the multi-stage plan was applied as
        # graph rewrites above; dispatch + emit + ONE verification re-trace
        # happen here regardless of how many stages were applied
        tuning = None
        if pstages:
            if config.resolve_kernel_dispatch():
                # one autotune pass per cold compile; the winning tuning is
                # persisted in the plan so warm replays pass it back in
                # (autotune_passes stays 0 on every cache/bucket hit)
                with span("compile.lower", stages=len(pstages)):
                    lowered, tuning = dispatch_graph(
                        lowered,
                        autotune=config.resolve_autotune(),
                        mask_mode=config.mask_mode,
                    )
            with span("compile.emit", stages=len(pstages)):
                cur = emit(lowered)
                g, _ = trace(
                    cur, self.flat_args, weight_argnums=self.weight_flat
                )
                prof = estimate_memory(g, mesh_spec=config.mesh_spec)
            if prof.peak_bytes > self.baseline_peak:
                # the rewrite-time estimate was optimistic and the emitted
                # program would raise the peak: keep the unchunked program
                lowered, records, pstages, tuning = self.graph, [], [], None
        if not pstages:  # nothing chunked: the baseline graph is the program
            cur, g, prof = self.flat_fn, self.graph, self.profile
        meta = {
            "io_bytes": prof.io_bytes,
            "weight_bytes": prof.weight_bytes,
            "compile_s": round(time.perf_counter() - self._t0, 3),
        }
        if config.mesh_spec is not None:
            stats.bump("sharded_plans")
            if config.mesh_spec.seq_axis is not None and pstages:
                # sequence-parallel execution specs for the chunk regions,
                # computed against the rewritten graph (the only place the
                # chunk_loop nodes are visible) and persisted so warm
                # replays — which skip the rewritten form — reuse them
                from .meshspec import sequence_parallel_in_specs

                specs = sequence_parallel_in_specs(lowered, config.mesh_spec)
                meta["exec_in_specs"] = [
                    None if s is None else list(s) for s in specs
                ]
        plan = ChunkPlan(
            cache_key=ckey,
            budget_bytes=self.budget_bytes,
            baseline_peak=self.baseline_peak,
            final_peak=prof.peak_bytes,
            stages=pstages,
            meta=meta,
            tuning=tuning.to_dict() if tuning is not None else None,
            mesh=(
                config.mesh_spec.to_dict()
                if config.mesh_spec is not None else None
            ),
        )
        if cache is not None:
            cache.put(ckey, plan)
        if bkey is not None:
            cf._bucket_plans[bkey] = plan
            if cache is not None:
                cache.put_bucket(bkey, plan)
        return Planned(
            traced=self, plan=plan, records=records,
            flat_fn=cur, graph=g, profile=prof,
            lowered_graph=lowered,
            from_cache=False, bucket_hit=False,
        )

    def _replay(self, saved: ChunkPlan, *, rescale: bool) -> Optional["Planned"]:
        """Apply a stored plan to this trace; None means fall back to search.

        Replay is lowering-backed: K stage rewrites on the already-traced
        baseline graph, one emit, ONE verification re-trace — the only
        trace of the whole warm path, independent of the stage count.
        """
        rec: List[Tuple[Graph, Any, int]] = []
        try:
            with span("compile.replay", stages=len(saved.stages),
                      rescale=rescale):
                fn, g, prof = build_fn_from_plan(
                    self.flat_fn, self.flat_args, saved,
                    weight_argnums=self.weight_flat,
                    baseline_graph=self.graph,
                    rescale=rescale,
                    record=rec,
                    kernel_dispatch=self.cf.config.resolve_kernel_dispatch(),
                    mask_mode=self.cf.config.mask_mode,
                    mesh_spec=self.cf.config.mesh_spec,
                )
        except PlanApplyError:
            stats.bump("plan_replay_failures")
            return None
        if rescale:
            # quality guard, shape-invariant: accept the rescaled replay if
            # it fits this shape's budget, or at least achieves (about) the
            # relative reduction the plan achieved at its home shape — a
            # fresh search would not do materially better there either.
            ok = prof.peak_bytes <= self.budget_bytes
            if not ok and saved.baseline_peak > 0:
                home_ratio = saved.final_peak / saved.baseline_peak
                ok = prof.peak_bytes <= self.baseline_peak * home_ratio * 1.05
            if not ok:
                stats.bump("plan_bucket_rejects")
                return None
        if rescale:
            # per-stage peaks at *this* shape: each recorded graph is the
            # state the stage was applied on, the next graph (or the final
            # profile) is the state after it
            peaks = [
                estimate_memory(gi, mesh_spec=self.cf.config.mesh_spec).peak_bytes
                for gi, _, _ in rec
            ]
            peaks.append(prof.peak_bytes)
            pstages = [
                PlanStage.from_candidate(
                    gi, cand, n, cost=saved.stages[i].cost,
                    peak_before=peaks[i], peak_after=peaks[i + 1],
                )
                for i, (gi, cand, n) in enumerate(rec)
            ]
            meta = dict(saved.meta)
            meta["rescaled_from"] = saved.cache_key
            plan = ChunkPlan(
                cache_key=self.cache_key(),
                budget_bytes=self.budget_bytes,
                baseline_peak=self.baseline_peak,
                final_peak=prof.peak_bytes,
                stages=pstages,
                meta=meta,
                tuning=saved.tuning,  # bucket hits inherit the home tuning
                mesh=saved.mesh,
            )
        else:
            plan = saved
        if self.cf.config.mesh_spec is not None:
            stats.bump("sharded_plans")
        records = [
            StageRecord(
                stage=i,
                region=(st.s, st.e),
                n_chunks=st.n_chunks,
                chunk_extent=st.chunk_extent,
                n_loop_eqns=len(st.in_loop),
                n_hoisted=len(st.hoisted),
                cost=st.cost,
                peak_before=st.peak_before,
                peak_after=st.peak_after,
            )
            for i, st in enumerate(plan.stages)
        ]
        return Planned(
            traced=self, plan=plan, records=records,
            flat_fn=fn, graph=g, profile=prof,
            from_cache=True, bucket_hit=rescale,
        )


@dataclass
class Lowered:
    """Product of :meth:`Planned.lower`: the final rewritten program.

    ``jaxpr``  the verified ``ClosedJaxpr`` of the emitted single callable
               (prefix/hoisted/suffix inline, one ``scan`` per chunk stage)
    ``graph``  the rewritten :class:`Graph` with its structured
               ``chunk_loop`` nodes, when produced by a cold compile
               (``None`` on plan replays, which skip the intermediate form)
    """

    jaxpr: Any
    graph: Optional[Graph] = None

    def as_text(self) -> str:
        return str(self.jaxpr)

    def eqn_count(self) -> int:
        return len(self.jaxpr.jaxpr.eqns)


@dataclass
class Planned:
    """Stage 2: a finished chunk search — the :class:`ChunkPlan` plus the
    verified rewritten callable.  Inspect/serialize the plan (``.plan``,
    ``.save()``) or ``.lower()`` to the rewritten jaxpr before deciding to
    pay for jit."""

    traced: Traced
    plan: ChunkPlan
    records: List[StageRecord]
    flat_fn: Callable
    graph: Graph
    profile: MemoryProfile
    lowered_graph: Optional[Graph] = None
    from_cache: bool = False
    bucket_hit: bool = False

    @property
    def final_peak(self) -> int:
        return self.profile.peak_bytes

    @property
    def baseline_peak(self) -> int:
        return self.traced.baseline_peak

    @property
    def budget_bytes(self) -> int:
        return self.traced.budget_bytes

    def save(self, path) -> None:
        self.plan.save(path)

    def lower(self) -> Lowered:
        """Expose the final rewritten jaxpr (for inspection, cross-process
        codegen, or AOT pipelines that want the IR rather than a callable).

        The jaxpr comes from the single verification re-trace the search or
        replay already performed — calling ``lower()`` never re-traces.
        """
        return Lowered(
            jaxpr=getattr(self.graph, "closed_jaxpr", None),
            graph=self.lowered_graph,
        )

    def compile(self) -> "CompiledFunction":
        """Stage 3: package the plan's callable (codegen already verified)."""
        t = self.traced
        result = _package_result(
            fn=self.flat_fn,
            out_tree_box=t.out_tree_box,
            plan=self.records,
            plan_stages=list(self.plan.stages),
            baseline_peak=t.baseline_peak,
            final_peak=self.profile.peak_bytes,
            budget_bytes=t.budget_bytes,
            io_bytes=self.profile.io_bytes,
            weight_bytes=self.profile.weight_bytes,
            elapsed_s=time.perf_counter() - t._t0,
            from_cache=self.from_cache,
            cache_key=self.plan.cache_key,
            tuning=self.plan.tuning,
        )
        result.accuracy = self.plan_accuracy()
        obs_accuracy.publish(result.accuracy)
        return CompiledFunction(
            result,
            bucket_hit=self.bucket_hit,
            mesh_spec=t.cf.config.mesh_spec,
            exec_in_specs=self.plan.meta.get("exec_in_specs"),
            in_tree=t.in_tree,
        )

    def plan_accuracy(self) -> obs_accuracy.PlanAccuracy:
        """Predicted-vs-measured activation peak for this plan.

        *Predicted* is the search-time analytic number — the selected
        candidate's modeled ``peak_after`` (the ``chunk_loop`` body-peak
        model, computed without any re-trace).  *Measured* is the exact
        SSA live-set watermark of the emitted, verified jaxpr (real
        ``scan`` bodies — the program that will actually run), so the
        error is the analytic model's structural drift.  On backends with
        allocator stats the serving layer upgrades the measurement to
        ``device.memory_stats()`` deltas after execution.
        """
        predicted = (
            self.plan.stages[-1].peak_after
            if self.plan.stages else self.plan.baseline_peak
        )
        closed = getattr(self.graph, "closed_jaxpr", None)
        mesh_spec = self.traced.cf.config.mesh_spec
        if mesh_spec is not None and closed is not None:
            # Per-device accuracy: the profile's sharded peak vs the full
            # watermark scaled down by the same estimation-derived factor.
            # The divisor is computed here (two estimation runs on the same
            # emitted graph) so obs stays importable without repro.core.
            full_peak = estimate_memory(self.graph).peak_bytes
            divisor = (
                full_peak / self.profile.peak_bytes
                if self.profile.peak_bytes > 0 else 1.0
            )
            return obs_accuracy.per_device_accuracy(
                predicted, closed,
                peak_divisor=max(divisor, 1.0),
                cache_key=self.plan.cache_key,
                final_peak_estimate=self.profile.peak_bytes,
            )
        if closed is not None:
            measured = obs_accuracy.watermark_jaxpr(closed)
        else:
            measured = self.profile.peak_bytes
        return obs_accuracy.compare(
            predicted, measured, "interpret",
            cache_key=self.plan.cache_key,
            final_peak_estimate=self.profile.peak_bytes,
        )


class CompiledFunction:
    """Stage 3 product: the chunked executable with its compilation report.

    Calling it jits lazily; ``.fn`` is the un-jitted callable (compose it
    with ``jax.jit``/``shard_map``/``grad`` yourself when preferred).
    """

    def __init__(
        self,
        result: AutoChunkResult,
        *,
        bucket_hit: bool = False,
        mesh_spec=None,
        exec_in_specs=None,
        in_tree=None,
    ):
        self.result = result
        self.fn = result.fn
        self.bucket_hit = bucket_hit
        self.autochunk_result = result  # legacy attribute location
        self.mesh_spec = mesh_spec
        self.exec_in_specs = exec_in_specs
        self._in_tree = in_tree
        self._jitted: Optional[Callable] = None

    @property
    def from_cache(self) -> bool:
        return self.result.from_cache

    @property
    def final_peak(self) -> int:
        return self.result.final_peak

    def report(self) -> str:
        return self.result.report()

    def xla_cache_size(self) -> int:
        """Number of XLA executables behind the lazy jit.

        The one-executable-per-bucket invariant is stated in these terms: a
        canonical bucket executable's cache size stays 1 no matter how many
        distinct lengths inside the bucket it serves (padded calls reuse the
        canonical input signature).
        """
        if self._jitted is None:
            return 0
        return int(self._jitted._cache_size())

    def _in_shardings(self):
        """Arg-tree of ``NamedSharding``s when a mesh is configured.

        Uses the plan's persisted sequence-parallel ``exec_in_specs`` when
        present (they subsume the user ``in_specs``); otherwise falls back
        to the mesh's declared input specs.  Returns ``None`` (plain jit)
        without a mesh or when the mesh cannot be built on this host.
        """
        if self.mesh_spec is None or self._in_tree is None:
            return None
        from jax.sharding import NamedSharding

        mesh = self.mesh_spec.build_mesh()
        n = self._in_tree.num_leaves
        specs = self.exec_in_specs
        if specs is None:
            specs = self.mesh_spec.in_specs
        leaves = []
        for i in range(n):
            spec = specs[i] if i < len(specs) else None
            if spec is not None:
                spec = tuple(
                    e if (e is None or isinstance(e, str)) else tuple(e)
                    for e in spec
                )
            leaves.append(NamedSharding(mesh, self.mesh_spec.pspec(spec)))
        return tree_util.tree_unflatten(self._in_tree, leaves)

    def __call__(self, *args):
        if self._jitted is None:
            shardings = self._in_shardings()
            if shardings is not None:
                self._jitted = jax.jit(self.fn, in_shardings=shardings)
            else:
                self._jitted = jax.jit(self.fn)
        return self._jitted(*args)


# ---------------------------------------------------------------------------
# The transform
# ---------------------------------------------------------------------------

class ChunkedFunction:
    """``autochunk(fn, config)``: a function transformed for chunked execution.

    Three ways to run it:

    * **Direct call** — ``cf(*args)`` compiles lazily for the concrete input
      shapes (one compile per shape bucket, replayed for sibling shapes) and
      executes.
    * **Staged AOT** — ``cf.trace(*specs).search().compile()`` exposes each
      compiler pass; specs may be ``ShapeDtypeStruct``s so nothing is
      materialized.
    * **Decorator** — ``@autochunk(ChunkConfig(...))`` above a function
      definition.
    """

    def __init__(
        self,
        fn: Callable,
        config: Optional[ChunkConfig] = None,
        *,
        cache=None,
        bucketer=_DEFAULT_BUCKETER,
    ):
        if not callable(fn):
            raise TypeError(f"autochunk target must be callable, got {fn!r}")
        self.fn = fn
        self.config = config if config is not None else ChunkConfig()
        if not isinstance(self.config, ChunkConfig):
            raise TypeError(
                f"config must be a ChunkConfig, got {type(self.config).__name__}"
            )
        self.cache = as_plan_cache(cache)
        self.bucketer: Optional[ShapeBucketer] = (
            ShapeBucketer() if bucketer is _DEFAULT_BUCKETER else bucketer
        )
        self._bucket_plans: Dict[str, ChunkPlan] = {}
        self._compiled: Dict[Any, CompiledFunction] = {}
        # canonical-shape bucket executables: one CompiledFunction per bucket
        # signature, compiled at the bucket boundary; `_padded` memoizes the
        # pad/unpad wrapper per exact (non-canonical) input signature
        self._bucket_execs: Dict[Any, CompiledFunction] = {}
        self._padded: Dict[Any, Callable] = {}
        self.counters: Dict[str, int] = {
            "calls": 0,
            "compiles": 0,
            "shape_hits": 0,
            "bucket_hits": 0,
            "bucket_misses": 0,
            "bucket_exec_hits": 0,
            "bucket_exec_compiles": 0,
        }
        functools.update_wrapper(self, fn, updated=())

    # -- staged AOT ---------------------------------------------------------
    def trace(self, *example_args) -> Traced:
        """Stage 1: trace + memory estimate at the given (abstract) args."""
        if not example_args:
            raise ValueError("trace() needs at least one example argument")
        return Traced(self, example_args)

    def compile(self, *example_args) -> CompiledFunction:
        """One-shot AOT: ``trace -> search -> compile`` for these args."""
        compiled = self.trace(*example_args).search().compile()
        self._maybe_evict()
        return compiled

    def _maybe_evict(self) -> int:
        """Honor the config's eviction knobs after a compile touched the
        plan cache (a compile is the only point this transform grows it)."""
        cfg = self.config
        if self.cache is None or cfg.cache_max_entries is None:
            return 0
        return self.cache.evict(
            policy=cfg.cache_policy, max_entries=cfg.cache_max_entries
        )

    # -- direct call --------------------------------------------------------
    def _shape_key(self, args) -> Any:
        leaves, treedef = tree_util.tree_flatten(tuple(args))
        return (str(treedef), tuple(_leaf_aval(x) for x in leaves))

    def __call__(self, *args):
        self.counters["calls"] += 1
        key = self._shape_key(args)
        compiled = self._compiled.get(key)
        if compiled is not None:
            self.counters["shape_hits"] += 1
            return compiled(*args)
        padded_fn = self._padded.get(key)
        if padded_fn is not None:
            # an already-wrapped non-canonical length: pure pad -> canonical
            # executable -> slice; still a bucket-executable hit
            self.counters["shape_hits"] += 1
            self.counters["bucket_exec_hits"] += 1
            stats.bump("bucket_exec_hits")
            return padded_fn(*args)
        if self.config.canonical_bucket_exec and self.bucketer is not None:
            return self._canonical_call(key, args)
        self.counters["compiles"] += 1
        compiled = self.compile(*args)
        self._compiled[key] = compiled
        return compiled(*args)

    # -- canonical-shape bucket executables ---------------------------------
    def _canonical_specs(self, args):
        """Bucket signature + canonical ShapeDtypeStruct args for ``args``.

        Non-weight leaves are rounded up to the bucket boundary (the
        canonical shape the bucket executable compiles at); weight leaves
        keep their exact shapes — padding parameters would change the
        program, and weight shapes do not vary across serving traffic.
        """
        flat, in_tree, weight_flat = _flatten_spec(
            args, self.config.weight_argnums
        )
        wset = frozenset(weight_flat)
        canon: List[Tuple[Tuple[int, ...], str]] = []
        needs_pad = False
        for i, leaf in enumerate(flat):
            shape, dtype = _leaf_aval(leaf)
            cshape = (
                shape if i in wset else self.bucketer.canonical_shape(shape)
            )
            if cshape != shape:
                needs_pad = True
            canon.append((cshape, dtype))
        key = (str(in_tree), tuple(canon))
        spec_args = tree_util.tree_unflatten(
            in_tree, [jax.ShapeDtypeStruct(s, d) for s, d in canon]
        )
        return key, spec_args, needs_pad

    def _canonical_call(self, key, args):
        """Serve ``args`` through the bucket's canonical executable.

        First sight of a bucket compiles ONE CompiledFunction at the bucket
        boundary; every other length in the bucket (including this call, if
        non-canonical) is padded up to the boundary and sliced back — zero
        traces, zero searches, zero new XLA executables.  The function must
        be length-masked (see ``ChunkConfig.canonical_bucket_exec``).
        """
        ckey, spec_args, needs_pad = self._canonical_specs(args)
        compiled = self._bucket_execs.get(ckey)
        if compiled is None:
            stats.bump("bucket_exec_misses")
            stats.bump("bucket_exec_compiles")
            self.counters["compiles"] += 1
            self.counters["bucket_exec_compiles"] += 1
            compiled = self.compile(*spec_args)
            self._bucket_execs[ckey] = compiled
        else:
            stats.bump("bucket_exec_hits")
            self.counters["bucket_exec_hits"] += 1
        if not needs_pad:
            self._compiled[key] = compiled  # the canonical shape itself
            return compiled(*args)
        # true output shapes via abstract eval only (no tracing pass of the
        # chunk pipeline, no XLA) — exact dim provenance for the un-padding
        out_specs = jax.eval_shape(self.fn, *args)
        padded_fn = emit_padded_call(compiled, spec_args, out_specs)
        self._padded[key] = padded_fn
        return padded_fn(*args)

    # -- introspection ------------------------------------------------------
    @property
    def autochunk_result(self) -> Optional[AutoChunkResult]:
        """Report of the most recent compile (legacy attribute location)."""
        if not self._compiled:
            return None
        return next(reversed(self._compiled.values())).result

    def stats(self) -> Dict[str, Any]:
        out = dict(self.counters)
        out["compiled_shapes"] = len(self._compiled)
        out["bucket_plans"] = len(self._bucket_plans)
        out["bucket_execs"] = len(self._bucket_execs)
        out["padded_shapes"] = len(self._padded)
        if self.cache is not None:
            out["plan_cache"] = self.cache.stats()
        return out

    def __repr__(self) -> str:
        name = getattr(self.fn, "__name__", repr(self.fn))
        return (
            f"ChunkedFunction({name},"
            f" budget={self.config.budget_bytes or self.config.budget_ratio},"
            f" shapes={len(self._compiled)})"
        )
