"""Pipeline stage counters: observable evidence of which passes ran.

The plan cache's contract is that a warm hit skips the search and selection
passes entirely; these counters make that contract testable (and expose
cache efficacy to the serving layer) without timing-based flakiness.

Since the observability PR this module is a thin compat shim over the
typed registry in :mod:`repro.obs.metrics`: every counter below is a
``Counter`` in the process-wide registry, updates are thread-safe (the
old dict ``bump`` was a read-modify-write race), and the same registry
carries the serving histograms/gauges exported by ``serve.py
--metrics-out``.  The historical API — ``bump`` / ``snapshot`` /
``reset`` / ``delta`` returning plain ``{name: int}`` dicts — is
preserved exactly.
"""
from __future__ import annotations

from typing import Dict

from ..obs import metrics as _metrics

_REGISTRY = _metrics.default_registry()

# Every known pipeline counter, pre-registered so snapshots always carry
# the full key set (tests diff snapshots taken before any bump).
_PIPELINE_COUNTERS = (
    "trace_calls",
    "estimate_calls",
    "search_calls",
    "rank_calls",
    # aliases bumped alongside search_calls/rank_calls: one "pass" per
    # invocation of the paper's chunk-search / chunk-selection stage.  The
    # staged-API contract (bucket hits replay with zero passes) is stated
    # and tested in these terms.
    "search_passes",
    "selection_passes",
    "codegen_calls",
    # jaxpr-native lowering backend (core.lowering): ``lowering_rewrites``
    # counts every apply_chunk (beam candidates included on the cold search
    # path; exactly one per stage on plan replay), ``lowering_emits`` one
    # per compiled plan.  ``lowering_emits`` together with ``trace_calls``
    # proves the single-lowering contract: a K-stage plan emits once and
    # re-traces once, independent of K.
    "lowering_rewrites",
    "lowering_emits",
    # Pallas kernel dispatch (core.kernel_dispatch): chunk-loop bodies
    # swapped for fused kernels vs bodies examined and left as scan codegen.
    "kernel_dispatch_hits",
    "kernel_dispatch_misses",
    # matcher or body validation raised: the body fell back to scan codegen
    # (a miss is a body the matchers examined and did not recognize)
    "kernel_dispatch_errors",
    # attention dispatches whose mask classified as causal/sliding-window and
    # lowered onto the position-computed kernel (no (Sq,Skv) bool array ever
    # exists); the remainder of kernel_dispatch_attention stream a boolean
    # mask, each counted under the one reason ``kernel_dispatch.
    # _try_attention`` gives (``kernel_dispatch_bool_mask.<reason>``)
    "kernel_dispatch_computed_mask",
    "kernel_dispatch_attention",
    "kernel_dispatch_bool_mask.mask_mode_bool",
    "kernel_dispatch_bool_mask.mask_not_2d",
    "kernel_dispatch_bool_mask.mask_not_chunked_on_q",
    "kernel_dispatch_bool_mask.kv_chunked",
    "kernel_dispatch_bool_mask.mask_eval_limit",
    "kernel_dispatch_bool_mask.mask_not_position_algebra",
    "kernel_dispatch_bool_mask.mask_not_band",
    # kernel autotune (kernels.autotune): ``autotune_passes`` counts actual
    # candidate-grid evaluations (one per distinct site set per process —
    # warm plan replays and bucket hits restore the persisted KernelTuning
    # and MUST show 0, counter-asserted in CI), ``autotune_cache_hits``
    # tuning requests served from the in-process site cache,
    # ``autotune_trials`` individual candidate configs costed/timed.
    "autotune_passes",
    "autotune_cache_hits",
    "autotune_trials",
    # measured-mode candidates that failed to compile or run on the chip
    "autotune_failures",
    "plan_cache_hits",
    "plan_cache_misses",
    "plan_replays",
    "plan_replay_failures",
    # shape-bucketed reuse (see core.config.ShapeBucketer)
    "plan_bucket_hits",
    "plan_bucket_misses",
    "plan_bucket_rejects",
    # canonical-shape bucket executables (ChunkConfig.canonical_bucket_exec):
    # one CompiledFunction per bucket, compiled at the bucket boundary.
    # ``bucket_exec_hits`` counts calls served by an already-built bucket
    # executable (zero traces, zero XLA compiles — the padded-call path),
    # ``bucket_exec_compiles`` the one boundary compile each bucket pays.
    "bucket_exec_hits",
    "bucket_exec_misses",
    "bucket_exec_compiles",
    "padded_calls",
    # telemetry-driven PlanCache.evict(): plan records removed (a record =
    # one plan plus all of its bucket aliases)
    "plan_evictions",
    # paged-KV continuous batching (serving.kv_pool / PagedServeEngine):
    # ``pages_allocated``/``pages_freed`` count physical pages leaving and
    # re-entering the pool free list (freed pages are reused, so a long-run
    # engine's allocated count can exceed the pool size many times over);
    # ``prefill_chunks`` counts planner-sized prompt chunks executed;
    # ``mixed_steps`` counts engine steps that ran prefill and decode tokens
    # in the SAME ragged batch — the observable signature of continuous
    # batching (asserted by CI's paged serving smoke).
    "pages_allocated",
    "pages_freed",
    "prefill_chunks",
    "mixed_steps",
    # requests the scheduler declined to admit because the pool could not
    # reserve enough pages (admission is bounded by pages, not slots)
    "admission_refusals",
    # prefix-sharing radix cache (serving.prefix_cache / KVPool refcounts):
    # ``prefix_hits`` counts admissions that matched a cached prompt prefix
    # (their prefill starts at the divergence point), ``prefix_tokens_reused``
    # the prompt tokens whose prefill was skipped entirely;
    # ``cow_copies`` counts partial boundary pages copy-on-written so a
    # matcher can extend a shared page without corrupting it;
    # ``pages_spilled``/``pages_restored`` count ref-free cached pages moved
    # to the host spill buffer under pool pressure and brought back on
    # re-match (a drained spill tier has spilled == restored + dropped).
    "prefix_hits",
    "prefix_tokens_reused",
    "cow_copies",
    "pages_spilled",
    "pages_restored",
    # mesh-aware planning (core.meshspec): plans searched or replayed under
    # a configured MeshSpec — i.e. ranked by per-device sharded bytes rather
    # than the single-device model (asserted >0 by CI's multi-device leg)
    "sharded_plans",
)

for _name in _PIPELINE_COUNTERS:
    _REGISTRY.counter(_name)


def bump(name: str, by: int = 1) -> None:
    """Thread-safe counter increment (creates the counter on first use)."""
    _REGISTRY.counter(name).inc(by)


def snapshot() -> Dict[str, int]:
    """Copy of all counters (safe to diff against a later snapshot)."""
    return _REGISTRY.counter_values()


def reset() -> None:
    _REGISTRY.reset(counters_only=True)


def delta(before: Dict[str, int]) -> Dict[str, int]:
    """Counter increments since ``before`` (a prior :func:`snapshot`)."""
    cur = _REGISTRY.counter_values()
    return {k: cur[k] - before.get(k, 0) for k in cur}
