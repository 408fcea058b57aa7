"""Kernel autotune pass: pick tile sizes and DMA buffer depth per plan.

The dispatched Pallas kernels historically ran fixed ``block_q = block_kv =
128`` tiles regardless of shape or backend.  This module searches a small
*legal* candidate grid per kernel site — tile sizes filtered through the
same :mod:`repro.kernels.tiling` rules the manual entry points apply, DMA
buffer depth double vs quad (quad realized by halving the streamed block so
twice as many blocks are in flight — ``pltpu.emit_pipeline``-style
multi-buffering granularity), and the paged kernel's pages-per-grid-step
width — and returns the winning :class:`KernelTuning`.

Cost model:

- **measured** (real backends): each candidate runs the actual jit'd kernel
  wrapper on representative zeros, min-of-``TIMING_REPS`` wall time.
- **analytic** (interpret mode, where wall time measures the Python
  interpreter, not the DMA engine): a deterministic VMEM-footprint /
  DMA-overlap cost — candidates whose working set exceeds the VMEM budget
  are rejected, surviving candidates are ranked by grid-step overhead plus
  streamed bytes discounted by buffer depth.  Deterministic by
  construction: same site → same winner, no timing noise.

Tuning is paid once per plan cache key: ``Traced.search`` runs this pass on
the cold path and persists the result in the ``ChunkPlan`` (schema v4), so
warm ``PlanCache`` replays and bucket hits restore the tuning with
``autotune_passes == 0`` — counter-asserted in CI.  An in-process cache
keyed by the canonical site set additionally dedupes tuning across plans
that share kernel shapes (``autotune_cache_hits``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..core import stats
from ..obs.tracing import span
from .tiling import (
    LANE,
    VMEM_BUDGET,
    ffn_vmem_bytes,
    legal_block,
    legal_candidates,
    paged_vmem_bytes,
)

# candidate grids (rounded to legal blocks per site before costing)
_ATTN_BQ = (64, 128, 256)
_ATTN_BKV = (128, 256, 512)
_FFN_BS = (64, 128, 256)
_FFN_BF = (256, 512, 1024)
_DEPTHS = (2, 4)
_PAGES_PER_STEP = (1, 2, 4)

TIMING_REPS = 3
# analytic model: relative cost of one grid step's fixed overhead, in
# "streamed byte" units — calibrated only to break ties toward fewer steps
# when the working sets are comparable
_STEP_OVERHEAD_BYTES = 4096


def _stream_block(size: int, block: int, depth: int, *,
                  align: int = 8) -> int:
    """Realized streamed-axis block at a buffer depth (mirrors ops)."""
    if depth >= 4:
        block = max(block // 2, 1)
    return legal_block(size, block, align=align)


@dataclass(frozen=True)
class KernelTuning:
    """The winning kernel configs for one plan, persisted in schema v4.

    Per-kind dicts hold exactly the kwargs the ops-layer wrappers accept
    (``kernel_kwargs``); ``None`` means the plan has no site of that kind
    and the kernel defaults apply.  ``mode`` records how the winner was
    chosen ('measured' wall time vs 'analytic' VMEM/DMA cost), ``trials``
    how many candidates were evaluated — both surface in serving telemetry.
    """

    attention: Optional[Dict[str, int]] = None  # block_q, block_kv, buffer_depth
    swiglu: Optional[Dict[str, int]] = None     # block_s, block_f, buffer_depth
    paged: Optional[Dict[str, int]] = None      # pages_per_step
    mode: str = "analytic"
    trials: int = 0

    def kernel_kwargs(self, kind: str) -> Dict[str, int]:
        """kwargs for the ops wrapper of ``kind`` ('' when untuned)."""
        cfg = getattr(self, kind, None)
        return dict(cfg) if cfg else {}

    def to_dict(self) -> Dict[str, Any]:
        return {
            "attention": dict(self.attention) if self.attention else None,
            "swiglu": dict(self.swiglu) if self.swiglu else None,
            "paged": dict(self.paged) if self.paged else None,
            "mode": self.mode,
            "trials": int(self.trials),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "KernelTuning":
        def _ints(v):
            return {k: int(x) for k, x in v.items()} if v else None

        return cls(
            attention=_ints(d.get("attention")),
            swiglu=_ints(d.get("swiglu")),
            paged=_ints(d.get("paged")),
            mode=str(d.get("mode", "analytic")),
            trials=int(d.get("trials", 0)),
        )

    def describe(self) -> str:
        """One-line summary for serving logs / benchmarks."""
        parts = []
        for kind in ("attention", "swiglu", "paged"):
            cfg = getattr(self, kind)
            if cfg:
                kv = ",".join(f"{k}={v}" for k, v in sorted(cfg.items()))
                parts.append(f"{kind}({kv})")
        return " ".join(parts) if parts else "none"


# ---------------------------------------------------------------------------
# candidate enumeration + analytic costs


def _attention_candidates(site: Dict[str, Any]) -> List[Dict[str, int]]:
    sq, skv = int(site["sq"]), int(site["skv"])
    out = []
    for bq in legal_candidates(sq, _ATTN_BQ):
        for bkv in legal_candidates(skv, _ATTN_BKV):
            for depth in _DEPTHS:
                out.append({"block_q": bq, "block_kv": bkv,
                            "buffer_depth": depth})
    return out


def _attention_cost(site: Dict[str, Any], cand: Dict[str, int]) -> float:
    sq, skv = int(site["sq"]), int(site["skv"])
    hd = int(site.get("hd", 64))
    n = int(site.get("n", 1))
    depth = cand["buffer_depth"]
    bq = legal_block(sq, cand["block_q"])
    bkv = _stream_block(skv, cand["block_kv"], depth)
    # working set: q block + double-buffered k/v stream blocks + f32
    # accumulator + the (bq, bkv) logits tile
    vmem = 4 * (bq * hd + 2 * 2 * bkv * hd + bq * hd + bq * bkv)
    if vmem > VMEM_BUDGET:
        return float("inf")
    steps = n * -(-sq // bq) * -(-skv // bkv)
    stream_bytes = steps * 4 * 2 * bkv * hd        # k + v per step
    # exposed (non-overlapped) copy time shrinks with buffer depth
    return steps * _STEP_OVERHEAD_BYTES + stream_bytes / depth


def _swiglu_candidates(site: Dict[str, Any]) -> List[Dict[str, int]]:
    s, f = int(site["s"]), int(site["f"])
    out = []
    for bs in legal_candidates(s, _FFN_BS):
        for bf in legal_candidates(f, _FFN_BF, align=LANE):
            for depth in _DEPTHS:
                out.append({"block_s": bs, "block_f": bf,
                            "buffer_depth": depth})
    return out


def _swiglu_cost(site: Dict[str, Any], cand: Dict[str, int]) -> float:
    s, f = int(site["s"]), int(site["f"])
    d = int(site.get("d", 256))
    depth = cand["buffer_depth"]
    bs = legal_block(s, cand["block_s"])
    bf = _stream_block(f, cand["block_f"], depth, align=LANE)
    itemsize = int(site.get("itemsize", 4))
    if ffn_vmem_bytes(bs, bf, d, itemsize) > VMEM_BUDGET:
        return float("inf")
    steps = -(-s // bs) * -(-f // bf)
    stream_bytes = steps * itemsize * 3 * d * bf   # wg + wu + wd per step
    return steps * _STEP_OVERHEAD_BYTES + stream_bytes / depth


def _paged_candidates(site: Dict[str, Any]) -> List[Dict[str, int]]:
    max_pages = int(site.get("max_pages", 1))
    seen, out = set(), []
    for pps in _PAGES_PER_STEP:
        pps = max(1, min(pps, max_pages))
        if pps not in seen:
            seen.add(pps)
            out.append({"pages_per_step": pps})
    return out


def _paged_cost(site: Dict[str, Any], cand: Dict[str, int]) -> float:
    page_size = int(site.get("page_size", 16))
    max_pages = int(site.get("max_pages", 1))
    q_max = int(site.get("q_max", 8))
    h = int(site.get("h", 8))
    hd = int(site.get("hd", 64))
    kv = int(site.get("kv", h))
    n_seqs = int(site.get("n_seqs", 1))
    pps = cand["pages_per_step"]
    page_bytes = 4 * page_size * 2 * kv * hd
    rows = min(q_max, 128) * max(h // kv, 1)
    if paged_vmem_bytes(rows, kv, hd, page_size, pps, 4) > VMEM_BUDGET:
        return float("inf")
    steps = n_seqs * -(-max_pages // pps)
    stream_bytes = steps * pps * page_bytes
    return steps * _STEP_OVERHEAD_BYTES + stream_bytes / min(2 * pps, 8)


# ---------------------------------------------------------------------------
# measured costs (real backends only)


def _measured_cost(kind: str, site: Dict[str, Any],
                   cand: Dict[str, int]) -> float:
    """Best-of-``TIMING_REPS`` wall time of one candidate on the device.

    Tuning can be reached while an outer jit is tracing (a model's block
    compiles lazily inside its layer scan).  There, the inputs are made
    eagerly and the candidate runs as an ahead-of-time compiled
    executable, so the timings measure the kernel, not the creation of
    tracers.
    """
    import jax

    with jax.ensure_compile_time_eval():
        fn, args = _candidate_run(kind, site, cand)
    run = jax.jit(fn).lower(*args).compile()
    jax.block_until_ready(run(*args))  # first run outside the timed region
    best = float("inf")
    for _ in range(TIMING_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _candidate_run(kind: str, site: Dict[str, Any], cand: Dict[str, int]):
    """``(fn, args)``: ``kind``'s kernel with ``cand`` on zeros at ``site``."""
    import jax.numpy as jnp

    from . import ops

    if kind == "attention":
        n = int(site.get("n", 1))
        sq, skv, hd = int(site["sq"]), int(site["skv"]), int(site.get("hd", 64))
        q = jnp.zeros((1, sq, n, hd), jnp.float32)
        k = jnp.zeros((1, skv, n, hd), jnp.float32)
        return (lambda q, k: ops.attention(q, k, k, causal=True, **cand),
                (q, k))
    if kind == "swiglu":
        s, d, f = int(site["s"]), int(site.get("d", 256)), int(site["f"])
        dt = jnp.bfloat16 if int(site.get("itemsize", 4)) == 2 else jnp.float32
        x = jnp.zeros((s, d), dt)
        wg = jnp.zeros((d, f), dt)
        wd = jnp.zeros((f, d), dt)
        return (lambda x, wg, wd: ops.swiglu_ffn(x, wg, wg, wd, **cand),
                (x, wg, wd))
    if kind == "paged":
        from .paged_attention import paged_attention_blocked

        ps = int(site.get("page_size", 16))
        mp = int(site.get("max_pages", 1))
        qm = int(site.get("q_max", 8))
        h = int(site.get("h", 8))
        hd = int(site.get("hd", 64))
        kvh = int(site.get("kv", h))
        n_seqs = int(site.get("n_seqs", 1))
        q = jnp.zeros((n_seqs, qm, h, hd), jnp.float32)
        pages = jnp.zeros((max(mp, 1), ps, 2 * kvh, hd), jnp.float32)
        pt = jnp.zeros((n_seqs, mp), jnp.int32)
        lens = jnp.full((n_seqs,), qm, jnp.int32)
        return (lambda q, pages, pt, lens: paged_attention_blocked(
            q, pages, pt, lens, lens, interpret=ops.interpret_default(),
            **cand), (q, pages, pt, lens))
    raise ValueError(f"unknown kernel kind {kind!r}")


# ---------------------------------------------------------------------------
# the pass

_KINDS = {
    "attention": (_attention_candidates, _attention_cost),
    "swiglu": (_swiglu_candidates, _swiglu_cost),
    "paged": (_paged_candidates, _paged_cost),
}

# (mode, canonical site tuple) -> KernelTuning; one grid evaluation per
# distinct site set per process even across plans
_TUNE_CACHE: Dict[Tuple, KernelTuning] = {}


def clear_cache() -> None:
    _TUNE_CACHE.clear()


def _canon(sites: Sequence[Dict[str, Any]]) -> Tuple:
    return tuple(sorted(
        tuple(sorted((k, int(v)) for k, v in s.items() if k != "kind"))
        + (("kind", s["kind"]),)
        for s in sites
    ))


def tune_sites(sites: Sequence[Dict[str, Any]], *,
               interpret: bool = True) -> KernelTuning:
    """Tune every kernel site and return the merged :class:`KernelTuning`.

    ``sites``: dicts with a ``kind`` key ('attention' | 'swiglu' | 'paged')
    plus that kind's shape fields (attention: n/sq/skv/hd; swiglu: s/d/f;
    paged: page_size/max_pages/q_max/h/kv/hd/n_seqs).  Multiple sites of one
    kind are costed jointly (summed cost — one config serves all sites of a
    kind, matching how the dispatcher applies tuning).  Deterministic in
    analytic mode: candidates are enumerated in sorted grid order and ties
    keep the earlier candidate.
    """
    sites = [s for s in sites if s.get("kind") in _KINDS]
    if not sites:
        return KernelTuning(mode="analytic" if interpret else "measured",
                            trials=0)

    mode = "analytic" if interpret else "measured"
    key = (mode, _canon(sites))
    cached = _TUNE_CACHE.get(key)
    if cached is not None:
        stats.bump("autotune_cache_hits")
        return cached

    stats.bump("autotune_passes")
    by_kind: Dict[str, List[Dict[str, Any]]] = {}
    for s in sites:
        by_kind.setdefault(s["kind"], []).append(s)

    winners: Dict[str, Dict[str, int]] = {}
    trials = 0
    with span("compile.autotune", sites=len(sites), mode=mode):
        for kind, kind_sites in sorted(by_kind.items()):
            enum, analytic = _KINDS[kind]
            # the candidate grid must be identical across this kind's sites
            # so one config can serve them all: enumerate per site and
            # intersect
            cand_lists = [enum(s) for s in kind_sites]
            cands = [c for c in cand_lists[0]
                     if all(c in cl for cl in cand_lists[1:])]
            if not cands:
                cands = cand_lists[0]
            best, best_cost = None, float("inf")
            failed = []
            for cand in cands:
                trials += 1
                if mode == "measured":
                    try:
                        cost = sum(
                            _measured_cost(kind, s, cand) for s in kind_sites
                        )
                    except Exception as e:  # a candidate the chip refuses
                        failed.append((cand, f"{type(e).__name__}: {e}"))
                        continue
                else:
                    cost = sum(analytic(s, cand) for s in kind_sites)
                if cost < best_cost:
                    best, best_cost = cand, cost
            if failed:
                stats.bump("autotune_failures", len(failed))
                print(f"[autotune] {kind}: {len(failed)} of {len(cands)}"
                      " candidates failed to compile or run")
                for cand, err in failed:
                    print(f"[autotune]   {cand}: {err.splitlines()[0][:200]}")
                if best is None:
                    raise RuntimeError(
                        f"autotune: no {kind} candidate compiled for"
                        f" {kind_sites}; first error: {failed[0][1][:500]}"
                    )
            if best is not None and best_cost != float("inf"):
                winners[kind] = best
    stats.bump("autotune_trials", trials)

    tuning = KernelTuning(
        attention=winners.get("attention"),
        swiglu=winners.get("swiglu"),
        paged=winners.get("paged"),
        mode=mode,
        trials=trials,
    )
    _TUNE_CACHE[key] = tuning
    return tuning
