"""Serving driver: batched requests through the ServeEngine.

  python -m repro.launch.serve --arch gpt-paper --local \
      --requests 8 --max-new 16 [--autochunk 0.3] [--plan-cache plans/]

``--plan-cache DIR`` points the engine at an on-disk plan cache (e.g. one
pre-built by ``python -m repro.tools.precompile``): the first run searches
and stores the chunk plan, every later run — or any other process sharing
the directory — starts warm, replaying the plan with zero search passes.
The cache status line (``plan cache: warm|cold``) is asserted by CI's
serving smoke step.

``--second-max-len N`` serves the request batch a second time after
reconfiguring the engine to ``N``.  When N lands in the same shape bucket
as ``--max-len``, the second run reuses the bucket's canonical executable:
the ``[serve] second run`` status line reports ``bucket_exec_hits=1
new_traces=0 new_wave_compiles=0``, which CI greps to prove the
padded-executable reuse path.

``--cache-max-entries`` / ``--cache-policy {lru,cost_lfu}`` bound the plan
cache with telemetry-driven eviction (triggered at the engine's idle
points; see ``PlanCache.evict``).

``--autotune`` runs the kernel autotune pass on cold compiles and prints
the ``[serve] autotune:`` counter line (``autotune_passes`` /
``autotune_cache_hits`` / ``autotune_trials`` / ``best=``).  The winning
tuning persists in the v4 plan, so a warm ``--plan-cache`` run reports
``autotune_passes=0``.  With ``--paged`` the pass instead tunes the paged
kernel's pages-per-grid-step for each compiled step width.

``--paged`` switches to :class:`~repro.serving.PagedServeEngine`:
continuous batching on a paged KV pool with planner-driven chunked prefill.
``--stagger`` serves staggered-length prompts (request ``i`` gets a
different prompt length) so short and long requests overlap — the
``[serve] paged:`` status line then reports the continuous-batching
counters (``mixed_steps``, ``pages_allocated``/``pages_freed``,
``padded_kv_waste_bytes=0``) that CI's paged serving smoke greps.

``--prefix-cache`` (paged only) enables the prefix-sharing radix cache;
``--spill-pages N`` adds the host spill tier.  ``--shared-prefix L`` runs
the deterministic prefix scenario CI's prefix smoke greps: requests share
an ``L``-token system prompt and are served **sequentially** (each drains
before the next submits, so every later request can match what the earlier
one cached), except every third request, which gets a one-off un-cached
prompt — the pool-pressure filler that forces cached pages to spill so the
following shared request restores them.  The ``[serve] prefix:`` line then
reports ``prefix_hits``/``prefix_tokens_reused``/``cow_copies``/
``pages_spilled``/``pages_restored``.

``--metrics-out FILE`` dumps the metrics registry (counters, gauges,
TTFT/step-latency histograms) plus the ``plan_accuracy`` block
(predicted vs measured activation peak) as JSON; ``--trace-out FILE``
exports every compile-stage and serving-step span as Chrome-trace JSON
(load in Perfetto or ``chrome://tracing``); ``--prom-out FILE`` writes
the Prometheus text exposition.  ``--no-obs`` turns engine recording and
the tracer off — the observability-overhead bench's baseline leg.
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax
import numpy as np

from ..configs import get_config
from ..core import stats
from ..core.plan import PlanCache
from ..models import model as M
from ..obs import metrics as obs_metrics
from ..obs.tracing import TRACER
from ..serving import PagedServeEngine, Request, ServeEngine
from .compile_cache import enable_compile_cache


def write_obs_outputs(args, engine) -> None:
    """Print the plan-accuracy status line and write ``--metrics-out`` /
    ``--trace-out`` / ``--prom-out`` artifacts.  Shared by the slot and
    paged paths; all exports happen after serving, off the hot path."""
    acc = engine.plan_accuracy()
    if acc is not None:
        print(f"[serve] {acc.status_line()}")
    if args.metrics_out:
        doc = {
            "counters": stats.snapshot(),
            "metrics": obs_metrics.default_registry().snapshot(),
        }
        if acc is not None:
            doc["plan_accuracy"] = acc.to_dict()
        with open(args.metrics_out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[serve] metrics snapshot -> {args.metrics_out}")
    if args.prom_out:
        with open(args.prom_out, "w") as f:
            f.write(obs_metrics.default_registry().to_prometheus())
        print(f"[serve] prometheus exposition -> {args.prom_out}")
    if args.trace_out:
        TRACER.export_chrome(args.trace_out)
        n_spans = len(TRACER.spans())
        print(f"[serve] chrome trace ({n_spans} spans) -> {args.trace_out}")


def print_mesh_line(engine) -> None:
    """The ``[serve] mesh:`` counter line CI's multi-device leg greps.

    ``sharded_plans`` proves plans were searched/replayed under the mesh;
    ``per_device_error_pct`` is the per-device plan-accuracy error (nan
    when the engine has no per-device accuracy record, e.g. the paged
    engine's unsharded prefill planner).
    """
    if getattr(engine, "mesh_spec", None) is None:
        return
    m = engine.metrics()["mesh"]
    acc = engine.plan_accuracy()
    err = "nan"
    if acc is not None and (
        acc.source == "per_device_watermark" or "peak_divisor" in acc.extra
    ) and math.isfinite(acc.error_pct):
        err = f"{acc.error_pct:.2f}"
    print(
        "[serve] mesh:"
        f" axes={m['axes']}"
        f" n_devices={m['n_devices']}"
        f" sharded_plans={m['sharded_plans']}"
        f" per_device_error_pct={err}"
    )


def serve_paged(cfg, params, rng, args):
    """Drive the paged continuous-batching engine (``--paged``)."""
    chunk = (
        "auto" if args.prefill_chunk == "auto" else int(args.prefill_chunk)
    )
    before = stats.snapshot()
    t0 = time.perf_counter()
    engine = PagedServeEngine(
        cfg, params,
        max_seqs=args.max_seqs, max_len=args.max_len,
        page_size=args.page_size, num_pages=args.num_pages,
        autochunk_budget=args.autochunk, autotune=args.autotune,
        prefill_chunk=chunk,
        prefix_cache=args.prefix_cache, spill_pages=args.spill_pages,
        greedy=not args.sample, seed=args.seed,
        obs=not args.no_obs,
        mesh=args.mesh_spec,
    )
    plan = engine.prefill_plan
    plan_note = (
        f" (planned: budget {plan.budget_bytes/2**20:.2f} MiB ->"
        f" peak {plan.peak_bytes/2**20:.2f} MiB)" if plan else " (fixed)"
    )
    print(f"[serve] paged engine built in {time.perf_counter()-t0:.2f}s;"
          f" pool {engine.pool.num_pages} pages x {engine.page_size} tokens,"
          f" prefill_chunk={engine.prefill_chunk}{plan_note}")

    t0 = time.perf_counter()
    if args.shared_prefix > 0:
        # deterministic prefix scenario (CI's prefix smoke): shared-prompt
        # requests served sequentially, with every third request a one-off
        # un-cached pressure filler (forces spill; the next shared request
        # restores).  Sequential draining guarantees each later request
        # sees the earlier one's cache insert.
        L = min(args.shared_prefix, args.prompt_len)
        shared = rng.integers(0, cfg.vocab_size, L).tolist()
        lens = [args.prompt_len] * args.requests
        for i in range(args.requests):
            if i % 3 == 2:
                prompt = rng.integers(
                    0, cfg.vocab_size, args.prompt_len
                ).tolist()
                req = Request(rid=i, prompt=prompt,
                              max_new_tokens=args.max_new,
                              cache_prefix=False)
            else:
                tail = rng.integers(
                    0, cfg.vocab_size, args.prompt_len - L
                ).tolist()
                req = Request(rid=i, prompt=shared + tail,
                              max_new_tokens=args.max_new)
            engine.submit(req)
            engine.run()
        done = engine.finished
    else:
        # staggered-length prompts: short decode-bound requests overlap
        # with long prefill-bound ones, which is what forces mixed steps
        if args.stagger:
            cap = max(1, args.max_len - args.max_new)
            lens = [
                max(1, min(cap, args.prompt_len * (1 + 3 * (i % 3)) // 2))
                for i in range(args.requests)
            ]
        else:
            lens = [args.prompt_len] * args.requests
        for i, n in enumerate(lens):
            prompt = rng.integers(0, cfg.vocab_size, n).tolist()
            engine.submit(
                Request(rid=i, prompt=prompt, max_new_tokens=args.max_new)
            )
        done = engine.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in done)
    m = engine.metrics()
    d = stats.delta(before)
    print(f"[serve] {len(done)} requests (lens {min(lens)}..{max(lens)}),"
          f" {toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s,"
          f" {engine.sched_stats['steps']} steps)")
    print(
        "[serve] paged:"
        f" mixed_steps={d['mixed_steps']}"
        f" prefill_chunks={d['prefill_chunks']}"
        f" pages_allocated={d['pages_allocated']}"
        f" pages_freed={d['pages_freed']}"
        f" peak_pages={engine.pool.peak_pages_in_use}"
        f" admission_refusals={d['admission_refusals']}"
        f" padded_kv_waste_bytes={m['kv_pool']['padded_kv_waste_bytes']}"
    )
    if args.autotune:
        tuned = engine.kernel_tuning
        print(
            "[serve] autotune:"
            f" autotune_passes={d['autotune_passes']}"
            f" autotune_cache_hits={d['autotune_cache_hits']}"
            f" autotune_trials={d['autotune_trials']}"
            f" best={tuned.describe() if tuned is not None else 'none'}"
        )
    if engine.prefix_cache is not None:
        pc = m["prefix_cache"]
        print(
            "[serve] prefix:"
            f" prefix_hits={d['prefix_hits']}"
            f" prefix_tokens_reused={d['prefix_tokens_reused']}"
            f" cow_copies={d['cow_copies']}"
            f" pages_spilled={d['pages_spilled']}"
            f" pages_restored={d['pages_restored']}"
            f" cached_nodes={pc['nodes']}"
            f" resident_pages={pc['resident_pages']}"
            f" spilled_nodes={pc['spilled_nodes']}"
        )
    print(f"[serve] kv pool: {m['kv_pool']}")
    print_mesh_line(engine)
    write_obs_outputs(args, engine)
    for r in done[:3]:
        print(f"  req {r.rid}: {r.generated[:8]}...")
    return engine


def main(argv=None):
    """Serve per ``argv``; returns the engine, drained, for inspection."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--autochunk", type=float, default=None)
    ap.add_argument("--autotune", action="store_true",
                    help="run the kernel autotune pass on cold compiles"
                         " (tile sizes, DMA depth, paged pages-per-step);"
                         " the winner persists in the v4 plan so warm"
                         " replays skip it")
    ap.add_argument("--plan-cache", type=str, default=None,
                    help="on-disk plan cache directory (shared across runs)")
    ap.add_argument("--bucket-lens", type=str, default=None,
                    help="comma-separated seq-len bucket boundaries for plan"
                         " reuse across max-len reconfigurations")
    ap.add_argument("--second-max-len", type=int, default=None,
                    help="serve the batch again after reconfiguring to this"
                         " max-len; inside the same bucket this reuses the"
                         " canonical executable (0 traces, 0 compiles)")
    ap.add_argument("--no-canonical-exec", action="store_true",
                    help="compile per exact max-len instead of at the bucket"
                         " boundary")
    ap.add_argument("--cache-policy", choices=list(PlanCache.POLICIES),
                    default="lru",
                    help="plan-cache eviction policy (see PlanCache.evict)")
    ap.add_argument("--cache-max-entries", type=int, default=None,
                    help="evict plans beyond this count at engine idle"
                         " points (one record per plan, aliases included)")
    ap.add_argument("--sample", action="store_true",
                    help="sample from the logits instead of greedy argmax")
    ap.add_argument("--seed", type=int, default=0)
    # --- observability ---
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write a JSON metrics snapshot (counters, gauges,"
                         " TTFT/latency histograms, plan_accuracy block)"
                         " after serving")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write compile+serve spans as Chrome-trace JSON"
                         " (Perfetto / chrome://tracing loadable)")
    ap.add_argument("--prom-out", type=str, default=None,
                    help="write the Prometheus text exposition of the"
                         " metrics registry")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable engine metric/span recording (the"
                         " overhead-bench off leg)")
    # --- paged continuous batching ---
    ap.add_argument("--paged", action="store_true",
                    help="serve on the paged KV pool (continuous batching,"
                         " mixed prefill+decode steps, admission bounded by"
                         " pages)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV tokens per pool page")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool capacity in pages (default: max_seqs *"
                         " pages(max_len))")
    ap.add_argument("--max-seqs", type=int, default=4,
                    help="step-batch rows for the paged engine")
    ap.add_argument("--prefill-chunk", type=str, default="auto",
                    help="'auto' = plan the chunk from the activation budget"
                         " via the AutoChunk estimator, or an integer")
    ap.add_argument("--stagger", action="store_true",
                    help="staggered prompt lengths (request i gets a varied"
                         " length) so prefill and decode overlap")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the prefix-sharing radix cache (paged"
                         " engine only): matched prompt prefixes share"
                         " ref-counted pool pages and skip prefill")
    ap.add_argument("--spill-pages", type=int, default=0,
                    help="host spill arena capacity in pages; >0 turns"
                         " out-of-pages admission into retry-after-spill")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="serve the deterministic shared-system-prompt"
                         " scenario (sequential drain; every 3rd request is"
                         " a one-off un-cached pressure filler) — the CI"
                         " prefix smoke")
    ap.add_argument("--mesh", type=str, default=None,
                    help="serve sharded on a device mesh, e.g."
                         " 'data=2,model=4' (axis sizes must multiply out"
                         " to the visible device count); plans are searched"
                         " by per-device sharded bytes and the decode wave"
                         " jits under in_shardings")
    ap.add_argument("--seq-axis", type=str, default=None,
                    help="mesh axis for sequence-parallel execution of"
                         " unsharded chunk regions (requires --mesh)")
    args = ap.parse_args(argv)
    if args.no_obs:
        TRACER.enabled = False
    if args.seq_axis and not args.mesh:
        ap.error("--seq-axis requires --mesh")
    args.mesh_spec = None
    if args.mesh:
        from ..core.meshspec import MeshSpec

        args.mesh_spec = MeshSpec.parse(args.mesh, seq_axis=args.seq_axis)

    cfg = get_config(args.arch)
    if args.local:
        cfg = cfg.reduced().with_(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    if args.paged:
        return serve_paged(cfg, params, rng, args)

    bucket_lens = (
        [int(s) for s in args.bucket_lens.split(",") if s]
        if args.bucket_lens else None
    )
    t_build0 = time.perf_counter()
    before_build = stats.snapshot()
    engine = ServeEngine(
        cfg, params,
        max_batch=args.max_batch, max_len=args.max_len,
        autochunk_budget=args.autochunk,
        autotune=args.autotune,
        plan_cache=args.plan_cache,
        bucket_lens=bucket_lens,
        canonical_bucket_exec=not args.no_canonical_exec,
        cache_policy=args.cache_policy,
        cache_max_entries=args.cache_max_entries,
        greedy=not args.sample,
        seed=args.seed,
        obs=not args.no_obs,
        mesh=args.mesh_spec,
    )
    t_build = time.perf_counter() - t_build0
    if args.autochunk is not None:
        res = engine.autochunk_result
        state = "warm" if res.from_cache else "cold"
        print(f"[serve] engine built in {t_build:.2f}s;"
              f" plan cache: {state}"
              f" (stages={len(res.plan)}, exec_len={engine.exec_len},"
              f" peak {res.baseline_peak/2**20:.1f} ->"
              f" {res.final_peak/2**20:.1f} MiB)")
        if args.autotune:
            db = stats.delta(before_build)
            tuned = getattr(res, "tuning", None)
            if tuned:
                from ..kernels.autotune import KernelTuning

                best = KernelTuning.from_dict(tuned).describe()
            else:
                best = "none"
            # warm replays restore the plan's persisted tuning, so
            # autotune_passes stays 0 — the line CI's serving smoke greps
            print(
                "[serve] autotune:"
                f" autotune_passes={db['autotune_passes']}"
                f" autotune_cache_hits={db['autotune_cache_hits']}"
                f" autotune_trials={db['autotune_trials']}"
                f" best={best}"
            )

    def serve_batch(tag: str):
        t0 = time.perf_counter()
        n0 = len(engine.finished)
        for i in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size, args.prompt_len).tolist()
            engine.submit(
                Request(rid=i, prompt=prompt, max_new_tokens=args.max_new)
            )
        done = engine.run()[n0:]
        dt = time.perf_counter() - t0
        toks = sum(len(r.generated) for r in done)
        print(f"[serve]{tag} {len(done)} requests, {toks} tokens in {dt:.2f}s"
              f" ({toks/dt:.1f} tok/s, {engine.n_decode_steps} decode waves)")
        return done

    done = serve_batch("")

    if args.second_max_len is not None:
        before = stats.snapshot()
        waves_before = dict(engine.exec_stats)
        engine.reconfigure(max_len=args.second_max_len)
        serve_batch(f" second run @ max_len={args.second_max_len}:")
        delta = stats.delta(before)
        new_waves = (
            engine.exec_stats["wave_compiles"] - waves_before["wave_compiles"]
        )
        print(
            "[serve] second run:"
            f" bucket_exec_hits={delta['bucket_exec_hits']}"
            f" new_traces={delta['trace_calls']}"
            f" new_searches={delta['search_passes']}"
            f" new_wave_compiles={new_waves}"
        )

    if engine.plan_cache is not None:
        print(f"[serve] plan cache stats: {engine.plan_cache.stats()}")
        if args.cache_max_entries is not None:
            print(f"[serve] cache eviction: policy={args.cache_policy}"
                  f" max_entries={args.cache_max_entries}"
                  f" evicted={engine.exec_stats['evicted']}")
    snap = stats.snapshot()
    print(
        "[serve] codegen stats:"
        f" lowering_emits={snap['lowering_emits']}"
        f" trace_calls={snap['trace_calls']}"
        f" kernel_dispatch_hits={snap['kernel_dispatch_hits']}"
        f" kernel_dispatch_misses={snap['kernel_dispatch_misses']}"
    )
    print_mesh_line(engine)
    write_obs_outputs(args, engine)
    for r in done[:3]:
        print(f"  req {r.rid}: {r.generated[:8]}...")
    return engine


if __name__ == "__main__":
    main()
