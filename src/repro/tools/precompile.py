"""Pre-build chunk plans ahead of deployment.

Runs the full AutoChunk pipeline for a matrix of (config, sequence length,
budget) tuples and writes the resulting :class:`~repro.core.plan.ChunkPlan`
artifacts into an on-disk :class:`~repro.core.plan.PlanCache` directory.  A
serving process pointed at the same directory (``ServeEngine(...,
plan_cache=dir)`` or ``autochunk(..., cache=dir)``) then starts without
paying search/selection compile latency.

Everything is traced through ShapeDtypeStructs — no parameters or
activations are materialized, so full-size configs are safe to precompile
on a small host.

Sequence lengths are mapped onto their shape-bucket *boundaries* before
compiling (the canonical shapes that serving engines with
``canonical_bucket_exec`` actually execute at), so a request for
``--seq-lens 100,120,500`` builds exactly the two plans the buckets need
(128 and 512) instead of three near-duplicates.  ``--exact-lens`` restores
per-length plans; ``--bucket-lens`` supplies explicit boundaries matching
the serving fleet's ``--bucket-lens``.

    python -m repro.tools.precompile --configs gpt-paper,hubert-xlarge \
        --seq-lens 128,512 --budgets 0.4 --cache-dir plans/
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs import REGISTRY, get_config
from ..core import ChunkConfig, ChunkedFunction, ShapeBucketer
from ..core.plan import PlanCache
from ..models import model as M


def _batch_specs(cfg, batch: int, seq: int) -> Dict[str, Any]:
    """Abstract input batch for one prefill/forward trace."""
    if cfg.family == "audio":
        return {
            "frames": jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.float32)
        }
    specs: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    }
    if cfg.family == "vlm":
        specs["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_frontend_tokens, cfg.d_model), jnp.float32
        )
    return specs


def precompile_one(
    cache: PlanCache,
    name: str,
    seq: int,
    budget: float,
    *,
    batch: int = 1,
    reduced: bool = True,
    verbose: bool = False,
) -> Dict[str, Any]:
    """Build (or reuse) the plan for one (config, seq, budget) cell."""
    cfg = get_config(name)
    if reduced:
        cfg = cfg.reduced().with_(dtype="float32", scan_layers=False)
    param_specs = jax.eval_shape(
        lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0)
    )
    batch_specs = _batch_specs(cfg, batch, seq)

    def fwd(params, batch_d):
        return M.forward(cfg, params, batch_d)[0]

    t0 = time.perf_counter()  # monotonic: durations survive clock steps
    # staged AOT: precompiling only needs trace -> search — the searched
    # ChunkPlan is the deployment artifact; serving processes pay codegen
    # (cheap) at start-up, never the search
    cf = ChunkedFunction(
        fwd, ChunkConfig(budget_ratio=budget, verbose=verbose), cache=cache
    )
    planned = cf.trace(param_specs, batch_specs).search()
    return {
        "config": name,
        "seq": seq,
        "budget": budget,
        "cached": planned.from_cache,
        "stages": len(planned.plan.stages),
        "baseline_mib": planned.baseline_peak / 2**20,
        "final_mib": planned.final_peak / 2**20,
        "key": planned.plan.cache_key,
        "elapsed_s": time.perf_counter() - t0,
    }


def main(argv=None) -> int:
    from ..launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(
        prog="python -m repro.tools.precompile", description=__doc__
    )
    ap.add_argument(
        "--configs",
        default="gpt-paper",
        help="comma-separated config names (or 'all'); known: "
        + ",".join(sorted(REGISTRY)),
    )
    ap.add_argument("--seq-lens", default="128", help="comma-separated ints")
    ap.add_argument("--budgets", default="0.4", help="comma-separated ratios")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--cache-dir", required=True)
    ap.add_argument(
        "--bucket-lens", default=None,
        help="comma-separated explicit bucket boundaries (match the serving"
             " fleet's --bucket-lens); default power-of-two buckets",
    )
    ap.add_argument(
        "--exact-lens", action="store_true",
        help="precompile at the requested lengths instead of collapsing"
             " them to bucket boundaries",
    )
    ap.add_argument(
        "--full",
        action="store_true",
        help="precompile the full-size config instead of the reduced variant",
    )
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    names = (
        sorted(REGISTRY)
        if args.configs == "all"
        else [n for n in args.configs.split(",") if n]
    )
    seqs = [int(s) for s in args.seq_lens.split(",") if s]
    budgets = [float(b) for b in args.budgets.split(",") if b]

    if not args.exact_lens:
        # compile at bucket boundaries only: one plan per bucket is all a
        # canonical-bucket serving engine will ever look up
        bucketer = ShapeBucketer(
            buckets=tuple(int(s) for s in args.bucket_lens.split(",") if s)
            if args.bucket_lens else None
        )
        canonical = list(dict.fromkeys(bucketer.canonical_dim(s) for s in seqs))
        if canonical != seqs:
            print(
                f"# canonical bucket boundaries: {seqs} -> {canonical}",
                file=sys.stderr,
            )
        seqs = canonical

    cache = PlanCache(args.cache_dir)
    failures = 0
    print("config,seq,budget,cached,stages,baseline_mib,final_mib,elapsed_s")
    for name in names:
        for seq in seqs:
            for budget in budgets:
                try:
                    row = precompile_one(
                        cache,
                        name,
                        seq,
                        budget,
                        batch=args.batch,
                        reduced=not args.full,
                        verbose=args.verbose,
                    )
                except Exception as e:  # keep going; report at the end
                    failures += 1
                    print(
                        f"# FAILED {name} seq={seq} budget={budget}: {e!r}",
                        file=sys.stderr,
                    )
                    continue
                print(
                    f"{row['config']},{row['seq']},{row['budget']}"
                    f",{int(row['cached'])},{row['stages']}"
                    f",{row['baseline_mib']:.2f},{row['final_mib']:.2f}"
                    f",{row['elapsed_s']:.2f}"
                )
    print(
        f"# cache dir {args.cache_dir}: {len(cache)} plan(s) on disk,"
        f" {failures} failure(s)",
        file=sys.stderr,
    )
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
