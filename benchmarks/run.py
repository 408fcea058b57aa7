# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper figure/table.

  fig1   max_seq.py               activation growth + max-seq extension
  fig5   throughput_vs_budget.py  throughput @ 50/40/20% activation budgets
  fig6   vs_fused_kernel.py       AutoChunk on top of fused attention
  fig7/8 vs_expert_chunk.py       vs expert-designed (OpenFold-style) chunk
  table1 ablation.py              selection-strategy ablation
  roofline roofline.py            dry-run roofline terms (deliverable g)

Run all: PYTHONPATH=src python -m benchmarks.run [--only fig5,table1]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

from . import (
    ablation,
    arch_coverage,
    codegen_bench,
    max_seq,
    mesh_bench,
    obs_bench,
    roofline,
    serving_bench,
    throughput_vs_budget,
    vs_expert_chunk,
    vs_fused_kernel,
)

SUITES = {
    "fig1": max_seq.run,
    "fig5": throughput_vs_budget.run,
    "fig6": vs_fused_kernel.run,
    "fig7": vs_expert_chunk.run,
    "table1": ablation.run,
    "archcov": arch_coverage.run,
    "roofline": roofline.run,
    "codegen": codegen_bench.run,
    "serving": serving_bench.run,
    "obs": obs_bench.run,
    "mesh": mesh_bench.run,
}

BASELINE_BENCH = str(Path(__file__).resolve().parent / "BENCH_codegen.json")
BASELINE_SERVING = str(Path(__file__).resolve().parent / "BENCH_serving.json")
BASELINE_KERNELS = str(Path(__file__).resolve().parent / "BENCH_kernels.json")
BASELINE_OBS = str(Path(__file__).resolve().parent / "BENCH_obs.json")
BASELINE_MESH = str(Path(__file__).resolve().parent / "BENCH_mesh.json")


def smoke(rows) -> None:
    """CI bitrot canary: one tiny config through the shared harness path
    (model builder -> estimate -> autochunk -> timed call).  Catches broken
    imports/APIs in the benchmark stack without measuring performance."""
    import jax

    from .common import chunked, gpt_block_model, peak_activation, time_fn

    cfg, params, batch, fwd = gpt_block_model(64, n_layers=1, d=64)
    baseline = peak_activation(fwd, (params, batch))
    res = chunked(fwd, (params, batch), budget_ratio=0.5)
    us = time_fn(res.fn, params, batch, iters=2, warmup=1)
    ok = res.final_peak <= baseline
    jax.block_until_ready(res.fn(params, batch))
    rows.append(("smoke_gpt_s64", us, f"peak_ok={int(ok)}"))
    if not ok:
        raise AssertionError(
            f"smoke: final peak {res.final_peak} > baseline {baseline}"
        )


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-config harness check for CI (no perf claims)")
    ap.add_argument("--plan-cache", type=str, default=None,
                    help="on-disk chunk-plan cache directory: repeated runs"
                         " replay stored plans instead of re-searching"
                         " (also settable via AUTOCHUNK_PLAN_CACHE)")
    ap.add_argument("--bench-out", type=str, default=None,
                    help="run the codegen backend benchmark (compile time,"
                         " retrace count, tokens/s; legacy vs lowered) and"
                         " write the JSON report to this path")
    ap.add_argument("--bench-check", action="store_true",
                    help="assert trace_calls/search_passes of the lowering"
                         " backend do not regress vs the committed"
                         " benchmarks/BENCH_codegen.json, the paged"
                         " serving counters vs BENCH_serving.json, and the"
                         " kernel autotune/computed-mask invariants vs"
                         " BENCH_kernels.json, and the mesh-aware planning"
                         " gates vs BENCH_mesh.json (CI gate; implies all"
                         " of the above benchmarks)")
    ap.add_argument("--serving-bench-out", type=str, default=None,
                    help="write the paged-vs-fixed-slot serving benchmark"
                         " JSON (TTFT, decode tok/s, peak pages, padded-KV"
                         " bytes saved) to this path")
    ap.add_argument("--kernel-bench-out", type=str, default=None,
                    help="write the kernel autotune + computed-mask"
                         " benchmark JSON (estimator peaks computed-vs-bool"
                         " per length, tuned-vs-default runtime, warm-replay"
                         " autotune counters) to this path")
    ap.add_argument("--obs-bench-out", type=str, default=None,
                    help="write the observability-overhead benchmark JSON"
                         " (paged decode tok/s with metrics on vs off,"
                         " span/histogram structure, plan_accuracy) to this"
                         " path")
    ap.add_argument("--mesh-bench-out", type=str, default=None,
                    help="write the mesh-aware planning benchmark JSON"
                         " (sharded vs unsharded predicted peak on the"
                         " quickstart GPT, plan-cache miss on mesh change)"
                         " to this path")
    args = ap.parse_args()
    from . import common

    if args.plan_cache:
        common.set_plan_cache(args.plan_cache)
    if (args.bench_out or args.bench_check or args.serving_bench_out
            or args.kernel_bench_out or args.obs_bench_out
            or args.mesh_bench_out):
        import json

        problems = []
        if args.bench_out or args.bench_check:
            fresh = codegen_bench.run_codegen_bench()
            print(json.dumps(fresh, indent=2))
            if args.bench_out:
                Path(args.bench_out).write_text(
                    json.dumps(fresh, indent=2) + "\n"
                )
            if args.bench_check:
                baseline = json.loads(Path(BASELINE_BENCH).read_text())
                problems += codegen_bench.check_against(baseline, fresh)
        if args.serving_bench_out or args.bench_check:
            fresh_srv = serving_bench.run_serving_bench()
            print(json.dumps(fresh_srv, indent=2))
            if args.serving_bench_out:
                Path(args.serving_bench_out).write_text(
                    json.dumps(fresh_srv, indent=2) + "\n"
                )
            if args.bench_check:
                srv_base = json.loads(Path(BASELINE_SERVING).read_text())
                problems += serving_bench.check_against(srv_base, fresh_srv)
        if args.kernel_bench_out or args.bench_check:
            fresh_k = vs_fused_kernel.run_kernel_bench()
            print(json.dumps(fresh_k, indent=2))
            if args.kernel_bench_out:
                Path(args.kernel_bench_out).write_text(
                    json.dumps(fresh_k, indent=2) + "\n"
                )
            if args.bench_check:
                k_base = json.loads(Path(BASELINE_KERNELS).read_text())
                problems += vs_fused_kernel.check_against(k_base, fresh_k)
        if args.obs_bench_out or args.bench_check:
            fresh_obs = obs_bench.run_obs_bench()
            print(json.dumps(fresh_obs, indent=2))
            if args.obs_bench_out:
                Path(args.obs_bench_out).write_text(
                    json.dumps(fresh_obs, indent=2) + "\n"
                )
            if args.bench_check:
                obs_base = json.loads(Path(BASELINE_OBS).read_text())
                problems += obs_bench.check_against(obs_base, fresh_obs)
        if args.mesh_bench_out or args.bench_check:
            fresh_mesh = mesh_bench.run_mesh_bench()
            print(json.dumps(fresh_mesh, indent=2))
            if args.mesh_bench_out:
                Path(args.mesh_bench_out).write_text(
                    json.dumps(fresh_mesh, indent=2) + "\n"
                )
            if args.bench_check:
                mesh_base = json.loads(Path(BASELINE_MESH).read_text())
                problems += mesh_bench.check_against(mesh_base, fresh_mesh)
        if args.bench_check:
            for p in problems:
                print(f"# BENCH REGRESSION: {p}", file=sys.stderr)
            if problems:
                sys.exit(1)
            print("# bench check ok: codegen counts, paged serving"
                  " counters, kernel autotune/computed-mask invariants,"
                  " observability overhead, and mesh-aware planning"
                  " within baseline", file=sys.stderr)
        return
    if args.smoke:
        names = ["smoke"]
        suites = {"smoke": smoke}
    else:
        names = args.only.split(",") if args.only else list(SUITES)
        suites = SUITES

    rows = []
    failed = False
    for name in names:
        t0 = time.time()
        try:
            suites[name](rows)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            traceback.print_exc()
            rows.append((f"{name}_FAILED", 0.0, "exception"))
            failed = True
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    cache = common.get_plan_cache()
    if cache is not None:
        print(f"# plan cache: {cache.stats()}", file=sys.stderr)
    if args.smoke and failed:
        sys.exit(1)  # smoke mode is a CI gate; real runs always report


if __name__ == "__main__":
    main()
