"""Size a serve cell's KV pool and find its knee, once, on the chip.

    python chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        [--size-pool] --rates r1 r2 ... [--write]

``--size-pool`` gives the pool every byte the chip has left: the chip's
``bytes_limit``, less the weights, the larger step program's temporaries
(``memory_analysis()``) and a margin, over twice a page's bytes, because
the step donates nothing and the old pool lives while the new one is
written.  Then each rate runs one open-loop pass on one engine; a rate is
sustained when the queue at the window's close is no longer than at its
opening plus one and the window's requests all finished.  The knee is
the highest sustained rate; the cell's fixed rate is four fifths of it.
``--write`` stores ``num_pages`` and ``rate_per_s`` in the cell's file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import bench

MARGIN_BYTES = 512 * 2**20
RATE_SHARE = 0.8


def step_bytes(engine, q_max: int) -> dict:
    """``memory_analysis()`` of the engine's step at width ``q_max``."""
    import jax
    import jax.numpy as jnp

    S, mp = engine.max_seqs, engine.max_pages_per_seq
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    args = (jax.tree.map(spec, engine.params), spec(engine.pool.pages),
            jax.ShapeDtypeStruct((S, q_max), jnp.int32),
            jax.ShapeDtypeStruct((S,), jnp.int32),
            jax.ShapeDtypeStruct((S,), jnp.int32),
            jax.ShapeDtypeStruct((S, mp), jnp.int32))
    ma = engine._step_fn(q_max).lower(*args).compile().memory_analysis()
    return {"argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes}


def size_pool(ctx, kinds_serve) -> int:
    import jax

    probe = kinds_serve.build(ctx, num_pages=64)
    page = probe.pool.pages.nbytes // probe.pool.pages.shape[1]
    weights = sum(x.nbytes for x in jax.tree.leaves(probe.params))
    temp = max(step_bytes(probe, q)["temp"]
               for q in (probe.prefill_chunk, 1))
    limit = ctx.devices[0].memory_stats()["bytes_limit"]
    n = int((limit - weights - temp - MARGIN_BYTES) // (2 * page))
    ctx.log(f"bytes_limit {limit}, weights {weights}, step temp {temp},"
            f" page {page} B -> {n} pages ({n * ctx.work['page_size']}"
            f" tokens)")
    del probe
    bench.free_device_state()
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--size-pool", action="store_true")
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = bench.HERE / "workloads" / f"{args.workload}.json"
    files = bench.cell_files(args.workload)
    devices = bench.require_chip(1)
    cfg = bench.model_config(files["config"])
    ns = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0)
    ctx = bench.Ctx(ns, files, cfg, devices,
                    bench.reference(files["config"]))
    serve = bench.load_module(bench.HERE / "kinds" / "serve.py")
    work = files["work"]
    if args.size_pool:
        work["num_pages"] = size_pool(ctx, serve)
    engine = serve.build(ctx, num_pages=work["num_pages"])
    for name, q in (("prefill", engine.prefill_chunk), ("decode", 1)):
        ctx.log(f"{name} step: {step_bytes(engine, q)}")
    rows, knee = [], None
    for rate in args.rates:
        t = time.perf_counter()
        win = serve.Window(ctx, engine, rate, args.seconds,
                           work["preroll_s"], work["drain_s"]).run()
        ttft, gaps, unfinished = win.latencies()
        steps = win.window_steps()
        row = {
            "rate": rate, "requests": len(win.in_window),
            "unfinished": unfinished, "queue": win.waiting_at,
            "ttft_p50_s": serve.percentile(ttft, 50),
            "ttft_p95_s": serve.percentile(ttft, 95),
            "itl_p50_ms": 1e3 * serve.percentile(gaps, 50),
            "itl_p95_ms": 1e3 * serve.percentile(gaps, 95),
            "steps": len(steps),
            "tokens": sum(s["tokens"] for s in steps),
            "refusals": sum(s["refusals"] for s in steps),
            "wall_s": time.perf_counter() - t,
        }
        q = win.waiting_at
        row["sustained"] = (unfinished == 0
                            and q.get("close", 0) <= q.get("open", 0) + 1)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if row["sustained"]:
            knee = rate
        # drain what is left before the next rate
        engine.waiting.clear()
        engine.run()
        engine.finished.clear()
    if knee is not None:
        work["rate_per_s"] = round(RATE_SHARE * knee, 4)
    print(json.dumps({"knee": knee, "rate_per_s": work.get("rate_per_s"),
                      "num_pages": work["num_pages"]}), flush=True)
    if args.write:
        path.write_text(json.dumps(work, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
