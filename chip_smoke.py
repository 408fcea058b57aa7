"""Drive AutoChunk's main path once on a TPU and check what comes out.

  python chip_smoke.py [--seed N]        # one chip: phases a, b, c
  python chip_smoke.py --four-chips      # four chips: phases a and d

The model is minitron-4b at its published widths and depth (32 layers,
d_model 3072, 24 query / 8 KV heads, d_ff 9216, vocabulary 256000) in
bf16, with random weights and random prompts made from ``--seed``.

a. device   prints platform, device kind and count; fails off a TPU, or
            when Pallas kernels would run in interpret mode.
b. prefill  next-token logits of one prompt through the AutoChunk'd
            forward (activation budget 0.2 of the unchunked peak) against
            the unchunked program at S=4096; then the chunked program alone
            at S=16384 under a 2 GiB activation budget, where the unchunked
            attention logits alone would need 24 GiB.
c. serve    ``repro.launch.serve.main`` on the paged engine: 4 staggered
            requests with mixed prefill and decode steps; each request's
            first-token logits against the same prompt through the model's
            forward pass.
d. mesh     phase c with ``--mesh model=4`` and again on one device: the
            tokens must agree up to bf16 near ties, and the KV pool and
            the weights must be spread over the mesh.

Every phase runs in this one process (a child could not reach a chip this
process holds).  A failed check raises, so the script exits non-zero before
it prints its last line, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "minitron-4b"
# the unchunked program still fits one 16 GB chip at this length (a v5e
# compile of it needs 11.0 GiB with the weights)
PREFILL_CMP_LEN = 4096
PREFILL_BUDGET_RATIO = 0.2
PREFILL_LONG_LEN = 16384
# activation budget that fits next to the 10.2 GB of weights on one chip
PREFILL_LONG_BUDGET = 2 * 2**30
# max |logits - reference| / max |reference|: both sides hold weights and
# activations in bf16 (8-bit mantissa) through 32 layers, and sum in
# different orders
LOGIT_TOL = 0.05
SERVE_ARGV = [
    "--arch", ARCH, "--paged", "--stagger", "--requests", "4",
    "--prompt-len", "512", "--max-len", "2048", "--max-new", "16",
]


class SmokeFailure(RuntimeError):
    """A phase's output is wrong."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_device(min_count: int = 1):
    """Phase a: the backend is a TPU and kernels compile through Mosaic."""
    import jax

    from repro.kernels import ops
    from repro.launch.mesh import device_peaks

    devices = jax.devices()
    dev = devices[0]
    print(f"[a] device: platform={dev.platform} kind={dev.device_kind}"
          f" count={len(devices)}", flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform} devices")
    check(not ops.INTERPRET,
          "Pallas kernels would run in interpret mode on a TPU"
          " (AUTOCHUNK_PALLAS_INTERPRET is set)")
    check(len(devices) >= min_count,
          f"needs {min_count} devices, found {len(devices)}")
    peaks = device_peaks(dev.device_kind)
    print(f"[a] peaks: bf16 {peaks['bf16_flops']:.3g} FLOP/s,"
          f" HBM {peaks['hbm_bytes_per_s']:.3g} B/s", flush=True)
    return devices


def phase_prefill(cfg, *, seed: int, cmp_len: int, budget_ratio: float,
                  long_len: int, long_budget: int, tol: float) -> None:
    """Phase b: the AutoChunk'd prefill matches the unchunked program, and
    runs where the unchunked program could not."""
    import jax
    import numpy as np

    from repro.core import stats
    from repro.models import model as M

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        M.init_params(cfg, jax.random.PRNGKey(seed)))
    print(f"[b] weights: {M.param_count(cfg)} parameters ({cfg.dtype})"
          f" built in {time.perf_counter() - t0:.1f}s", flush=True)

    def next_token_logits(c, S):
        toks = jax.random.randint(
            jax.random.PRNGKey(seed + S), (1, S), 0, cfg.vocab_size)
        fn = jax.jit(lambda p, t: M.next_token_logits(c, p, {"tokens": t}))
        return jax.block_until_ready(fn(params, toks))

    def chunked(c, S):
        before = stats.snapshot()
        t = time.perf_counter()
        out = next_token_logits(c, S)
        d = stats.delta(before)
        res = M.autochunk_results(c)
        check(len(res) == 1, f"expected one chunked block, got {len(res)}")
        res = res[0]
        print(f"[b] chunked S={S}: budget={res.budget_bytes}"
              f" stages={len(res.plan)}"
              f" predicted_peak_bytes={res.final_peak}"
              f" unchunked_estimate_bytes={res.baseline_peak}"
              f" kernel_dispatch_hits={d['kernel_dispatch_hits']}"
              f" kernel_dispatch_misses={d['kernel_dispatch_misses']}"
              f" kernel_dispatch_errors={d['kernel_dispatch_errors']}"
              f" peak_bytes_in_use={peak_bytes(dev)}"
              f" wall_s={time.perf_counter() - t:.1f}", flush=True)
        check(len(res.plan) >= 1, f"S={S}: the plan has no chunk stage")
        check(d["kernel_dispatch_hits"] > 0,
              f"S={S}: no chunk-loop body was dispatched to a kernel")
        check(d["kernel_dispatch_errors"] == 0,
              f"S={S}: kernel dispatch raised and fell back")
        check(bool(np.isfinite(np.asarray(out, np.float32)).all()),
              f"S={S}: non-finite logits")
        return out

    t = time.perf_counter()
    ref = next_token_logits(cfg, cmp_len)
    print(f"[b] unchunked S={cmp_len}: peak_bytes_in_use={peak_bytes(dev)}"
          f" wall_s={time.perf_counter() - t:.1f}", flush=True)
    got = chunked(cfg.with_(autochunk_budget=budget_ratio), cmp_len)
    err = rel_err(got, ref)
    top1 = int(np.argmax(np.asarray(got, np.float32)))
    top1_ref = int(np.argmax(np.asarray(ref, np.float32)))
    print(f"[b] S={cmp_len} chunked vs unchunked: rel_err={err:.3e}"
          f" tol={tol} top1={top1} top1_ref={top1_ref}", flush=True)
    check(err <= tol, f"chunked logits differ: rel_err {err:.3e} > {tol}")
    out = chunked(cfg.with_(autochunk_budget=float(long_budget)), long_len)
    check(out.shape == (1, cfg.vocab_padded), f"logits shape {out.shape}")
    del params, ref, got, out
    gc.collect()


def phase_serve(argv, *, tol: float, tag: str = "[c]"):
    """Phase c: paged serving answers every request, with mixed steps, no
    leaked pages, and first-token logits that match the forward pass.

    Returns the engine (its ``params`` and pool are still on the device).
    """
    import numpy as np

    from repro.core import stats
    from repro.launch import serve

    before = stats.snapshot()
    t = time.perf_counter()
    engine = serve.main(argv)
    d = stats.delta(before)
    done = sorted(engine.finished, key=lambda r: r.rid)
    n_req = int(argv[argv.index("--requests") + 1])
    print(f"{tag} serve: requests={len(done)}"
          f" steps={engine.sched_stats['steps']}"
          f" mixed_steps={d['mixed_steps']}"
          f" prefill_chunk={engine.prefill_chunk}"
          f" pages_allocated={d['pages_allocated']}"
          f" pages_freed={d['pages_freed']}"
          f" wall_s={time.perf_counter() - t:.1f}", flush=True)
    check(len(done) == n_req, f"{len(done)} of {n_req} requests answered")
    check(d["mixed_steps"] > 0, "no step mixed prefill and decode rows")
    check(d["pages_allocated"] == d["pages_freed"],
          f"pages leaked: {d['pages_allocated']} allocated,"
          f" {d['pages_freed']} freed")

    ref = reference_logits(engine, [r.prompt for r in done])
    for i, r in enumerate(done):
        got = np.asarray(r.first_logits, np.float32)
        err = rel_err(got, ref[i])
        top1 = int(np.argmax(ref[i]))
        print(f"{tag} req {r.rid}: prompt_len={len(r.prompt)}"
              f" tokens={r.generated[:4]}... first_token={r.generated[0]}"
              f" ref_top1={top1} rel_err={err:.3e} tol={tol}", flush=True)
        check(len(r.generated) == r.max_new_tokens,
              f"request {r.rid} produced {len(r.generated)} tokens")
        check(err <= tol,
              f"request {r.rid}: first-token logits rel_err {err:.3e} > {tol}")
    return engine


def reference_logits(engine, seqs):
    """Next-token logits after each token sequence, through the model's
    forward pass on the engine's weights: (len(seqs), vocab) float32.

    The sequences are padded at the end to one length (causal, so padding
    never reaches a sequence's last position), and only that position is
    unembedded.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import layers as L
    from repro.models import model as M

    cfg = engine.cfg
    lens = np.asarray([len(s) for s in seqs], np.int32)
    toks = np.zeros((len(seqs), int(lens.max())), np.int32)
    for i, s in enumerate(seqs):
        toks[i, : lens[i]] = s

    @jax.jit
    def reference(params, tokens, last):
        h, _ = M.forward_hidden(cfg, params, {"tokens": tokens})
        return L.unembed(cfg, params["embed"], h[jnp.arange(len(last)), last])

    return np.asarray(reference(engine.params, toks, lens - 1), np.float32)


def placement(x) -> str:
    """Devices an array lives on, and its partition spec where it has one."""
    ids = sorted(d.id for d in x.sharding.device_set)
    spec = getattr(x.sharding, "spec", None)
    return f"devices={ids} spec={spec}"


def phase_mesh(argv, *, tol: float) -> None:
    """Phase d: sharded serving on a 4-chip mesh gives the tokens of one
    chip, and spreads the pool and the weights over the mesh."""
    engine = phase_serve(argv + ["--mesh", "model=4"], tol=tol, tag="[d]")
    pool, wq = engine.pool.pages, engine.params["blocks"]["attn"]["wq"]
    print(f"[d] mesh placement: kv_pool {placement(pool)};"
          f" blocks.attn.wq {placement(wq)}", flush=True)
    for name, x in (("KV pool", pool), ("weights", wq)):
        check(len(x.sharding.device_set) == 4,
              f"{name} on {len(x.sharding.device_set)} devices, not 4")
        check(not x.sharding.is_fully_replicated,
              f"{name} replicated on every device, not sharded")
    sharded = {r.rid: list(r.generated) for r in engine.finished}
    del engine, pool, wq
    gc.collect()

    engine = phase_serve(argv, tol=tol, tag="[d]")
    done = sorted(engine.finished, key=lambda r: r.rid)
    same = sum(sharded[r.rid] == r.generated for r in done)
    print(f"[d] sharded vs one device: {same} of {len(done)} requests"
          " produced identical tokens", flush=True)
    # bf16 tensor parallelism sums partial products in another order, so a
    # greedy stream may part at a near tie.  Where one does, the reference
    # forward over the common prefix must score both choices within the
    # logit tolerance of each other.
    parted = [r for r in done if sharded[r.rid] != r.generated]
    if parted:
        at = [next(i for i, (a, b) in enumerate(zip(sharded[r.rid],
                                                     r.generated)) if a != b)
              for r in parted]
        ref = reference_logits(
            engine, [r.prompt + r.generated[:i] for r, i in zip(parted, at)])
        for r, i, lg in zip(parted, at, ref):
            a, b = sharded[r.rid][i], r.generated[i]
            gap = float(abs(lg[a] - lg[b]) / abs(lg).max())
            print(f"[d] req {r.rid} parts at token {i}: sharded {a},"
                  f" one device {b}, reference gap {gap:.3e} tol={tol}",
                  flush=True)
            check(gap <= tol, f"request {r.rid}: the runs chose tokens {a}"
                  f" and {b} at {i}, {gap:.3e} of the largest logit apart")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-serving comparison (phase d)"
                         " on a 4-chip mesh")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = phase_device(min_count=4 if args.four_chips else 1)
    serve_argv = SERVE_ARGV + ["--seed", str(args.seed)]
    if args.four_chips:
        phase_mesh(serve_argv, tol=LOGIT_TOL)
    else:
        from repro.configs import get_config

        phase_prefill(
            get_config(ARCH), seed=args.seed, cmp_len=PREFILL_CMP_LEN,
            budget_ratio=PREFILL_BUDGET_RATIO, long_len=PREFILL_LONG_LEN,
            long_budget=PREFILL_LONG_BUDGET, tol=LOGIT_TOL,
        )
        phase_serve(serve_argv, tol=LOGIT_TOL)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
