"""Multi-pod dry-run: prove every (arch x input-shape x mesh) combination
lowers, compiles, and what it costs.

For each combination this builds the real step function (train_step with
grads+AdamW, prefill, or single-token decode), pjit-shards it with the
production rules, runs ``.lower().compile()``, and records:

  * memory_analysis()  — per-device argument/output/temp bytes (fits or not),
  * cost_analysis()    — per-device FLOPs and HBM bytes,
  * collective bytes   — parsed from the post-SPMD optimized HLO,
  * the derived three-term roofline at TPU v5e peaks (see
    benchmarks/roofline.py).

``main()`` forces 512 placeholder CPU devices (``XLA_FLAGS``) before JAX
initializes; importing this module changes nothing.  Results accumulate in
a JSON ledger (default: experiments/dryrun.json).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch minitron-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--autochunk 0.2]
"""
import argparse
import json
import os
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs import ASSIGNED, INPUT_SHAPES, get_config
from ..data import batch_specs
from ..models import model as M
from ..optim import adamw_init
from ..training.loop import make_train_step
from ..optim.schedules import linear_warmup_cosine
from .mesh import V5E, device_peaks, make_production_mesh, mesh_chips
from .sharding import (
    batch_pspecs,
    cache_pspecs,
    opt_state_pspecs,
    param_pspecs,
    to_shardings,
)
from jax.sharding import NamedSharding, PartitionSpec as P

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum per-device output bytes of every collective op in optimized HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        m = re.search(r"=\s+(.*?)\s+(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(", line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue  # avoid double counting async pairs
        type_str, coll = m.group(1), m.group(2)
        total = 0
        for dt, dims in shape_re.findall(type_str):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[coll] += total
    return out


def should_skip(arch: str, shape_name: str) -> Optional[str]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "decode" and not cfg.supports_decode():
        return f"{cfg.family} arch has no autoregressive decode (DESIGN.md §6)"
    if shape_name == "long_500k" and not cfg.supports_long_context():
        return "requires sub-quadratic attention (DESIGN.md §6)"
    return None


# ---------------------------------------------------------------------------
# Step builders: (fn, arg_specs, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

def build_step(cfg, shape, mesh, *, autochunk_budget=None):
    if autochunk_budget:
        cfg = cfg.with_(autochunk_budget=autochunk_budget)
    pspecs = M.param_specs(cfg)
    fsdp = shape.kind == "train"
    p_sh = to_shardings(mesh, param_pspecs(cfg, pspecs, mesh, fsdp=fsdp))
    window = cfg.sliding_window if shape.name == "long_500k" else None

    # pin (B, S, d) activations to data parallelism at block boundaries
    dp_axes = ("pod", "data") if "pod" in mesh.axis_names else "data"
    act_sh = NamedSharding(mesh, P(dp_axes, None, None))
    M.set_activation_constraint(
        lambda x: jax.lax.with_sharding_constraint(x, act_sh)
    )

    if shape.kind == "train":
        b_specs = batch_specs(cfg, shape.global_batch, shape.seq_len)
        b_sh = to_shardings(mesh, batch_pspecs(cfg, b_specs, mesh))
        opt_specs = jax.eval_shape(lambda p: adamw_init(p, moment_dtype=None), pspecs)
        o_sh = to_shardings(mesh, opt_state_pspecs(cfg, opt_specs, mesh, fsdp=fsdp))
        lr_fn = linear_warmup_cosine(3e-4, 100, 10_000)
        step = make_train_step(cfg, lr_fn, remat=True)
        rep = NamedSharding(mesh, P())
        metrics_sh = {"ce": rep, "aux": rep, "loss": rep, "lr": rep}
        if cfg.mtp:
            metrics_sh["mtp_ce"] = rep
        return (
            step,
            (pspecs, opt_specs, b_specs),
            (p_sh, o_sh, b_sh),
            (p_sh, o_sh, metrics_sh),
        )

    if shape.kind == "prefill":
        b_specs = batch_specs(cfg, shape.global_batch, shape.seq_len, with_labels=False)
        b_sh = to_shardings(mesh, batch_pspecs(cfg, b_specs, mesh))

        def prefill_step(params, batch):
            # next-token logits (serving semantics)
            return M.next_token_logits(cfg, params, batch, window=window)

        dp = ("pod", "data") if "pod" in mesh.axis_names else "data"
        out_sh = NamedSharding(
            mesh,
            P(dp if shape.global_batch % _dp(mesh) == 0 else None,
              "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None),
        )
        return prefill_step, (pspecs, b_specs), (p_sh, b_sh), out_sh

    # decode
    cache_sp = M.cache_specs(cfg, shape.global_batch, shape.seq_len)
    seq_shard = shape.global_batch == 1
    c_sh = to_shardings(mesh, cache_pspecs(cfg, cache_sp, mesh, seq_shard=seq_shard))
    tok_specs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos_specs = jax.ShapeDtypeStruct((), jnp.int32)
    dp = ("pod", "data") if "pod" in mesh.axis_names else "data"
    bshard = dp if shape.global_batch % _dp(mesh) == 0 else None
    tok_sh = NamedSharding(mesh, P(bshard, None))
    pos_sh = NamedSharding(mesh, P())

    def serve_step(params, cache, tokens, pos):
        return M.decode_step(cfg, params, cache, tokens, pos, window=window)

    lg_sh = NamedSharding(
        mesh,
        P(bshard, None, "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None),
    )
    return (
        serve_step,
        (pspecs, cache_sp, tok_specs, pos_specs),
        (p_sh, c_sh, tok_sh, pos_sh),
        (lg_sh, c_sh),
    )


def _dp(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


# ---------------------------------------------------------------------------
# The dry-run proper
# ---------------------------------------------------------------------------

def dryrun_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    autochunk_budget: Optional[float] = None,
    tag: Optional[str] = None,
    verbose: bool = True,
) -> Dict[str, Any]:
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "autochunk": autochunk_budget,
        "tag": tag,
        "time": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    skip = should_skip(arch, shape_name)
    if skip:
        rec["status"] = "skip"
        rec["reason"] = skip
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: SKIP ({skip})")
        return rec

    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    peaks = device_peaks(V5E)  # the pod the placeholder devices stand for
    t0 = time.time()
    try:
        fn, arg_specs, in_sh, out_sh = build_step(
            cfg, shape, mesh, autochunk_budget=autochunk_budget
        )
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jitted.lower(*arg_specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)

        flops_dev = float(cost.get("flops", 0.0))
        bytes_dev = float(cost.get("bytes accessed", 0.0))
        coll_dev = float(sum(coll.values()))
        rec.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops_per_device=flops_dev,
            hbm_bytes_per_device=bytes_dev,
            collective_bytes_per_device=coll_dev,
            collectives=coll,
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            },
            roofline={
                "compute_s": flops_dev / peaks["bf16_flops"],
                "memory_s": bytes_dev / peaks["hbm_bytes_per_s"],
                "collective_s": coll_dev / peaks["ici_bytes_per_s"],
            },
        )
        terms = rec["roofline"]
        rec["bottleneck"] = max(terms, key=terms.get).replace("_s", "")
        if verbose:
            print(
                f"[dryrun] {arch} x {shape_name} @ {rec['mesh']}"
                f"{' +autochunk' if autochunk_budget else ''}: OK"
                f" (lower {t_lower:.1f}s, compile {t_compile:.1f}s,"
                f" temp {rec['memory']['temp_bytes'] and rec['memory']['temp_bytes']/2**30:.2f} GiB/dev,"
                f" bottleneck {rec['bottleneck']})"
            )
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: ERROR {rec['error'][:200]}")
    return rec


def rec_key(rec: Dict[str, Any]) -> str:
    ac = f"+ac{rec.get('autochunk')}" if rec.get("autochunk") else ""
    tg = f"+{rec['tag']}" if rec.get("tag") else ""
    return f"{rec['arch']}|{rec['shape']}|{rec['mesh']}{ac}{tg}"


def load_ledger(path: str) -> Dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_ledger(path: str, ledger: Dict[str, Any]):
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(ledger, f, indent=1, default=str)
    os.replace(tmp, path)


def main():
    # must precede JAX's backend initialization, which locks the device count
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true", help="all assigned arch x shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--autochunk", type=float, default=None)
    ap.add_argument("--tag", type=str, default=None,
                    help="variant label for perf-iteration entries")
    ap.add_argument("--out", type=str, default="experiments/dryrun.json")
    ap.add_argument("--force", action="store_true", help="recompute cached entries")
    args = ap.parse_args()

    combos = []
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    ledger = load_ledger(args.out)
    for arch, shape_name, mp in combos:
        probe = {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if mp else "16x16", "autochunk": args.autochunk,
            "tag": args.tag,
        }
        key = rec_key(probe)
        if key in ledger and ledger[key].get("status") in ("ok", "skip") and not args.force:
            print(f"[dryrun] {key}: cached ({ledger[key]['status']})")
            continue
        rec = dryrun_one(
            arch, shape_name, multi_pod=mp, autochunk_budget=args.autochunk,
            tag=args.tag,
        )
        ledger[rec_key(rec)] = rec
        save_ledger(args.out, ledger)

    ok = sum(1 for r in ledger.values() if r.get("status") == "ok")
    sk = sum(1 for r in ledger.values() if r.get("status") == "skip")
    er = sum(1 for r in ledger.values() if r.get("status") == "error")
    print(f"[dryrun] ledger: {ok} ok, {sk} skip, {er} error -> {args.out}")


if __name__ == "__main__":
    main()
