"""Roofline analysis (deliverable g): derive the three-term roofline for
every (arch x shape x mesh) from the dry-run ledger and emit the table.

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / link_bw

plus MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) and the
MODEL_FLOPS / HLO_FLOPs usefulness ratio (catches remat/redundancy waste).
"""
from __future__ import annotations

import json
import os
from typing import Dict

from repro.configs import INPUT_SHAPES, get_config
from repro.models import model as M

LEDGER = os.environ.get("DRYRUN_LEDGER", "experiments/dryrun.json")


def model_flops(arch: str, shape_name: str) -> float:
    """6*N*D for train (fwd+bwd); 2*N*D for inference; N = active params."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    n = M.active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch * 1  # one decode step
    return 2.0 * n * tokens


def summarize(ledger_path: str = LEDGER):
    with open(ledger_path) as f:
        ledger: Dict[str, dict] = json.load(f)
    rows = []
    for key, rec in sorted(ledger.items()):
        if rec.get("status") == "skip":
            rows.append({
                "key": key, "status": "skip", "reason": rec.get("reason", "")
            })
            continue
        if rec.get("status") != "ok":
            rows.append({"key": key, "status": "error",
                         "reason": rec.get("error", "")[:120]})
            continue
        chips = rec["chips"]
        mf = model_flops(rec["arch"], rec["shape"])
        hlo_total = rec["flops_per_device"] * chips
        r = rec["roofline"]
        dominant = max(r, key=r.get)
        rows.append({
            "key": key,
            "status": "ok",
            "compute_s": r["compute_s"],
            "memory_s": r["memory_s"],
            "collective_s": r["collective_s"],
            "bottleneck": dominant.replace("_s", ""),
            "model_flops": mf,
            "hlo_flops_total": hlo_total,
            "useful_ratio": mf / hlo_total if hlo_total else 0.0,
            "temp_gib": (rec["memory"]["temp_bytes"] or 0) / 2**30,
            "fits_16g": (rec["memory"]["temp_bytes"] or 0) / 2**30 < 16.0,
        })
    return rows


def run(csv_rows):
    if not os.path.exists(LEDGER):
        csv_rows.append(("roofline", 0.0, "no dryrun ledger; run repro.launch.dryrun"))
        return csv_rows
    for row in summarize():
        if row["status"] != "ok":
            csv_rows.append((f"roofline_{row['key']}", 0.0,
                             f"{row['status']}:{row['reason'][:80]}"))
            continue
        csv_rows.append(
            (f"roofline_{row['key']}", 0.0,
             f"compute_s={row['compute_s']:.3e};memory_s={row['memory_s']:.3e};"
             f"collective_s={row['collective_s']:.3e};bottleneck={row['bottleneck']};"
             f"useful={row['useful_ratio']:.2f};temp_GiB={row['temp_gib']:.1f};"
             f"fits={row['fits_16g']}")
        )
    return csv_rows


def markdown_table(ledger_path: str = LEDGER) -> str:
    rows = summarize(ledger_path)
    out = [
        "| arch × shape @ mesh | compute s | memory s | collective s |"
        " bottleneck | useful | temp GiB/dev | fits 16G |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] != "ok":
            out.append(f"| {r['key']} | — | — | — | {r['status']}: {r['reason'][:60]} | | | |")
            continue
        out.append(
            f"| {r['key']} | {r['compute_s']:.2e} | {r['memory_s']:.2e} |"
            f" {r['collective_s']:.2e} | {r['bottleneck']} |"
            f" {r['useful_ratio']:.2f} | {r['temp_gib']:.1f} |"
            f" {'yes' if r['fits_16g'] else 'NO'} |"
        )
    return "\n".join(out)


if __name__ == "__main__":
    print(markdown_table())
