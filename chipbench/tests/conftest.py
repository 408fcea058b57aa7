"""CPU rehearsal of the benchmark at a reduced size.

``cell_root`` lays out a benchmark tree in a temporary directory: the
real drivers (``kinds/``), readers (``metrics/``) and architecture modules
(``references/``), copied, and a reduced configuration, traffic mixes and
cells of its own, with a ``BENCHMARK.json`` naming them.  The chip gate is replaced from inside the
test, so the drivers run end to end on the CPU with Pallas interpreted.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

SIZES = {
    "family": "dense", "n_layers": 2, "d_model": 128, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 32, "d_ff": 256, "vocab_size": 512,
    "act": "swiglu", "norm": "rmsnorm", "rope_theta": 10000.0,
    "sliding_window": None, "tie_embeddings": False, "dtype": "bfloat16",
}
CONFIG = {
    "registry": "phi3-mini-3.8b",
    "overrides": {"sliding_window": None, "n_layers": 2, "d_model": 128,
                  "n_heads": 4, "n_kv_heads": 2, "d_ff": 256,
                  "vocab_size": 512},
    "source": "test", "reference": "dense", "sizes": SIZES, "reduced": [],
}
MIXES = {
    "tiny-prefill": {"kind": "prefill", "prompt_len": 256, "batch": 1,
                     "distinct_prompts": 2, "loop": "closed"},
    "tiny-serve": {"kind": "serve", "loop": "open", "arrival": "poisson",
                   "prompt": {"median": 24, "sigma": 0.5, "min": 8,
                              "max": 48},
                   "output": {"median": 16, "sigma": 0.5, "min": 8,
                              "max": 32}},
}
# Limits of the reduced cells, set from readings on the CPU over seeds 1
# to 12: the program's logit_err 0.0072-0.0144 against the control's
# 0.098-0.244, and its token_gap 0-0.024 against the control's 0.246-0.560.
CELLS = {
    "tiny.prefill": {"config": "tiny", "traffic": "tiny-prefill",
                     "autochunk_budget_bytes": 200000,
                     "limits": {"logit_err": 0.04}},
    "tiny.serve": {"config": "tiny", "traffic": "tiny-serve",
                   "rate_per_s": 4.0, "max_seqs": 4, "max_len": 128,
                   "page_size": 16, "num_pages": 48, "preroll_s": 1.0,
                   "drain_s": 60.0, "trace_s": 1.0, "check_tokens": 60,
                   "limits": {"token_gap": 0.12}},
}


# What a serve cell reports.  The benchmark file names these once it has
# a serve cell; until then the reduced serve cell takes them from here.
# A per-layer entry whose name the file has already adds this cell to it.
SERVE = ["tiny.serve"]
SERVE_METRICS = {
    "end_to_end": [
        {"name": "ttft_p95_s", "unit": "s", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": SERVE},
        {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": SERVE},
    ],
    "per_layer": [
        {"name": "autochunk_compile_s", "workloads": SERVE},
    ] + [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": moves, "workloads": SERVE}
        for name, unit, better, source, layer, moves in [
            ("paged_attn_roofline.serve", "%", "higher", "device_trace",
             "kernels (kernels/)", "itl_p95_ms"),
            ("mfu.serve", "%", "higher", "host_clock",
             "model step and device", "itl_p95_ms"),
            ("idle_share.serve", "%", "lower", "device_trace", "device",
             "itl_p95_ms"),
            ("decode_step_ms.serve", "ms", "lower", "host_clock",
             "scheduler (serving/engine.py)", "itl_p95_ms"),
            ("mixed_step_ms.serve", "ms", "lower", "host_clock",
             "scheduler (serving/engine.py)", "itl_p95_ms"),
            ("admit_refusal_share.serve", "%", "lower", "program_counter",
             "KV pool (serving/kv_pool.py)", "ttft_p95_s"),
        ]
    ],
}


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def bench_json(cells, real: dict) -> dict:
    """A BENCHMARK.json over ``cells`` with the real file's metrics and
    the serve metrics: a metric listed for the real cells of a kind is
    listed for the test cells of that kind."""
    names = list(cells)
    out = {k: v for k, v in real.items()
           if k not in ("workloads", "end_to_end", "per_layer")}
    out["workloads"] = [{"name": n, "config": cells[n]["config"],
                         "traffic": cells[n]["traffic"], "chips": 1,
                         "why": "test"} for n in names]
    kinds = {n: MIXES[cells[n]["traffic"]]["kind"] for n in names}
    for group in ("end_to_end", "per_layer"):
        merged = {}
        for m in real[group] + SERVE_METRICS[group]:
            m = dict(m)
            if "workloads" in m:
                listed = {w.rsplit(".", 1)[1].split("-")[0]
                          for w in m["workloads"]}
                m["workloads"] = [n for n in names if kinds[n] in listed]
                if m["name"] in merged:
                    merged[m["name"]]["workloads"] += m["workloads"]
                    continue
            merged[m["name"]] = m
        out[group] = list(merged.values())
    return out


@pytest.fixture
def cell_root(tmp_path):
    for sub in ("kinds", "metrics", "references"):
        shutil.copytree(HERE / sub, tmp_path / sub)
    write_json(tmp_path / "configs" / "tiny.json", CONFIG)
    for name, mix in MIXES.items():
        write_json(tmp_path / "traffic" / f"{name}.json", mix)
    for name, cell in CELLS.items():
        write_json(tmp_path / "workloads" / f"{name}.json", cell)
    real = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    write_json(tmp_path / "BENCHMARK.json", bench_json(CELLS, real))
    return tmp_path


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """Let the drivers run on the CPU: the chip gate hands out the CPU
    device, and traces and plans go to a temporary directory."""
    import jax

    import bench

    import peaks

    monkeypatch.setattr(bench, "require_chip",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(bench, "CACHE", tmp_path / "cache")
    # the readers divide by a peak; the CPU gets the v5e's, and no CPU
    # number is ever reported as a device metric
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    return bench


def run_cell(bench, root: Path, name: str, *, seed=7, seconds=3.0,
             trace=0, control=False) -> dict:
    args = bench.parse_args(["--workload", name, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace",
                             str(trace)])
    return bench.run(args, root=root, bench_file=root / "BENCHMARK.json",
                     control=control)
