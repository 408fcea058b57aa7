"""Device time by the program's own scopes, from a profiler trace.

Each device op event of the ``*.trace.json.gz`` that ``jax.profiler``
writes carries ``tf_op``, the op's jax name stack
(``jit(<lambda>)/autochunk/while/body/closed_call/mlp/dot_general:``).  An
op belongs to the innermost of the program's scopes on that stack:

* the model's layers (``models/layers.py``, ``models/model.py``): ``qkv``,
  ``attention``, ``attn_out``, ``mlp``, ``norm``, ``unembed``;
* AutoChunk's chunk I/O (``core/lowering.py``): ``autochunk.io``, the
  zeroed output buffers and the slicing in and writing out of each chunk;
* AutoChunk's loop itself, outside both: ``autochunk``;

and to ``none`` when its stack holds none of them.  Times are each op's
own time (``trace_reduce.self_times``), and busy time is the union of the
ops' intervals (``trace_reduce.union``), both averaged over the devices.

    python chipbench/scopes.py [trace_dir]

prints the device time of each scope and of its largest ops, from the
newest trace under ``trace_dir`` (the benchmark's own, by default).
"""
from __future__ import annotations

import gzip
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Optional, Tuple

import trace_reduce

MODEL = ("qkv", "attention", "attn_out", "mlp", "norm", "unembed")
CHUNK_IO = "autochunk.io"
CHUNK_LOOP = "autochunk"
NONE = "none"


def trace_dir() -> Path:
    """Where ``bench.py`` has the profiler write a traced run's trace."""
    import bench

    return bench.CACHE / "trace"


def scope_of(tf_op: str) -> str:
    parts = [p.split(":")[0] for p in tf_op.split("/")]
    if CHUNK_IO in parts:
        return CHUNK_IO
    for p in reversed(parts):
        if p in MODEL:
            return p
    return CHUNK_LOOP if CHUNK_LOOP in parts else NONE


def device_ops(events) -> Dict[str, list]:
    """(scope, op name, start, end) of each op, per device; times in
    microseconds as the trace gives them."""
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("ph") == "M" and ev.get("name") == "thread_name":
            threads[(ev["pid"], ev.get("tid"))] = ev["args"]["name"]
    out = defaultdict(list)
    for ev in events:
        proc = procs.get(ev.get("pid"), "")
        if (ev.get("ph") != "X" or not trace_reduce.DEVICE.match(proc)
                or threads.get((ev["pid"], ev.get("tid"))) != "XLA Ops"):
            continue
        s = float(ev["ts"])
        scope = scope_of((ev.get("args") or {}).get("tf_op", ""))
        out[proc].append((scope, trace_reduce.op_name(ev), s,
                          s + float(ev.get("dur", 0))))
    return dict(out)


def scope_times(events) -> Tuple[Dict[Tuple[str, str], float], float]:
    """({(scope, op name): own seconds}, busy seconds), averaged over the
    devices."""
    devices = device_ops(events)
    n_dev = max(1, len(devices))
    times: Dict[Tuple[str, str], float] = defaultdict(float)
    busy = 0.0
    for ops in devices.values():
        keyed = [((scope, name), s, e) for scope, name, s, e in ops]
        for key, own in trace_reduce.self_times(keyed):
            times[key] += own / 1e6 / n_dev
        merged = trace_reduce.union([(s, e) for _, _, s, e in ops])
        busy += sum(e - s for s, e in merged) / 1e6 / n_dev
    return dict(times), busy


def read_trace(path) -> Optional[list]:
    found = trace_reduce.find_trace(path)
    if found is None:
        return None
    with gzip.open(found, "rt") as f:
        data = json.load(f)
    return data["traceEvents"] if isinstance(data, dict) else data


def by_scope(times: Dict[Tuple[str, str], float]) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for (scope, _), s in times.items():
        out[scope] += s
    return dict(out)


def main(argv) -> int:
    events = read_trace(argv[0] if argv else trace_dir())
    if events is None:
        print("no trace found", file=sys.stderr)
        return 1
    times, busy = scope_times(events)
    scopes = sorted(by_scope(times).items(), key=lambda kv: -kv[1])
    print(f"busy {busy!r} s")
    for scope, s in scopes:
        print(f"{scope}\t{s!r}\t{100 * s / busy if busy else 0:.4f}%")
        ops = sorted(((n, t) for (sc, n), t in times.items() if sc == scope),
                     key=lambda kv: -kv[1])
        for name, t in ops[:6]:
            print(f"  {name}\t{t!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
