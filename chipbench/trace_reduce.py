"""From a profiler trace to device busy time, kernel times and idle gaps.

``reduce_dir`` reads the ``*.trace.json.gz`` that ``jax.profiler`` writes
beside its ``.xplane.pb`` (the same events, with each op's HLO category
and source line, which the xplane reader does not expose) and returns a
:class:`TraceSummary`:

* busy: the union of the intervals in which an operation ran on a device
  (a ``/device:TPU:N`` process's ``XLA Ops`` thread), averaged over the
  devices;
* op time by stable name, each op's own time without the ops nested in
  it (a ``while`` loop encloses its body): a Pallas kernel is named by
  the source line that called ``pallas_call`` (``paged_attention.py:228``),
  any other op by its HLO name without the instance number (``fusion``);
* idle gaps: each stretch with no device op, named after the host span
  (a ``jax.profiler.TraceAnnotation`` the benchmark wrote) that overlaps
  it most.

The device clock runs a little apart from the host's; device times are
moved onto the host's clock by the median distance between each program
launch (``PJRT_LoadedExecutable_Execute``) and the start of the module it
launched, where both count the same programs.  The traced window's length
is the host's own measure of the time between starting and stopping the
profiler.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import statistics
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

# the source files of the program's Pallas kernels
PREFILL_ATTENTION = r"chunked_attention\.py"
PAGED_ATTENTION = r"paged_attention\.py"
# host spans the benchmark writes around what it does between device ops
HOST_SPANS = ("bench.", "engine.")
LAUNCH = "PJRT_LoadedExecutable_Execute"
DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    ops: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def kernel_s(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.ops.items() if rx.search(name))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        by_span: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            by_span[name] += s
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def op_name(ev: dict) -> str:
    """The stable name of a device op event."""
    args = ev.get("args") or {}
    source = args.get("source", "")
    if args.get("hlo_category") == "custom-call" and source:
        return os.path.basename(source)
    return re.sub(r"(\.\d+)+$", "", ev["name"])


def self_times(evs: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """(name, time not covered by ops nested inside it) of each op: a
    ``while`` loop's events enclose those of its body on the same line."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    own = [e - s for _, s, e in evs]
    stack: List[int] = []
    for i in order:
        _, s, e = evs[i]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(evs[i][0], own[i]) for i in range(len(evs))]


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def attribute(gap: Tuple[float, float],
              spans: Sequence[Tuple[float, float, str]]) -> str:
    """The host span that overlaps ``gap`` the most, else ``"host"``."""
    best, name = 0.0, "host"
    for s, e, n in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def clock_shift(launches: List[float], modules: List[float]) -> float:
    """What to add to device times to put them on the host's clock."""
    if not launches or len(launches) != len(modules):
        return 0.0
    return statistics.median(h - d for h, d in zip(sorted(launches),
                                                   sorted(modules)))


def parse(events: List[dict]):
    """(device ops per device, host spans, launches, device-0 modules),
    times in microseconds as the trace gives them."""
    procs, threads = {}, {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            procs[ev["pid"]] = ev["args"]["name"]
        elif ev.get("ph") == "M" and ev.get("name") == "thread_name":
            threads[(ev["pid"], ev.get("tid"))] = ev["args"]["name"]
    devices: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
    host: List[Tuple[float, float, str]] = []
    launches: List[float] = []
    modules: Dict[str, List[float]] = defaultdict(list)
    for ev in events:
        if ev.get("ph") != "X":
            continue
        proc = procs.get(ev["pid"], "")
        thread = threads.get((ev["pid"], ev.get("tid")), "")
        s, e = float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0))
        if DEVICE.match(proc):
            if thread == "XLA Ops":
                devices[proc].append((op_name(ev), s, e))
            elif thread == "XLA Modules":
                modules[proc].append(s)
        elif proc.startswith("/host"):
            if ev["name"].startswith(HOST_SPANS):
                host.append((s, e, ev["name"]))
            elif ev["name"] == LAUNCH:
                launches.append(s)
    first = min(modules, default=None)
    return dict(devices), host, launches, modules.get(first, [])


def summarize(devices: Dict[str, List[Tuple[str, float, float]]],
              host: List[Tuple[float, float, str]], window_s: float,
              shift_us: float = 0.0) -> TraceSummary:
    """Times in microseconds; ``shift_us`` moves device times onto the
    host's clock."""
    ops: Dict[str, float] = defaultdict(float)
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    n_dev = max(1, len(devices))
    for evs in devices.values():
        for name, own in self_times(evs):
            ops[name] += own / 1e6 / n_dev
        merged = union([(s + shift_us, e + shift_us) for _, s, e in evs])
        busy += sum(e - s for s, e in merged) / 1e6 / n_dev
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((attribute((e0, s1), host), (s1 - e0) / 1e6 / n_dev))
    return TraceSummary(window_s=window_s, busy_s=min(busy, window_s),
                        ops=dict(ops), gaps=gaps)


def find_trace(trace_dir) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.trace.json.gz"),
                             recursive=True))
    return files[-1] if files else None


def reduce_file(path: str, window_s: float) -> Optional[TraceSummary]:
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data["traceEvents"] if isinstance(data, dict) else data
    devices, host, launches, modules = parse(events)
    if not devices:
        return None
    return summarize(devices, host, window_s,
                     clock_shift(launches, modules))


def reduce_dir(trace_dir, window) -> Optional[TraceSummary]:
    """Summary of the trace in ``trace_dir``; ``window`` is the host's
    (start, stop) of the profiler in seconds."""
    path = find_trace(trace_dir)
    return None if path is None else reduce_file(path, window[1] - window[0])
