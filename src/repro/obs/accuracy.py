"""Predicted-vs-measured activation-peak accounting.

AutoChunk's contract is a *bounded activation peak* chosen by the cost
model at search time.  This module closes the loop: after a plan is
compiled (and, on devices with allocator stats, after it executes), the
search-time *prediction* is recorded next to a *measurement* and the
relative error becomes a first-class, gated number
(:class:`PlanAccuracy`: ``predicted_bytes`` / ``measured_bytes`` /
``error_pct``).

Two measurement sources:

* ``device`` — ``Device.memory_stats()`` deltas (``peak_bytes_in_use``
  minus a baseline captured before execution).  Available on TPU/GPU
  allocators; CPU returns nothing.
* ``interpret`` — a deterministic fallback: the exact live-set watermark
  of the final rewritten/emitted jaxpr (:func:`watermark_jaxpr`).  The
  prediction came from the analytic candidate model (``chunk_loop``
  ``body_peak`` terms, never re-traced), while the watermark walks the
  *emitted* program with its real ``scan`` bodies — so the error is the
  estimator's structural drift, not a tautology.

Under a device mesh a third source, ``per_device_watermark``
(:func:`per_device_accuracy`), scales the interpret watermark down to one
device's shard so mesh-aware (per-device) predictions compare against a
per-device measurement.

``watermark_jaxpr`` deliberately re-implements the SSA liveness walk from
``core.estimation`` instead of importing it: ``repro.obs`` must stay
importable without ``repro.core`` (core.stats imports obs.metrics), and
the walker here additionally supports *state exclusions* — buffer sizes
(e.g. the paged KV pool) that are persistent state rather than
activations and would otherwise dominate the watermark.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, FrozenSet, Optional


# ---------------------------------------------------------------------------
# jaxpr live-set watermark (interpret-mode measurement)
# ---------------------------------------------------------------------------

def _nbytes(atom) -> int:
    aval = getattr(atom, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    for d in shape:
        size *= int(d)
    return size * dtype.itemsize


def _inner_peak(eqn, exclude: FrozenSet[int]) -> int:
    """Internal peak of a structured-control-flow equation's body."""
    name = eqn.primitive.name
    params = eqn.params
    if name == "chunk_loop":
        return int(params["body_peak"])
    if name == "cond":
        return max(
            (_walk(b.jaxpr, exclude) for b in params["branches"]), default=0
        )
    closed = None
    if name == "scan":
        closed = params.get("jaxpr")
    elif name == "while":
        closed = params.get("body_jaxpr")
    elif name in ("jit", "closed_call", "remat", "checkpoint",
                  "custom_jvp_call", "custom_vjp_call"):
        closed = params.get("jaxpr") or params.get("call_jaxpr")
    if closed is None:
        return 0
    inner = getattr(closed, "jaxpr", closed)  # ClosedJaxpr or raw jaxpr
    return _walk(inner, exclude)


def _walk(jaxpr, exclude: FrozenSet[int]) -> int:
    """Exact SSA liveness watermark over one jaxpr (recursive)."""
    from jax.extend import core as jex_core

    last_use: Dict[Any, int] = {}
    n = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for iv in eqn.invars:
            if isinstance(iv, jex_core.Var):
                last_use[iv] = i
    for ov in jaxpr.outvars:
        if isinstance(ov, jex_core.Var):
            last_use[ov] = n
    inputs = set(jaxpr.invars) | set(jaxpr.constvars)

    def counted(v) -> int:
        b = _nbytes(v)
        return 0 if b in exclude else b

    live = set()
    live_bytes = 0
    peak = 0
    for i, eqn in enumerate(jaxpr.eqns):
        extra = _inner_peak(eqn, exclude)
        out_b = sum(
            counted(ov) for ov in eqn.outvars
            if isinstance(ov, jex_core.Var) and ov not in inputs
        )
        peak = max(peak, live_bytes + out_b + extra)
        for ov in eqn.outvars:
            if (isinstance(ov, jex_core.Var) and ov not in inputs
                    and last_use.get(ov, -1) > i and ov not in live):
                live.add(ov)
                live_bytes += counted(ov)
        for v in [v for v in live if last_use.get(v, -1) <= i]:
            live.remove(v)
            live_bytes -= counted(v)
    return peak


def watermark_jaxpr(closed_jaxpr, exclude_nbytes=()) -> int:
    """Peak live *intermediate* bytes of a (closed) jaxpr.

    ``exclude_nbytes``: buffer sizes to count as zero — persistent state
    (KV-pool pages, donated in-place updates) that the activation
    estimator never modeled and the allocator aliases in place.
    """
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    return _walk(jaxpr, frozenset(int(b) for b in exclude_nbytes))


# ---------------------------------------------------------------------------
# device allocator stats (real-hardware measurement)
# ---------------------------------------------------------------------------

def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """``Device.memory_stats()`` if the backend exposes one, else None
    (CPU does not).  Never raises."""
    try:
        import jax

        d = device if device is not None else jax.local_devices()[0]
        st = d.memory_stats()
    except Exception:
        return None
    return st if isinstance(st, dict) and st else None


def device_bytes_in_use(device=None) -> Optional[int]:
    st = device_memory_stats(device)
    return None if st is None else st.get("bytes_in_use")


def device_peak_bytes(device=None) -> Optional[int]:
    """Peak device bytes: the peak in use plus the peak reserved.  On a
    TPU an executable's temporaries are reserved apart from the buffers in
    use, so the peak in use alone leaves them out."""
    st = device_memory_stats(device)
    if st is None or "peak_bytes_in_use" not in st:
        return None
    return int(st["peak_bytes_in_use"]) + int(st.get("peak_bytes_reserved", 0))


# ---------------------------------------------------------------------------
# the accuracy record
# ---------------------------------------------------------------------------

@dataclass
class PlanAccuracy:
    """Per-plan predicted-vs-measured activation peak."""

    predicted_bytes: int
    measured_bytes: int
    error_pct: float
    source: str                      # 'device' | 'interpret'
    cache_key: str = ""
    extra: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "predicted_bytes": int(self.predicted_bytes),
            "measured_bytes": int(self.measured_bytes),
            "error_pct": float(self.error_pct),
            "source": self.source,
        }
        if self.cache_key:
            d["cache_key"] = self.cache_key
        d.update(self.extra)
        return d

    def status_line(self) -> str:
        return (
            f"plan_accuracy: predicted_bytes={int(self.predicted_bytes)}"
            f" measured_bytes={int(self.measured_bytes)}"
            f" error_pct={self.error_pct:.2f} source={self.source}"
        )


def compare(predicted_bytes: int, measured_bytes: int, source: str,
            cache_key: str = "", **extra) -> PlanAccuracy:
    """Build a :class:`PlanAccuracy`; error is relative to the measurement
    (``|p - m| / m``), the convention of the paper's §4 peak tables."""
    p = int(predicted_bytes)
    m = int(measured_bytes)
    if m > 0:
        err = abs(p - m) / m * 100.0
    elif p == 0:
        err = 0.0
    else:
        err = math.inf
    return PlanAccuracy(p, m, err, source, cache_key, dict(extra))


def per_device_accuracy(
    predicted_bytes: int,
    closed_jaxpr,
    *,
    peak_divisor: float = 1.0,
    cache_key: str = "",
    exclude_nbytes=(),
    device=None,
    **extra,
) -> PlanAccuracy:
    """Predicted-vs-measured peak at *per-device* granularity.

    When the compile pipeline plans against a mesh, its prediction is the
    sharded (per-device) peak.  The emitted jaxpr, however, is the global
    program — its :func:`watermark_jaxpr` is the full unsharded watermark.
    ``peak_divisor`` is the caller-computed ratio between the full and the
    per-device estimate of the *same* emitted graph (two estimation runs in
    ``repro.core``; this module stays importable without it), so the
    partitioned measurement is ``watermark / peak_divisor`` — the same
    structural watermark, charged at the device's shard of every var.
    Where the backend exposes allocator stats, the device's current peak
    (:func:`device_peak_bytes`) rides along in ``extra`` for the serving
    layer.
    """
    full = watermark_jaxpr(closed_jaxpr, exclude_nbytes=exclude_nbytes)
    div = float(peak_divisor) if peak_divisor and peak_divisor > 0 else 1.0
    measured = int(full / div)
    acc = compare(
        predicted_bytes, measured, "per_device_watermark",
        cache_key=cache_key,
        full_watermark_bytes=full,
        peak_divisor=div,
        **extra,
    )
    dev_peak = device_peak_bytes(device)
    if dev_peak is not None:
        acc.extra["device_peak_bytes"] = int(dev_peak)
    return acc


def with_device_measurement(
    acc: PlanAccuracy, baseline_bytes: Optional[int]
) -> PlanAccuracy:
    """Upgrade an interpret-mode record with the allocator's peak delta
    since ``baseline_bytes`` (captured before execution).  Returns ``acc``
    unchanged when the backend has no ``memory_stats()`` (CPU) or the
    delta is degenerate; the interpret watermark rides along in
    ``extra`` so both measurements stay visible."""
    if baseline_bytes is None:
        return acc
    peak = device_peak_bytes()
    if peak is None:
        return acc
    measured = peak - int(baseline_bytes)
    if measured <= 0:
        return acc
    new = compare(acc.predicted_bytes, measured, "device",
                  cache_key=acc.cache_key, **acc.extra)
    new.extra["interpret_measured_bytes"] = acc.measured_bytes
    return new


def publish(acc: PlanAccuracy, registry=None) -> PlanAccuracy:
    """Mirror an accuracy record into the metrics registry (gauges keep
    the latest plan; the counter counts reports)."""
    from . import metrics as _metrics

    reg = registry if registry is not None else _metrics.default_registry()
    reg.gauge("plan_predicted_bytes",
              "search-time predicted activation peak of the latest plan"
              ).set(acc.predicted_bytes)
    reg.gauge("plan_measured_bytes",
              "measured activation peak of the latest plan"
              ).set(acc.measured_bytes)
    reg.gauge("plan_error_pct",
              "relative predicted-vs-measured error of the latest plan"
              ).set(acc.error_pct if math.isfinite(acc.error_pct) else -1.0)
    reg.counter("plan_accuracy_reports",
                "plan accuracy records published").inc()
    return acc
