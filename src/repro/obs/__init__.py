"""repro.obs — the observability layer.

Three legs (see each submodule):

* :mod:`repro.obs.tracing` — nested spans over the compile pipeline, the
  serving step loop and jax's own jit compile stages, exported as
  Chrome-trace/Perfetto JSON and shown on a ``jax.profiler`` trace.
* :mod:`repro.obs.metrics` — typed metrics registry (counters / gauges /
  fixed-bucket histograms) with Prometheus text exposition and JSON
  snapshots.  ``core.stats`` is now a thin compat shim over this.
* :mod:`repro.obs.accuracy` — predicted-vs-measured activation-peak
  accounting (``plan_accuracy``), closing the loop on the estimator.

Import discipline: nothing in this package may import ``repro.core``
(``core.stats`` imports us — a cycle would break the package).
"""
from . import accuracy, clock, metrics, tracing  # noqa: F401
from .accuracy import PlanAccuracy, watermark_jaxpr  # noqa: F401
from .clock import ManualClock  # noqa: F401
from .metrics import REGISTRY, MetricsRegistry, default_registry  # noqa: F401
from .tracing import TRACER, span, traced  # noqa: F401
