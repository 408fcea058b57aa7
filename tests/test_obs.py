"""Telemetry subsystem tests: metrics registry, tracing spans, compat shim,
plan-accuracy accounting, injected clocks, and the serve CLI exports.

Covers the ISSUE-9 satellites: histogram bucket-edge (``le``) semantics and
edge validation; ``core.stats`` compat-shim equivalence with the old flat
dict API; a Prometheus exposition golden; span nesting/ordering on a
:class:`ManualClock` (no sleeping); ``stats.bump`` thread safety;
``PlanCache`` eviction on an injected clock; counter-asserted
predicted-vs-measured ``plan_accuracy``; and an end-to-end ``serve.py
--metrics-out/--trace-out`` run over the paged prefix-cache scenario.
"""
import json
import math
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.core import ChunkConfig, ChunkedFunction, PlanCache, stats
from repro.core.plan import ChunkPlan
from repro.obs import accuracy as obs_accuracy
from repro.obs.clock import ManualClock
from repro.obs.metrics import (
    LATENCY_BUCKETS_S,
    Counter,
    MetricsRegistry,
    default_registry,
)
from repro.obs.tracing import TRACER, Tracer, traced


def _mini_block(w, x):
    q = x @ w["wq"]
    k = x @ w["wk"]
    v = x @ w["wv"]
    logits = jnp.einsum("bsd,btd->bst", q, k) / jnp.sqrt(x.shape[-1])
    a = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bst,btd->bsd", a, v) @ w["wo"]
    h = x + o
    ff = jax.nn.gelu(h @ w["w1"]) @ w["w2"]
    return h + ff


def _mini_weights(d=32, f=64, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    return {
        "wq": jax.random.normal(ks[0], (d, d)) * 0.1,
        "wk": jax.random.normal(ks[1], (d, d)) * 0.1,
        "wv": jax.random.normal(ks[2], (d, d)) * 0.1,
        "wo": jax.random.normal(ks[3], (d, d)) * 0.1,
        "w1": jax.random.normal(ks[4], (d, f)) * 0.1,
        "w2": jax.random.normal(ks[5], (f, d)) * 0.1,
    }


def _x(seq=48, d=32, key=9):
    return jax.random.normal(jax.random.PRNGKey(key), (2, seq, d))


# ---------------------------------------------------------------------------
# Histograms: bucket-edge semantics and validation
# ---------------------------------------------------------------------------

def test_histogram_le_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 5.0, 7.0):
        h.observe(v)
    # v lands in the FIRST bucket with v <= le; 1.0 belongs to le=1.0,
    # 5.0 to le=5.0, 7.0 overflows into the implicit +Inf slot
    assert h.bucket_counts() == [2, 1, 1, 1]
    assert h.cumulative() == [
        (1.0, 2), (2.0, 3), (5.0, 4), (float("inf"), 5),
    ]
    assert h.count == 5
    assert h.sum == pytest.approx(15.0)


def test_histogram_edges_must_increase():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("bad2", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("bad3", buckets=())
    # the shipped default edges satisfy their own validator
    assert reg.histogram("ok", buckets=LATENCY_BUCKETS_S) is not None


def test_registry_get_or_create_and_type_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    assert reg.counter("x_total") is c          # idempotent registration
    with pytest.raises(TypeError):
        reg.gauge("x_total")                    # same name, different type
    with pytest.raises(ValueError):
        c.inc(-1)                               # counters are monotonic
    assert reg.get("x_total") is c
    assert reg.get("never_registered") is None


def test_registry_reset_counters_only():
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    reg.gauge("g").set(7.0)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    reg.reset(counters_only=True)
    assert reg.counter("c").value == 0
    assert reg.gauge("g").value == 7.0
    assert reg.histogram("h", buckets=(1.0,)).count == 1
    reg.reset()
    assert reg.gauge("g").value == 0.0
    assert reg.histogram("h", buckets=(1.0,)).count == 0


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("a_total", help="requests served").inc(3)
    reg.gauge("g_pages").set(2.5)
    h = reg.histogram("h_lat", buckets=(0.5, 1.0), help="step latency")
    for v in (0.25, 0.5, 5.0):                  # exact binary fractions
        h.observe(v)
    golden = (
        "# HELP a_total requests served\n"
        "# TYPE a_total counter\n"
        "a_total 3\n"
        "# TYPE g_pages gauge\n"
        "g_pages 2.5\n"
        "# HELP h_lat step latency\n"
        "# TYPE h_lat histogram\n"
        'h_lat_bucket{le="0.5"} 2\n'
        'h_lat_bucket{le="1"} 2\n'
        'h_lat_bucket{le="+Inf"} 3\n'
        "h_lat_sum 5.75\n"
        "h_lat_count 3\n"
    )
    assert reg.to_prometheus() == golden


def test_snapshot_and_json_roundtrip():
    reg = MetricsRegistry()
    reg.counter("c").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h", buckets=(1.0, 2.0)).observe(3.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"c": 2}
    assert snap["gauges"] == {"g": 1.5}
    assert snap["histograms"]["h"] == {
        "buckets": [1.0, 2.0], "counts": [0, 0, 1], "sum": 3.0, "count": 1,
    }
    assert json.loads(reg.to_json(extra_key="v"))["extra_key"] == "v"


# ---------------------------------------------------------------------------
# core.stats compat shim over the registry
# ---------------------------------------------------------------------------

def test_stats_shim_preserves_dict_api():
    before = stats.snapshot()
    # the pre-registered pipeline counters are always present in snapshots
    assert "trace_calls" in before and "bucket_exec_hits" in before
    stats.bump("obs_shim_test_counter")
    stats.bump("obs_shim_test_counter", 4)
    d = stats.delta(before)
    assert d["obs_shim_test_counter"] == 5
    # untouched counters diff to zero, exactly like the old flat dict
    assert d["trace_calls"] == 0
    after = stats.snapshot()
    assert after["obs_shim_test_counter"] == before.get(
        "obs_shim_test_counter", 0) + 5
    # the shim writes through to the shared typed registry
    c = default_registry().get("obs_shim_test_counter")
    assert isinstance(c, Counter) and c.value == after[
        "obs_shim_test_counter"]


def test_stats_bump_is_thread_safe():
    """Satellite (a): the old dict bump was a read-modify-write race."""
    n_threads, n_incs = 8, 2000
    name = "obs_concurrency_test_counter"
    base = stats.snapshot().get(name, 0)
    barrier = threading.Barrier(n_threads)

    def worker():
        barrier.wait()
        for _ in range(n_incs):
            stats.bump(name)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert stats.snapshot()[name] - base == n_threads * n_incs


# ---------------------------------------------------------------------------
# Tracing on a manual clock (no sleeping)
# ---------------------------------------------------------------------------

def test_manual_clock():
    clk = ManualClock(10.0)
    assert clk() == 10.0
    clk.advance(2.5)
    assert clk() == 12.5
    with pytest.raises(ValueError):
        clk.advance(-1.0)


def test_span_nesting_and_ordering_on_manual_clock():
    clk = ManualClock()
    tr = Tracer(clock=clk)
    with tr.span("compile.outer"):
        clk.advance(1.0)
        with tr.span("compile.inner", chunk=16):
            clk.advance(0.5)
        clk.advance(0.25)
    spans = tr.spans()
    assert [s.name for s in spans] == ["compile.outer", "compile.inner"]
    outer, inner = spans
    assert (outer.start, outer.end, outer.depth) == (0.0, 1.75, 0)
    assert (inner.start, inner.end, inner.depth) == (1.0, 1.5, 1)
    assert outer.parent is None and inner.parent == "compile.outer"
    assert inner.args == {"chunk": 16}
    assert inner.duration == pytest.approx(0.5)


def test_disabled_tracer_records_nothing():
    tr = Tracer(clock=ManualClock())
    tr.enabled = False
    with tr.span("x") as s:
        assert s is None
    tr.instant("y")
    assert tr.spans() == []


def test_tracer_clear_and_instant():
    clk = ManualClock(5.0)
    tr = Tracer(clock=clk)
    tr.instant("mark", eqns=3)
    (m,) = tr.spans()
    assert m.duration == 0.0 and m.args == {"eqns": 3}
    tr.clear()
    assert tr.spans() == []


def test_chrome_export_structure(tmp_path):
    clk = ManualClock(100.0)
    tr = Tracer(clock=clk)                      # origin pinned at 100.0
    with tr.span("serve.step"):
        clk.advance(0.002)
        with tr.span("serve.decode_wave", rows=4):
            clk.advance(0.001)
    doc = tr.to_chrome()
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta, *xs = events
    assert meta["ph"] == "M" and meta["name"] == "process_name"
    assert [e["name"] for e in xs] == ["serve.step", "serve.decode_wave"]
    step, wave = xs
    # µs timestamps relative to the tracer origin
    assert step["ts"] == pytest.approx(0.0)
    assert step["dur"] == pytest.approx(3000.0)
    assert wave["ts"] == pytest.approx(2000.0)
    assert wave["dur"] == pytest.approx(1000.0)
    for e in xs:
        assert e["ph"] == "X" and e["cat"] == "serve"
        assert {"ts", "dur", "pid", "tid"} <= set(e)
    path = tmp_path / "t.json"
    tr.export_chrome(str(path))
    assert json.loads(path.read_text())["traceEvents"] == events


def test_traced_decorator_records_span():
    before = len(TRACER.spans("unit.traced_fn"))

    @traced("unit.traced_fn")
    def fn(a, b):
        return a + b

    assert fn(2, 3) == 5
    assert len(TRACER.spans("unit.traced_fn")) == before + 1


# ---------------------------------------------------------------------------
# watermark + accuracy records
# ---------------------------------------------------------------------------

def test_watermark_jaxpr_counts_live_intermediates():
    x = jnp.zeros((8,), jnp.float32)            # 32 bytes

    def f(x):
        y = x * 2.0
        return y + 1.0

    closed = jax.make_jaxpr(f)(x)
    # peak at the add: y (32, still live) + z (32, being produced)
    assert obs_accuracy.watermark_jaxpr(closed) == 2 * x.nbytes
    # state exclusion: buffers of the excluded size count as zero
    assert obs_accuracy.watermark_jaxpr(closed,
                                        exclude_nbytes=(x.nbytes,)) == 0


def test_compare_error_formula():
    acc = obs_accuracy.compare(80, 100, "interpret", cache_key="k", chunk=16)
    assert acc.error_pct == pytest.approx(20.0)
    assert acc.to_dict() == {
        "predicted_bytes": 80, "measured_bytes": 100, "error_pct": 20.0,
        "source": "interpret", "cache_key": "k", "chunk": 16,
    }
    assert "error_pct=20.00" in acc.status_line()
    assert obs_accuracy.compare(0, 0, "interpret").error_pct == 0.0
    assert math.isinf(obs_accuracy.compare(5, 0, "interpret").error_pct)


def test_publish_mirrors_accuracy_into_registry():
    reg = MetricsRegistry()
    acc = obs_accuracy.compare(50, 100, "interpret")
    obs_accuracy.publish(acc, registry=reg)
    assert reg.gauge("plan_predicted_bytes").value == 50.0
    assert reg.gauge("plan_measured_bytes").value == 100.0
    assert reg.gauge("plan_error_pct").value == pytest.approx(50.0)
    assert reg.counter("plan_accuracy_reports").value == 1
    # non-finite error is published as the -1 sentinel, not inf
    obs_accuracy.publish(obs_accuracy.compare(5, 0, "interpret"),
                         registry=reg)
    assert reg.gauge("plan_error_pct").value == -1.0


def test_planned_plan_accuracy_counter_asserted():
    """The report's three numbers are re-derivable: predicted is the
    selected candidate's modeled peak, measured is the watermark of the
    emitted jaxpr, error is |p-m|/m."""
    w, x = _mini_weights(), _x(seq=256)
    planned = ChunkedFunction(
        _mini_block, ChunkConfig(budget_ratio=0.3)).trace(w, x).search()
    assert planned.plan.stages, "budget 0.3 @ seq 256 must force chunking"
    acc = planned.plan_accuracy()
    assert acc.predicted_bytes == planned.plan.stages[-1].peak_after
    assert acc.measured_bytes == obs_accuracy.watermark_jaxpr(
        planned.graph.closed_jaxpr)
    assert acc.error_pct == pytest.approx(
        abs(acc.predicted_bytes - acc.measured_bytes)
        / acc.measured_bytes * 100.0)
    assert acc.source == "interpret"
    assert acc.cache_key == planned.plan.cache_key
    # compile() attaches the report to the result and publishes it
    reports = default_registry().counter("plan_accuracy_reports").value
    compiled = planned.compile()
    assert compiled.result.accuracy is acc or (
        compiled.result.accuracy.to_dict() == acc.to_dict())
    assert default_registry().counter(
        "plan_accuracy_reports").value == reports + 1


# ---------------------------------------------------------------------------
# PlanCache on an injected clock (satellite f: no sleeping)
# ---------------------------------------------------------------------------

def _plan(key):
    return ChunkPlan(cache_key=key, budget_bytes=1, baseline_peak=2,
                     final_peak=1)


def test_plan_cache_lru_eviction_on_manual_clock(tmp_path):
    clk = ManualClock(1_000.0)
    cache = PlanCache(tmp_path / "plans", clock=clk)
    for k in "abc":
        cache.put(k, _plan(k))
        clk.advance(10.0)
    cache.record_use("a")                       # refresh a's recency last
    clk.advance(10.0)
    removed = cache.evict(policy="lru", max_entries=1)
    assert removed == 2
    assert cache.get("a") is not None
    assert cache.get("b") is None and cache.get("c") is None


def test_plan_cache_max_age_on_manual_clock(tmp_path):
    clk = ManualClock(1_000.0)
    cache = PlanCache(tmp_path / "plans", clock=clk)
    cache.put("old", _plan("old"))
    clk.advance(100.0)
    cache.put("new", _plan("new"))
    removed = cache.evict(policy="lru", max_age_s=50.0)
    assert removed == 1
    assert cache.get("new") is not None


def test_plan_cache_record_accuracy_in_telemetry(tmp_path):
    cache = PlanCache(tmp_path / "plans", clock=ManualClock(1.0))
    cache.put("k", _plan("k"))
    cache.record_accuracy("k", obs_accuracy.compare(90, 100, "interpret"))
    meta = cache.entry_meta("k")
    assert meta["accuracy"]["predicted_bytes"] == 90
    assert meta["accuracy"]["error_pct"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# serve CLI end to end: paged prefix-cache scenario with exports
# ---------------------------------------------------------------------------

def test_serve_cli_writes_metrics_and_trace(tmp_path, capsys):
    from repro.launch import serve as serve_cli
    from repro.tools import trace_export

    m = tmp_path / "metrics.json"
    t = tmp_path / "trace.json"
    p = tmp_path / "metrics.prom"
    serve_cli.main([
        "--arch", "gpt-paper", "--local", "--paged", "--prefix-cache",
        "--shared-prefix", "8", "--requests", "3", "--prompt-len", "12",
        "--max-new", "2", "--max-len", "32", "--page-size", "8",
        "--metrics-out", str(m), "--trace-out", str(t),
        "--prom-out", str(p),
    ])
    out = capsys.readouterr().out
    assert "plan_accuracy: predicted_bytes=" in out

    doc = json.loads(m.read_text())
    assert doc["counters"]["prefill_chunks"] >= 1
    acc = doc["plan_accuracy"]
    assert acc["source"] == "interpret"
    assert math.isfinite(acc["error_pct"]) and acc["error_pct"] < 50.0
    hists = doc["metrics"]["histograms"]
    for name in ("serve_ttft_seconds", "serve_step_latency_seconds",
                 "serve_decode_tok_per_s", "serve_queue_wait_seconds"):
        assert name in hists, name
    assert hists["serve_ttft_seconds"]["count"] >= 3
    assert "serve_pages_in_use" in doc["metrics"]["gauges"]

    names = {e["name"] for e in trace_export.load_events(str(t))
             if e.get("ph") == "X"}
    # BOTH pipeline legs are on the timeline: estimator spans from the
    # prefill-chunk planner and serving-step spans from the engine loop
    assert {"compile.plan_prefill", "compile.estimate"} <= names
    assert {"serve.step", "serve.decode_wave", "serve.prefill_chunk",
            "serve.admit"} <= names

    prom = p.read_text()
    assert "# TYPE serve_ttft_seconds histogram" in prom
    assert 'serve_ttft_seconds_bucket{le="+Inf"}' in prom


def test_trace_export_cli_summary_and_merge(tmp_path, capsys):
    from repro.tools import trace_export

    clk = ManualClock()
    tr = Tracer(clock=clk)
    with tr.span("serve.step"):
        clk.advance(0.004)
    with tr.span("compile.estimate"):
        clk.advance(0.001)
    t1 = tmp_path / "a.json"
    tr.export_chrome(str(t1))

    rows = trace_export.summarize(trace_export.load_events(str(t1)))
    assert [r["name"] for r in rows] == ["serve.step", "compile.estimate"]
    assert rows[0]["total_ms"] == pytest.approx(4.0)
    assert rows[0]["mean_ms"] == pytest.approx(4.0)

    merged = tmp_path / "merged.json"
    assert trace_export.main(
        [str(t1), str(t1), "--summary", "-o", str(merged)]) == 0
    out = capsys.readouterr().out
    assert "[trace] 2 file(s), 4 spans" in out
    events = trace_export.load_events(str(merged))
    assert sum(1 for e in events if e.get("ph") == "X") == 4
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.json"
        bad.write_text('{"notatrace": 1}')
        trace_export.load_events(str(bad))


# ---------------------------------------------------------------------------
# jit compile stages, the profiler's timeline, the device's peak
# ---------------------------------------------------------------------------

@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in ``tmp_path``, caching every
    executable; the process's own settings come back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in keys.items():
            jax.config.update(k, v)
        cc.reset_cache()
        yield tmp_path
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _jit_probe(x):
    return jnp.sin(x) * 3.0


def _probe_spans(fun):
    return [(s.name, s.args.get("retrieval_s") is not None)
            for s in TRACER.spans() if s.name.startswith("jit.")
            and s.args.get("fun") in (fun, f"jit({fun})")]


def test_jit_stages_recorded_as_spans_and_cache_counted(persistent_cache,
                                                        monkeypatch):
    monkeypatch.setattr(TRACER, "enabled", True)
    x = jnp.ones((8,))
    before = stats.snapshot()
    jax.jit(_jit_probe).lower(x).compile()   # compiled, written to the cache
    jax.clear_caches()
    jax.jit(_jit_probe).lower(x).compile()   # read back from the cache
    delta = stats.delta(before)
    assert _probe_spans("_jit_probe") == [
        ("jit.trace", False), ("jit.lower", False), ("jit.compile", False),
        ("jit.trace", False), ("jit.lower", False), ("jit.cache_load", True),
    ]
    assert delta["jit_cache_hits"] == 1 and delta["jit_cache_misses"] >= 1
    load = [s for s in TRACER.spans("jit.cache_load")
            if s.args["fun"] == "jit(_jit_probe)"][-1]
    assert 0 < load.args["retrieval_s"] <= load.duration
    assert load.parent is None or not load.parent.startswith("jit.")

    monkeypatch.setattr(TRACER, "enabled", False)
    before = stats.snapshot()
    jax.clear_caches()
    jax.jit(_jit_probe).lower(x).compile()
    delta = stats.delta(before)
    assert len(_probe_spans("_jit_probe")) == 6
    assert delta["jit_cache_hits"] == delta["jit_cache_misses"] == 0


def test_jit_spans_never_parent_a_compile_span(monkeypatch):
    """``autochunk_compile_s`` sums the ``compile.*`` spans outside any
    other: a compile span run inside a jit's trace stays top-level."""
    monkeypatch.setattr(TRACER, "enabled", True)

    def traced_fn(x):
        with TRACER.span("compile.inside_probe"):
            return jnp.cos(x)

    jax.jit(traced_fn).lower(jnp.ones((4,))).compile()
    (inner,) = TRACER.spans("compile.inside_probe")[-1:]
    assert inner.parent is None and inner.depth == 0
    (outer,) = [s for s in TRACER.spans("jit.trace")
                if s.args["fun"] == "traced_fn"][-1:]
    assert outer.start <= inner.start and inner.end <= outer.end


def test_span_shows_on_the_profiler_timeline(tmp_path, monkeypatch):
    import glob
    import gzip

    monkeypatch.setattr(TRACER, "enabled", True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TRACER.span("compile.profiled_probe"):
            jnp.ones((4,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.trace.json.gz"),
                        recursive=True)
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    assert [e for e in events if e.get("name") == "compile.profiled_probe"
            and e.get("ph") == "X"]


@pytest.mark.parametrize("stats_dict,want", [
    ({"bytes_in_use": 10, "peak_bytes_in_use": 100,
      "peak_bytes_reserved": 40}, 140),
    ({"bytes_in_use": 10, "peak_bytes_in_use": 100}, 100),
    ({"bytes_in_use": 10}, None),
])
def test_device_peak_counts_reserved_bytes(stats_dict, want):
    """On a TPU an executable's temporaries are reserved apart from the
    buffers in use: the peak counts both."""

    class FakeDevice:
        def memory_stats(self):
            return dict(stats_dict)

    assert obs_accuracy.device_peak_bytes(FakeDevice()) == want


def test_prometheus_names_keep_to_the_format():
    reg = MetricsRegistry()
    reg.counter("kernel_dispatch_bool_mask.kv_chunked").inc(2)
    assert reg.to_prometheus().splitlines() == [
        "# TYPE kernel_dispatch_bool_mask_kv_chunked counter",
        "kernel_dispatch_bool_mask_kv_chunked 2",
    ]
