"""Canonical-shape bucket executables + telemetry-driven cache eviction.

ISSUE-5 acceptance contract: a `ChunkedFunction` with
``canonical_bucket_exec`` compiles ONE executable per shape bucket (at the
bucket boundary) and serves every other length in the bucket through the
pad/unpad path — a warm-bucket call performs zero traces, zero
search/selection passes, and adds zero XLA executables (``bucket_exec_hits``
/ jit cache-size asserted, not timed).  Padded outputs have exactly the
reference shapes and match an unpadded eager reference under causal and
sliding-window masks, including non-divisible bucket boundaries.  PlanCache
eviction policies (LRU vs cost-weighted LFU) are exercised under synthetic
telemetry, with the one-record-per-plan alias accounting regression pinned.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChunkConfig, PlanCache, ShapeBucketer, autochunk, stats
from repro.core.lowering import emit_padded_call, pad_to_shape, slice_to_shape
from repro.core.plan import ChunkPlan


# ---------------------------------------------------------------------------
# Length-masked test blocks (the canonical-exec semantics contract: real
# outputs never depend on padded buffer content, because attention is masked
# by the true length carried in a scalar argument)
# ---------------------------------------------------------------------------

def _weights(d=32, f=64, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    return {
        "wq": jax.random.normal(ks[0], (d, d)) * 0.1,
        "wk": jax.random.normal(ks[1], (d, d)) * 0.1,
        "wv": jax.random.normal(ks[2], (d, d)) * 0.1,
        "wo": jax.random.normal(ks[3], (d, d)) * 0.1,
        "w1": jax.random.normal(ks[4], (d, f)) * 0.1,
        "w2": jax.random.normal(ks[5], (f, d)) * 0.1,
    }


def _x(seq, d=32, key=9):
    return jax.random.normal(jax.random.PRNGKey(key), (2, seq, d))


def _masked_block(w, x, length, window=None):
    s = x.shape[1]
    q = x @ w["wq"]
    k = x @ w["wk"]
    v = x @ w["wv"]
    logits = jnp.einsum("bsd,btd->bst", q, k) / jnp.sqrt(x.shape[-1])
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = (j <= i) & (j < length)
    if window is not None:
        mask = mask & (j > i - window)
    a = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    o = jnp.einsum("bst,btd->bsd", a, v) @ w["wo"]
    h = x + o
    ff = jax.nn.gelu(h @ w["w1"]) @ w["w2"]
    return h + ff


def _causal_block(w, x, length):
    return _masked_block(w, x, length)


def _window_block(w, x, length):
    return _masked_block(w, x, length, window=8)


def _len(n):
    return jnp.asarray(n, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Pad/unpad primitives
# ---------------------------------------------------------------------------

def test_pad_and_slice_roundtrip():
    x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    p = pad_to_shape(x, (5, 4))
    assert p.shape == (5, 4)
    np.testing.assert_array_equal(np.asarray(p[:3]), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(p[3:]), 0.0)
    np.testing.assert_array_equal(
        np.asarray(slice_to_shape(p, (3, 4))), np.asarray(x)
    )
    assert pad_to_shape(x, (3, 4)) is x or pad_to_shape(x, (3, 4)).shape == x.shape
    with pytest.raises(ValueError):
        pad_to_shape(x, (2, 4))
    with pytest.raises(ValueError):
        slice_to_shape(x, (4, 4))


def test_emit_padded_call_slices_by_true_output_specs():
    """Dim provenance is exact: an output axis that coincides with the
    padded extent but is NOT the padded axis must be left alone."""

    def fn(x):  # (s, 8) -> (8, s): transposed, so axes swap roles
        return x.T

    spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)  # canonical: s -> 8
    x = jnp.ones((5, 8))
    out_specs = jax.eval_shape(fn, x)
    wrapped = emit_padded_call(fn, (spec,), out_specs)
    y = wrapped(x)
    assert y.shape == (8, 5)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x.T))


# ---------------------------------------------------------------------------
# Canonical bucket executables
# ---------------------------------------------------------------------------

def test_bucket_exec_zero_traces_zero_compiles_on_warm_bucket():
    """Acceptance: the second call at a *different* length inside a warm
    bucket performs 0 traces, 0 search passes, and adds 0 XLA executables."""
    w = _weights()
    cf = autochunk(
        _causal_block,
        ChunkConfig(budget_ratio=0.4, canonical_bucket_exec=True),
    )
    x60 = _x(60)
    y60 = cf(w, x60, _len(60))
    assert y60.shape == x60.shape
    np.testing.assert_allclose(
        np.asarray(y60), np.asarray(_causal_block(w, x60, _len(60))), atol=1e-5
    )
    assert cf.counters["compiles"] == 1
    assert cf.counters["bucket_exec_compiles"] == 1
    assert cf.stats()["bucket_execs"] == 1

    x50 = _x(50, key=3)  # same pow2 bucket (-> 64), different length
    before = stats.snapshot()
    y50 = cf(w, x50, _len(50))
    delta = stats.delta(before)
    assert delta["trace_calls"] == 0
    assert delta["search_passes"] == 0 and delta["selection_passes"] == 0
    assert delta["bucket_exec_compiles"] == 0
    assert delta["bucket_exec_hits"] == 1
    assert delta["padded_calls"] == 1
    assert cf.counters["compiles"] == 1  # still the one boundary compile
    assert y50.shape == x50.shape
    np.testing.assert_allclose(
        np.asarray(y50), np.asarray(_causal_block(w, x50, _len(50))), atol=1e-5
    )

    # one-executable-per-bucket invariant: the canonical jit holds exactly
    # one XLA executable no matter how many lengths it served
    exec_ = next(iter(cf._bucket_execs.values()))
    assert exec_.xla_cache_size() == 1

    # repeat length: memoized padded wrapper, still zero compile work
    before = stats.snapshot()
    cf(w, x50, _len(50))
    delta = stats.delta(before)
    assert delta["bucket_exec_hits"] == 1 and delta["trace_calls"] == 0
    assert cf.stats()["padded_shapes"] == 2  # 60 and 50


def test_bucket_exec_boundary_length_needs_no_padding():
    w = _weights()
    cf = autochunk(
        _causal_block,
        ChunkConfig(budget_ratio=0.4, canonical_bucket_exec=True),
    )
    x64 = _x(64, key=5)
    before = stats.snapshot()
    y = cf(w, x64, _len(64))
    delta = stats.delta(before)
    assert delta["padded_calls"] == 0  # exactly at the boundary
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_causal_block(w, x64, _len(64))), atol=1e-5
    )
    # the canonical shape itself lands in the exact-shape table
    assert cf.stats()["compiled_shapes"] == 1
    before = stats.snapshot()
    cf(w, x64, _len(64))
    assert stats.delta(before)["bucket_exec_compiles"] == 0


def test_padded_call_equivalence_sliding_window():
    w = _weights()
    cf = autochunk(
        _window_block,
        ChunkConfig(budget_ratio=0.4, canonical_bucket_exec=True),
    )
    for seq, key in ((60, 1), (49, 2)):
        x = _x(seq, key=key)
        y = cf(w, x, _len(seq))
        assert y.shape == x.shape
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(_window_block(w, x, _len(seq))),
            atol=1e-5,
        )
    assert cf.counters["bucket_exec_compiles"] == 1
    assert cf.counters["bucket_exec_hits"] == 1


def test_padded_call_equivalence_non_divisible_boundary():
    """A non-power-of-two boundary (72) forces chunk counts that do not
    divide the canonical extent; the clamp-and-recover codegen tail must
    stay exact through the padded path."""
    w = _weights()
    cf = autochunk(
        _causal_block,
        ChunkConfig(budget_ratio=0.4, canonical_bucket_exec=True),
        bucketer=ShapeBucketer(buckets=(72,), min_dim=48),
    )
    x60 = _x(60, key=7)
    y = cf(w, x60, _len(60))
    assert y.shape == x60.shape
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_causal_block(w, x60, _len(60))), atol=1e-5
    )
    # compiled at the 72 boundary, not at 60
    ((_, canon),) = [k for k in cf._bucket_execs]
    assert ((2, 72, 32), "float32") in canon

    before = stats.snapshot()
    x65 = _x(65, key=8)
    y65 = cf(w, x65, _len(65))
    delta = stats.delta(before)
    assert delta["bucket_exec_hits"] == 1 and delta["trace_calls"] == 0
    np.testing.assert_allclose(
        np.asarray(y65), np.asarray(_causal_block(w, x65, _len(65))), atol=1e-5
    )


def test_canonical_exec_off_by_default():
    cf = autochunk(_causal_block, ChunkConfig(budget_ratio=0.4))
    assert not cf.config.canonical_bucket_exec
    w = _weights()
    cf(w, _x(60), _len(60))
    assert cf.stats()["bucket_execs"] == 0 and cf.counters["compiles"] == 1


def test_chunked_function_honors_cache_eviction_knobs(tmp_path):
    """The ChunkConfig eviction knobs are real on the transform itself: a
    compile that grows the plan cache beyond cache_max_entries triggers
    eviction with cache_policy."""
    w = _weights()
    cf = autochunk(
        _causal_block,
        ChunkConfig(budget_ratio=0.4, cache_max_entries=1),
        cache=tmp_path / "plans",
    )
    cf.compile(w, _x(48), _len(48))
    assert len(cf.cache) == 1
    cf.compile(w, _x(100, key=2), _len(100))  # new bucket -> second plan
    assert len(cf.cache) == 1  # bounded: LRU evicted the 48-bucket plan
    assert cf.cache.stats()["evictions"] >= 1


def test_config_eviction_knob_validation():
    with pytest.raises(ValueError):
        ChunkConfig(cache_policy="mru")
    with pytest.raises(ValueError):
        ChunkConfig(cache_max_entries=-1)
    cfg = ChunkConfig(canonical_bucket_exec=True, cache_max_entries=4)
    # canonical_bucket_exec feeds the cache identity; eviction knobs do not
    assert cfg.cache_token() != ChunkConfig().cache_token()
    assert (
        ChunkConfig(cache_max_entries=4).cache_token()
        == ChunkConfig().cache_token()
    )


# ---------------------------------------------------------------------------
# Eviction policies under synthetic telemetry
# ---------------------------------------------------------------------------

def _plan(key):
    return ChunkPlan(cache_key=key, budget_bytes=1, baseline_peak=2, final_peak=1)


def test_evict_lru_drops_least_recently_used(tmp_path):
    cache = PlanCache(tmp_path / "plans")
    now = time.time()
    for i, k in enumerate("abcd"):
        cache.put(k, _plan(k))
        cache.record_use(k, now=now - 100 + i * 10)
    removed = cache.evict(policy="lru", max_entries=2, now=now)
    assert removed == 2
    assert cache.get("a") is None and cache.get("b") is None
    assert cache.get("c") is not None and cache.get("d") is not None
    assert cache.stats()["evictions"] == 2


def test_evict_cost_lfu_keeps_high_hit_times_cost_plans():
    """Cost-weighted LFU keep-set: a hot cheap plan and a cold but very
    expensive compile both survive; the cold cheap plan goes — where plain
    LRU would instead have dropped the expensive (oldest) one."""
    now = time.time()

    def build():
        cache = PlanCache()
        for k in ("hot_cheap", "cold_costly", "cold_cheap"):
            cache.put(k, _plan(k))
        for _ in range(10):
            cache.record_use("hot_cheap", compile_s=0.1, now=now)
        cache.record_use("cold_costly", compile_s=50.0, now=now - 500)
        cache.record_use("cold_cheap", compile_s=0.1, now=now - 100)
        return cache

    lfu = build()
    assert lfu.evict(policy="cost_lfu", max_entries=2, now=now) == 1
    assert lfu.get("cold_cheap") is None
    assert lfu.get("hot_cheap") is not None
    assert lfu.get("cold_costly") is not None

    lru = build()
    assert lru.evict(policy="lru", max_entries=2, now=now) == 1
    assert lru.get("cold_costly") is None  # oldest, cost-blind

    with pytest.raises(ValueError):
        PlanCache().evict(policy="mru")


def test_evict_cost_lfu_reads_persisted_compile_cost(tmp_path):
    """A fresh process (empty local telemetry) must still protect a plan
    whose persisted meta says it took minutes to search — the scorer falls
    back to the compile_s stored in the plan file itself."""
    writer = PlanCache(tmp_path / "plans")
    costly, cheap = _plan("costly"), _plan("cheap")
    costly.meta["compile_s"] = 120.0
    cheap.meta["compile_s"] = 0.2
    writer.put("costly", costly)
    writer.put("cheap", cheap)

    fresh = PlanCache(tmp_path / "plans")  # restarted: no telemetry yet
    assert fresh.evict(policy="cost_lfu", max_entries=1) == 1
    assert fresh.get("costly") is not None
    assert fresh.get("cheap") is None


def test_evict_max_age_uses_recency(tmp_path):
    cache = PlanCache(tmp_path / "plans")
    now = time.time()
    cache.put("stale", _plan("stale"))
    cache.put("fresh", _plan("fresh"))
    cache.record_use("stale", now=now - 1000)
    cache.record_use("fresh", now=now)
    assert cache.evict(policy="lru", max_age_s=500, now=now) == 1
    assert cache.get("stale") is None and cache.get("fresh") is not None


def test_telemetry_recorded_on_get_put(tmp_path):
    cache = PlanCache(tmp_path / "plans")
    plan = _plan("k")
    plan.meta["compile_s"] = 7.5
    cache.put("k", plan)
    m = cache.entry_meta("k")
    assert m["hits"] == 0 and m["compile_s"] == 7.5
    cache.get("k")
    cache.record_use("k", bucket=128)
    m = cache.entry_meta("k")
    assert m["hits"] == 2 and m["buckets"] == {"128": 1}
    # a bucket-alias hit counts as a use of the HOME plan
    cache.put_bucket("bk", plan)
    cache.get_bucket("bk")
    assert cache.entry_meta("k")["hits"] == 3


# ---------------------------------------------------------------------------
# Unified entry accounting (the prune/alias bugfix)
# ---------------------------------------------------------------------------

def test_evict_counts_one_record_per_plan_with_aliases(tmp_path):
    """Regression: bucket aliases were trimmed as an independent second
    population.  Eviction must see ONE record per plan; evicting the plan
    removes its aliases, and surviving plans keep theirs."""
    cache = PlanCache(tmp_path / "plans")
    now = time.time()
    pa, pb = _plan("ka"), _plan("kb")
    cache.put("ka", pa)
    cache.put_bucket("bucket-a", pa)
    cache.put("kb", pb)
    cache.put_bucket("bucket-b", pb)
    cache.record_use("ka", now=now - 100)
    cache.record_use("kb", now=now)
    assert len(list((tmp_path / "plans").glob("*.json"))) == 2
    assert len(list((tmp_path / "plans" / "buckets").glob("*.json"))) == 2

    removed = cache.prune(max_entries=1, now=now)
    assert removed == 1  # one plan record — not "3 files"
    assert cache.get("ka") is None
    assert cache.get_bucket("bucket-a") is None  # alias rode along
    assert cache.get("kb") is not None
    assert cache.get_bucket("bucket-b") is not None  # survivor keeps its alias
    assert len(list((tmp_path / "plans" / "buckets").glob("*.json"))) == 1


def test_evict_in_memory_aliases_ride_along():
    cache = PlanCache()
    now = time.time()
    pa, pb = _plan("ka"), _plan("kb")
    cache.put("ka", pa)
    cache.put_bucket("bucket-a", pa)
    cache.put("kb", pb)
    cache.put_bucket("bucket-b", pb)
    cache.record_use("ka", now=now - 100)
    cache.record_use("kb", now=now)
    assert cache.evict(policy="lru", max_entries=1, now=now) == 1
    assert cache.get("ka") is None and cache.get_bucket("bucket-a") is None
    assert cache.get("kb") is not None
    assert cache.get_bucket("bucket-b") is not None
