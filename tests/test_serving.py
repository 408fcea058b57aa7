"""Serving engine behaviour tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving import Request, ServeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gpt-paper").reduced().with_(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _naive_greedy(cfg, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        lg, _ = M.forward(cfg, params, {"tokens": jnp.asarray([toks])})
        toks.append(int(jnp.argmax(lg[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_naive_greedy(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64)
    req = Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=6)
    eng.submit(req)
    eng.run()
    assert req.done
    assert req.generated == _naive_greedy(cfg, params, req.prompt, 6)


def test_engine_batches_more_requests_than_slots(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64)
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=4) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.generated) == 4 for r in done)


def test_engine_interleaved_slots_are_isolated(setup):
    """Requests with different prompts in concurrent slots must produce the
    same outputs as when served alone (cache isolation across slots)."""
    cfg, params = setup
    prompts = [[2, 7, 1], [9, 9, 9, 9], [5]]
    solo = []
    for i, p in enumerate(prompts):
        e = ServeEngine(cfg, params, max_batch=1, max_len=64)
        r = Request(rid=i, prompt=p, max_new_tokens=5)
        e.submit(r)
        e.run()
        solo.append(r.generated)
    eng = ServeEngine(cfg, params, max_batch=3, max_len=64)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=5) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, s in zip(sorted(reqs, key=lambda r: r.rid), solo):
        assert r.generated == s


def test_engine_eos_stops_early(setup):
    cfg, params = setup
    probe = Request(rid=0, prompt=[3, 1, 4], max_new_tokens=8)
    e = ServeEngine(cfg, params, max_batch=1, max_len=64)
    e.submit(probe)
    e.run()
    eos = probe.generated[2]
    r = Request(rid=1, prompt=[3, 1, 4], max_new_tokens=8, eos_id=eos)
    e2 = ServeEngine(cfg, params, max_batch=1, max_len=64)
    e2.submit(r)
    e2.run()
    assert r.generated[-1] == eos
    assert len(r.generated) <= 3


def test_engine_with_autochunk_logit_exact(setup):
    """The autochunk'd decode wave must produce (numerically) the same
    logits as the plain wave — token sequences can flip on argmax ties."""
    cfg, params = setup
    e1 = ServeEngine(cfg, params, max_batch=2, max_len=64)
    e2 = ServeEngine(cfg, params, max_batch=2, max_len=64, autochunk_budget=0.5)
    for e in (e1, e2):
        e.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=1))
        e._admit()
    toks = jnp.asarray([5, 0], dtype=jnp.int32)
    pos = jnp.asarray([3, 0], dtype=jnp.int32)
    lg1, _ = e1._decode_wave(e1.cache, toks, pos)
    lg2, _ = e2._decode_wave(e2.cache, toks, pos)
    np.testing.assert_allclose(
        np.asarray(lg1[0]), np.asarray(lg2[0]), atol=1e-4
    )


def test_engine_plan_cache_and_reconfigure(setup, tmp_path):
    """The engine warms its plan cache at construction; reconfiguring back
    to a previously seen shape replays the stored plan with zero search or
    selection passes, and a second engine sharing the on-disk cache starts
    warm."""
    from repro.core import stats

    cfg, params = setup
    cache_dir = tmp_path / "plans"
    e1 = ServeEngine(
        cfg, params, max_batch=2, max_len=64,
        autochunk_budget=0.5, plan_cache=cache_dir,
    )
    assert e1.plan_cache.stats()["entries"] == 1
    assert not e1.autochunk_result.from_cache

    # a second engine over the same directory compiles from the saved plan
    before = stats.snapshot()
    e2 = ServeEngine(
        cfg, params, max_batch=2, max_len=64,
        autochunk_budget=0.5, plan_cache=cache_dir,
    )
    delta = stats.delta(before)
    assert e2.autochunk_result.from_cache
    assert delta["search_calls"] == 0 and delta["rank_calls"] == 0

    # logits agree between the cold-compiled and plan-replayed waves
    for e in (e1, e2):
        e.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=1))
        e._admit()
    toks = jnp.asarray([5, 0], dtype=jnp.int32)
    pos = jnp.asarray([3, 0], dtype=jnp.int32)
    lg1, _ = e1._decode_wave(e1.cache, toks, pos)
    lg2, _ = e2._decode_wave(e2.cache, toks, pos)
    np.testing.assert_array_equal(np.asarray(lg1), np.asarray(lg2))
    e1.run()
    e2.run()

    # reconfigure to a new shape (cold: 96 -> bucket boundary 128), then
    # back (warm: the bucket's canonical executable is reused outright —
    # zero traces, zero searches, zero new executables)
    e2.reconfigure(max_len=96)
    assert e2.exec_len == 128
    assert len(e2.plan_cache) == 2
    before = stats.snapshot()
    e2.reconfigure(max_len=64)
    delta = stats.delta(before)
    assert delta["search_calls"] == 0 and delta["trace_calls"] == 0
    assert delta["bucket_exec_hits"] == 1 and delta["bucket_exec_compiles"] == 0
    e2.submit(Request(rid=1, prompt=[1, 2, 3], max_new_tokens=2))
    done = e2.run()
    assert done[-1].done

    # reconfigure refuses to drop in-flight requests
    e2.submit(Request(rid=2, prompt=[4], max_new_tokens=2))
    with pytest.raises(RuntimeError):
        e2.reconfigure(max_len=96)
    e2.run()


def test_engine_canonical_bucket_exec(setup):
    """One executable serves the whole bucket: an engine at max_len=60
    executes at the 64 boundary with identical tokens, and reconfiguring to
    another length inside the bucket performs zero traces and zero new
    executables (ISSUE-5 acceptance counter)."""
    from repro.core import stats

    cfg, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=60,
                      autochunk_budget=0.5)
    assert eng.exec_len == 64
    r = Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=6)
    eng.submit(r)
    eng.run()

    ref = ServeEngine(cfg, params, max_batch=2, max_len=64)
    r_ref = Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=6)
    ref.submit(r_ref)
    ref.run()
    assert r.generated == r_ref.generated  # padded cache tail is inert

    before = stats.snapshot()
    eng.reconfigure(max_len=50)  # same bucket: reuse, don't recompile
    delta = stats.delta(before)
    assert delta["trace_calls"] == 0 and delta["search_passes"] == 0
    assert delta["bucket_exec_hits"] == 1
    assert delta["bucket_exec_compiles"] == 0
    assert eng.exec_stats["wave_reuses"] == 1

    r2 = Request(rid=1, prompt=[3, 1, 4, 1, 5], max_new_tokens=6)
    eng.submit(r2)
    eng.run()
    assert r2.generated == r_ref.generated  # same executable, same tokens

    m = eng.metrics()
    assert m["exec_len"] == 64
    assert m["bucket_exec"]["wave_compiles"] == 1
    assert m["plan_telemetry"]["hits"] >= 1
    assert "64" in m["plan_telemetry"]["buckets"]


def test_engine_telemetry_driven_eviction(setup, tmp_path):
    """cache_max_entries triggers PlanCache.evict at engine idle points;
    the LRU plan (the bucket not in use) is the one that goes."""
    cfg, params = setup
    eng = ServeEngine(
        cfg, params, max_batch=2, max_len=64, autochunk_budget=0.5,
        plan_cache=tmp_path / "plans", cache_max_entries=1,
    )
    assert len(eng.plan_cache) == 1 and eng.exec_stats["evicted"] == 0
    eng.reconfigure(max_len=200)  # new bucket (256): second plan, then evict
    assert eng.exec_len == 256
    assert len(eng.plan_cache) == 1  # the 64-bucket plan was evicted
    assert eng.exec_stats["evicted"] == 1
    assert eng.plan_cache.stats()["evictions"] == 1
    # the surviving plan is the one the engine is currently serving with
    key = eng.autochunk_result.cache_key
    assert eng.plan_cache.get(key) is not None


def test_admit_samples_first_token_when_not_greedy(setup):
    """Regression: _admit() used to argmax the first token even with
    greedy=False; it must draw from the prefill logits with the engine PRNG
    key, exactly like step() does for subsequent tokens."""
    cfg, params = setup
    prompt = [3, 1, 4, 1, 5]
    eng = ServeEngine(cfg, params, max_batch=1, max_len=64,
                      greedy=False, seed=7)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=1))
    eng._admit()

    # reproduce the engine's draw: prefill logits + first split of the key
    lg, _ = jax.jit(
        lambda b: M.prefill(cfg, params, b, 64)
    )({"tokens": jnp.asarray([prompt], dtype=jnp.int32)})
    _, sub = jax.random.split(jax.random.PRNGKey(7))
    expected = int(jax.random.categorical(sub, lg[0, -1]))
    assert eng.slot_req[0].generated[0] == expected

    # greedy engines keep the argmax first token
    eng2 = ServeEngine(cfg, params, max_batch=1, max_len=64, greedy=True)
    eng2.submit(Request(rid=1, prompt=prompt, max_new_tokens=1))
    eng2._admit()
    assert eng2.slot_req[0].generated[0] == int(jnp.argmax(lg[0, -1]))


def test_engine_metrics(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[1, 2, 3 + i], max_new_tokens=4))
    eng.run()
    m = eng.metrics()
    assert m["requests"] == 3 and m["tokens"] == 12
    assert m["throughput_tok_s"] > 0 and m["mean_ttft_s"] >= 0


# ===========================================================================
# Paged continuous batching
# ===========================================================================

def test_paged_engine_matches_naive_greedy(setup):
    from repro.serving import PagedServeEngine

    cfg, params = setup
    eng = PagedServeEngine(cfg, params, max_seqs=2, max_len=64, page_size=8)
    req = Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=6)
    eng.submit(req)
    eng.run()
    assert req.done
    assert req.generated == _naive_greedy(cfg, params, req.prompt, 6)


def test_paged_engine_interleaved_sequences_are_isolated(setup):
    """Concurrent staggered sequences on the shared pool must generate
    exactly what each generates alone (no KV bleed across page tables)."""
    from repro.serving import PagedServeEngine

    cfg, params = setup
    prompts = [[2, 7, 1, 8, 2, 8], [9, 9, 9], [5] * 12]
    solo = [_naive_greedy(cfg, params, p, 5) for p in prompts]
    eng = PagedServeEngine(cfg, params, max_seqs=3, max_len=64, page_size=8)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, s in zip(reqs, solo):
        assert r.generated == s


def test_paged_engine_mixed_steps_and_page_reuse(setup):
    """Staggered lengths force mixed prefill+decode steps; a full run must
    free every page it allocated and report zero padded-KV waste."""
    from repro.core import stats
    from repro.serving import PagedServeEngine

    cfg, params = setup
    eng = PagedServeEngine(
        cfg, params, max_seqs=3, max_len=64, page_size=8, prefill_chunk=8,
    )
    before = stats.snapshot()
    lens = [3, 20, 33, 3, 20, 33]
    for i, n in enumerate(lens):
        eng.submit(Request(rid=i, prompt=[(i + j) % 50 for j in range(n)],
                           max_new_tokens=4))
    done = eng.run()
    d = stats.delta(before)
    assert len(done) == 6 and all(len(r.generated) == 4 for r in done)
    assert d["mixed_steps"] > 0
    assert eng.sched_stats["mixed_steps"] == d["mixed_steps"]
    # long prompts chunk at prefill_chunk=8 -> several chunks per request
    assert d["prefill_chunks"] > len(lens)
    assert d["pages_allocated"] == d["pages_freed"] > 0
    assert eng.pool.pages_in_use == 0
    assert eng.pool.stats()["padded_kv_waste_bytes"] == 0
    # exactly two jitted step shapes: (prefill_chunk, 1)
    assert eng.sched_stats["step_compiles"] == 2


def test_paged_engine_admission_bounded_by_pages(setup):
    """With slots to spare but a pool too small for everyone, admission
    must refuse (head-of-line blocks) and resume after pages free up —
    every request still completes."""
    from repro.core import stats
    from repro.serving import PagedServeEngine

    cfg, params = setup
    # each request needs pages_for(6+2)=2 pages @ page_size=4; pool of 4
    # pages holds two concurrent sequences despite max_seqs=4
    eng = PagedServeEngine(
        cfg, params, max_seqs=4, max_len=32, page_size=4, num_pages=4,
    )
    before = stats.snapshot()
    for i in range(5):
        eng.submit(Request(rid=i, prompt=[1 + i, 2, 3, 4, 5, 6],
                           max_new_tokens=2))
    done = eng.run()
    d = stats.delta(before)
    assert len(done) == 5 and all(len(r.generated) == 2 for r in done)
    assert d["admission_refusals"] > 0
    assert eng.pool.peak_pages_in_use <= 4
    assert d["pages_allocated"] == d["pages_freed"] == 10


def test_paged_engine_rejects_oversized_request(setup):
    from repro.serving import PagedServeEngine

    cfg, params = setup
    eng = PagedServeEngine(cfg, params, max_seqs=2, max_len=16, page_size=4)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=list(range(15)), max_new_tokens=4))


def test_paged_engine_planned_prefill_chunk(setup):
    """prefill_chunk='auto' derives the chunk from the AutoChunk activation
    estimator: a tighter budget must not plan a larger chunk."""
    from repro.serving import PagedServeEngine

    cfg, params = setup
    loose = PagedServeEngine(cfg, params, max_seqs=2, max_len=64,
                             page_size=8, autochunk_budget=0.9)
    tight = PagedServeEngine(cfg, params, max_seqs=2, max_len=64,
                             page_size=8, autochunk_budget=0.1)
    assert loose.prefill_plan is not None and tight.prefill_plan is not None
    assert tight.prefill_chunk <= loose.prefill_chunk
    # the loose budget is satisfiable, so its plan must fit under it; an
    # unsatisfiable budget falls back to the min chunk with fits=False
    assert loose.prefill_plan.fits
    assert loose.prefill_plan.peak_bytes <= loose.prefill_plan.budget_bytes


def test_paged_step_takes_weights_as_arguments(setup):
    """The jitted paged step reads the weights as arguments: none of them is
    baked into the program as a constant."""
    import math
    import re

    from repro.serving import PagedServeEngine

    cfg, params = setup
    eng = PagedServeEngine(cfg, params, max_seqs=2, max_len=64, page_size=8)
    S, q_max = eng.max_seqs, eng.prefill_chunk
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    text = eng._step_fn(q_max).lower(
        params, eng.pool.pages, i32(S, q_max), i32(S), i32(S),
        i32(S, eng.max_pages_per_seq),
    ).as_text()
    smallest_weight = min(x.size for x in jax.tree.leaves(params))
    const_sizes = [
        math.prod(int(d) for d in dims.split("x")[:-1])
        for dims in re.findall(r"stablehlo\.constant dense<.*?> : tensor<([^>]*)>", text)
    ]
    assert const_sizes  # the parse found the program's constants
    assert max(const_sizes) < smallest_weight
