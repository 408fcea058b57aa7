"""Jaxpr-native lowering backend: rewrite semantics + single-lowering contract.

Covers the ISSUE-3 acceptance criteria: a K-stage plan lowers with a trace
count independent of K (counter-asserted), stage rewrites compose on one
graph, the emitted callable matches the unchunked function exactly, and
``Planned.lower()`` exposes the final rewritten jaxpr.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ChunkConfig,
    ChunkPlan,
    apply_chunk,
    autochunk,
    build_fn_from_plan,
    emit,
    estimate_memory,
    search_chunks,
    stats,
    trace,
)
from repro.core.lowering import is_chunk_loop


def _two_softmax(w, x):
    s = jnp.einsum("bsd,btd->bst", x @ w["a"], x @ w["b"])
    y1 = jnp.einsum("bst,btd->bsd", jax.nn.softmax(s, axis=-1), x)
    h = jnp.tanh(y1 @ w["m"])
    s2 = jnp.einsum("bsd,btd->bst", h @ w["c"], h @ w["d"])
    y2 = jnp.einsum("bst,btd->bsd", jax.nn.softmax(s2, axis=-1), h)
    return y1 + y2


def _weights(d=32, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    return {n: jax.random.normal(k, (d, d)) * 0.1 for n, k in zip("abmcd", ks)}


def _flat(fn, args):
    flat, tree = jax.tree_util.tree_flatten(tuple(args))

    def flat_fn(*leaves):
        return (fn(*jax.tree_util.tree_unflatten(tree, leaves)),)

    return flat_fn, flat


def _softmax_chain(w, x):
    """Three softmax-attention blocks — three chunkable memory peaks."""
    h = x
    for i in range(3):
        wi = w[f"b{i}"]
        s = jnp.einsum("bsd,btd->bst", h @ wi["a"], h @ wi["b"])
        h = h + jnp.einsum("bst,btd->bsd", jax.nn.softmax(s, axis=-1), h)
    return h


def _chain_weights(d=32, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    return {
        f"b{i}": {
            "a": jax.random.normal(ks[2 * i], (d, d)) * 0.1,
            "b": jax.random.normal(ks[2 * i + 1], (d, d)) * 0.1,
        }
        for i in range(3)
    }


def _tight_candidates(g, prof, extent):
    return [
        c
        for c in search_chunks(g, prof)
        if c.chunk_extent == extent and c.e - c.s < 12
    ]


def _three_stage_plan(w, x):
    """Search a genuine 3-stage plan (window=12 keeps regions per-block)."""
    cf = autochunk(
        _softmax_chain,
        ChunkConfig(budget_ratio=0.15, anneal=0, window=12),
        bucketer=None,
    )
    planned = cf.trace(w, x).search()
    assert len(planned.plan.stages) == 3, len(planned.plan.stages)
    return planned


def test_apply_chunk_is_pure_rewrite_no_trace():
    from repro.core import rank_candidates
    from repro.core.selection import CostHyper

    w = _weights()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 32))
    flat_fn, flat = _flat(_two_softmax, (w, x))
    g, _ = trace(flat_fn, flat)
    prof = estimate_memory(g)
    budget = prof.peak_bytes // 3
    ranked = rank_candidates(
        g, prof, search_chunks(g, prof), budget, CostHyper()
    )
    cand, n = ranked[0][0], ranked[0][1]
    before = stats.snapshot()
    g2 = apply_chunk(g, cand, n)
    delta = stats.delta(before)
    assert delta["trace_calls"] == 0
    assert delta["lowering_rewrites"] == 1
    # same vars, restructured nodes: exactly one chunk_loop, graph est works
    loops = [e for e in g2.eqns if is_chunk_loop(e)]
    assert len(loops) == 1
    assert estimate_memory(g2).peak_bytes < prof.peak_bytes
    # the original graph is untouched
    assert not any(is_chunk_loop(e) for e in g.eqns)


def test_emitted_fn_matches_reference_exactly():
    w = _chain_weights()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 32))
    planned = _three_stage_plan(w, x)
    assert planned.lowered_graph is not None
    fn = emit(planned.lowered_graph)
    flat, _ = jax.tree_util.tree_flatten((w, x))
    y = np.asarray(fn(*flat)[0])
    np.testing.assert_allclose(y, np.asarray(_softmax_chain(w, x)), atol=1e-5)


def test_three_stage_plan_single_retrace():
    """Acceptance: a 3-stage plan compiles with exactly ONE final re-trace —
    the trace count is independent of the stage count."""
    w = _chain_weights()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 256, 32))
    plan = ChunkPlan.from_json(_three_stage_plan(w, x).plan.to_json())
    assert len(plan.stages) == 3

    flat_fn, flat = _flat(_softmax_chain, (w, x))
    g0, _ = trace(flat_fn, flat)
    before = stats.snapshot()
    fn, _, prof = build_fn_from_plan(flat_fn, flat, plan, baseline_graph=g0)
    delta = stats.delta(before)
    assert delta["trace_calls"] == 1          # ONLY the final verification
    assert delta["lowering_emits"] == 1       # one lowering for 3 stages
    assert delta["lowering_rewrites"] == 3    # one rewrite per stage
    assert delta["search_passes"] == 0 and delta["selection_passes"] == 0
    np.testing.assert_allclose(
        np.asarray(fn(*flat)[0]), np.asarray(_softmax_chain(w, x)), atol=1e-5
    )


@pytest.mark.parametrize("budget", [0.4, 0.2])
def test_cold_compile_trace_count_independent_of_stages(budget):
    """Cold staged compile: baseline trace + one verification trace, no
    matter how many stages the search applies."""
    w = _weights(d=48)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 48))
    cf = autochunk(
        _two_softmax,
        ChunkConfig(budget_ratio=budget, anneal=0),
        bucketer=None,
    )
    before = stats.snapshot()
    planned = cf.trace(w, x).search()
    delta = stats.delta(before)
    expected = 2 if planned.plan.stages else 1
    assert delta["trace_calls"] == expected
    assert delta["lowering_emits"] == (1 if planned.plan.stages else 0)


def test_planned_lower_exposes_rewritten_jaxpr():
    w = _weights()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 256, 32))
    cf = autochunk(_two_softmax, ChunkConfig(budget_ratio=0.3), bucketer=None)
    planned = cf.trace(w, x).search()
    assert planned.plan.stages
    low = planned.lower()
    assert low.jaxpr is not None
    # the rewritten program runs the chunk stages as scan loops
    assert "scan" in low.as_text()
    assert low.eqn_count() > 0
    # the pre-emission graph carries the structured loop nodes
    assert low.graph is not None
    assert any(is_chunk_loop(e) for e in low.graph.eqns)


def test_nested_stage_on_rewritten_graph_hoists_prior_loop():
    """A later stage whose region covers an earlier chunk_loop node must
    hoist it (loops are opaque), and the emitted program stays exact."""
    w = _weights()
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 256, 32))
    flat_fn, flat = _flat(_two_softmax, (w, x))
    g, _ = trace(flat_fn, flat)
    prof = estimate_memory(g)
    cands = _tight_candidates(g, prof, 256)
    g = apply_chunk(g, cands[0], 4)
    prof = estimate_memory(g)
    # second stage: wide window so regions may enclose the first loop node
    wide = [c for c in search_chunks(g, prof, window=64) if c.chunk_extent == 256]
    assert wide
    loop_idx = next(i for i, e in enumerate(g.eqns) if is_chunk_loop(e))
    enclosing = [c for c in wide if c.s <= loop_idx <= c.e]
    pick = enclosing[0] if enclosing else wide[0]
    if enclosing:
        assert loop_idx in pick.hoisted  # opaque loops never enter a body
    g2 = apply_chunk(g, pick, 4)
    y = np.asarray(emit(g2)(*flat)[0])
    np.testing.assert_allclose(y, np.asarray(_two_softmax(w, x)), atol=1e-5)


def test_non_divisible_chunks_via_lowering():
    """Clamped-slice exactness holds through the rewrite backend too."""
    w = _weights(d=16)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 100, 16))
    flat_fn, flat = _flat(_two_softmax, (w, x))
    g, _ = trace(flat_fn, flat)
    prof = estimate_memory(g)
    cands = [c for c in search_chunks(g, prof) if c.chunk_extent == 100]
    assert cands
    for n in (3, 7):
        fn = emit(apply_chunk(g, cands[0], n))
        np.testing.assert_allclose(
            np.asarray(fn(*flat)[0]),
            np.asarray(_two_softmax(w, x)),
            atol=1e-5,
        )


def test_gradients_through_emitted_fn():
    w = _weights()
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 64, 32))
    cf = autochunk(_two_softmax, ChunkConfig(budget_ratio=0.3), bucketer=None)
    compiled = cf.trace(w, x).search().compile()

    g0 = jax.grad(lambda w: jnp.sum(_two_softmax(w, x) ** 2))(w)
    g1 = jax.grad(lambda w: jnp.sum(compiled.fn(w, x) ** 2))(w)
    for k in w:
        np.testing.assert_allclose(
            np.asarray(g0[k]), np.asarray(g1[k]), atol=1e-3, rtol=1e-3
        )


# ---------------------------------------------------------------------------
# source lines and name stacks through the emit
# ---------------------------------------------------------------------------

def _op_locations(lowered_text):
    """(op line, its location with every ``#locN`` reference expanded) of
    each op in ``Lowered.as_text(debug_info=True)``."""
    defs = dict(re.findall(r"^(#loc\d+) = (loc\(.*\))$", lowered_text, re.M))

    def expand(text, depth=0):
        if depth > 64:
            return text
        return re.sub(r"#loc\d+",
                      lambda m: expand(defs.get(m.group(0), ""), depth + 1),
                      text)

    out = []
    for line in lowered_text.splitlines():
        m = re.search(r" = (\S+).* loc\((#loc\d+)\)$", line)
        if m:
            out.append((m.group(1), expand(m.group(2))))
    return out


def _mlp_scoped(w, x):
    with jax.named_scope("mlp"):
        h = x @ w
        return jax.nn.silu(h) @ w.T


def test_chunk_loop_ops_keep_model_scope_and_source_line():
    line = _mlp_scoped.__code__.co_firstlineno + 2  # h = x @ w
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 256)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 64))
    compiled = autochunk(
        _mlp_scoped, ChunkConfig(budget_ratio=0.3, weight_argnums=(0,)),
        bucketer=None,
    ).trace(w, x).search().compile()
    lowered = jax.jit(compiled.fn).lower(w, x)
    ops = _op_locations(lowered.as_text(debug_info=True))

    dots = [loc for op, loc in ops if op == "stablehlo.dot_general"]
    assert dots and all('loc("mlp/' in loc for loc in dots)
    assert any(f'test_lowering.py":{line}:' in loc for loc in dots)
    for op in ("stablehlo.dynamic_slice", "stablehlo.dynamic_update_slice"):
        assert any('loc("autochunk.io/' in loc
                   for name, loc in ops if name == op), op

    # inside the chunk loop, and silu's ops (inlined from its nested jit)
    # keep the caller's scope
    hlo = lowered.compile().as_text()
    body = r'op_name="[^"]*autochunk/while/body/[^"]*'
    assert re.search(body + r'mlp/dot_general"', hlo)
    assert re.search(body + r'mlp/(exp|logistic)"', hlo)
    assert re.search(body + r'autochunk\.io/dynamic_update_slice"', hlo)


def test_dispatched_kernel_keeps_its_own_source_line():
    """A fused kernel runs under the replaced equation's scope but keeps
    its ``pallas_call``'s source, by which the benchmark names kernels."""
    from repro.models import layers as L

    S = 64

    def attn(qkv):
        q, k, v = qkv
        pos = jnp.arange(S)
        return L.gqa_attention(q, k, v, q_pos=pos, kv_pos=pos, causal=True)

    key = jax.random.PRNGKey(2)
    qkv = tuple(jax.random.normal(jax.random.fold_in(key, i), (2, S, 4, 8))
                for i in range(3))
    compiled = autochunk(
        attn, ChunkConfig(budget_ratio=0.3, kernel_dispatch="on"),
        bucketer=None,
    ).trace(qkv).search().compile()
    ops = _op_locations(jax.jit(compiled.fn).lower(qkv).as_text(
        debug_info=True))
    kernel = [loc for _, loc in ops if 'computed_attention/' in loc]
    assert kernel
    assert all("chunked_attention.py" in loc and "layers.py" not in loc
               for loc in kernel)
    hlo = jax.jit(compiled.fn).lower(qkv).compile().as_text()
    assert re.search(r'op_name="[^"]*autochunk/while/body/[^"]*attention/'
                     r'[^"]*jit\(computed_attention\)', hlo)
