"""Paged KV pool allocator + paged attention equivalence tests."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.paged_attention import interleave_kv, split_kv
from repro.kernels.ref import paged_attention_ref
from repro.serving import KVPool, OutOfPagesError


def _pool(num_pages=8, page_size=4):
    return KVPool(
        n_layers=2, n_kv_heads=2, head_dim=8,
        num_pages=num_pages, page_size=page_size,
    )


# ======================================================================
# allocator
# ======================================================================

def test_reserve_and_free_roundtrip():
    p = _pool()
    p.reserve(0, 10)  # 3 pages @ page_size=4
    assert p.pages_in_use == 3
    assert p.free_pages == 5
    assert p.free(0) == 3
    assert p.pages_in_use == 0
    assert p.free_pages == 8


def test_freed_pages_are_reused():
    p = _pool(num_pages=4)
    p.reserve(0, 16)  # all 4 pages
    p.ensure(0, 16)
    first = set(p.table(0))
    assert p.free_pages == 0
    p.free(0)
    p.reserve(1, 16)
    p.ensure(1, 16)
    # with the whole pool recycled, the new sequence must hold exactly
    # the pages the retired one returned
    assert set(p.table(1)) == first
    assert p.alloc_events == 8 and p.free_events == 4


def test_table_grows_lazily_from_reservation():
    p = _pool(page_size=4)
    p.reserve(0, 12)  # 3 pages reserved
    assert p.table(0) == []
    p.ensure(0, 3)
    assert len(p.table(0)) == 1
    p.ensure(0, 5)
    assert len(p.table(0)) == 2
    p.ensure(0, 5)  # idempotent
    assert len(p.table(0)) == 2
    p.ensure(0, 12)
    assert len(p.table(0)) == 3
    # pages_in_use never changed: the table grew from the reservation
    assert p.pages_in_use == 3


def test_ensure_past_reservation_draws_from_free_list():
    p = _pool(num_pages=3, page_size=4)
    p.reserve(0, 4)  # 1 page reserved
    p.ensure(0, 8)   # needs a 2nd page -> free list
    assert len(p.table(0)) == 2
    assert p.pages_in_use == 2
    p.ensure(0, 12)
    with pytest.raises(OutOfPagesError):
        p.ensure(0, 16)  # pool exhausted


def test_reserve_refuses_without_side_effects():
    p = _pool(num_pages=4, page_size=4)
    p.reserve(0, 12)  # 3 of 4 pages
    assert not p.can_reserve(8)
    with pytest.raises(OutOfPagesError):
        p.reserve(1, 8)
    # the failed reservation must not leak state
    assert p.free_pages == 1
    assert p.can_reserve(4)
    p.reserve(1, 4)


def test_fragmentation_accounting():
    p = _pool(num_pages=8, page_size=4)
    assert p.frag_token_slots() == 0
    p.reserve(0, 10)  # 3 pages = 12 slots, all reserved slack
    assert p.frag_token_slots() == 12
    p.ensure(0, 5)    # 2 table pages (8 slots, 5 live) + 1 reserved (4)
    assert p.frag_token_slots() == (8 - 5) + 4
    assert p.frag_bytes() == p.frag_token_slots() * p.token_bytes()
    p.free(0)
    assert p.frag_token_slots() == 0
    # paged KV never pays exec_len padding
    assert p.stats()["padded_kv_waste_bytes"] == 0


def test_out_of_pages_error_is_actionable():
    """The refusal names the shortfall, occupancy, and the remedies."""
    p = _pool(num_pages=4, page_size=4)
    p.reserve(0, 12)  # 3 of 4 pages
    with pytest.raises(OutOfPagesError) as ei:
        p.reserve(1, 8)  # needs 2, only 1 free
    e = ei.value
    assert (e.need, e.free, e.in_use, e.num_pages) == (2, 1, 3, 4)
    msg = str(e)
    assert "need 2 page(s)" in msg and "only 1 free" in msg
    assert "3 of 4 in use" in msg
    assert "--num-pages" in msg


# ======================================================================
# property test: allocator invariants under random op interleavings
# ======================================================================

def _run_allocator_program(seed: int, n_ops: int = 60) -> None:
    """One seeded random interleaving of every allocator operation.

    Models the full PR-7 surface: plain reservations, shared (ref-counted)
    reservations with COW boundaries, lazy table growth, frees, external
    holds (the radix cache's refs), spill and restore.  After every op the
    pool's conservation laws must hold (free + refcounted + reserved ==
    num_pages; no page in two tables beyond its refcount), and at full
    drain every page is back on the free list with allocated == freed.
    """
    rnd = random.Random(seed)
    p = KVPool(n_layers=1, n_kv_heads=1, head_dim=4,
               num_pages=8, page_size=4)
    p.enable_spill(3)
    live = {}        # seq_id -> reserved token budget
    holds = []       # external page refs (the cache stand-in)
    spilled = []     # host slots
    next_sid = 0
    for _ in range(n_ops):
        op = rnd.choice(
            ["reserve", "reserve_shared", "ensure", "free",
             "hold", "unhold", "spill", "restore"]
        )
        free_before = p.free_pages
        if op == "reserve":
            try:
                p.reserve(next_sid, rnd.randint(1, 20))
                live[next_sid] = 20
                next_sid += 1
            except OutOfPagesError:
                assert p.free_pages == free_before  # refusal is side-effect-free
        elif op == "reserve_shared" and holds:
            cand = list(dict.fromkeys(holds))
            k = rnd.randint(0, min(2, len(cand)))
            fulls, boundary, part = cand[:k], None, 0
            if len(cand) > k and rnd.random() < 0.5:
                boundary = cand[k]
                part = rnd.randint(1, p.page_size - 1)
            shared = k * p.page_size + part
            n = shared + rnd.randint(1, 10)
            try:
                p.reserve(next_sid, n, shared_pages=fulls,
                          shared_tokens=shared, boundary_page=boundary)
                live[next_sid] = n
                next_sid += 1
            except OutOfPagesError:
                assert p.free_pages == free_before
        elif op == "ensure" and live:
            sid = rnd.choice(list(live))
            try:
                p.ensure(sid, rnd.randint(1, live[sid] + 4))
            except OutOfPagesError:
                pass  # over-budget growth may fail mid-way; invariants hold
        elif op == "free" and live:
            sid = rnd.choice(list(live))
            p.free(sid)
            del live[sid]
        elif op == "hold":
            tabs = [pg for sid in live for pg in p.table(sid)]
            if tabs:
                pg = rnd.choice(tabs)
                p.incref(pg)
                holds.append(pg)
        elif op == "unhold" and holds:
            p.decref(holds.pop(rnd.randrange(len(holds))))
        elif op == "spill":
            sole = [pg for pg in dict.fromkeys(holds)
                    if p.refcount(pg) == 1 and holds.count(pg) == 1]
            if sole and p.spilled_pages < p.host_capacity:
                pg = rnd.choice(sole)
                holds.remove(pg)
                spilled.append(p.spill_page(pg))
        elif op == "restore" and spilled:
            slot = rnd.choice(spilled)
            try:
                holds.append(p.restore_page(slot))
                spilled.remove(slot)
            except OutOfPagesError:
                assert p.free_pages == free_before
        p.check_invariants()
    # ---- full drain: every page must come home, ledger balanced -------
    for sid in list(live):
        p.free(sid)
    while holds:
        p.decref(holds.pop())
    for i, slot in enumerate(list(spilled)):
        if i % 2 == 0:
            p.decref(p.restore_page(slot))  # restore then release
        else:
            p.drop_spilled(slot)            # host-side discard
    p.check_invariants()
    assert p.free_pages == p.num_pages
    assert p.spilled_pages == 0
    assert p.alloc_events == p.free_events


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**32 - 1))
def test_pool_invariants_under_random_interleavings(seed):
    _run_allocator_program(seed)


def test_for_config_shapes():
    cfg = get_config("gpt-paper").reduced().with_(dtype="float32")
    p = KVPool.for_config(cfg, num_pages=4, page_size=8)
    # +1 physical page: the trash page for padded-row writes
    assert p.pages.shape == (
        cfg.n_layers, 5, 8, 2 * cfg.n_kv_heads, cfg.hd
    )
    assert p.trash_page == 4
    assert p.token_bytes() == cfg.n_layers * 2 * cfg.n_kv_heads * cfg.hd * 4


def test_interleave_roundtrip():
    k = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)
    v = -k
    fused = interleave_kv(k, v)
    assert fused.shape == (2, 6, 4)
    # K and V of each head are adjacent on the fused head axis
    np.testing.assert_array_equal(fused[:, 0], k[:, 0])
    np.testing.assert_array_equal(fused[:, 1], v[:, 0])
    k2, v2 = split_kv(fused)
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)


# ======================================================================
# paged attention: kernel vs pure-JAX reference vs dense oracle
# ======================================================================

H, KV, HD, PS = 4, 2, 16, 8


def _ragged_case(q_lens, kv_lens, seed=0):
    """Build a ragged batch in paged layout + the dense per-seq K/V."""
    rng = np.random.default_rng(seed)
    T = sum(q_lens)
    q = jnp.asarray(rng.standard_normal((T, H, HD)), jnp.float32)
    n_pages = sum(-(-kl // PS) for kl in kv_lens)
    pages = np.zeros((n_pages + 1, PS, 2 * KV, HD), np.float32)
    max_pages = max(-(-kl // PS) for kl in kv_lens)
    table = np.zeros((len(kv_lens), max_pages), np.int32)
    dense = []
    # hand pages out in a shuffled order so the test exercises real
    # page-table indirection, not identity mapping
    order = rng.permutation(n_pages).tolist()
    for s, kl in enumerate(kv_lens):
        k = rng.standard_normal((kl, KV, HD)).astype(np.float32)
        v = rng.standard_normal((kl, KV, HD)).astype(np.float32)
        dense.append((k, v))
        fused = np.asarray(interleave_kv(jnp.asarray(k), jnp.asarray(v)))
        for j in range(-(-kl // PS)):
            pid = order.pop()
            table[s, j] = pid
            chunk = fused[j * PS:(j + 1) * PS]
            pages[pid, :len(chunk)] = chunk
    cu_q = jnp.asarray(np.cumsum([0] + list(q_lens)), jnp.int32)
    cu_kv = jnp.asarray(np.cumsum([0] + list(kv_lens)), jnp.int32)
    return q, jnp.asarray(pages), jnp.asarray(table), cu_q, cu_kv, dense


def _dense_oracle(q, cu_q, kv_lens, dense):
    """Straight softmax attention per sequence on the gathered dense KV."""
    outs = []
    starts = np.asarray(cu_q)
    for s, (k, v) in enumerate(dense):
        qs = np.asarray(q[starts[s]:starts[s + 1]], np.float32)
        ql, kl = qs.shape[0], kv_lens[s]
        kh = np.repeat(k, H // KV, axis=1)  # GQA head expansion
        vh = np.repeat(v, H // KV, axis=1)
        logits = np.einsum("qhd,khd->hqk", qs, kh) / np.sqrt(HD)
        qpos = kl - ql + np.arange(ql)
        mask = np.arange(kl)[None, :] <= qpos[:, None]
        logits = np.where(mask[None], logits, -np.inf)
        w = np.exp(logits - logits.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        outs.append(np.einsum("hqk,khd->qhd", w, vh))
    return np.concatenate(outs, 0)


RAGGED_CASES = [
    # prefill-only, aligned and unaligned lengths
    ([8, 16], [8, 16]),
    ([5, 11, 3], [5, 11, 3]),
    # single-token decode rows against a longer context
    ([1, 1, 1], [9, 17, 4]),
    # mixed prefill chunk + decode in one batch (the engine's mixed step)
    ([8, 1, 5, 1], [24, 13, 5, 1]),
]


@pytest.mark.parametrize("q_lens,kv_lens", RAGGED_CASES)
def test_paged_attention_matches_dense(q_lens, kv_lens):
    q, pages, table, cu_q, cu_kv, dense = _ragged_case(q_lens, kv_lens)
    want = _dense_oracle(q, cu_q, kv_lens, dense)
    ref = paged_attention_ref(q, pages, table, cu_q, cu_kv)
    np.testing.assert_allclose(np.asarray(ref), want, atol=2e-5, rtol=2e-5)
    got = ops.paged_attention(q, pages, table, cu_q, cu_kv)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_q", [1, 4])
def test_paged_attention_blocks_the_query_axis(block_q):
    """Query blocks narrower than q_max (and the causal page skip between
    them) give the same rows as the oracle."""
    from repro.kernels.paged_attention import paged_attention_blocked

    q_lens, kv_lens = [8, 1, 5, 1], [24, 13, 5, 1]
    q, pages, table, cu_q, cu_kv, dense = _ragged_case(q_lens, kv_lens)
    want = _dense_oracle(q, cu_q, kv_lens, dense)
    q_max = max(q_lens)
    qb = np.zeros((len(q_lens), q_max, H, HD), np.float32)
    starts = np.asarray(cu_q)
    for s, ql in enumerate(q_lens):
        qb[s, :ql] = np.asarray(q[starts[s]:starts[s] + ql])
    out = paged_attention_blocked(
        jnp.asarray(qb), pages, table,
        jnp.asarray(q_lens, jnp.int32), jnp.asarray(kv_lens, jnp.int32),
        block_q=block_q, interpret=True,
    )
    got = np.concatenate(
        [np.asarray(out[s, :ql]) for s, ql in enumerate(q_lens)], 0
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_paged_attention_ignores_offtable_pages():
    """Garbage in unused pages must not leak into any sequence's output."""
    q, pages, table, cu_q, cu_kv, dense = _ragged_case([1, 7], [6, 7], seed=3)
    want = ops.paged_attention(q, pages, table, cu_q, cu_kv)
    used = set(np.asarray(table).ravel().tolist())
    poison = np.asarray(pages).copy()
    for pid in range(pages.shape[0]):
        if pid not in used:
            poison[pid] = 1e9
    got = ops.paged_attention(q, jnp.asarray(poison), table, cu_q, cu_kv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.tpu
def test_paged_attention_mosaic_lowering():
    """Compile the kernel through Mosaic (no interpret) on real TPU."""
    if jax.default_backend() != "tpu":
        pytest.skip("requires a TPU backend")
    from repro.kernels.paged_attention import paged_attention_blocked

    q_lens, kv_lens = [8, 1], [16, 9]
    q, pages, table, cu_q, cu_kv, dense = _ragged_case(q_lens, kv_lens)
    want = _dense_oracle(q, cu_q, kv_lens, dense)
    q_max = max(q_lens)
    qb = np.zeros((len(q_lens), q_max, H, HD), np.float32)
    starts = np.asarray(cu_q)
    for s, ql in enumerate(q_lens):
        qb[s, :ql] = np.asarray(q[starts[s]:starts[s] + ql])
    out = paged_attention_blocked(
        jnp.asarray(qb), pages, table,
        jnp.asarray(q_lens, jnp.int32), jnp.asarray(kv_lens, jnp.int32),
        interpret=False,
    )
    got = np.concatenate(
        [np.asarray(out[s, :ql]) for s, ql in enumerate(q_lens)], 0
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
