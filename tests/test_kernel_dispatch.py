"""Kernel-dispatch equivalence: fused Pallas bodies == scan-body codegen.

Covers the ISSUE-3 satellite contract: dispatched vs scan-body outputs are
allclose across causal/non-causal masks, GQA grouping, and non-divisible
chunk counts; SwiGLU bodies dispatch in both fused-``w_in`` and separate-
weights form; lookalike patterns (gelu-gated FFN) do NOT dispatch; and the
``kernel_dispatch_hits``/``misses`` counters expose coverage.

Runs in Pallas interpret mode on CPU — numerically exact but slow, which is
why ``kernel_dispatch='auto'`` only turns the pass on under a TPU backend;
tests force ``'on'``.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChunkConfig, autochunk, stats
from repro.models import layers as L

ATOL = 1e-4


def _attn_fn(S, causal, window=None):
    def attn(qkv):
        q, k, v = qkv
        pos = jnp.arange(S)
        return L.gqa_attention(
            q, k, v, q_pos=pos, kv_pos=pos, causal=causal, window=window
        )

    return attn


def _qkv(B=2, S=64, H=4, Kv=2, hd=8, key=0):
    k0 = jax.random.PRNGKey(key)
    return (
        jax.random.normal(k0, (B, S, H, hd)),
        jax.random.normal(jax.random.fold_in(k0, 1), (B, S, Kv, hd)),
        jax.random.normal(jax.random.fold_in(k0, 2), (B, S, Kv, hd)),
    )


def _compile(fn, args, *, kernel_dispatch, weight_argnums=(), **kw):
    cf = autochunk(
        fn,
        ChunkConfig(
            budget_ratio=0.3,
            weight_argnums=weight_argnums,
            kernel_dispatch=kernel_dispatch,
            **kw,
        ),
        bucketer=None,
    )
    return cf.trace(*args).search().compile()


@pytest.mark.parametrize(
    "causal,Kv,window",
    [
        (True, 2, None),    # causal + GQA
        (False, 4, None),   # full attention, MHA
        (True, 4, None),    # causal MHA
        (True, 2, 16),      # sliding window + GQA
    ],
)
def test_attention_dispatch_matches_scan_body(causal, Kv, window):
    S = 64
    attn = _attn_fn(S, causal, window)
    qkv = _qkv(S=S, Kv=Kv)
    y_ref = np.asarray(attn(qkv))

    off = _compile(attn, (qkv,), kernel_dispatch="off")
    before = stats.snapshot()
    on = _compile(attn, (qkv,), kernel_dispatch="on")
    delta = stats.delta(before)
    assert delta["kernel_dispatch_hits"] >= 1

    y_off = np.asarray(off.fn(qkv))
    y_on = np.asarray(on.fn(qkv))
    np.testing.assert_allclose(y_off, y_ref, atol=ATOL)
    np.testing.assert_allclose(y_on, y_ref, atol=ATOL)
    np.testing.assert_allclose(y_on, y_off, atol=ATOL)


def test_attention_dispatch_non_divisible_chunks():
    """S=60 never splits evenly: clamped tail chunks must stay exact."""
    S = 60
    attn = _attn_fn(S, True)
    qkv = _qkv(S=S, Kv=2)
    y_ref = np.asarray(attn(qkv))
    before = stats.snapshot()
    on = _compile(attn, (qkv,), kernel_dispatch="on", beam=8)
    delta = stats.delta(before)
    y_on = np.asarray(on.fn(qkv))
    np.testing.assert_allclose(y_on, y_ref, atol=ATOL)
    # whatever chunk count selection picked, dispatch coverage is counted
    assert delta["kernel_dispatch_hits"] + delta["kernel_dispatch_misses"] >= 1


def _swiglu_fused(w, x):
    h = x @ w["w_in"]
    u, g = jnp.split(h, 2, axis=-1)
    return (u * jax.nn.silu(g)) @ w["w_out"]


def _swiglu_split(w, x):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def _geglu(w, x):
    h = x @ w["w_in"]
    u, g = jnp.split(h, 2, axis=-1)
    return (u * jax.nn.gelu(g)) @ w["w_out"]


def test_swiglu_dispatch_fused_w_in():
    d, f = 32, 256
    key = jax.random.PRNGKey(0)
    w = {
        "w_in": jax.random.normal(key, (d, 2 * f)) * 0.1,
        "w_out": jax.random.normal(jax.random.fold_in(key, 1), (f, d)) * 0.1,
    }
    x = jax.random.normal(jax.random.fold_in(key, 2), (2, 48, d))
    y_ref = np.asarray(_swiglu_fused(w, x))
    before = stats.snapshot()
    on = _compile(_swiglu_fused, (w, x), kernel_dispatch="on",
                  weight_argnums=(0,))
    delta = stats.delta(before)
    assert delta["kernel_dispatch_hits"] == 1
    np.testing.assert_allclose(np.asarray(on.fn(w, x)), y_ref, atol=ATOL)


def test_swiglu_dispatch_split_weights_odd_seq():
    d, f = 32, 256
    key = jax.random.PRNGKey(1)
    w = {
        "wg": jax.random.normal(key, (d, f)) * 0.1,
        "wu": jax.random.normal(jax.random.fold_in(key, 1), (d, f)) * 0.1,
        "wd": jax.random.normal(jax.random.fold_in(key, 2), (f, d)) * 0.1,
    }
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 17, d))
    y_ref = np.asarray(_swiglu_split(w, x))
    before = stats.snapshot()
    on = _compile(_swiglu_split, (w, x), kernel_dispatch="on",
                  weight_argnums=(0,))
    delta = stats.delta(before)
    assert delta["kernel_dispatch_hits"] == 1
    np.testing.assert_allclose(np.asarray(on.fn(w, x)), y_ref, atol=ATOL)


def test_dispatch_error_falls_back_and_is_counted(monkeypatch):
    """A matcher that raises leaves scan codegen in place and bumps
    ``kernel_dispatch_errors``, not ``kernel_dispatch_misses``."""
    from repro.core import kernel_dispatch as KD

    def boom(ctx, mask_mode="auto"):
        raise TypeError("matcher bug")

    monkeypatch.setattr(KD, "match_body", boom)
    d, f = 32, 256
    key = jax.random.PRNGKey(4)
    w = {
        "w_in": jax.random.normal(key, (d, 2 * f)) * 0.1,
        "w_out": jax.random.normal(jax.random.fold_in(key, 1), (f, d)) * 0.1,
    }
    x = jax.random.normal(jax.random.fold_in(key, 2), (2, 48, d))
    before = stats.snapshot()
    on = _compile(_swiglu_fused, (w, x), kernel_dispatch="on",
                  weight_argnums=(0,))
    delta = stats.delta(before)
    assert delta["kernel_dispatch_errors"] >= 1
    assert delta["kernel_dispatch_hits"] == 0
    assert delta["kernel_dispatch_misses"] == 0
    np.testing.assert_allclose(
        np.asarray(on.fn(w, x)), np.asarray(_swiglu_fused(w, x)), atol=ATOL
    )


def test_geglu_does_not_dispatch():
    """gelu-gated FFN is NOT SwiGLU: matcher must refuse, output exact."""
    d, f = 32, 128
    key = jax.random.PRNGKey(2)
    w = {
        "w_in": jax.random.normal(key, (d, 2 * f)) * 0.1,
        "w_out": jax.random.normal(jax.random.fold_in(key, 1), (f, d)) * 0.1,
    }
    x = jax.random.normal(jax.random.fold_in(key, 2), (2, 48, d))
    before = stats.snapshot()
    on = _compile(_geglu, (w, x), kernel_dispatch="on", weight_argnums=(0,))
    delta = stats.delta(before)
    assert delta["kernel_dispatch_hits"] == 0
    np.testing.assert_allclose(
        np.asarray(on.fn(w, x)), np.asarray(_geglu(w, x)), atol=1e-5
    )


def test_attention_dispatch_inverted_mask_convention():
    """``jnp.where(banned, -1e30, scores)`` (True = MASKED) must dispatch
    with the mask negated — the kernel's convention is True = attend."""
    B, S, H, hd = 2, 48, 2, 8

    def attn(qkv):
        q, k, v = qkv
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        banned = ~jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(banned[None, None], -1e30, s)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    key = jax.random.PRNGKey(4)
    qkv = tuple(
        jax.random.normal(jax.random.fold_in(key, i), (B, S, H, hd))
        for i in range(3)
    )
    y_ref = np.asarray(attn(qkv))
    before = stats.snapshot()
    on = _compile(attn, (qkv,), kernel_dispatch="on")
    delta = stats.delta(before)
    assert delta["kernel_dispatch_hits"] >= 1
    np.testing.assert_allclose(np.asarray(on.fn(qkv)), y_ref, atol=ATOL)


def test_dispatch_off_never_touches_kernels():
    attn = _attn_fn(64, True)
    qkv = _qkv(S=64)
    before = stats.snapshot()
    _compile(attn, (qkv,), kernel_dispatch="off")
    delta = stats.delta(before)
    assert delta["kernel_dispatch_hits"] == 0
    assert delta["kernel_dispatch_misses"] == 0


def test_dispatch_resolution_feeds_cache_key():
    on = ChunkConfig(kernel_dispatch="on")
    off = ChunkConfig(kernel_dispatch="off")
    assert on.resolve_kernel_dispatch() is True
    assert off.resolve_kernel_dispatch() is False
    assert on.search_knobs()["kernel_dispatch"] is True
    assert on.cache_token() != off.cache_token()


def test_masked_attention_kernel_direct():
    """The dispatch target itself: flat masked kernel vs reference softmax."""
    from repro.kernels import ops

    N, Sq, Skv, hd = 4, 32, 32, 8
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (N, Sq, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (N, Skv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (N, Skv, hd))
    mask = jnp.tril(jnp.ones((Sq, Skv), bool))[None]
    scale = 1.0 / np.sqrt(hd)

    s = jnp.einsum("nqd,nkd->nqk", q, k) * scale
    s = jnp.where(mask, s, -1e30)
    ref = jnp.einsum("nqk,nkd->nqd", jax.nn.softmax(s, axis=-1), v)
    out = ops.masked_attention(q, k, v, mask, scale=float(scale))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _holed_attn(S):
    """Causal attention that hides key position 1 from every query:
    position algebra, but not a band."""
    def attn(qkv):
        q, k, v = qkv
        pos = jnp.arange(S)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        ok = (pos[None, :] <= pos[:, None]) & (pos[None, :] != 1)
        s = jnp.where(ok[None, None], s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    return attn


def _valid_attn(S):
    """Causal attention masked by a validity vector the caller passes: a
    mask no position algebra gives."""
    def attn(qkv, valid):
        q, k, v = qkv
        pos = jnp.arange(S)
        return L.gqa_attention(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                               kv_valid=valid)

    return attn


@pytest.mark.parametrize("reason", [
    None, "mask_mode_bool", "mask_eval_limit", "mask_not_position_algebra",
    "mask_not_band",
])
def test_bool_mask_dispatch_counts_its_reason(reason, monkeypatch):
    """Each attention dispatch is counted once, and each one that streams
    a bool mask under exactly one ``kernel_dispatch_bool_mask.<reason>``."""
    from repro.core import kernel_dispatch as KD

    S = 64
    qkv = _qkv(S=S, Kv=4)
    fn, args, kw = _attn_fn(S, True), (qkv,), {}
    if reason == "mask_mode_bool":
        kw = {"mask_mode": "bool"}
    elif reason == "mask_eval_limit":
        # a causal mask classifies symbolically whatever its size; a mask
        # the symbolic classifier has no answer for is evaluated, and that
        # evaluation is what the limit refuses
        monkeypatch.setattr(KD, "_MASK_EVAL_LIMIT", S * S - 1)
        fn = _holed_attn(S)
    elif reason == "mask_not_position_algebra":
        fn, args = _valid_attn(S), (qkv, jnp.arange(S) < S - 5)
    elif reason == "mask_not_band":
        fn = _holed_attn(S)
    before = stats.snapshot()
    on = _compile(fn, args, kernel_dispatch="on", **kw)
    delta = stats.delta(before)
    n = delta["kernel_dispatch_attention"]
    assert n >= 1
    counted = {k.rsplit(".", 1)[1]: v for k, v in delta.items()
               if k.startswith("kernel_dispatch_bool_mask.") and v}
    if reason is None:
        assert counted == {} and delta["kernel_dispatch_computed_mask"] == n
        assert delta["kernel_dispatch_band.symbolic"] == n
    else:
        assert counted == {reason: n}
        assert delta["kernel_dispatch_computed_mask"] == 0
    np.testing.assert_allclose(np.asarray(on.fn(*args)),
                               np.asarray(fn(*args)), atol=ATOL)


def test_causal_band_classifies_past_the_eval_limit(monkeypatch):
    """Under a limit no full mask fits, a causal mask still dispatches onto
    the position-computed kernel: its band is read symbolically, and the
    numpy evaluation never runs."""
    from repro.core import kernel_dispatch as KD

    S = 64
    monkeypatch.setattr(KD, "_MASK_EVAL_LIMIT", S * S - 1)
    fn, qkv = _attn_fn(S, True), _qkv(S=S, Kv=2)
    before = stats.snapshot()
    on = _compile(fn, (qkv,), kernel_dispatch="on")
    delta = stats.delta(before)
    n = delta["kernel_dispatch_attention"]
    assert n >= 1
    assert delta["kernel_dispatch_computed_mask"] == n
    assert delta["kernel_dispatch_band.symbolic"] == n
    assert delta["kernel_dispatch_band.evaluated"] == 0
    assert delta["kernel_dispatch_bool_mask.mask_eval_limit"] == 0
    np.testing.assert_allclose(np.asarray(on.fn(qkv)), np.asarray(fn(qkv)),
                               atol=ATOL)


# --- symbolic band classification against the materialised mask ----------

def _mask_ctx(mask_fn, *args):
    """A mask function's graph as the classifiers see a mask's outer chain:
    every equation an outer producer, so ``iota`` counts from 0 to S-1."""
    from repro.core import graph as G
    from repro.core import kernel_dispatch as KD

    g, _ = G.trace(mask_fn, args, weight_argnums=())
    ctx = KD._BodyCtx(eqns=[], outer=KD._outer_producers(g), consts=g.consts)
    return ctx, g.outvars[0]


def _band_mask(sq, skv, *, causal=True, window=None, q_off=0):
    def mask():
        return L.attention_scores_mask(
            jnp.arange(sq) + q_off, jnp.arange(skv), causal=causal,
            window=window)

    return mask


def _pos(sq, skv):
    a = jnp.arange(sq)[:, None]
    j = jnp.arange(skv)[None, :]
    return a, j


_BAND_CASES = {
    # name: (mask fn, flip, invert)
    "causal": (_band_mask(48, 48), False, False),
    "full": (_band_mask(48, 48, causal=False), False, False),
    "sliding_window": (_band_mask(48, 48, window=16), False, False),
    "window_only": (_band_mask(40, 40, causal=False, window=7), False, False),
    "causal_sq_lt_skv": (_band_mask(24, 40, q_off=16), False, False),
    "window_sq_lt_skv": (_band_mask(24, 40, window=9, q_off=16), False, False),
    "causal_sq_gt_skv": (_band_mask(40, 24), False, False),
    "masked_true_convention": (
        lambda: (lambda a, j: j > a)(*_pos(32, 32)), False, True),
    "masked_true_sq_lt_skv": (
        lambda: (lambda a, j: j > a + 16)(*_pos(24, 40)), False, True),
    "masked_true_below": (
        lambda: (lambda a, j: j <= a - 4)(*_pos(32, 32)), False, True),
    "not_of_half_plane": (
        lambda: (lambda a, j: ~(j > a))(*_pos(32, 32)), False, False),
    "one_corner_cut": (
        lambda: (lambda a, j: j <= a + 30)(*_pos(32, 32)), False, False),
    "scaled_lower_bound": (
        lambda: (lambda a, j: 2 * j + 3 >= 2 * a)(*_pos(24, 24)),
        False, False),
    "transposed_kv_q": (lambda: _band_mask(32, 40, q_off=8)().T, True, False),
    "kv_q_built": (
        lambda: (lambda a, j: a.T <= j.T)(*_pos(20, 30)), True, False),
    "strictly_above": (lambda: (lambda a, j: j > a)(*_pos(8, 16)),
                       False, False),
    "scaled_offset": (
        lambda: (lambda a, j: 2 * j <= 2 * a + 3)(*_pos(24, 24)),
        False, False),
    "float_positions": (
        lambda: (lambda a, j: j.astype(jnp.float32) - 4.0
                 <= a.astype(jnp.float32))(*_pos(24, 24)), False, False),
    "reshape_unit_dims": (
        lambda: jnp.arange(16).reshape(16, 1) >= jnp.arange(16)[None, :],
        False, False),
    "single_row": (lambda: (lambda a, j: j <= a + 10)(*_pos(1, 24)),
                   False, False),
}


@pytest.mark.parametrize("name", sorted(_BAND_CASES))
def test_symbolic_band_matches_materialised_mask(name):
    """The symbolic classifier gives exactly the ``(U, L)`` that
    ``_band_params`` reads off the materialised mask."""
    from repro.core import kernel_dispatch as KD

    mask_fn, flip, invert = _BAND_CASES[name]
    ctx, m_var = _mask_ctx(mask_fn)
    m = np.asarray(mask_fn())
    m2 = m.T if flip else m
    if invert:
        m2 = ~m2
    sq, skv = m2.shape
    want = KD._band_params(m2)
    assert want is not None
    assert KD._symbolic_band(ctx, m_var, flip, invert, sq, skv) == want


_NOT_BAND_CASES = {
    "holed": lambda: (lambda a, j: (j <= a) & (j != 1))(*_pos(32, 32)),
    "or_of_two_bands": lambda: (
        lambda a, j: (j <= a - 8) | (j >= a))(*_pos(32, 32)),
    "row_only_empties_rows": lambda: jnp.ones((32, 32), bool)
    & (jnp.arange(32)[:, None] < 32 - 5),
    "two_intervals_inverted": lambda: (
        lambda a, j: (j <= a + 2) & (j >= a - 2))(*_pos(32, 32)),
    "rows_past_the_last_key": lambda: (
        lambda a, j: j >= a + 3)(*_pos(32, 32)),
    "rows_before_the_first_key": lambda: (
        lambda a, j: j <= a - 3)(*_pos(32, 32)),
    "uneven_slopes": lambda: (lambda a, j: 2 * j <= a)(*_pos(32, 32)),
    "nowhere_true": lambda: (
        lambda a, j: (j <= a) & (a - j >= 64))(*_pos(32, 32)),
}


@pytest.mark.parametrize("name", sorted(_NOT_BAND_CASES) + ["kv_valid"])
def test_symbolic_band_refuses_non_bands(name):
    """Masks that are not a band, or not position algebra, get no symbolic
    answer (the numpy evaluation then decides)."""
    from repro.core import kernel_dispatch as KD

    S = 32
    invert = name == "two_intervals_inverted"
    if name == "kv_valid":
        def mask_fn(valid):
            a, j = _pos(S, S)
            return (j <= a) & valid[None, :]

        args = (jnp.arange(S) < S - 5,)
    else:
        mask_fn, args = _NOT_BAND_CASES[name], ()
    ctx, m_var = _mask_ctx(mask_fn, *args)
    assert KD._symbolic_band(ctx, m_var, False, invert, S, S) is None
    m = np.asarray(mask_fn(*args))
    assert KD._band_params(~m if invert else m) is None


def test_symbolic_band_full_size_builds_no_square(monkeypatch):
    """The 32,768^2 causal chain of ``attention_scores_mask`` classifies
    from its jaxpr alone, without an array of the mask's size."""
    from repro.core import kernel_dispatch as KD

    S = 32768
    limit = 1 << 20
    real_broadcast, real_arange = np.broadcast_to, np.arange

    def small_broadcast(x, shape, *a, **k):
        assert math.prod(shape) <= limit, shape
        return real_broadcast(x, shape, *a, **k)

    def small_arange(*a, **k):
        out = real_arange(*a, **k)
        assert out.size <= limit, out.size
        return out

    ctx, m_var = _mask_ctx(_band_mask(S, S))
    monkeypatch.setattr(np, "broadcast_to", small_broadcast)
    monkeypatch.setattr(np, "arange", small_arange)
    t0 = time.perf_counter()
    band = KD._symbolic_band(ctx, m_var, False, False, S, S)
    assert time.perf_counter() - t0 < 1.0
    assert band == (0, S - 1)
