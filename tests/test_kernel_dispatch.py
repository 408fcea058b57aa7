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
        monkeypatch.setattr(KD, "_MASK_EVAL_LIMIT", S * S - 1)
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
    else:
        assert counted == {reason: n}
        assert delta["kernel_dispatch_computed_mask"] == 0
    np.testing.assert_allclose(np.asarray(on.fn(*args)),
                               np.asarray(fn(*args)), atol=ATOL)
