"""Kernel autotune + computed-mask contract (ISSUE 8).

Covers:

* computed-mask vs boolean-mask vs ``kernels.ref`` oracle equivalence
  through the full staged pipeline — causal, sliding window, GQA, and
  non-divisible chunk counts;
* the ``kernel_dispatch_computed_mask`` counter (fires under
  ``mask_mode='auto'``, silent under ``'bool'``);
* autotune determinism (same sites -> identical KernelTuning) and the
  in-process tune cache;
* the acceptance counter: a warm plan-cache replay restores the persisted
  tuning with ``autotune_passes == 0``;
* tile legality on shapes the candidate grid does not divide (the
  min()+assert -> legal_block clamping fix);
* v3 plans are rejected with a message naming both versions.

Runs in Pallas interpret mode on CPU (same caveat as test_kernel_dispatch).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChunkConfig, autochunk, stats
from repro.core.plan import PLAN_FORMAT_VERSION, PlanApplyError, PlanCache
from repro.kernels import autotune as at
from repro.kernels import ops, ref
from repro.models import layers as L

ATOL = 1e-4


def _attn_fn(S, causal=True, window=None):
    def attn(qkv):
        q, k, v = qkv
        pos = jnp.arange(S)
        return L.gqa_attention(
            q, k, v, q_pos=pos, kv_pos=pos, causal=causal, window=window
        )

    return attn


def _qkv(B=2, S=64, H=4, Kv=4, hd=8, key=0):
    k0 = jax.random.PRNGKey(key)
    return (
        jax.random.normal(k0, (B, S, H, hd)),
        jax.random.normal(jax.random.fold_in(k0, 1), (B, S, Kv, hd)),
        jax.random.normal(jax.random.fold_in(k0, 2), (B, S, Kv, hd)),
    )


def _compile(fn, args, **kw):
    kw.setdefault("kernel_dispatch", "on")
    cf = autochunk(
        fn, ChunkConfig(budget_ratio=0.3, **kw), bucketer=None
    )
    return cf.trace(*args).search().compile()


# ---------------------------------------------------------------------------
# computed vs boolean vs oracle


@pytest.mark.parametrize(
    "S,causal,Kv,window",
    [
        (64, True, 4, None),    # causal MHA
        (64, True, 2, None),    # causal + GQA
        (64, True, 4, 16),      # sliding window
        (60, True, 2, None),    # non-divisible chunks + GQA
    ],
)
def test_computed_vs_bool_vs_oracle(S, causal, Kv, window):
    attn = _attn_fn(S, causal, window)
    qkv = _qkv(S=S, Kv=Kv)
    y_eager = np.asarray(attn(qkv))

    before = stats.snapshot()
    auto = _compile(attn, (qkv,), mask_mode="auto")
    d_auto = stats.delta(before)
    before = stats.snapshot()
    boolean = _compile(attn, (qkv,), mask_mode="bool")
    d_bool = stats.delta(before)

    assert d_auto["kernel_dispatch_hits"] >= 1
    assert d_auto["kernel_dispatch_computed_mask"] >= 1
    assert d_bool["kernel_dispatch_computed_mask"] == 0

    y_auto = np.asarray(auto.fn(qkv))
    y_bool = np.asarray(boolean.fn(qkv))
    np.testing.assert_allclose(y_auto, y_eager, atol=ATOL)
    np.testing.assert_allclose(y_bool, y_eager, atol=ATOL)
    np.testing.assert_allclose(y_auto, y_bool, atol=ATOL)


def test_computed_kernel_against_ref_oracle():
    """ops.computed_attention directly vs the pure-jnp oracle."""
    N, S, hd = 4, 64, 16
    k0 = jax.random.PRNGKey(3)
    q = jax.random.normal(k0, (N, S, hd))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (N, S, hd))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (N, S, hd))
    scale = 1.0 / np.sqrt(hd)
    for window in (None, 16):
        out = ops.computed_attention(
            q, k, v, scale=scale, causal=True, window=window
        )
        # oracle speaks (B, S, H, hd): fold the flat N axis into heads
        want = ref.attention_ref(
            jnp.moveaxis(q, 0, 1)[None],
            jnp.moveaxis(k, 0, 1)[None],
            jnp.moveaxis(v, 0, 1)[None],
            causal=True,
            window=window,
        )[0]
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(jnp.moveaxis(want, 1, 0)), atol=ATOL
        )


# ---------------------------------------------------------------------------
# autotune determinism + cache


_SITES = (
    {"kind": "attention", "n": 4, "sq": 64, "skv": 128, "hd": 64},
    {"kind": "swiglu", "s": 64, "d": 128, "f": 256},
)


def test_autotune_deterministic():
    at.clear_cache()
    before = stats.snapshot()
    t1 = at.tune_sites(list(_SITES), interpret=True)
    d1 = stats.delta(before)
    at.clear_cache()
    t2 = at.tune_sites(list(_SITES), interpret=True)
    assert t1 == t2
    assert d1["autotune_passes"] == 1
    assert d1["autotune_trials"] >= 2
    assert t1.attention is not None and t1.swiglu is not None
    # round-trips through the plan's serialized form
    assert at.KernelTuning.from_dict(t1.to_dict()) == t1


def test_autotune_inproc_cache():
    at.clear_cache()
    at.tune_sites(list(_SITES), interpret=True)
    before = stats.snapshot()
    at.tune_sites(list(_SITES), interpret=True)
    d = stats.delta(before)
    assert d["autotune_cache_hits"] == 1
    assert d["autotune_passes"] == 0


# ---------------------------------------------------------------------------
# warm replay: the paid-once contract


def test_warm_replay_restores_tuning_without_retuning(tmp_path):
    S = 64
    attn = _attn_fn(S)
    qkv = _qkv(S=S)
    cache = PlanCache(str(tmp_path))

    def compile_once():
        cf = autochunk(
            attn,
            ChunkConfig(
                budget_ratio=0.3,
                kernel_dispatch="on",
                autotune="on",
                mask_mode="auto",
            ),
            cache=cache,
            bucketer=None,
        )
        return cf.trace(qkv).search().compile()

    at.clear_cache()
    before = stats.snapshot()
    cold = compile_once()
    d_cold = stats.delta(before)
    assert d_cold["autotune_passes"] == 1
    assert cold.result.tuning is not None

    # a fresh ChunkedFunction over the same disk cache: plan replay must
    # restore the persisted tuning and never re-enter the autotuner
    at.clear_cache()
    before = stats.snapshot()
    warm = compile_once()
    d_warm = stats.delta(before)
    assert d_warm["plan_cache_hits"] >= 1
    assert d_warm["autotune_passes"] == 0
    assert d_warm["autotune_cache_hits"] == 0
    assert warm.result.tuning == cold.result.tuning
    np.testing.assert_allclose(
        np.asarray(warm.fn(qkv)), np.asarray(cold.fn(qkv)), atol=ATOL
    )


# ---------------------------------------------------------------------------
# tile legality on awkward shapes


def test_tuned_tiles_legal_on_non_divisible_shapes():
    """Every candidate the tuner can emit must run on shapes the grid does
    not divide — the wrappers clamp via legal_block, not min()+assert."""
    N, S, hd = 2, 60, 16
    k0 = jax.random.PRNGKey(7)
    q = jax.random.normal(k0, (N, S, hd))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (N, S, hd))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (N, S, hd))
    scale = 1.0 / np.sqrt(hd)
    want = ref.attention_ref(
        jnp.moveaxis(q, 0, 1)[None],
        jnp.moveaxis(k, 0, 1)[None],
        jnp.moveaxis(v, 0, 1)[None],
        causal=True,
    )[0]
    want = np.asarray(jnp.moveaxis(want, 1, 0))

    at.clear_cache()
    tuning = at.tune_sites(
        [{"kind": "attention", "n": N, "sq": S, "skv": S, "hd": hd}],
        interpret=True,
    )
    kw = tuning.kernel_kwargs("attention")
    assert kw  # the legality filter left at least one candidate
    out = ops.computed_attention(q, k, v, scale=scale, causal=True, **kw)
    np.testing.assert_allclose(np.asarray(out), want, atol=ATOL)


def test_legal_block_never_returns_a_block_mosaic_refuses():
    from repro.kernels.tiling import LANE, is_legal_block, legal_block

    # no 8-aligned divisor of 60 fits 32: the whole axis, not 30
    assert legal_block(60, 32) == 60
    assert legal_block(4096, 1000) == 512
    for total in (1, 7, 60, 96, 128, 9216):
        for want in (1, 16, 64, 256, 512):
            b = legal_block(total, want)
            assert is_legal_block(total, b), (total, want, b)
            b = legal_block(total, want, align=LANE)
            assert is_legal_block(total, b, align=LANE), (total, want, b)


def test_ffn_default_tiles_fit_vmem_at_real_width():
    from repro.kernels.chunked_ffn import ffn_blocks
    from repro.kernels.tiling import VMEM_BUDGET, ffn_vmem_bytes

    bs, bf = ffn_blocks(1024, 3072, 9216, 2)
    assert bf % 128 == 0 and 9216 % bf == 0
    assert ffn_vmem_bytes(bs, bf, 3072, 2) <= VMEM_BUDGET


def test_measured_autotune_counts_failures_and_raises_when_none_compiles(
        monkeypatch, capsys):
    site = {"kind": "swiglu", "s": 64, "d": 32, "f": 1024}

    def refuse_wide(kind, s, cand):
        if cand["block_f"] > 256:
            raise RuntimeError("RESOURCE_EXHAUSTED: vmem")
        return float(cand["block_s"])

    at.clear_cache()
    monkeypatch.setattr(at, "_measured_cost", refuse_wide)
    before = stats.snapshot()
    tuning = at.tune_sites([site], interpret=False)
    failed = stats.delta(before)["autotune_failures"]
    assert failed > 0
    assert tuning.swiglu["block_f"] == 256
    assert f"{failed} of" in capsys.readouterr().out

    def refuse_all(kind, s, cand):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    at.clear_cache()
    monkeypatch.setattr(at, "_measured_cost", refuse_all)
    with pytest.raises(RuntimeError, match="no swiglu candidate compiled"):
        at.tune_sites([site], interpret=False)
    at.clear_cache()


# ---------------------------------------------------------------------------
# plan schema


def test_v3_plan_rejected_naming_both_versions():
    d = {
        "cache_key": "k",
        "budget_bytes": 1,
        "baseline_peak": 1,
        "final_peak": 1,
        "stages": [],
        "meta": {},
        "version": 3,
    }
    from repro.core.plan import ChunkPlan

    with pytest.raises(PlanApplyError) as e:
        ChunkPlan.from_dict(d)
    msg = str(e.value)
    assert "v3" in msg
    assert f"v{PLAN_FORMAT_VERSION}" in msg
    assert "recompile" in msg


def test_plan_roundtrip_carries_tuning(tmp_path):
    S = 64
    attn = _attn_fn(S)
    qkv = _qkv(S=S)
    at.clear_cache()
    res = _compile(attn, (qkv,), autotune="on").result
    plan = res.to_chunk_plan()
    assert plan.version == PLAN_FORMAT_VERSION
    from repro.core.plan import ChunkPlan

    back = ChunkPlan.from_dict(plan.to_dict())
    assert back.tuning == plan.tuning == res.tuning
    assert res.tuning is not None
