"""The shape-derived counts against XLA's own ``cost_analysis()`` on the
CPU, and against counts by hand."""
import jax
import jax.numpy as jnp
import pytest

import flops
from conftest import CONFIG, SIZES


def xla_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_attended_pairs_by_hand():
    assert flops.attended_pairs(4, 4) == 10
    assert flops.attended_pairs(1, 100) == 100
    assert flops.attended_pairs(2, 5) == 4 + 5
    assert flops.attended_pairs(3, 7, causal=False) == 21


def test_paged_step_sums_rows_and_layers():
    f, b = flops.paged_step(SIZES, [(1, 10), (3, 3)])
    H, Kv, hd, L = (SIZES[k] for k in ("n_heads", "n_kv_heads", "head_dim",
                                       "n_layers"))
    assert f == L * 4 * H * hd * (10 + 6)
    assert b == L * 2 * hd * (2 * H * 4 + 2 * Kv * 13)


def test_attention_flops_match_xla():
    """A plain attention over all (query, key) pairs: XLA counts the two
    matmuls plus the softmax's elementwise work; within 5%."""
    B, S, H, Kv, hd = 1, 512, 8, 2, 64
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, S, Kv, hd), jnp.float32)

    def attn(q, k, v):
        qg = q.reshape(B, S, Kv, H // Kv, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k)
        return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v)

    got = xla_flops(attn, q, kv, kv)
    want, nbytes = flops.attention(n_heads=H, n_kv_heads=Kv, head_dim=hd,
                                   q_len=S, kv_len=S, causal=False,
                                   itemsize=4)
    assert abs(got / want - 1) < 0.05
    assert nbytes == 4 * hd * S * (2 * H + 2 * Kv)


@pytest.mark.parametrize("S", [256, 512])
def test_prefill_flops_match_xla(S):
    """The program's unchunked forward, layers unrolled so that XLA
    counts each: matmuls, attention over every pair (the mask is applied
    after the product) and the last position's unembedding, within 5%."""
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config(CONFIG["registry"]).with_(
        **CONFIG["overrides"], scan_layers=False, dtype="float32")
    specs = M.param_specs(cfg)
    tok = jax.ShapeDtypeStruct((1, S), jnp.int32)
    got = xla_flops(lambda p, t: M.next_token_logits(cfg, p, {"tokens": t}),
                    specs, tok)
    want = flops.prefill(SIZES, S, causal=False)
    assert abs(got / want - 1) < 0.05
    # the causal count keeps about half of the attention pairs
    attn_full = SIZES["n_layers"] * flops.attention(
        n_heads=SIZES["n_heads"], n_kv_heads=SIZES["n_kv_heads"],
        head_dim=SIZES["head_dim"], q_len=S, kv_len=S, causal=False)[0]
    assert flops.prefill(SIZES, S) == pytest.approx(
        want - attn_full * (S - 1) / (2 * S))


def test_matmul_params_match_the_program():
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config(CONFIG["registry"]).with_(**CONFIG["overrides"])
    blocks = M.param_specs(cfg)["blocks"]
    n = sum(x.size for k, g in blocks.items() if k in ("attn", "mlp")
            for x in jax.tree.leaves(g))
    assert flops.matmul_params(SIZES) == n
