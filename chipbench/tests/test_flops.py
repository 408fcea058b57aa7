"""The shape-derived counts against XLA's own ``cost_analysis()`` on the
CPU, and against counts by hand; and the readers that divide by them."""
import json

import jax
import jax.numpy as jnp
import pytest

import bench
import flops
from conftest import CONFIG, SIZES
from references import dense


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


PHI3 = json.loads((bench.HERE / "configs" / "phi3-mini-3.8b.json")
                  .read_text())["sizes"]


def test_dense_counts_at_phi3_sizes():
    """The counts the benchmark's readers divide by, at the phi3 sizes,
    written as numbers: they never move with the harness's code."""
    assert dense.prefill_flops(PHI3, 32768) == 448607383584768
    assert dense.prefill_flops(PHI3, 4096) == 32986351140864
    assert dense.prefill_attention(PHI3, 32768) == (211112674983936,
                                                    25769803776)
    assert dense.prefill_attention(PHI3, 4096) == (3299340189696,
                                                   3221225472)
    assert dense.tokens_flops(PHI3, 7, 300, unembedded=0) == 51551797248
    assert dense.unembed_flops(PHI3) == 197001216
    assert dense.paged_step(PHI3, [(1, 300), (64, 64), (16, 200)]) == (
        2146959360, 253624320)


class _Trace:
    """A reduced trace whose attention kernels took 7.3964 s."""

    def kernel_s(self, which):
        return 7.3964


@pytest.mark.parametrize("name,want", [
    ("mfu.prefill", 24.932790224800918),
    ("attn_roofline.prefill", 14.48864236765039),
    ("mfu.serve", 1.6840733801692043),
    ("paged_attn_roofline.serve", 0.006147193278662643),
])
def test_readers_divide_by_the_module_counts(name, want):
    """With fixed data and the phi3 sizes, each reader that divides by a
    count gives the number it gave when it read ``flops.py`` by name."""
    data = {
        "reference": dense, "sizes": PHI3, "device_kind": "TPU v5 lite",
        "kind": "prefill", "trace": _Trace(),
        "prefill": {"count": 3, "seq_len": 32768, "elapsed_s": 27.4,
                    "traced": 1},
        "steps": [
            {"t0": 1.0, "t1": 1.05, "rows": [(1, 300), (64, 64)],
             "tokens": 2},
            {"t0": 1.05, "t1": 1.08, "rows": [(1, 301), (16, 200)],
             "tokens": 1}],
        "traced_steps": [{"rows": [(1, 300), (64, 64)]},
                         {"rows": [(1, 301), (16, 200)]}],
        "window": (0.9, 2.0),
    }
    reader = bench.load_module(bench.HERE / "metrics" / f"{name}.py")
    assert reader.read(data) == want
