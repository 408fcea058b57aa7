"""The benchmark's weights and the plain reference, at a reduced size."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import weights as W
from conftest import CONFIG, SIZES
from references import dense as ref

SEED = 2**31 + 99
# sha256 of every leaf's bits, in tree order, of the dense weights at the
# reduced size for SEED.  A seed's weights are fixed: a change to how the
# harness finds or lays them out must not move what the benchmark reads.
DENSE_DIGEST = \
    "5e4c0a73593c6820184cef09266da974afac876488a920a873ead13ea802b7e4"


def program_cfg(**kw):
    from repro.configs import get_config

    return get_config(CONFIG["registry"]).with_(**CONFIG["overrides"], **kw)


def test_layers_drawn_again_equal_the_stack():
    from repro.models import model as M

    params = ref.make(SIZES, SEED, M.param_specs(program_cfg()))
    base = W.base_key(SEED)
    for i in range(SIZES["n_layers"]):
        one = ref.layer(SIZES, W.layer_key(base, i))
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda x: x[i],
                                                     params["blocks"]))):
            assert (a.astype(jnp.bfloat16) == b).all()
    V = SIZES["vocab_size"]
    emb = params["embed"]["embedding"]
    assert emb.shape[0] % 256 == 0 and (emb[V:] == 0).all()
    assert (ref.embedding(SIZES, base).astype(jnp.bfloat16) == emb[:V]).all()


def test_dense_weights_are_bit_identical_for_a_seed():
    from repro.models import model as M

    params = ref.make(SIZES, SEED, M.param_specs(program_cfg()))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == DENSE_DIGEST


def test_make_refuses_another_tree():
    """``make`` raises where the program's parameters differ from the
    layout it draws: here, a program with one more layer."""
    from repro.models import model as M

    spec = M.param_specs(program_cfg().with_(n_layers=SIZES["n_layers"] + 1))
    with pytest.raises(ValueError, match="differs"):
        ref.make(SIZES, SEED, spec)


def test_values_have_the_stated_spread():
    x = np.asarray(W.uniform(W.base_key(3), (512, 1024), 0.5))
    assert abs(x.mean()) < 0.005 and abs(x.std() / 0.5 - 1) < 0.01
    assert np.abs(x).max() <= 0.5 * 3**0.5
    # neighbouring keys and elements are not correlated
    y = np.asarray(W.uniform(W.fold(W.base_key(3), 1), (512, 1024), 0.5))
    assert abs(np.corrcoef(x.ravel(), y.ravel())[0, 1]) < 0.01
    assert abs(np.corrcoef(x[:, :-1].ravel(), x[:, 1:].ravel())[0, 1]) < 0.01


def test_big_seeds_differ():
    assert int(W.base_key(2**31 + 5)) != int(W.base_key(2**31 + 6))
    assert int(W.base_key(2**40 + 5)) != int(W.base_key(5))


def test_weights_differ_by_seed():
    from repro.models import model as M

    spec = M.param_specs(program_cfg())
    a = ref.make(SIZES, 1, spec)["blocks"]["attn"]["wq"]
    b = ref.make(SIZES, 2, spec)["blocks"]["attn"]["wq"]
    assert not (a == b).all()


def test_reference_matches_the_program_in_float32():
    """With float32 weights and activations on both sides, the program's
    unchunked forward and the reference agree to float32 rounding: the
    reference's equations are the program's."""
    from repro.models import model as M

    cfg = program_cfg(dtype="float32")
    sizes = dict(SIZES, dtype="float32")
    params = ref.make(sizes, SEED, M.param_specs(cfg))
    toks = np.arange(300, dtype=np.int32) * 7 % SIZES["vocab_size"]
    with jax.default_matmul_precision("highest"):
        got = M.next_token_logits(cfg, params, {"tokens": jnp.asarray(
            toks)[None]})
    got = np.asarray(got[0, : SIZES["vocab_size"]], np.float32)
    want = ref.last_logits(sizes, SEED, toks, dtype="float32")
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-4


def test_position_stats_agree_with_full_logits():
    toks = np.arange(40, dtype=np.int32) * 3 % SIZES["vocab_size"]
    last = ref.last_logits(SIZES, 5, toks)
    best, top, got = ref.position_stats(SIZES, 5, toks, [39],
                                        [[int(toks[0])]])
    np.testing.assert_allclose(best[0], np.max(last), rtol=1e-6)
    assert top[0] == np.argmax(last)
    np.testing.assert_allclose(got[0, 0], last[toks[0]], rtol=1e-6)
