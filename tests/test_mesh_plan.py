"""Mesh-aware chunk planning (ISSUE-10).

Covers the acceptance contract: sharding-aware estimation charges
per-device bytes (sharded peak < unsharded peak), the mesh is structural
identity for the plan cache (same model + different mesh = different key,
same mesh reconstructed from its serialized form = same key), v4 plans are
rejected with a recompile message that names the mesh, and — on a
multi-device host (CI forces 8 via ``--xla_force_host_platform_device_count``)
— the same model compiles and serves sharded with token-exact outputs vs
the single-device path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ChunkConfig,
    ChunkedFunction,
    MeshSpec,
    estimate_memory,
    propagate_divisors,
    sequence_parallel_in_specs,
    stats,
    total_divisors,
    trace,
    validate_mesh_axes,
)
from repro.core.plan import PLAN_FORMAT_VERSION, ChunkPlan

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (CI forces them via"
           " XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


# ---------------------------------------------------------------------------
# MeshSpec construction / serialization
# ---------------------------------------------------------------------------

class TestMeshSpec:
    def test_parse_and_describe(self):
        ms = MeshSpec.parse("data=2,model=4")
        assert ms.axes == (("data", 2), ("model", 4))
        assert ms.describe() == "data=2,model=4"
        assert ms.n_devices == 8
        assert ms.axis_size("model") == 4

    def test_round_trip_with_specs(self):
        ms = MeshSpec(
            axes=(("pod", 2), ("data", 2), ("model", 2)),
            in_specs=(None, (("pod", "data"), None, "model")),
            out_specs=((("pod", "data"),),),
            seq_axis="data",
        )
        ms2 = MeshSpec.from_dict(ms.to_dict())
        assert ms2 == ms
        assert ms2.to_dict() == ms.to_dict()

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError, match="name=size"):
            MeshSpec.parse("data:2")

    def test_duplicate_axis_rejected(self):
        with pytest.raises(ValueError, match="unique"):
            MeshSpec(axes=(("data", 2), ("data", 4)))

    def test_unknown_axis_in_specs_rejected(self):
        with pytest.raises(ValueError, match="unknown mesh axis"):
            MeshSpec(axes=(("data", 2),), in_specs=(("model",),))

    def test_bad_seq_axis_rejected(self):
        with pytest.raises(ValueError, match="seq_axis"):
            MeshSpec(axes=(("data", 2),), seq_axis="model")

    def test_validate_mesh_axes_names_the_axes(self):
        with pytest.raises(ValueError) as ei:
            validate_mesh_axes((("data", 2), ("model", 16)), 8)
        msg = str(ei.value)
        assert "data=2" in msg and "model=16" in msg
        assert "32 devices" in msg and "8 are available" in msg

    def test_production_mesh_builder_validates(self):
        # launch.mesh builds 16x16 from jax.devices(): on this host that
        # must fail with the named-axes error, not an opaque reshape
        from repro.launch.mesh import make_production_mesh

        if len(jax.devices()) == 256:
            pytest.skip("host actually has 256 devices")
        with pytest.raises(ValueError, match="data=16 x model=16"):
            make_production_mesh()

    def test_dim_divisors_require_divisibility(self):
        ms = MeshSpec(axes=(("data", 2), ("model", 4)))
        # 8 % 4 == 0 divides; 6 % 4 != 0 charges full bytes (GSPMD padding)
        assert ms.dim_divisors(("model",), (8,)) == (4,)
        assert ms.dim_divisors(("model",), (6,)) == (1,)
        # multi-axis dim: product of the axis sizes
        assert ms.dim_divisors((("data", "model"),), (16,)) == (8,)


# ---------------------------------------------------------------------------
# Forward divisor propagation
# ---------------------------------------------------------------------------

class TestDivisorPropagation:
    def _graph(self, fn, args, weight_argnums=()):
        g, _ = trace(fn, args, weight_argnums=weight_argnums)
        return g

    def test_elementwise_inherits(self):
        g = self._graph(lambda x: jnp.tanh(x) * 2.0, (jnp.ones((8, 16)),))
        ms = MeshSpec(axes=(("data", 2),), in_specs=(("data",),))
        div = total_divisors(g, ms)
        for ov in g.outvars:
            assert div[ov] == 2

    def test_contraction_drops_divisor(self):
        # x:(8,16) sharded on dim1; x @ w contracts dim1 away -> output
        # keeps only the dim0 replication (divisor 1)
        def f(w, x):
            return x @ w

        g = self._graph(f, (jnp.ones((16, 4)), jnp.ones((8, 16))),
                        weight_argnums=(0,))
        ms = MeshSpec(axes=(("model", 2),), in_specs=(None, (None, "model")))
        div = total_divisors(g, ms)
        for ov in g.outvars:
            assert div[ov] == 1

    def test_batch_dim_flows_through_dot(self):
        def f(w, x):
            return jnp.tanh(x @ w)

        g = self._graph(f, (jnp.ones((16, 16)), jnp.ones((8, 32, 16))),
                        weight_argnums=(0,))
        ms = MeshSpec(axes=(("data", 2),), in_specs=(None, ("data",)))
        div = total_divisors(g, ms)
        for ov in g.outvars:
            assert div[ov] == 2

    def test_per_dim_rows_cover_every_var(self):
        def f(x):
            return (x @ x.T).sum()

        g = self._graph(f, (jnp.ones((8, 8)),))
        ms = MeshSpec(axes=(("data", 2),), in_specs=(("data",),))
        rows = propagate_divisors(g, ms)
        for eqn in g.eqns:
            for ov in eqn.outvars:
                shape = getattr(ov.aval, "shape", ())
                assert len(rows[ov]) == len(shape)


# ---------------------------------------------------------------------------
# Sharding-aware estimation
# ---------------------------------------------------------------------------

def _block(w, x):
    h = jnp.tanh(x @ w["w1"])
    a = jax.nn.softmax(
        jnp.einsum("bsd,btd->bst", h, h) / np.sqrt(h.shape[-1]), axis=-1
    )
    o = jnp.einsum("bst,btd->bsd", a, h)
    return jnp.tanh(o @ w["w2"])


def _block_args(b=8, s=64, d=32):
    w = {"w1": jnp.ones((d, d)), "w2": jnp.ones((d, d))}
    return (w, jnp.ones((b, s, d)))


class TestShardedEstimation:
    def test_sharded_peak_below_unsharded(self):
        g, _ = trace(_block, _block_args(), weight_argnums=(0,))
        ms = MeshSpec(
            axes=(("data", 2), ("model", 4)),
            in_specs=(None, None, ("data",)),
        )
        full = estimate_memory(g)
        shard = estimate_memory(g, mesh_spec=ms)
        assert shard.peak_bytes < full.peak_bytes
        # batch-sharded activations divide by exactly the data axis
        assert shard.peak_bytes == full.peak_bytes // 2
        assert shard.shard_divisors is not None
        assert full.shard_divisors is None

    def test_profile_nbytes_matches_divisors(self):
        g, _ = trace(_block, _block_args(), weight_argnums=(0,))
        ms = MeshSpec(axes=(("data", 2),), in_specs=(None, None, ("data",)))
        prof = estimate_memory(g, mesh_spec=ms)
        from repro.core.graph import atom_bytes

        for v, k in prof.shard_divisors.items():
            assert prof.nbytes(v) == atom_bytes(v) // k if k > 1 \
                else prof.nbytes(v) == atom_bytes(v)

    def test_indivisible_batch_charges_full(self):
        g, _ = trace(_block, _block_args(b=3), weight_argnums=(0,))
        ms = MeshSpec(axes=(("data", 2),), in_specs=(None, None, ("data",)))
        assert estimate_memory(g, mesh_spec=ms).peak_bytes == \
            estimate_memory(g).peak_bytes


# ---------------------------------------------------------------------------
# Plan identity: the mesh is structural
# ---------------------------------------------------------------------------

class TestMeshPlanIdentity:
    def _key(self, mesh_spec):
        cf = ChunkedFunction(
            _block,
            ChunkConfig(budget_ratio=0.5, weight_argnums=(0,),
                        mesh_spec=mesh_spec),
        )
        return cf.trace(*_block_args()).cache_key()

    def test_mesh_changes_cache_key(self):
        ms_a = MeshSpec(axes=(("data", 2), ("model", 4)),
                        in_specs=(None, None, ("data",)))
        ms_b = MeshSpec(axes=(("data", 4), ("model", 2)),
                        in_specs=(None, None, ("data",)))
        k_none = self._key(None)
        k_a = self._key(ms_a)
        k_b = self._key(ms_b)
        assert len({k_none, k_a, k_b}) == 3

    def test_same_mesh_reconstructed_matches(self):
        # "across processes": an identical spec rebuilt from its serialized
        # form must produce the same structural key
        ms = MeshSpec(axes=(("data", 2), ("model", 4)),
                      in_specs=(None, None, ("data",)), seq_axis="data")
        ms2 = MeshSpec.from_dict(ms.to_dict())
        assert self._key(ms) == self._key(ms2)

    def test_config_round_trip_keeps_mesh(self):
        ms = MeshSpec(axes=(("data", 2),), in_specs=(("data",),),
                      seq_axis="data")
        cfg = ChunkConfig(budget_ratio=0.5, mesh_spec=ms)
        cfg2 = ChunkConfig.from_dict(cfg.to_dict())
        assert cfg2.mesh_spec == ms
        assert cfg2.cache_token() == cfg.cache_token()

    def test_v4_plan_rejected_with_mesh_message(self):
        doc = {
            "version": PLAN_FORMAT_VERSION - 1,
            "cache_key": "k", "budget_bytes": 1, "baseline_peak": 1,
            "final_peak": 1, "stages": [],
        }
        from repro.core.plan import PlanApplyError

        with pytest.raises(PlanApplyError) as ei:
            ChunkPlan.from_dict(doc)
        msg = str(ei.value)
        assert "recompile" in msg and "mesh" in msg

    def test_plan_round_trips_mesh_field(self):
        ms = MeshSpec(axes=(("data", 2),))
        plan = ChunkPlan(cache_key="k", budget_bytes=1, baseline_peak=2,
                         final_peak=1, stages=[], mesh=ms.to_dict())
        plan2 = ChunkPlan.from_dict(plan.to_dict())
        assert plan2.mesh == ms.to_dict()
        assert MeshSpec.from_dict(plan2.mesh) == ms


# ---------------------------------------------------------------------------
# Sequence-parallel execution specs
# ---------------------------------------------------------------------------

class TestSequenceParallelSpecs:
    def test_chunk_loop_invar_gets_seq_axis(self):
        from repro.core import ChunkConfig as CC

        cf = ChunkedFunction(
            _block,
            CC(budget_ratio=0.3, weight_argnums=(0,)),
        )
        planned = cf.trace(*_block_args()).search()
        lowered = planned.lowered_graph
        if lowered is None or not planned.plan.stages:
            pytest.skip("budget met without chunking at this size")
        ms = MeshSpec(axes=(("data", 2), ("model", 4)), seq_axis="data")
        specs = sequence_parallel_in_specs(lowered, ms)
        upgraded = [s for s in specs if s is not None
                    and any(e == "data" for e in s)]
        assert upgraded, "no sliced chunk input picked up the seq axis"

    def test_no_seq_axis_returns_declared_specs(self):
        g, _ = trace(_block, _block_args(), weight_argnums=(0,))
        ms = MeshSpec(axes=(("data", 2),), in_specs=(None, None, ("data",)))
        assert sequence_parallel_in_specs(g, ms) == ms.in_specs


# ---------------------------------------------------------------------------
# Compile pipeline under a mesh (single-device-safe: data=1)
# ---------------------------------------------------------------------------

class TestMeshCompileSingleDevice:
    def test_sharded_plans_counter_and_exactness(self):
        ms = MeshSpec(axes=(("data", 1),), in_specs=(None, None, ("data",)))
        before = stats.snapshot()
        cf = ChunkedFunction(
            _block, ChunkConfig(budget_ratio=0.4, weight_argnums=(0,),
                                mesh_spec=ms))
        args = _block_args()
        out = cf(*args)
        assert stats.delta(before)["sharded_plans"] >= 1
        base = ChunkedFunction(
            _block, ChunkConfig(budget_ratio=0.4, weight_argnums=(0,)))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(base(*args)), rtol=1e-5, atol=1e-5
        )

    def test_compiled_accuracy_is_per_device(self):
        ms = MeshSpec(axes=(("data", 1),), in_specs=(None, None, ("data",)))
        cf = ChunkedFunction(
            _block, ChunkConfig(budget_ratio=0.4, weight_argnums=(0,),
                                mesh_spec=ms))
        compiled = cf.trace(*_block_args()).search().compile()
        acc = compiled.result.accuracy
        assert acc is not None
        assert acc.source == "per_device_watermark"
        assert np.isfinite(acc.error_pct)
        assert acc.error_pct < 50.0


# ---------------------------------------------------------------------------
# Forced-multi-device legs (the CI job's raison d'etre)
# ---------------------------------------------------------------------------

@multi_device
class TestMeshExecution:
    def test_sharded_compile_token_exact(self):
        ms = MeshSpec(
            axes=(("data", 2), ("model", 4)),
            in_specs=(None, None, ("data",)),
            seq_axis="data",
        )
        args = _block_args()
        sharded = ChunkedFunction(
            _block, ChunkConfig(budget_ratio=0.4, weight_argnums=(0,),
                                mesh_spec=ms))
        plain = ChunkedFunction(
            _block, ChunkConfig(budget_ratio=0.4, weight_argnums=(0,)))
        np.testing.assert_allclose(
            np.asarray(sharded(*args)), np.asarray(plain(*args)),
            rtol=1e-5, atol=1e-5,
        )

    def test_sharded_plan_differs_from_unsharded(self):
        ms = MeshSpec(axes=(("data", 2), ("model", 4)),
                      in_specs=(None, None, ("data",)))
        cf_m = ChunkedFunction(
            _block, ChunkConfig(budget_ratio=0.5, weight_argnums=(0,),
                                mesh_spec=ms))
        cf_p = ChunkedFunction(
            _block, ChunkConfig(budget_ratio=0.5, weight_argnums=(0,)))
        t_m = cf_m.trace(*_block_args())
        t_p = cf_p.trace(*_block_args())
        assert t_m.cache_key() != t_p.cache_key()
        assert t_m.baseline_peak < t_p.baseline_peak
        planned = t_m.search()
        assert planned.plan.mesh == ms.to_dict()

    def test_serve_engine_sharded_token_exact(self):
        from repro.configs import get_config
        from repro.models import model as M
        from repro.serving import Request, ServeEngine

        cfg = get_config("gpt-paper").reduced().with_(dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))

        def run(mesh):
            eng = ServeEngine(cfg, params, max_batch=4, max_len=64,
                              autochunk_budget=0.7, mesh=mesh)
            rng = np.random.default_rng(0)
            for i in range(3):
                eng.submit(Request(
                    rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 8).tolist(),
                    max_new_tokens=4,
                ))
            done = eng.run()
            return eng, {r.rid: r.generated for r in done}

        ms = MeshSpec.parse("data=2,model=4")
        eng_m, toks_m = run(ms)
        _, toks_p = run(None)
        assert toks_m == toks_p
        m = eng_m.metrics()
        assert m["mesh"]["axes"] == "data=2,model=4"
        assert m["mesh"]["sharded_plans"] >= 1
        acc = eng_m.plan_accuracy()
        assert acc is not None and np.isfinite(acc.error_pct)
        assert acc.error_pct < 50.0

    def test_paged_engine_head_sharded_token_exact(self):
        """Under ``model=4`` the paged engine shards the KV pool over KV
        heads and runs the paged kernel per device: same tokens as one
        device."""
        from repro.configs import get_config
        from repro.models import model as M
        from repro.serving import PagedServeEngine, Request

        cfg = get_config("minitron-4b").reduced().with_(
            dtype="float32", n_heads=8, n_kv_heads=4, sliding_window=None)
        params = M.init_params(cfg, jax.random.PRNGKey(0))

        def run(mesh):
            eng = PagedServeEngine(cfg, params, max_seqs=3, max_len=64,
                                   page_size=8, prefill_chunk=8, mesh=mesh)
            rng = np.random.default_rng(0)
            for i, n in enumerate([3, 20, 33]):
                eng.submit(Request(
                    rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new_tokens=4,
                ))
            done = eng.run()
            return eng, {r.rid: r.generated for r in done}

        eng_m, toks_m = run("model=4")
        _, toks_p = run(None)
        assert toks_m == toks_p
        pages = eng_m.pool.pages
        assert pages.sharding.spec[3] == "model"
        assert pages.sharding.shard_shape(pages.shape)[3] == pages.shape[3] // 4
