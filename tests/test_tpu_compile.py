"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode accepts blocks and VMEM footprints that Mosaic refuses, so
these tests compile each kernel of the chip path through the TPU compiler
for a described (not attached) ``v5e:2x2`` topology, at minitron-4b's
widths: 24 query heads, 8 KV heads, head dim 128, d_model 3072, d_ff 9216,
bf16.  Nothing runs; a refused block shape or a kernel over the scoped VMEM
limit fails the compile.  Each compile takes about two seconds.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.chunked_attention import computed_attention, masked_attention
from repro.kernels.chunked_ffn import chunked_ffn
from repro.kernels.paged_attention import paged_attention_blocked

H, KV, HD, D, DFF = 24, 8, 128, 3072, 9216
# paged serving: 4 step rows, 2048-token contexts in 16-token pages; 1024 is
# the prefill chunk the planner picks for minitron-4b at max_len 2048
ROWS, PAGE, MAX_LEN, PREFILL_CHUNK = 4, 16, 2048, 1024


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: a ShapeDtypeStruct placed on one v5e chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("q_max", [1, PREFILL_CHUNK])
def test_paged_attention_compiles(spec, q_max):
    n_pages = ROWS * MAX_LEN // PAGE + 1
    _compile(
        lambda q, pages, table, ql, kl: paged_attention_blocked(
            q, pages, table, ql, kl, interpret=False
        ),
        spec((ROWS, q_max, H, HD), jnp.bfloat16),
        spec((n_pages, PAGE, 2 * KV, HD), jnp.bfloat16),
        spec((ROWS, MAX_LEN // PAGE), jnp.int32),
        spec((ROWS,), jnp.int32),
        spec((ROWS,), jnp.int32),
    )


def test_computed_attention_compiles(spec):
    # one chunk of a 4096-token prefill: f32 q/k/v, as the dispatched
    # chunk-loop body hands them over
    _compile(
        lambda q, k, v, off: computed_attention(
            q, k, v, q_offset=off, scale=HD ** -0.5, causal=True,
            interpret=False,
        ),
        spec((H, 512, HD), jnp.float32),
        spec((H, 4096, HD), jnp.float32),
        spec((H, 4096, HD), jnp.float32),
        spec((), jnp.int32),
    )


def test_masked_attention_compiles(spec):
    _compile(
        lambda q, k, v, m: masked_attention(
            q, k, v, m, scale=HD ** -0.5, interpret=False
        ),
        spec((H, 256, HD), jnp.float32),
        spec((H, 4096, HD), jnp.float32),
        spec((H, 4096, HD), jnp.float32),
        spec((1, 256, 4096), jnp.bool_),
    )


def test_swiglu_ffn_default_tiles_compile(spec):
    _compile(
        lambda x, wg, wu, wd: chunked_ffn(x, wg, wu, wd, interpret=False),
        spec((1024, D), jnp.bfloat16),
        spec((D, DFF), jnp.bfloat16),
        spec((D, DFF), jnp.bfloat16),
        spec((DFF, D), jnp.bfloat16),
    )
