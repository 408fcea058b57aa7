"""``chip_smoke.py`` rehearsed on the CPU at a reduced size.

The script's phases run here on a 2-layer minitron-4b with its Pallas
kernels in interpret mode, so a broken path, argument or check fails in the
test suite before it costs a chip run.  Phase a must refuse the CPU.
"""
import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.core.config import ChunkConfig

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_a_tpu(smoke, capsys):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_prefill_phase_reduced(smoke, monkeypatch, capsys):
    # the chip's choice: chunk-loop bodies go to the Pallas kernels
    monkeypatch.setattr(ChunkConfig, "resolve_kernel_dispatch",
                        lambda self: self.kernel_dispatch != "off")
    cfg = get_config(smoke.ARCH).reduced().with_(dtype="float32")
    smoke.phase_prefill(
        cfg, seed=0, cmp_len=128, budget_ratio=smoke.PREFILL_BUDGET_RATIO,
        long_len=512, long_budget=2 * 2**20, tol=smoke.LOGIT_TOL,
    )
    out = capsys.readouterr().out
    assert "[b] chunked S=128:" in out and "[b] chunked S=512:" in out


def test_serve_phase_reduced(smoke, capsys):
    # the reduced config's sliding window is 32: contexts stay within it
    argv = [
        "--arch", smoke.ARCH, "--local", "--paged", "--stagger",
        "--requests", "4", "--prompt-len", "8", "--max-len", "32",
        "--max-new", "4",
    ]
    engine = smoke.phase_serve(argv, tol=smoke.LOGIT_TOL)
    assert len(engine.finished) == 4
    out = capsys.readouterr().out
    assert out.count("[c] req ") == 4


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(
            _PATH.parent / ".jax_cache")
        # set in the environment: JAX reads it, and no path is set in code
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_peak_table_refuses_an_unknown_device():
    from repro.launch.mesh import device_peaks

    assert device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")
