"""Launch surface: compile-cache placement, the platform-resolved kernel
default, the serve entry point's return value, and the device peaks table."""
import jax
import numpy as np
import pytest

from repro.core.config import KVPolicyConfig
from repro.kernels.dms_decode import dms_decode as dk
from repro.launch import compile_cache, roofline, serve
from repro.serving.engine import Engine


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch,
                                                  cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.setup_compile_cache()
    assert got == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.parent.joinpath("src").is_dir()
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("asked,want", [(None, False), (True, True),
                                        (False, False)])
def test_engine_kernel_default_follows_platform(tiny_arch, tiny_params, asked,
                                                want):
    # this suite runs on CPU: the default is the reference attention
    eng = Engine(tiny_arch, tiny_params, KVPolicyConfig(kind="dms", cr=2.0),
                 use_kernel=asked)
    assert eng.use_kernel is want


@pytest.mark.parametrize("flag,want", [([], False), (["--use-kernel"], True),
                                       (["--no-use-kernel"], False)])
def test_serve_main_returns_engine_scheduler_results(monkeypatch, capsys,
                                                     flag, want):
    monkeypatch.setattr(serve, "setup_compile_cache", lambda: None)
    engine, sched, results = serve.main(
        ["--policy", "dms", "--requests", "2", "--num-lanes", "2",
         "--prompt-len", "8", "--max-new", "3"] + flag)
    assert engine.use_kernel is want
    assert sorted(r.uid for r in results) == [0, 1]
    assert all(r.status == "ok" for r in results)
    assert all(int(r.lengths[0]) == 3 for r in results)
    assert sched.ticks > 0
    assert all(0 < r.ttft_s <= r.finished_s - r.submitted_s for r in results)
    out = capsys.readouterr().out
    assert f'"kernel": {str(want).lower()}' in out
    assert '"ttft_s": ' in out and '"tpot_s": ' in out


def test_device_peaks_known_kind_and_unknown_refused():
    v5e = roofline.device_peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.device_peaks("cpu")


@pytest.mark.parametrize("bh,nb,need", [
    (128, 2048, 128 * 2048 * 4 + 128 * 4),    # 64 lanes x 32k at block_p 16
    (2200, 100, 2200 * 128 * 4 + 2200 * 4),   # columns pad to 128
    (2026, 128, 2032 * 128 * 4 + 2032 * 4),   # rows pad to 8
])
def test_smem_table_bytes_follow_compiler_padding(bh, nb, need):
    assert dk.smem_table_bytes(bh, nb) == need


def test_check_smem_refuses_only_over_the_bound():
    dk.check_smem(96, 2048)                   # 48 lanes x 32k: fits
    with pytest.raises(ValueError, match="needs 1049088 bytes of SMEM"):
        dk.check_smem(128, 2048)
    assert np.isclose(dk.SMEM_BYTES, 2 ** 20)
