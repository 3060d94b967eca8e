"""Compile the decode path for a described TPU v5e, at qwen-r1-1.5b widths.

The TPU compiler ships with jax and compiles for a chip that is described,
not attached.  It refuses what the chip would refuse — block layouts off the
(8, 128) tiling, scalar memory overflow — which interpret-mode tests cannot
see.  The kernel's interpret decision follows ``jax.default_backend()``
(the CPU here), so each test steers it to the compiled kernel.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.config import KVPolicyConfig
from repro.kernels.dms_decode import ops as dkops
from repro.models import transformer as tfm
from repro.serving.scheduler import make_chunk_fn

ARCH = get_arch("qwen-r1-1.5b")
LANES, CHUNK, MAX_LEN = 8, 8, 576


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernel(monkeypatch):
    monkeypatch.setattr(dkops, "_default_interpret", lambda: False)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _kernel_args(one_chip, lanes, arena, block_p):
    a = ARCH.attn
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    nb = arena // block_p
    return dict(
        q=s((lanes, 1, a.num_heads, a.head_dim), jnp.bfloat16),
        kv=s((lanes, a.num_kv_heads, arena, a.head_dim), jnp.bfloat16),
        valid=s((lanes, a.num_kv_heads, arena), jnp.bool_),
        tbl=s((lanes, a.num_kv_heads, nb), jnp.int32),
        n=s((lanes, a.num_kv_heads), jnp.int32),
        pool=s((lanes * a.num_kv_heads * nb, block_p, a.head_dim),
               jnp.bfloat16))


def _compile_kernel(args, mode, block_p):
    def fixed(q, k, valid, tbl, n):
        return dkops.dms_decode_attention(
            q, k, k, valid, block_tbl=tbl, block_n=n, block_p=block_p,
            need_weights=mode == "weights_out")

    def paged(q, k, valid, tbl, n, pool):
        return dkops.dms_decode_attention(
            q, k, k, valid, block_tbl=tbl, block_n=n, block_p=block_p,
            pool_k=pool, pool_v=pool, phys=tbl)

    base = (args["q"], args["kv"], args["valid"], args["tbl"], args["n"])
    if mode == "paged":
        return jax.jit(paged).lower(*base, args["pool"]).compile()
    return jax.jit(fixed).lower(*base).compile()


MODES = ["fixed", "paged", "weights_out"]
# (mode, lanes, arena slots a lane): a small arena, then the two benchmark
# cells' shapes — dms-cr8 (80 lanes x 848 slots) and vanilla (16 x 4608)
KERNEL_CASES = [pytest.param(m, LANES, 336, id=m) for m in MODES] + [
    pytest.param(m, lanes, arena, id=f"{m}-{cell}") for cell, lanes, arena in
    (("dms-cr8", 80, 848), ("vanilla", 16, 4608)) for m in MODES]


@pytest.mark.parametrize("mode,lanes,arena", KERNEL_CASES)
def test_kernel_compiles_for_v5e(one_chip, compiled_kernel, mode, lanes,
                                 arena):
    args = _kernel_args(one_chip, lanes, arena, 16)
    text = _compile_kernel(args, mode, 16).as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # the benchmark finds the kernel's time in the trace by this name
    assert "dms_decode" in text


def test_smem_overflow_is_refused_with_table_size(one_chip, compiled_kernel):
    # 64 lanes x 32k-slot arenas: a 128 x 2048 int32 table fills the 1 MiB
    # of scalar memory at block_p 16, and fits at block_p 128
    with pytest.raises(ValueError, match=r"128 lane-heads x 2048 blocks.*"
                                         r"needs 1049088 bytes of SMEM"):
        _compile_kernel(_kernel_args(one_chip, 64, 32768, 16), "fixed", 16)
    text = _compile_kernel(_kernel_args(one_chip, 64, 32768, 128), "fixed",
                           128).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind,paged", [("dms", False), ("dms", True),
                                        ("tova", False)])
def test_chunk_program_compiles_for_v5e(one_chip, compiled_kernel, kind,
                                        paged):
    """The scheduler's whole chunk step at full width holds the kernel."""
    policy = KVPolicyConfig(kind=kind, cr=8.0, window=ARCH.dms.window,
                            paged=paged)
    params = _on(one_chip, jax.eval_shape(
        lambda k: tfm.init_model(k, ARCH), jax.random.PRNGKey(0)))
    state = _on(one_chip, jax.eval_shape(
        lambda: tfm.init_decode_state(ARCH, LANES, MAX_LEN, policy)))
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lane_i, lane_b = s((LANES,), jnp.int32), s((LANES,), jnp.bool_)
    compiled = jax.jit(make_chunk_fn(ARCH, use_kernel=True)).lower(
        params, state, s((LANES, CHUNK), jnp.int32),
        s((LANES, CHUNK), jnp.bool_), lane_i, lane_i, lane_b, lane_b,
        lane_i, lane_i, s((2,), jnp.uint32), lane_b).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "dms_decode" in text
    # fits one 16 GB chip: f32 parameters plus the step's temporaries
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
