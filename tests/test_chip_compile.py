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
import re

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


_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(")
_CALLS = re.compile(r"calls=%([\w.-]+)")
_COMPUTATION = re.compile(r"^(ENTRY )?%([\w.-]+) .*\{$")


def _whole_arena_ops(text, state):
    """Ops inside the compiled chunk program's loops (the per-step work)
    whose output is a whole fixed K/V arena, one layer's or the stack's:
    copies, selects, slices, stacking.  The in-place row write — a scatter,
    or a fusion whose root is one — is the one allowed; the entry's copy of
    the undonated state runs once a chunk, outside the loops."""
    dims = set()
    for pc in jax.tree_util.tree_leaves(
            state, is_leaf=lambda x: hasattr(x, "policy")):
        k = pc.cache.k
        if k.shape[-1]:
            dims |= {",".join(map(str, k.shape)), ",".join(map(str, k.shape[1:]))}
    ops, roots, comp = [], {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = None if head.group(1) else head.group(2)
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        if line.lstrip().startswith("ROOT"):
            roots[comp] = m.group(2)
        if m.group(1) in dims:
            calls = _CALLS.search(line)
            ops.append((m.group(2), calls and calls.group(1), line.strip()))
    return [txt[:160] for op, calls, txt in ops
            if op not in ("parameter", "get-tuple-element", "bitcast",
                          "scatter")
            and not (op == "fusion" and roots.get(calls) == "scatter")]


@pytest.mark.parametrize("kind,paged", [("dms", False), ("dms", True),
                                        ("tova", False), ("vanilla", False)])
def test_chunk_program_compiles_for_v5e(one_chip, compiled_kernel, kind,
                                        paged):
    """The scheduler's whole chunk step at full width holds the kernel; a
    fixed arena is touched in the step only by in-place row writes."""
    policy = KVPolicyConfig(kind=kind, cr=8.0, window=ARCH.dms.window,
                            paged=paged, block_p=16)
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
    assert not _whole_arena_ops(text, state)


MELLUM = get_arch("mellum2-12b-a2.5b")


@pytest.mark.parametrize("tile_f", [896, 128])
def test_moe_experts_compiles_for_v5e(one_chip, tile_f):
    """The expert kernel at Mellum2 widths and the benchmark cell's 104
    lanes: resident rows, scalar-prefetched pair list, streamed weights."""
    from repro.kernels.moe_experts import ops as moe_ops
    m, d = MELLUM.mlp, MELLUM.d_model
    eh, f = m.moe.num_held, m.d_ff
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def layer(x, wg, wu, wd, top_e, top_p):
        groups = moe_ops.group_pairs(top_e, top_p, m.moe.held_experts,
                                     m.moe.num_experts)
        return moe_ops.moe_experts(x, wg, wu, wd, *groups, tile_f=tile_f,
                                   interpret=False)

    text = jax.jit(layer).lower(
        s((104, d), jnp.bfloat16), s((eh, d, f), jnp.bfloat16),
        s((eh, d, f), jnp.bfloat16), s((eh, f, d), jnp.bfloat16),
        s((104, m.moe.top_k), jnp.int32),
        s((104, m.moe.top_k), jnp.float32)).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # the benchmark finds the kernel's time in the trace by this name
    assert "moe_experts" in text


def test_mellum_chunk_program_compiles_for_v5e(one_chip, compiled_kernel):
    """Mellum2's chunk step at full width and the benchmark cell's 104
    lanes, bfloat16 weights: four attention layers and four expert layers a
    period, each through its kernel.  (At a toy lane count the compiler
    parks the small arenas in VMEM and pages them in and out of the loop,
    which says nothing of the cell.)"""
    lanes = 104
    policy = KVPolicyConfig(kind="dms", cr=8.0, window=MELLUM.dms.window,
                            block_p=16)
    params = _on(one_chip, jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: tfm.init_model(k, MELLUM),
                       jax.random.PRNGKey(0))))
    state = _on(one_chip, jax.eval_shape(
        lambda: tfm.init_decode_state(MELLUM, lanes, 1152, policy)))
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lane_i, lane_b = s((lanes,), jnp.int32), s((lanes,), jnp.bool_)
    text = jax.jit(make_chunk_fn(MELLUM, use_kernel=True)).lower(
        params, state, s((lanes, CHUNK), jnp.int32),
        s((lanes, CHUNK), jnp.bool_), lane_i, lane_i, lane_b, lane_b,
        lane_i, lane_i, s((2,), jnp.uint32), lane_b).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    assert "dms_decode" in text and "moe_experts" in text
    # the expert kernel reads each layer's matrices from the stack in place:
    # no per-layer copy of a held expert matrix is made before the call
    assert not re.search(r"= bf16\[8,(2304,896|896,2304)\]", text)
    # and both arena sizes take only in-place row writes in the step
    assert not _whole_arena_ops(text, state)
