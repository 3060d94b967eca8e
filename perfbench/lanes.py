"""Lane-count arithmetic: how many lanes of a configuration's chunk program
fit one TPU v5e.

    JAX_PLATFORMS=cpu python perfbench/lanes.py perfbench/configs/<name>.json \
        --lanes 96 104 112

Compiles the scheduler's chunk program (``make_chunk_fn``) at the
configuration's published widths, ``max_len`` and ``chunk`` for a *described*
v5e (no chip needed) and prints, per lane count, the compiler's
``memory_analysis()``: arguments, outputs and temporaries.  The decode state
is not donated, so it is in both the arguments and the outputs: the sum
``args + outputs + temp`` is what one tick needs on the device.  A
configuration takes the largest multiple of 8 whose sum stays under the
chip's ``bytes_limit`` less the margin its file states.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fit(cfg_path: str, lanes_list) -> list:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench import spec
    from repro.kernels.dms_decode import ops as dkops
    from repro.models import transformer as tfm
    from repro.serving.scheduler import make_chunk_fn

    jax.config.update("jax_enable_compilation_cache", False)
    dkops._default_interpret = lambda: False    # the compiled kernel
    cfg = spec.load_json(cfg_path)
    model = spec.model_module(cfg)
    arch, policy = model.program_arch(cfg), model.program_policy(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    params = on(model.param_shapes(cfg))
    out = []
    for lanes in lanes_list:
        state = on(jax.eval_shape(lambda: tfm.init_decode_state(
            arch, lanes, cfg["max_len"], policy)))
        s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
        li, lb = s((lanes,), jnp.int32), s((lanes,), jnp.bool_)
        c = cfg["chunk"]
        compiled = jax.jit(make_chunk_fn(arch, use_kernel=True)).lower(
            params, state, s((lanes, c), jnp.int32), s((lanes, c), jnp.bool_),
            li, li, lb, lb, li, li, s((2,), jnp.uint32), lb).compile()
        m = compiled.memory_analysis()
        state_b = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
        row = {"lanes": lanes, "args": m.argument_size_in_bytes,
               "outputs": m.output_size_in_bytes,
               "temp": m.temp_size_in_bytes, "state": state_b,
               "total": m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--lanes", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    fit(args.config, args.lanes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
