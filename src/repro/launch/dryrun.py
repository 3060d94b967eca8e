import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

AOT-lowers and compiles every (architecture × input shape) cell on the
production meshes — 16×16 = 256 chips single-pod and 2×16×16 = 512 chips
multi-pod — and extracts memory / cost / collective analyses for the
roofline study.  No device allocation: all inputs are ShapeDtypeStructs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-mini-3.8b \
        --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all          # 40 cells x 2 meshes
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, get_arch
from repro.core.config import KVPolicyConfig, SHAPES
from repro.launch import roofline, steps
from repro.launch.mesh import make_production_mesh
from repro.optim import adamw
from repro.parallel import sharding

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def cell_is_skipped(arch, shape) -> str | None:
    """Shape-grid skip rules (see DESIGN.md §Arch-applicability)."""
    if shape.name == "long_500k" and not arch.sub_quadratic:
        return "long_500k skipped: pure full-attention arch (sub-quadratic required)"
    return None


def lower_cell(arch_name: str, shape_name: str, mesh, *, policy_kind: str = "vanilla",
               cr: float = 1.0, dms_train: bool = False, use_kernel: bool = False,
               remat: bool = True, scan_layers: bool = False, attn_impl="chunked",
               accum_steps: int = 1, tp: int = None):
    """Build, lower and compile one cell.  Returns (compiled, lowered, meta)."""
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    skip = cell_is_skipped(arch, shape)
    if skip and policy_kind == "vanilla":
        raise SkipCell(skip)

    pspec = steps.params_spec(arch, dtype=arch.dtype)
    p_sh = sharding.param_shardings(pspec, arch, mesh, tp=tp)
    dp_only = tp == 1

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        ospec = steps.opt_state_spec(pspec)
        o_sh = sharding.opt_shardings(pspec, arch, mesh, tp=tp)
        step_fn = steps.make_train_step(arch, opt_cfg, dms_train=dms_train,
                                        remat=remat, use_kernel=use_kernel,
                                        scan_layers=scan_layers,
                                        attn_impl=attn_impl,
                                        accum_steps=accum_steps,
                                        grad_shardings=o_sh.mu if accum_steps > 1
                                        else None)
        batch = steps.train_input_specs(arch, shape, accum_steps=accum_steps)
        b_sh = sharding.batch_shardings(mesh, batch, microbatched=accum_steps > 1,
                                        batch_over_model=dp_only)
        step_spec = jax.ShapeDtypeStruct((), jnp.int32)
        jitted = jax.jit(step_fn,
                         in_shardings=(p_sh, o_sh, b_sh, None),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(pspec, ospec, batch, step_spec)
    elif shape.kind == "prefill":
        step_fn = steps.make_prefill_step(arch, dms=policy_kind == "dms",
                                          use_kernel=use_kernel,
                                          scan_layers=scan_layers,
                                          attn_impl=attn_impl)
        batch = steps.prefill_input_specs(arch, shape)
        b_sh = sharding.batch_shardings(mesh, batch, batch_over_model=dp_only)
        out_shape = jax.eval_shape(step_fn, pspec, batch)
        o_sh = sharding.prefill_out_shardings(out_shape, mesh, arch)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, b_sh), out_shardings=o_sh)
        lowered = jitted.lower(pspec, batch)
    else:  # decode
        policy = KVPolicyConfig(kind=policy_kind, cr=cr)
        step_fn = steps.make_serve_step(arch, use_kernel=use_kernel,
                                        scan_layers=scan_layers)
        batch = steps.decode_input_specs(arch, shape, policy)
        cache_spec = batch.pop("cache")
        c_sh = sharding.cache_shardings(cache_spec, mesh, shape.global_batch, arch)
        b_sh = sharding.batch_shardings(mesh, batch)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, c_sh, b_sh),
                         out_shardings=(None, c_sh, None),
                         donate_argnums=(1,))
        lowered = jitted.lower(pspec, cache_spec, batch)

    compiled = lowered.compile()
    return compiled, lowered, {"arch": arch, "shape": shape}


class SkipCell(Exception):
    pass


def run_cell(arch_name, shape_name, *, multi_pod=False, policy_kind="vanilla",
             cr=1.0, dms_train=False, use_kernel=False, remat=True,
             attn_impl="chunked", accum_steps=None, save=True, verbose=True,
             variant="", memory_pass=True, flops_pass=True, tp=None):
    """Two compiles per cell:

    * **flops pass** — layers unrolled, no grad accumulation: XLA's cost model
      sees every layer, so FLOPs / bytes / collective counts are exact.
    * **memory pass** — ``lax.scan`` over layers + microbatch accumulation:
      while-loop buffer reuse makes ``memory_analysis()`` reflect the real
      per-device working set (the CPU backend's concurrent scheduler inflates
      unrolled-graph temp sizes by scheduling independent layer recomputes in
      parallel; scan restores the sequential schedule a TPU would use).
    Roofline terms come from the flops pass; the memory-fit proof from the
    memory pass.  Both must compile — that is the dry-run gate.
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_desc = "x".join(map(str, mesh.devices.shape))
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    if accum_steps is None:
        if shape.kind == "train" and shape.global_batch >= 8:
            # fine-grained MoE dispatch flats scale with microbatch tokens
            moe = arch.mlp is not None and arch.mlp.moe is not None
            accum_steps = 32 if moe else 8
        else:
            accum_steps = 1

    rec = {}
    report = None
    if flops_pass:
        t0 = time.time()
        with mesh:
            compiled, lowered, meta = lower_cell(
                arch_name, shape_name, mesh, policy_kind=policy_kind, cr=cr,
                dms_train=dms_train, use_kernel=use_kernel, remat=remat,
                scan_layers=False, attn_impl=attn_impl, accum_steps=1, tp=tp)
        compile_s = time.time() - t0
        cost = compiled.cost_analysis()
        if verbose:
            print(f"[{arch_name} × {shape_name} × {mesh_desc}] flops-pass "
                  f"compiled in {compile_s:.1f}s")
            print(f"  cost_analysis: flops={cost.get('flops', 0):.3e} "
                  f"bytes={cost.get('bytes accessed', 0):.3e}")
        from repro.launch.mesh import dp_size, tp_size
        modeled = roofline.modeled_bytes_per_device(
            arch, shape, shape.kind, num_devices=mesh.size,
            tp=(tp or tp_size(mesh)),
            dp=dp_size(mesh) * (tp_size(mesh) if tp == 1 else 1),
            policy=policy_kind, cr=cr,
            accum=accum_steps, remat=remat)
        report = roofline.analyze(
            compiled, arch=arch_name, shape=shape_name, mesh_desc=mesh_desc,
            num_devices=mesh.size, modeled=modeled,
            device_kind=roofline.DRYRUN_DEVICE_KIND,
            model_flops=roofline.model_flops_for(arch, shape, shape.kind))
        rec = report.as_dict()
        rec["compile_seconds"] = compile_s

    if memory_pass:
        t0 = time.time()
        with mesh:
            compiled_m, _, _ = lower_cell(
                arch_name, shape_name, mesh, policy_kind=policy_kind, cr=cr,
                dms_train=dms_train, use_kernel=use_kernel, remat=remat,
                scan_layers=True, attn_impl="chunked_scan",
                accum_steps=accum_steps, tp=tp)
        mem = compiled_m.memory_analysis()
        fit = {
            "argument_bytes": float(mem.argument_size_in_bytes),
            "output_bytes": float(mem.output_size_in_bytes),
            "alias_bytes": float(mem.alias_size_in_bytes),
            "temp_bytes": float(mem.temp_size_in_bytes),
            "peak_bytes": float(mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes),
            "accum_steps": accum_steps,
            "compile_seconds": time.time() - t0,
            "fits_hbm_16g": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
                            < 16e9,
        }
        rec["memory_fit"] = fit
        if verbose:
            print(f"  memory-pass (scan, accum={accum_steps}): "
                  f"peak={fit['peak_bytes']/1e9:.2f}GB/device "
                  f"fits_16GB={fit['fits_hbm_16g']} "
                  f"({fit['compile_seconds']:.1f}s)")

    rec.update(policy=policy_kind, cr=cr, variant=variant or policy_kind,
               multi_pod=multi_pod)
    if save:
        ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
        tag = f"{arch_name}__{shape_name}__{mesh_desc}"
        if variant:
            tag += f"__{variant}"
        (ARTIFACT_DIR / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    if verbose and report is not None:
        print(f"  roofline: compute={report.compute_s:.4f}s "
              f"memory={report.memory_model_s:.4f}s (hlo-ub {report.memory_s:.4f}s) "
              f"collective={report.collective_s:.4f}s -> {report.bottleneck}-bound; "
              f"useful-FLOPs={report.useful_flops_ratio:.2f} util={report.hw_util:.3f}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all 40 cells")
    from repro.core.policy import available_policies
    ap.add_argument("--policy", default="vanilla",
                    choices=list(available_policies()))
    ap.add_argument("--cr", type=float, default=1.0)
    ap.add_argument("--dms-train", action="store_true")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--variant", default="")
    ap.add_argument("--memory-only", action="store_true")
    ap.add_argument("--flops-only", action="store_true")
    ap.add_argument("--accum", type=int, default=0,
                    help="microbatch accumulation steps for the memory pass")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    results, failures, skips = [], [], []
    for arch_name in archs:
        for shape_name in shapes:
            for mp in meshes:
                try:
                    rec = run_cell(arch_name, shape_name, multi_pod=mp,
                                   policy_kind=args.policy, cr=args.cr,
                                   dms_train=args.dms_train,
                                   use_kernel=args.use_kernel,
                                   remat=not args.no_remat,
                                   flops_pass=not args.memory_only,
                                   memory_pass=not args.flops_only,
                                   accum_steps=args.accum or None,
                                   variant=args.variant)
                    results.append(rec)
                except SkipCell as e:
                    print(f"[{arch_name} × {shape_name} × mp={mp}] SKIP: {e}")
                    skips.append((arch_name, shape_name, mp, str(e)))
                except Exception as e:
                    print(f"[{arch_name} × {shape_name} × mp={mp}] FAIL: {e}")
                    traceback.print_exc()
                    failures.append((arch_name, shape_name, mp, repr(e)))
    print(f"\n=== dry-run summary: {len(results)} ok, {len(skips)} skipped, "
          f"{len(failures)} failed ===")
    for f in failures:
        print("  FAIL:", f[:3])
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
