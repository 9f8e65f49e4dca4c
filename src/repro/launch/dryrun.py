import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# The two lines above MUST run before any other import (jax locks the device
# count at first initialisation).  Everything below may import jax.

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input-shape) cell, on the single-pod (16 x 16) and
multi-pod (2 x 16 x 16) production meshes:

    lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(*specs)
    compiled = lowered.compile()
    print(compiled.memory_analysis())      # proves it fits 16 GB/chip
    print(compiled.cost_analysis())        # FLOPs/bytes for §Roofline

Results (memory stats, cost stats, collective-byte totals parsed from the
SPMD-partitioned HLO) are appended to experiments/dryrun/<cell>.json for
EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""

import argparse
import json
import time
import traceback

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHITECTURES, get_config, shape_cells
from repro.distributed.sharding import activation_rules
from repro.launch.cells import build_cell
from repro.launch.mesh import describe, make_production_mesh
from repro.roofline import collective_bytes, cost_summary, memory_summary

HBM_BYTES = 16 * 1024**3  # TPU v5e


def _predicted_artifact(arch: str):
    """Plan-predicted compression artifact for ``arch`` (no solver runs —
    the dry-run only needs manifest shapes to lower the compressed-serving
    program through the fused bitlinear kernel)."""
    from repro.compression import CompressionArtifact, CompressionPolicy, plan_compression
    from repro.training.loop import _axes_trees

    shapes, _ = _axes_trees(get_config(arch))
    policy = CompressionPolicy(
        method="alternating", tile_n=32, tile_d=128, rank_ratio=0.125,
        min_size=1 << 16,
    )
    return CompressionArtifact.from_plan(plan_compression(shapes, policy))


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             compress: bool = False) -> dict:
    from repro.kernels import ops

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    artifact = _predicted_artifact(arch) if compress else None
    # kernel hooks are process-global and bind at trace time: compressed
    # cells lower the fused-kernel serving program, and a prior compressed
    # cell must not change the baseline cells' lowered programs
    if compress:
        ops.enable_kernels()
    else:
        ops.disable_kernels()
    cell = build_cell(arch, shape_name, mesh, artifact=artifact)
    with jax.set_mesh(mesh), activation_rules(cell.pcfg, mesh):
        lowered = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        ).lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = memory_summary(compiled)
    cost = cost_summary(compiled)
    coll = collective_bytes(compiled.as_text())
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": describe(mesh),
        "kind": cell.shape.kind,
        "compressed": bool(compress),
        "pcfg": {
            "microbatches": cell.pcfg.microbatches,
            "optimizer": cell.pcfg.optimizer,
            "accum_dtype": cell.pcfg.accum_dtype,
        },
        "memory": mem,
        "cost": cost,
        "collectives": coll,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "fits_hbm": mem["per_device_total"] <= HBM_BYTES,
    }
    print(compiled.memory_analysis())
    print({k: v for k, v in cost.items() if k in ("flops", "bytes")})
    print(
        f"[{arch} x {shape_name} @ {describe(mesh)}] "
        f"per-device {mem['per_device_total']/2**30:.2f} GiB "
        f"({'FITS' if rec['fits_hbm'] else 'OVER'} 16 GiB) | "
        f"flops/dev {cost['flops']:.3e} | coll bytes/dev {coll['total']:.3e} | "
        f"lower {t_lower:.0f}s compile {t_compile:.0f}s"
    )
    os.makedirs(out_dir, exist_ok=True)
    tag = "multipod" if multi_pod else "pod"
    if compress:
        tag += "__compressed"
    with open(os.path.join(out_dir, f"{arch}__{shape_name}__{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="lower serving cells with a plan-predicted "
                         "compression artifact: manifest-templated params + "
                         "the fused bitlinear kernel (serving cells only)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.all:
        cells = [(a, s) for a in ARCHITECTURES for s in shape_cells(a)]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True]
    if args.multi_pod_only:
        meshes = [True]
    if args.single_pod_only:
        meshes = [False]

    if args.compress:
        from repro.configs import SHAPES

        cells = [(a, s) for a, s in cells if SHAPES[s].kind != "train"]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = "multipod" if mp else "pod"
            if args.compress:
                tag += "__compressed"
            path = os.path.join(args.out, f"{arch}__{shape}__{tag}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip existing] {arch} x {shape} @ {tag}")
                continue
            try:
                run_cell(arch, shape, mp, args.out, compress=args.compress)
            except Exception as e:  # noqa: BLE001 - report-and-continue CLI
                failures.append((arch, shape, tag, repr(e)))
                traceback.print_exc()
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
