import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# must precede any jax import (same rule as launch/dryrun.py)

"""Roofline analysis (EXPERIMENTS.md §Roofline): compositional per-cell
terms via launch/costing.py, on the single-pod production mesh.

    PYTHONPATH=src python -m benchmarks.roofline --all
    PYTHONPATH=src python -m benchmarks.roofline --arch qwen3-32b --shape decode_32k
    ... --causal-skip   (costs the causal-block-skip attention variant)

Writes experiments/roofline/<arch>__<shape>[__skip].json and prints the
summary table used by EXPERIMENTS.md.
"""

import argparse
import json
import traceback

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHITECTURES, SHAPES, get_config, shape_cells
from repro.launch.costing import cost_cell


def model_flops(arch: str, shape_name: str) -> float:
    """MODEL_FLOPS = 6*N_active*tokens (train) or 2*N_active*tokens (serve),
    GLOBAL (divide by 256 chips to compare with per-device HLO flops)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    mult = 6 if shape.kind == "train" else 2
    return mult * n * shape.tokens_per_step


def run_cell(arch, shape, causal_skip, out_dir):
    rec = cost_cell(arch, shape, multi_pod=False, causal_skip=causal_skip)
    mf = model_flops(arch, shape) / 256  # per device
    rec["model_flops_per_dev"] = mf
    rec["useful_fraction"] = mf / max(rec["flops"], 1.0)
    # analytic (napkin) memory model: VMEM-resident inner tiles, see
    # repro.roofline.analytic_memory_bytes — the HLO-parsed bytes are an
    # upper bound that includes CPU-backend-unfused score traffic.
    from repro import roofline as rl
    from repro.launch.presets import parallel_preset
    cfg = get_config(arch)
    pcfg = parallel_preset(cfg, SHAPES[shape], multi_pod=False)
    amem = rl.analytic_memory_bytes(cfg, SHAPES[shape], pcfg)
    rec["memory_s_analytic"] = amem / rl.HBM_BW
    rec["dominant_analytic"] = max(
        ("compute", rec["compute_s"]),
        ("memory", rec["memory_s_analytic"]),
        ("collective", rec["collective_s"]),
        key=lambda kv: kv[1],
    )[0]
    os.makedirs(out_dir, exist_ok=True)
    tag = "__skip" if causal_skip else ""
    with open(os.path.join(out_dir, f"{arch}__{shape}{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(
        f"{arch:26s} {shape:12s} "
        f"comp {rec['compute_s']*1e3:9.2f}ms | mem {rec['memory_s']*1e3:9.2f}ms "
        f"(~{rec['memory_s_analytic']*1e3:8.2f}ms) | "
        f"coll {rec['collective_s']*1e3:9.2f}ms | {rec['dominant_analytic']:10s} | "
        f"useful {rec['useful_fraction']*100:5.1f}%",
        flush=True,
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--out", default="experiments/roofline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cells = (
        [(a, s) for a in ARCHITECTURES for s in shape_cells(a)]
        if args.all else [(args.arch, args.shape)]
    )
    failures = []
    for arch, shape in cells:
        tag = "__skip" if args.causal_skip else ""
        path = os.path.join(args.out, f"{arch}__{shape}{tag}.json")
        if args.skip_existing and os.path.exists(path):
            continue
        try:
            run_cell(arch, shape, args.causal_skip, args.out)
        except Exception as e:  # noqa: BLE001
            failures.append((arch, shape, repr(e)))
            traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
