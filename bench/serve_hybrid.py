#!/usr/bin/env python3
"""Serving a compressed granite-4.0-h (``granitemoehybrid``) checkpoint:
``bench/serve.py``'s serving cell with this architecture's float32
reference (``bench/hybrid_reference.py``) in place of granite-moe's.

    python3 bench/serve_hybrid.py --config <name> --traffic <mix> \\
        --seconds <s> --seeds 1 2 ... [--control]

runs, for each seed in one process, the mix's window through ``Engine``
and ``Scheduler`` (``bench/serve.py``'s set-up, window and sample), then
compares what the timed path served against the reference, and prints one
JSON line per seed: the readings, the decode step time, the device's
memory peak.  It names no cell of ``BENCHMARK.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_logs"))

from bench import cells, device, hybrid_reference, weights  # noqa: E402
from bench.device import log  # noqa: E402
from bench.serve import ServeCell  # noqa: E402


class HybridServeCell(ServeCell):
    def check(self, samples, control: bool = False) -> dict:
        """The served tiles against the seeded dense weights, then the
        logits over the sampled requests against the hybrid reference;
        with ``control``, the float8 reference's too."""
        dense = dict(weights.paths(weights.dense(self.cfg, self.seed)))
        got = self.check_compression(dense, control)
        got |= hybrid_reference.compare(
            self.cell.config, self._reference_weights(dense), samples,
            self.engine_spec["max_len"], first=self.cfg.first_expert_held,
            control=control)
        for k, v in got.items():
            log(f"[check] {k} {v}")
        return got

    def _reference_weights(self, dense: dict) -> dict:
        """The reference's weights: every leaf the mix leaves dense from
        ``dense``, each compressed one as served (``m_packed``, ``C``)."""
        def leaf(path):
            if path in self.compressed:
                _, mp, C, _ = self.compressed[path]
                return {"m_packed": mp[0], "C": C[0]}
            return dense[path][0]

        return reference_weights(self.cell.config["layer_types"], leaf, dense)


def reference_weights(layer_types, leaf, flat: dict) -> dict:
    """``bench/hybrid_reference.py``'s weights from the program's tree:
    ``leaf(path)`` gives group 0's slice of the leaf at ``path`` (layer
    ``i`` of the period is block ``i`` of group 0), ``flat`` the
    embedding and final norm by path."""
    layers = []
    for i, kind in enumerate(layer_types):
        g = f"groups/{i}/"
        lw = {"norm1": leaf(g + "norm1/scale"), "norm2": leaf(g + "norm2/scale"),
              "router": leaf(g + "moe/router"),
              **{n: leaf(g + f"moe/{n}") for n in ("gate", "up", "down")},
              **{f"shared_{n}": leaf(g + f"moe/shared/{n}/w")
                 for n in ("gate", "up", "down")}}
        if kind == "mamba":
            lw |= {n: leaf(g + f"ssm/{n}/w") for n in ("in_proj", "out_proj")}
            lw |= {n: leaf(g + f"ssm/{n}")
                   for n in ("conv_w", "conv_b", "A_log", "D", "dt_bias")}
            lw["norm"] = leaf(g + "ssm/norm/scale")
        else:
            lw |= {n: leaf(g + f"attn/{n}/w") for n in ("wq", "wk", "wv", "wo")}
        layers.append(lw)
    return {"embed": flat["embed/table"], "final_norm": flat["final_norm/scale"],
            "layers": layers}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    devices = device.require_chips(1)
    device.enable_cache(ROOT)
    bench_dir = ROOT / "bench"
    cell = cells.Cell(
        name=f"{args.config}.{args.traffic}", workload={"chips": 1},
        config=cells.load_json(bench_dir / "configs" / f"{args.config}.json"),
        traffic=cells.load_json(bench_dir / "traffic" / f"{args.traffic}.json"),
        limits={}, end_to_end=[], per_layer=[], root=ROOT)
    drv = HybridServeCell(cell, args.seeds[0])
    drv.setup(trace=False)
    for i, seed in enumerate(args.seeds):
        if i:
            drv.reseed(seed)
        w = drv.window(args.seconds, None)
        span = w["t_end"] - w["t0"]
        out = {"seed": seed, "attempted": drv.attempted(w),
               "output_tokens_per_s": w["emitted"] / span,
               "step_s": span / max(w["decode_steps"], 1),
               "occupancy": w["occupancy"],
               "prompts": sorted({int(n) for n in drv.schedule.prompt})}
        samples = drv.sample(w)
        out["sampled_prompts"] = [len(s[0]) for s in samples]
        drv.free()
        out["memory_peak_bytes"] = device.memory_peak(devices)
        out |= drv.check(samples, control=args.control)
        out["device"] = device.describe(devices)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
