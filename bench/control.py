#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...
        [--fault <one of bench/faults.py FAULTS>]

For each seed, in one process: a window of the cell's own load through the
timed path (with ``--fault``, the program broken as ``bench/faults.py``
says), then every reading of the reference's comparison of what it
produced, and of what the float8 reference gives in the program's place
(``control_<name>``).  One JSON line per seed.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_logs"))

from bench import cells, device, faults  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=faults.FAULTS, default=None)
    args = ap.parse_args()

    cell = cells.load_cell(args.workload, ROOT)
    device.require_chips(cell.workload["chips"])
    device.enable_cache(ROOT)
    if args.fault:
        faults.plant(args.fault)
    drv = cells.runner(cell)(cell, args.seeds[0])
    drv.setup(trace=False)
    for i, seed in enumerate(args.seeds):
        if i:
            drv.reseed(seed)
        w = drv.window(args.seconds, None)
        samples = drv.sample(w)
        drv.free()
        got = drv.check(samples, control=True)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "attempted": drv.attempted(w)[0], **got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
