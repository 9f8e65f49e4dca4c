#!/usr/bin/env python3
"""The knee of a serving cell: the highest arrival rate it sustains.

    python3 bench/sweep.py --workload <serve cell> --seed <n> --seconds <s>
        --rates 0.2 0.4 0.8 ...

In one process: the cell's set-up once, then for each rate, from an empty
scheduler, a window of the cell's mix with no backlog and Poisson arrivals
at that rate.  One JSON line per rate: arrivals, completions, requests
waiting at the close, tokens per second, mean occupied slots and time to
first token (median and 95th percentile, from when each request was due).
A rate is sustained while the queue does not grow: nearly every arrival is
admitted and few wait at the close.  The cell's traffic file records the
knee found so; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_logs"))

from bench import cells, device  # noqa: E402
from bench.serve import Schedule  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    cell = cells.load_cell(args.workload, ROOT)
    device.require_chips(cell.workload["chips"])
    device.enable_cache(ROOT)
    drv = cells.runner(cell)(cell, args.seed)
    drv.setup(trace=False)
    sched = drv.sched
    for rate in args.rates:
        for s, r in enumerate(sched.slot_req):
            if r is not None:
                sched.pool.release(s)
                sched.slot_req[s] = None
        sched.pending.clear()
        mix = dict(cell.traffic, arrivals=dict(cell.traffic["arrivals"],
                                               rate_per_s=rate, backlog=0))
        drv.schedule = Schedule(mix, args.seed, drv.cfg.vocab_size)
        drv.requests = []
        w = drv.window(args.seconds, None)
        ttft = [r.t_first_token - due for r, due in drv.requests if r.tokens]
        print(json.dumps({
            "rate_per_s": rate, "arrivals": w["arrived"],
            "admitted": sum(r.state != "pending" for r, _ in drv.requests),
            "completed": sum(r.state == "done" for r, _ in drv.requests),
            "waiting_at_close": len(sched.pending),
            "output_tokens_per_s": w["emitted"] / (w["t_end"] - w["t0"]),
            "occupancy": w["occupancy"], "decode_steps": w["decode_steps"],
            "ttft_p50_s": float(np.median(ttft)) if ttft else None,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else None,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
