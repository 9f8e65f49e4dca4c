#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell from ``BENCHMARK.json`` and its files, sets up (weights from
the seed, every program compiled or read from ``<checkout>/.jax_cache/``),
measures for ``--seconds``, checks what the timed path produced against
the float32 reference, and prints one JSON line last on stdout:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device`` and, when
traced, ``breakdown``; ``checks`` comes last, each number compared beside
its limit (also the last lines on stderr).  Exits 2 without a result when
JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# The TPU runtime's logs stay inside the checkout (its default is /tmp).
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_logs"))

from bench import cells, device  # noqa: E402
from bench.device import log  # noqa: E402


def _checks(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct iff every number is
    within it.  A number without a limit in ``bench/limits/<cell>.json``
    cannot pass."""
    out, ok = {}, True
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        passed = lim is not None and value is not None and value <= lim
        ok &= passed
        out[name] = {"value": value, "limit": lim}
    return ok, out


def _trace_dir(cell_name: str) -> str:
    d = ROOT / ".bench_trace" / cell_name
    shutil.rmtree(d, ignore_errors=True)
    return str(d)


def run(args) -> dict:
    cell = cells.load_cell(args.workload, ROOT)
    devices = device.require_chips(cell.workload["chips"])
    device.enable_cache(ROOT)
    clock = device.CompileCounter()
    peaks = device.peaks(devices[0].device_kind)
    trace_dir = _trace_dir(cell.name) if args.trace else None

    drv = cells.runner(cell)(cell, args.seed)
    drv.setup(trace=bool(args.trace))
    setup_s = time.perf_counter() - T_START
    setup_compile = (clock.count, clock.seconds)
    clock.reset()
    log(f"[{cell.name}] set-up {setup_s:.3f} s, {setup_compile[0]} programs "
        f"compiled in {setup_compile[1]:.3f} s")

    w = drv.window(args.seconds, trace_dir)
    log(f"[{cell.name}] compiles inside the window: {clock.count} "
        f"({clock.seconds:.3f} s)")
    mem = device.memory_peak(devices)
    metrics = {}
    breakdown = None
    dev = device.describe(devices)
    dev["memory_peak_bytes"] = mem
    if args.trace:
        from bench import trace_reduce

        red = trace_reduce.reduce_trace(trace_dir)
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        ctx = {"cell": cell, "runner": drv, "window": w, "trace": red,
               "peaks": peaks}
        for m in cell.per_layer:
            value = cells.metric_reader(ROOT, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {
            "device_ops": [[n, s] for n, s in red.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in red.gaps[:10]],
        }
        log(f"[{cell.name}] trace: busy {red.busy_s:.4f} s of "
            f"{red.window_s:.4f} s; top ops {red.top_ops(10)}")
    else:
        e2e = drv.end_to_end(w)
        e2e["setup_s"] = (setup_s, "s")
        for m in cell.end_to_end:
            value, unit = e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": unit}

    attempted, failed = drv.attempted(w)
    samples = drv.sample(w)
    drv.free()
    readings = drv.check(samples)
    numbers = {k: readings.get(k) for k in cell.traffic["compared"]}
    correct, checks = _checks(numbers, cell.limits)
    correct &= failed == 0 and bool(numbers)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except device.NoChip as e:
        log(f"[bench] {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"[checks] {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
