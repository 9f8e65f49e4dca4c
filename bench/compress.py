"""Compression cells: ``execute_plan`` jobs back to back over a seeded dense
model, with the mix's policy.

Set-up makes the dense model on the device from the seed, plans, and runs
one whole job (every program compiled).  The window starts jobs until
``seconds`` have passed, ``AHEAD`` at a time; ``compress_MB_per_s`` is the
dense bf16 bytes of the tensors the jobs compressed over the time from the
window's start to the return of the last job started before the deadline.
Once the window is read, the float32 reference checks every job's answer
tile by tile (``bench/reference.py``).
"""

from __future__ import annotations

import gc
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import jax

from bench import reference, weights
from bench.cells import model_config
from bench.device import log

# Jobs in flight in the window: ~1.7 s of device work each, so the chip
# holds some 5 s of work queued behind the job that the window waits for.
AHEAD = 4


def policy(spec: dict):
    """The program's CompressionPolicy for a mix's ``policy`` entry."""
    from repro.compression import CompressionPolicy

    pol = dict(spec)
    for k in ("targets", "exclude"):
        if k in pol:
            pol[k] = tuple(pol[k])
    return CompressionPolicy(**pol)


class CompressCell:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg = model_config(cell.config)
        self.traffic = cell.traffic

    def setup(self, trace: bool) -> None:
        from repro.compression import plan_compression

        self.values = weights.dense(self.cfg, self.seed)
        self.plan = plan_compression(self.values, policy(self.traffic["policy"]))
        if not self.plan.tensors:
            raise ValueError("the mix's policy compresses no tensor")
        self._job(0)

    def reseed(self, seed: int) -> None:
        self.seed = seed
        self.values = weights.dense(self.cfg, seed)

    def _job(self, j: int):
        from repro.compression import execute_plan

        key = jax.random.fold_in(weights.seed_key(self.seed), j)
        with jax.profiler.TraceAnnotation("bench.job"):
            out, artifact = execute_plan(self.plan, self.values, key=key)
            answers = {}
            flat = dict(weights.paths(out))
            for t in self.plan.tensors:
                answers[t.path] = (flat[t.path + "/m_packed"],
                                   flat[t.path + "/C"],
                                   artifact.manifest["tensors"][t.path]["tile_resid"])
            jax.block_until_ready([a[:2] for a in answers.values()])
        return answers, artifact

    def window(self, seconds: float, trace_dir: str | None):
        """Jobs until ``seconds`` have passed, ``AHEAD`` in flight on as
        many threads, so that the chip has work queued while the host
        stands still.  Once the time is up no job starts; the window ends
        when every job started has returned, and all of them count.  A
        traced run profiles one job alone: a job's trace is ~120 MB, and
        reading a whole window's back would not fit a run's time."""
        jobs, ends = [], []
        if trace_dir:
            with jax.profiler.trace(trace_dir):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.window"):
                    jobs.append(self._job(1))
                    ends.append(time.perf_counter())
            return {"t0": t0, "t_last": ends[-1], "jobs": jobs, "ends": ends}
        with ThreadPoolExecutor(AHEAD) as pool:
            t0 = time.perf_counter()
            pending, j = deque(), 1
            while True:
                if time.perf_counter() < t0 + seconds and len(pending) < AHEAD:
                    pending.append(pool.submit(self._job, j))
                    j += 1
                elif pending:
                    jobs.append(pending.popleft().result())
                    ends.append(time.perf_counter())
                else:
                    break
        return {"t0": t0, "t_last": ends[-1], "jobs": jobs, "ends": ends}

    def end_to_end(self, w) -> dict:
        nbytes = sum(a.manifest["totals"]["orig_bytes"] for _, a in w["jobs"])
        span = w["t_last"] - w["t0"]
        gaps = [b - a for a, b in zip([w["t0"]] + w["ends"], w["ends"])]
        log(f"[compress] {len(w['jobs'])} jobs, {nbytes} dense bytes in "
            f"{span:.3f} s; per job {span / len(w['jobs']):.3f} s; seconds "
            "from one job's return to the next: "
            + " ".join(f"{g:.4f}" for g in gaps))
        return {"compress_MB_per_s": (nbytes / 1e6 / span, "MB/s")}

    def attempted(self, w) -> tuple[int, int]:
        return len(w["jobs"]), 0

    def sample(self, w):
        return w["jobs"]

    def free(self) -> None:
        self.values = None
        gc.collect()

    def check(self, jobs, control: bool = False) -> dict:
        """The worst job's reading of every number the reference reads
        (``reference.job_numbers``); with ``control``, also the same
        numbers for the float8 reference's answer, as ``control_<name>``."""
        flat = dict(weights.paths(weights.dense(self.cfg, self.seed)))
        W = {t.path: reference.tiles_of(flat[t.path], t.tile_n, t.tile_d)
             for t in self.plan.tensors}
        del flat
        per_job = []
        for answers, _ in jobs:
            readings = []
            for t in self.plan.tensors:
                mp, C, rec = answers[t.path]
                readings.append(reference.tile_readings(
                    W[t.path], reference.decode_M(mp, t.K),
                    C.reshape(-1, t.K, t.tile_d), rec))
            per_job.append(reference.job_numbers(readings))
        worst = {k: max(j[k] for j in per_job) for k in per_job[0]}
        if control:
            got = reference.job_numbers([
                reference.tile_readings(w, *reference.control_answer(w))
                for w in W.values()])
            worst |= {f"control_{k}": v for k, v in got.items()}
        for k, v in worst.items():
            log(f"[check] {k} {v}")
        return worst


Runner = CompressCell
