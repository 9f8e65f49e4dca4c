"""The compress window keeps ``AHEAD`` jobs in flight and counts every job
it started over the time until the last returned (stub jobs, no
device)."""

import pathlib
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compress  # noqa: E402


class StubCell(compress.CompressCell):
    def __init__(self, job_s: float):
        self.job_s, self.lock = job_s, threading.Lock()
        self.started, self.running, self.most = [], 0, 0

    def _job(self, j):
        with self.lock:
            self.started.append((j, time.perf_counter()))
            self.running += 1
            self.most = max(self.most, self.running)
        time.sleep(self.job_s)
        with self.lock:
            self.running -= 1
        return j, time.perf_counter()


def test_the_window_keeps_jobs_in_flight_and_counts_all_it_started():
    cell = StubCell(job_s=0.05)
    w = cell.window(0.3, None)
    assert cell.most == compress.AHEAD
    assert [j for j, _ in w["jobs"]] == list(range(1, len(cell.started) + 1))
    assert w["t_last"] >= max(t for _, t in w["jobs"])
    assert w["t_last"] > w["t0"] + 0.3
    assert len(w["ends"]) == len(w["jobs"])
