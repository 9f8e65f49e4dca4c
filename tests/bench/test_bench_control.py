"""The control and the planted faults that each cell's comparison must
catch, at a size a test run can hold (CPU), held to the cell's own
committed limits: the compress-bbo policy as the cell runs it over 64
tiles, and the serve-chat mix cut to 4 slots of a 2-layer granite.  The
float8 reference in the program's place reads above them, and a run whose
timed path is broken underneath comes out not correct."""

import pathlib
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from bench import cells, faults, reference, run  # noqa: E402
from test_bench_cells import (  # noqa: E402,F401
    on_cpu, run_tiny, run_tiny_serve, tiny_root, tiny_serve_root)


@pytest.fixture
def plant(monkeypatch):
    """Plants a fault for one test; no program traced under it outlives
    the test."""
    yield lambda name: faults.plant(name, monkeypatch.setattr)
    jax.clear_caches()


def test_compress_cell_correct_and_its_control_is_not(tmp_path, monkeypatch,
                                                      on_cpu):  # noqa: F811
    root = tiny_root(tmp_path)
    cell = cells.load_cell("tiny-moe.tiny-bbo", root)
    drv = cells.runner(cell)(cell, seed=5)
    drv.setup(trace=False)
    w = drv.window(0.1, None)
    drv.free()
    got = drv.check(w["jobs"], control=True)
    compared = cell.traffic["compared"]
    ok, _ = run._checks({k: got[k] for k in compared}, cell.limits)
    assert ok, got
    control = {k: got["control_" + k] for k in compared}
    ok, checks = run._checks(control, cell.limits)
    assert not ok, checks
    assert control["c_gap"] > cell.limits["c_gap"]["limit"]


def test_an_altered_compression_answer_is_not_correct(tmp_path, monkeypatch,
                                                      plant,
                                                      on_cpu):  # noqa: F811
    """One tile's C is scaled where ``execute_plan`` packs it."""
    plant("altered_C")
    res = run_tiny(tiny_root(tmp_path), monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["c_gap"]["value"] > res["checks"]["c_gap"]["limit"]


@pytest.mark.parametrize("fault,number", [("keep_init", "off_best"),
                                          ("half_tiles", "c_gap")])
def test_a_broken_timed_path_is_not_correct(fault, number, tmp_path,
                                            monkeypatch, plant,
                                            on_cpu):  # noqa: F811
    """``keep_init``: the BBO stage returns its state unchanged;
    ``half_tiles``: half of every chunk is left out (``bench/faults.py``)."""
    plant(fault)
    res = run_tiny(tiny_root(tmp_path), monkeypatch)
    assert res["correct"] is False
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]


def test_serve_cell_correct_and_its_control_is_not(tmp_path, monkeypatch,
                                                   on_cpu):  # noqa: F811
    root = tiny_serve_root(tmp_path)
    cell = cells.load_cell("tiny-moe.tiny-chat", root)
    drv = cells.runner(cell)(cell, seed=5)
    drv.setup(trace=False)
    w = drv.window(1.0, None)
    samples = drv.sample(w)
    drv.free()
    got = drv.check(samples, control=True)
    compared = cell.traffic["compared"]
    ok, _ = run._checks({k: got[k] for k in compared}, cell.limits)
    assert ok, got
    ok, checks = run._checks({k: got["control_" + k] for k in compared},
                             cell.limits)
    assert not ok, checks


@pytest.mark.parametrize("fault,number", [("wrong_kv_page", "logit_err_p90"),
                                          ("dropped_expert", "logit_err_p90"),
                                          ("altered_token", "token_gap"),
                                          ("altered_C", "c_gap")])
def test_a_broken_serving_path_is_not_correct(fault, number, tmp_path,
                                              monkeypatch, plant,
                                              on_cpu):  # noqa: F811
    """``wrong_kv_page``: decode reads the scratch page for each slot's
    first; ``dropped_expert``: expert 0's output is dropped;
    ``altered_token``: each first token is not the one the logits chose;
    ``altered_C``: one tile's C of each served tensor is scaled where
    ``execute_plan`` packs it, and the reference serves it as given, so
    only the served tiles' check against the dense weights sees it
    (``bench/faults.py``)."""
    plant(fault)
    res = run_tiny_serve(tiny_serve_root(tmp_path), monkeypatch)
    assert res["correct"] is False
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]


def test_the_served_tiles_read_the_same_in_blocks(tmp_path, monkeypatch,
                                                  on_cpu):  # noqa: F811
    """The check of the served tiles against the dense weights takes them
    in blocks (one batched SVD over an expert stack overflows the chip's
    scoped memory); the blocks change no reading, the control's included."""
    from bench import weights

    root = tiny_serve_root(tmp_path)
    cell = cells.load_cell("tiny-moe.tiny-chat", root)
    drv = cells.runner(cell)(cell, seed=7)
    drv.compress()
    dense = dict(weights.paths(weights.dense(drv.cfg, 7)))
    whole = drv.check_compression(dense, control=True)
    assert max(t.num_tiles for t, *_ in drv.compressed.values()) > 100
    monkeypatch.setitem(type(drv).compress.__globals__, "TILE_BLOCK", 100)
    blocks = drv.check_compression(dense, control=True)
    assert blocks == pytest.approx(whole, rel=1e-5, abs=1e-9)
    assert whole["control_c_gap"] > cell.limits["c_gap"]["limit"] \
        > whole["c_gap"]


def test_best_pair_search_matches_brute_force():
    W = jax.random.normal(jax.random.PRNGKey(0), (3, 6, 8))
    opt, M = reference.best_pair_M(W)
    S = np.asarray(reference._signs(6))
    for t in range(3):
        best = np.inf
        for i in range(64):
            for j in range(64):
                m = np.stack([S[i], S[j]], -1)
                c = np.linalg.pinv(m) @ np.asarray(W[t])
                best = min(best, float(((np.asarray(W[t]) - m @ c) ** 2).sum()))
        assert float(opt[t]) == pytest.approx(best, rel=1e-4, abs=1e-4)
        c = np.linalg.pinv(np.asarray(M[t])) @ np.asarray(W[t])
        got = float(((np.asarray(W[t]) - np.asarray(M[t]) @ c) ** 2).sum())
        assert got == pytest.approx(best, rel=1e-4, abs=1e-4)


def test_off_best_counts_tiles_above_the_margin():
    readings = [{"c_gap": np.array([0.0, 0.01]),
                 "resid_gap": np.array([0.0, 0.0]),
                 "excess": np.array([0.0, reference.OFF_BEST * 2])},
                {"c_gap": np.array([0.002]), "resid_gap": np.array([1e-6]),
                 "excess": np.array([reference.OFF_BEST / 2])}]
    got = reference.job_numbers(readings)
    assert got["c_gap"] == 0.01 and got["resid_gap"] == 1e-6
    assert got["off_best"] == pytest.approx(1 / 3)
    assert got["excess"] == pytest.approx(reference.OFF_BEST * 2.5 / 3)
