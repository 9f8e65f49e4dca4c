"""The granite-4.0-h-small compress cell, added by files, runs end to end
at a tiny size on the CPU (device check stubbed): its configuration file
narrowed to two layers of the period, its mix and its limits as they are."""

import json
import pathlib
import shutil
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cells, device, faults, run  # noqa: E402

CELL = "granite-4.0-h-small.compress-bbo-mixer"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A checkout whose cell is this one, its configuration narrowed to a
    Mamba2 and an attention layer at d_model 2048 (Mamba2 64 heads of 64,
    2 of the 72 experts held): layer 0's in_proj 2048 x 8288 and out_proj
    4096 x 2048 give 512 tiles of 8 x 4144 and 512 of 8 x 2048 under the
    mix's own policy (tile_d 4096), wide enough that its limits hold."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "bench/configs/granite-4.0-h-small.json"
    conf = json.loads(path.read_text())
    conf.update(hidden_size=2048, num_attention_heads=16, num_key_value_heads=4,
                intermediate_size=32, shared_intermediate_size=48,
                mamba_n_heads=64, mamba_d_state=16, vocab_size=257,
                num_local_experts=2, num_hidden_layers=2,
                layer_types=["mamba", "attention"])
    path.write_text(json.dumps(conf))
    return root


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """The harness's chip check and peaks, stubbed inside the test only."""
    monkeypatch.setattr(device, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def test_the_hybrid_compress_cell_runs_end_to_end(tmp_path, monkeypatch,
                                                  on_cpu):
    root = tiny_root(tmp_path)
    cell = cells.load_cell(CELL, root)
    assert [m["name"] for m in cell.per_layer] == [
        "device_idle.compress", "ising_Mspin_updates_per_s"]
    drv = cells.runner(cell)(cell, 1)
    assert drv.cfg.block_pattern == ("ssm_moe", "attn_moe")
    monkeypatch.setattr(run, "ROOT", root)
    args = type("A", (), dict(workload=CELL, seed=2**33 + 41, seconds=1.0,
                              trace=0))
    res = run.run(args)
    assert res["correct"] is True, res
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"compress_MB_per_s", "setup_s"}
    assert set(res["checks"]) == {"c_gap", "resid_gap", "excess"}
    for name, c in res["checks"].items():
        assert c["limit"] == cell.limits[name]["limit"]


@pytest.fixture
def plant(monkeypatch):
    """Plants a fault for one test; no program traced under it outlives
    the test."""
    yield lambda name: faults.plant(name, monkeypatch.setattr)
    jax.clear_caches()


@pytest.mark.parametrize("fault,number", [("keep_init", "excess"),
                                          ("altered_C", "c_gap")])
def test_a_broken_mixer_job_is_not_correct(fault, number, tmp_path,
                                           monkeypatch, plant, on_cpu):
    """The cell's committed limits catch a BBO stage that returns its
    state unchanged (``keep_init``, which ``off_best`` hardly sees at
    these tile widths) and one tile's C altered where it is packed."""
    plant(fault)
    monkeypatch.setattr(run, "ROOT", tiny_root(tmp_path))
    args = type("A", (), dict(workload=CELL, seed=2**33 + 43, seconds=1.0,
                              trace=0))
    res = run.run(args)
    assert res["correct"] is False
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]
