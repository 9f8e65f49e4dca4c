"""Every cell loads from its data, ``BENCHMARK.json`` keeps to the
benchmark's contract, and a cell defined only by new files runs end to
end (device check stubbed here, tiny sizes, CPU)."""

import dataclasses
import json
import pathlib
import re
import shutil
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cells, device, run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_contract_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            reported = e2e[m["moves"]].get("workloads")
            assert reported is None or cell in reported


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_from_its_data(w):
    cell = cells.load_cell(w["name"], ROOT)
    assert cells.runner(cell)
    assert cell.limits, "a cell needs the limits of its compared numbers"
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.metric_reader(ROOT, m["name"]))
    cfg = cells.model_config(cell.config)
    assert cfg.name == cell.config["arch"]


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files_state_the_published_sizes(c):
    """The program runs every key the file states, and the file's sizes
    are the program's registry entry for the model, but where ``reduced``
    names them."""
    from repro.configs import get_config

    conf = json.loads((ROOT / c["file"]).read_text())
    assert c["source"] == conf["source"]
    published = get_config(conf["arch"])
    run_cfg = cells.model_config(conf)
    for key, field in cells.HF_KEYS.items():
        if key not in conf or field == "dtype":
            continue
        assert getattr(run_cfg, field) == conf[key], key
        if isinstance(conf[key], int) and key not in c["reduced"]:
            assert getattr(published, field) == conf[key], key


def tiny_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A checkout whose benchmark holds one new cell, defined only by new
    files: a configuration, a traffic mix (the compress-bbo policy as the
    cell runs it), its limits (the cell's own), and a new per-layer
    metric.  The model is granite's, narrowed so that its two ``attn/wk``
    tensors hold 64 tiles of 8 x 512."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((ROOT / "bench/configs/granite-moe-1b-a400m.json")
                      .read_text())
    conf.update(hidden_size=256, intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=8, num_key_value_heads=8, head_dim=64,
                num_local_experts=4, num_experts_per_tok=2, vocab_size=257)
    (root / "bench/configs/tiny-moe.json").write_text(json.dumps(conf))
    shutil.copy(ROOT / "bench/traffic/compress-bbo.json",
                root / "bench/traffic/tiny-bbo.json")
    shutil.copy(ROOT / "bench/limits/granite-moe-1b-a400m.compress-bbo.json",
                root / "bench/limits/tiny-moe.tiny-bbo.json")
    (root / "bench/metrics/tiles_per_job.py").write_text(
        "def read(ctx):\n"
        "    jobs = ctx['window']['jobs']\n"
        "    tiles = [sum(e['num_tiles'] for e in a.manifest['tensors'].values())\n"
        "             for _, a in jobs]\n"
        "    return sum(tiles) / len(tiles) if tiles else None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "a cell added by files"})
    bench["workloads"].append({"name": "tiny-moe.tiny-bbo",
                               "config": "tiny-moe", "traffic": "tiny-bbo",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-moe.tiny-bbo")
    bench["per_layer"].append({
        "name": "tiles_per_job", "unit": "tiles", "better": "higher",
        "source": "program_counter", "layer": "compression execute",
        "moves": "compress_MB_per_s", "workloads": ["tiny-moe.tiny-bbo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
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


def run_tiny(root, monkeypatch, trace=0, seed=2**31 + 17, seconds=1.0):
    monkeypatch.setattr(run, "ROOT", root)
    args = type("A", (), dict(workload="tiny-moe.tiny-bbo", seed=seed,
                              seconds=seconds, trace=trace))
    return run.run(args)


def test_a_cell_added_by_files_runs_end_to_end(tmp_path, monkeypatch, on_cpu):
    root = tiny_root(tmp_path)
    cell = cells.load_cell("tiny-moe.tiny-bbo", root)
    assert [m["name"] for m in cell.per_layer] == [
        "device_idle.compress", "ising_Mspin_updates_per_s", "tiles_per_job"]
    res = run_tiny(root, monkeypatch, trace=0)
    assert res["correct"] is True, res
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"compress_MB_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"c_gap", "resid_gap", "off_best"}
    for name, c in res["checks"].items():
        assert c["limit"] == cell.limits[name]["limit"]
    traced = run_tiny(root, monkeypatch, trace=1, seconds=5.0)
    # No device plane in a CPU trace: the trace's readers find nothing and
    # are left out; the counter's reader reads the window's one job.
    assert traced["metrics"] == {"tiles_per_job": {"value": 64.0,
                                                   "unit": "tiles"}}
    assert traced["correct"] is True and traced["attempted"] == 1


def test_no_chip_means_no_result(monkeypatch, capsys):
    monkeypatch.setattr(device.jax, "devices", lambda: [dataclasses.make_dataclass(
        "D", [("platform", str), ("device_kind", str)])("cpu", "cpu")])
    assert run.main(["--workload", BENCH["workloads"][0]["name"], "--seed",
                     "1", "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""
