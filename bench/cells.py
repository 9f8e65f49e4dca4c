"""Cells from data: ``BENCHMARK.json`` names each cell's configuration and
traffic mix; their files, the cell's correctness limits and the per-layer
metric readers are found by name under ``bench/``.

    bench/configs/<config>.json    model sizes as run, and the deployment
    bench/traffic/<traffic>.json   the mix; its ``kind`` names the runner
    bench/<kind>.py                ``Runner``, the class that drives a kind
    bench/limits/<cell>.json       the limit of each number ``correct`` compares
    bench/metrics/<metric>.py      ``read(ctx) -> float | None``

A configuration file maps each published key to a field of the program's
``ModelConfig`` through ``HF_KEYS``, or through a ``keys`` map of its own,
which overrides ``HF_KEYS`` for that file; a key the program has no field
for is listed under ``not_run`` with the reason.

A new cell, configuration, mix, kind or metric is a new file and a new
entry in ``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent

# Published config key -> ModelConfig field of the program.
HF_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps",
    "capacity_factor": "capacity_factor",
    "torch_dtype": "dtype",
}

# Keys of a configuration file that describe it rather than size the model.
META_KEYS = ("arch", "source", "model_type", "deployment", "assumed",
             "reduced", "keys", "not_run")


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: pathlib.Path = BENCH_DIR.parent

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def runner(cell: Cell):
    """``Runner`` of ``bench/<kind>.py`` in the cell's checkout: the class
    that drives a cell of the mix's ``kind``."""
    return _module(cell.root / "bench" / f"{cell.kind}.py",
                   "bench_kind_").Runner


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    cells = metric.get("workloads")
    if cells is not None:
        return cell in cells
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def load_cell(name: str, root: pathlib.Path | None = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files.
    ``root`` is the checkout (default: the one this file lies in)."""
    root = BENCH_DIR.parent if root is None else pathlib.Path(root)
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / confs[w["config"]]["file"])
    bench_dir = root / "bench"
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits_path = bench_dir / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}
    e2e = [m for m in bench["end_to_end"]
           if m.get("workloads") is None or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, w, config, traffic, limits, e2e, per_layer, root)


def config_keys(config: dict) -> dict:
    """Published key -> ModelConfig field for a configuration file: its own
    ``keys`` over ``HF_KEYS``."""
    return {**HF_KEYS, **config.get("keys", {})}


def model_config(config: dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry for ``arch`` with every mapped key the file states (lists as
    tuples)."""
    from repro.configs import get_config

    base = get_config(config["arch"])
    fields = {f: tuple(config[k]) if isinstance(config[k], list) else config[k]
              for k, f in config_keys(config).items() if k in config}
    return dataclasses.replace(base, **fields)


def _module(path: pathlib.Path, prefix: str):
    """The Python file ``path`` as a module (its name may hold dots and
    dashes)."""
    stem = pathlib.Path(path).stem
    name = prefix + stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_module(root: pathlib.Path, name: str):
    """``bench/metrics/<name>.py`` as a module (names may hold dots)."""
    return _module(pathlib.Path(root) / "bench" / "metrics" / f"{name}.py",
                   "bench_metric_")


def metric_reader(root: pathlib.Path, name: str):
    """``read(ctx) -> float | None`` of ``bench/metrics/<name>.py``."""
    return metric_module(root, name).read
