"""Every cell loads from its data, ``BENCHMARK.json`` keeps to the
benchmark's contract, and a cell, a configuration key or a kind defined
only by new files runs end to end (device check stubbed here, tiny sizes,
CPU)."""

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


def config_problems(conf: dict, reduced) -> list:
    """What breaks the contract of a configuration file: every key it states
    is a meta key, or mapped (``HF_KEYS`` and the file's own ``keys``) and
    equal in the run config, or listed under ``not_run`` with a reason; a
    stated size is the registry's, but where ``reduced`` names it."""
    from repro.configs import get_config

    published = get_config(conf["arch"])
    run_cfg = cells.model_config(conf)
    keys = cells.config_keys(conf)
    not_run = conf.get("not_run", {})
    problems = [f"{k}: no reason given under not_run"
                for k, why in not_run.items() if not str(why).strip()]
    for key, value in conf.items():
        if key in cells.META_KEYS:
            continue
        if key in not_run:
            if key in keys:
                problems.append(f"{key}: both mapped and under not_run")
            continue
        if key not in keys:
            problems.append(f"{key}: neither mapped nor under not_run")
            continue
        want = tuple(value) if isinstance(value, list) else value
        if getattr(run_cfg, keys[key]) != want:
            problems.append(f"{key}: the run config does not hold {value!r}")
        if (isinstance(value, int) and key not in reduced
                and getattr(published, keys[key]) != value):
            problems.append(f"{key}: {value!r} is not the registry's size")
    return problems


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files_state_the_published_sizes(c):
    """The program runs every key the file states (or the file says why
    not), and the file's sizes are the program's registry entry for the
    model, but where ``reduced`` names them."""
    conf = json.loads((ROOT / c["file"]).read_text())
    assert c["source"] == conf["source"]
    assert config_problems(conf, c["reduced"]) == []


def test_a_stated_key_the_program_does_not_run_fails_the_contract():
    conf = json.loads((ROOT / "bench/configs/granite-moe-1b-a400m.json")
                      .read_text())
    conf["shared_intermediate_size"] = 1536
    assert config_problems(conf, []) == [
        "shared_intermediate_size: neither mapped nor under not_run"]
    conf["not_run"] = {"shared_intermediate_size": "no shared expert"}
    assert config_problems(conf, []) == []
    conf["hidden_size"] = 512
    assert config_problems(conf, []) == [
        "hidden_size: 512 is not the registry's size"]
    assert config_problems(conf, ["hidden_size"]) == []


def test_a_key_outside_hf_keys_runs_through_the_files_own_map():
    conf = json.loads((ROOT / "bench/configs/granite-moe-1b-a400m.json")
                      .read_text())
    # DeepSeek's meanings: the expert width has a key of its own, and
    # intermediate_size is the dense layers' width (overriding HF_KEYS).
    conf["keys"] = {"moe_intermediate_size": "d_ff",
                    "intermediate_size": "d_ff_dense"}
    conf["moe_intermediate_size"] = 256
    conf["intermediate_size"] = 1024
    cfg = cells.model_config(conf)
    assert (cfg.d_ff, cfg.d_ff_dense) == (256, 1024)
    assert config_problems(conf, ["moe_intermediate_size",
                                  "intermediate_size"]) == []


def test_the_granite_file_gives_the_model_config_it_gave_before():
    """Field for field the ModelConfig of the file as it stood before the
    serving cell (its 14 ``HF_KEYS`` sizes) but ``capacity_factor``, now
    stated so that serving routes dropless; compression never reads it,
    so the compress cell's input tree and plan are the same."""
    from bench.compress import policy
    from repro.compression import plan_compression
    from repro.configs import get_config
    from repro.models import init_model
    from repro.models.params import split

    conf = json.loads((ROOT / "bench/configs/granite-moe-1b-a400m.json")
                      .read_text())
    before = dataclasses.replace(
        get_config("granite-moe-1b-a400m"), d_model=1024, d_ff=512,
        num_layers=24, num_heads=16, num_kv_heads=8, head_dim=64,
        num_experts=32, experts_per_token=8, vocab_size=49155,
        rope_theta=10000.0, tie_embeddings=True, norm_eps=1e-6,
        dtype="bfloat16")
    now = cells.model_config(conf)
    assert now.capacity_factor == 4.0 == now.num_experts / now.experts_per_token
    assert dataclasses.replace(now, capacity_factor=before.capacity_factor) \
        == before

    def tree(cfg):
        return jax.eval_shape(
            lambda: split(init_model(jax.random.PRNGKey(0), cfg))[0])

    assert tree(now) == tree(before)
    mix = json.loads((ROOT / "bench/traffic/compress-bbo.json").read_text())
    pol = policy(mix["policy"])
    assert plan_compression(tree(now), pol) == plan_compression(tree(before),
                                                                pol)


def test_runner_finds_a_kind_added_as_a_module(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/echo.py").write_text(
        "class EchoCell:\n"
        "    def __init__(self, cell, seed):\n"
        "        self.seed = seed\n\n\n"
        "Runner = EchoCell\n")
    (root / "bench/traffic/echo.json").write_text('{"kind": "echo"}')
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "granite-moe-1b-a400m.echo",
                               "config": "granite-moe-1b-a400m",
                               "traffic": "echo", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell("granite-moe-1b-a400m.echo", root)
    got = cells.runner(cell)
    assert got.__name__ == "EchoCell" and got(cell, 3).seed == 3


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
        if "granite-moe-1b-a400m.compress-bbo" in m.get("workloads", []):
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


SERVE = "granite-moe-1b-a400m.serve-chat"
# The serve-chat mix's metrics, as a serving cell of the benchmark lists
# them (``BENCHMARK.json`` holds no serving cell yet).
SERVE_METRICS = {
    "end_to_end": [
        {"name": "output_tokens_per_s", "unit": "tok/s", "better": "higher",
         "bound": 0.25, "source": "host_clock"}],
    "per_layer": [
        {"name": "device_idle.serve", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "output_tokens_per_s"},
        {"name": "sched_batch_occupancy", "unit": "slots", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "output_tokens_per_s"},
        {"name": "bitlinear_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "bit-linear kernel",
         "moves": "output_tokens_per_s"},
        {"name": "serve_mfu", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "model step",
         "moves": "output_tokens_per_s"}],
}


def tiny_serve_root(tmp_path: pathlib.Path, dtype: str = "bfloat16"):
    """A checkout whose benchmark holds one new serving cell, defined only
    by new files: granite narrowed to 2 layers of 4 experts, whose
    capacity factor comes in through a key of the file's own ``keys`` map;
    the serve-chat mix cut to 4 slots of 64 positions; the serve-chat
    limits; the serving metrics."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((ROOT / "bench/configs/granite-moe-1b-a400m.json")
                      .read_text())
    del conf["capacity_factor"]
    conf.update(hidden_size=256, intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=8, num_key_value_heads=4, head_dim=32,
                num_local_experts=4, num_experts_per_tok=2, vocab_size=257,
                torch_dtype=dtype, moe_capacity_factor=2.0,
                keys={"moe_capacity_factor": "capacity_factor"})
    (root / "bench/configs/tiny-moe.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/serve-chat.json").read_text())
    mix.update(
        policy=dict(mix["policy"], tile_n=16, tile_d=32, min_size=1024),
        engine={"num_slots": 4, "max_len": 64, "page_size": 16}, block=4,
        arrivals=dict(mix["arrivals"], backlog=4, rate_per_s=50.0),
        prompt_len={"dist": "loguniform", "min": 8, "max": 32},
        output_len={"dist": "lognormal", "median": 6, "sigma": 0.5,
                    "min": 2, "max": 16},
        sample=3)
    (root / "bench/traffic/tiny-chat.json").write_text(json.dumps(mix))
    shutil.copy(ROOT / f"bench/limits/{SERVE}.json",
                root / "bench/limits/tiny-moe.tiny-chat.json")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "a cell added by files"})
    bench["workloads"].append({"name": "tiny-moe.tiny-chat",
                               "config": "tiny-moe", "traffic": "tiny-chat",
                               "chips": 1, "why": "test"})
    for kind, metrics in SERVE_METRICS.items():
        bench[kind] += [dict(m, workloads=["tiny-moe.tiny-chat"])
                        for m in metrics]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_tiny_serve(root, monkeypatch, trace=0, seed=2**33 + 5, seconds=2.0):
    monkeypatch.setattr(run, "ROOT", root)
    args = type("A", (), dict(workload="tiny-moe.tiny-chat", seed=seed,
                              seconds=seconds, trace=trace))
    return run.run(args)


def test_a_serve_cell_added_by_files_runs_end_to_end(tmp_path, monkeypatch,
                                                     on_cpu):
    root = tiny_serve_root(tmp_path)
    cell = cells.load_cell("tiny-moe.tiny-chat", root)
    assert [m["name"] for m in cell.per_layer] == [
        "device_idle.serve", "sched_batch_occupancy", "bitlinear_roofline",
        "serve_mfu"]
    assert cells.runner(cell)(cell, 1).cfg.capacity_factor == 2.0
    res = run_tiny_serve(root, monkeypatch)
    assert res["correct"] is True, res
    assert res["attempted"] > 4 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cell.traffic["compared"])
    # No device plane in a CPU trace: only the scheduler's counter reads.
    traced = run_tiny_serve(root, monkeypatch, trace=1)
    assert set(traced["metrics"]) == {"sched_batch_occupancy"}
    assert 0 < traced["metrics"]["sched_batch_occupancy"]["value"] <= 4
    assert traced["correct"] is True


def test_the_serving_path_matches_the_float32_reference(tmp_path, on_cpu):
    """Prefill, then decode through the paged cache, of the float32 tiny
    model against the reference's full forward, at every served position.
    Both compute in float32 from the same decoded M C: what is left is the
    order of summation and the reference's HIGHEST against the CPU's own
    float32 products, ~1e-6 of a logit vector's norm through two layers;
    1e-4 leaves room and is far below the float8 control's ~1e-1."""
    root = tiny_serve_root(tmp_path, dtype="float32")
    cell = cells.load_cell("tiny-moe.tiny-chat", root)
    drv = cells.runner(cell)(cell, seed=12)
    drv.setup(trace=False)
    w = drv.window(1.0, None)
    samples = drv.sample(w)
    drv.free()
    assert sum(len(s[1]) for s in samples) >= 10
    got = drv.check(samples, control=True)
    assert got["logit_err_max"] < 1e-4, got
    assert got["token_gap"] == 0.0
    assert got["control_logit_err_p50"] > 1e-2


def test_every_seed_serves_the_same_sizes_in_another_order():
    from bench.serve import Schedule

    mix = json.loads((ROOT / "bench/traffic/serve-chat.json").read_text())
    a, b = (Schedule(mix, seed, 49155) for seed in (3, 2**40 + 3))
    n = mix["block"]
    for blk in range(3):
        sizes = [sorted(len(s.request(blk * n + k)[0]) for k in range(n))
                 for s in (a, b)]
        outs = [sorted(s.request(blk * n + k)[1] for k in range(n))
                for s in (a, b)]
        assert sizes[0] == sizes[1] and outs[0] == outs[1]
    assert [len(a.request(k)[0]) for k in range(n)] != \
        [len(b.request(k)[0]) for k in range(n)]
    gaps = [s.arrival(3 * n) for s in (a, b)]
    assert gaps[0] == pytest.approx(gaps[1])
    assert gaps[0] == pytest.approx(3 * n / mix["arrivals"]["rate_per_s"],
                                    rel=0.2)
    # The mid-quantiles of log-uniform 64-1024, unrounded to any grid: a
    # distinct length for every request of a block.
    lens = sorted({len(a.request(k)[0]) for k in range(2 * n)})
    assert len(lens) == n and lens[0] > 64 and lens[-1] < 1024
    assert lens == sorted(len(a.request(k)[0]) for k in range(n))
    assert all(16 <= a.request(k)[1] <= 256 for k in range(4 * n))


def test_no_chip_means_no_result(monkeypatch, capsys):
    monkeypatch.setattr(device.jax, "devices", lambda: [dataclasses.make_dataclass(
        "D", [("platform", str), ("device_kind", str)])("cpu", "cpu")])
    assert run.main(["--workload", BENCH["workloads"][0]["name"], "--seed",
                     "1", "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""
