"""Kernel schedule autotuner: keys, heuristics, timed search, and the
probe-then-serve round trip (tune_artifact -> manifest -> Engine restore ->
trace-time cache hits, proven via the resolution log)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_for_smoke
from repro.kernels import autotune, ops
from repro.kernels.autotune import Schedule
from repro.models import init_model
from repro.models.params import split
from repro.serving.engine import Engine


@pytest.fixture(autouse=True)
def _clean_autotune_state():
    """_CACHE/_LOG are process-global — no test inherits another's tuning."""
    autotune.clear_schedules()
    autotune.clear_log()
    yield
    autotune.clear_schedules()
    autotune.clear_log()


def _pack_tiles(M):
    from repro.core.decomposition import pack_bits

    nr, nc = M.shape[:2]
    return jnp.stack([
        jnp.stack([pack_bits(M[r, c]) for c in range(nc)]) for r in range(nr)
    ])


def _operands(key, nr, nc, tn, K, td, T, E=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    lead = (E,) if E else ()
    M = jnp.sign(jax.random.normal(k1, (*lead, nr, nc, tn, K)))
    M = jnp.where(M == 0, 1.0, M)
    mp = (jnp.stack([_pack_tiles(M[e]) for e in range(E)]) if E
          else _pack_tiles(M))
    C = (jax.random.normal(k2, (*lead, nr, nc, K, td)) * 0.3).astype(dtype)
    x = jax.random.normal(k3, (*lead, T, nr * tn)).astype(dtype)
    return x, mp, C


# ---------------------------------------------------------------------------
# keys / schedules / heuristics
# ---------------------------------------------------------------------------


def test_schedule_dict_roundtrip():
    s = Schedule(mode="grid", math="bitplane", block_t=64, r_chunk=4)
    assert Schedule.from_dict(s.to_dict()) == s
    assert s.kwargs() == {
        "mode": "grid", "math": "bitplane", "block_t": 64, "r_chunk": 4,
    }
    # missing optional fields take the defaults (forward-compatible tables)
    assert Schedule.from_dict({"mode": "jnp"}) == Schedule(mode="jnp")


def test_t_bucket():
    assert [autotune.t_bucket(t) for t in (1, 2, 3, 16, 17, 129)] == \
        [1, 2, 4, 16, 32, 256]
    assert autotune.t_bucket(100_000) == 512   # capped


def test_schedule_key_embeds_device_and_buckets_T():
    k1 = autotune.schedule_key(
        "bitlinear", n_r=2, n_c=2, tn=16, K=4, td=32, T=3, dtype=jnp.float32
    )
    k2 = autotune.schedule_key(
        "bitlinear", n_r=2, n_c=2, tn=16, K=4, td=32, T=4, dtype=jnp.float32
    )
    assert k1 == k2                          # same bucket
    assert autotune.device_kind() in k1
    assert autotune.pallas_mode() in k1
    k3 = autotune.schedule_key(
        "bitlinear", n_r=2, n_c=2, tn=16, K=4, td=32, T=3, dtype=jnp.bfloat16
    )
    assert k1 != k3                          # dtype is part of the key


def test_heuristic_interpret_is_jnp():
    s = autotune.heuristic(
        "bitlinear", n_r=2, n_c=2, tn=16, kb=1, K=4, td=32, T=4,
        x_itemsize=4, c_itemsize=4, interpret=True,
    )
    assert s.mode == "jnp"


def test_heuristic_compiled_decode_then_grid():
    small = dict(n_r=2, n_c=2, tn=16, kb=1, K=4, td=32,
                 x_itemsize=4, c_itemsize=4, interpret=False)
    assert autotune.heuristic("bitlinear", T=4, **small).mode == "decode"
    # a token count past one block forces the pipelined grid, with the
    # r-reduction chunked to a divisor of n_r
    big = autotune.heuristic(
        "bitlinear", n_r=48, n_c=4, tn=32, kb=1, K=8, td=128, T=512,
        x_itemsize=4, c_itemsize=4, interpret=False,
    )
    assert big.mode == "grid" and 48 % big.r_chunk == 0 and big.r_chunk > 1


# ---------------------------------------------------------------------------
# resolve: cache vs heuristic, resolution log
# ---------------------------------------------------------------------------


def test_resolve_heuristic_then_cache_hit():
    sig = dict(n_r=2, n_c=2, tn=16, kb=1, K=4, td=32, T=3, dtype=jnp.float32)
    s0 = autotune.resolve("bitlinear", **sig)
    log = autotune.last_resolutions()
    assert log[-1]["source"] == "heuristic"
    assert log[-1]["schedule"] == s0.to_dict()

    key = autotune.schedule_key(
        "bitlinear", n_r=2, n_c=2, tn=16, K=4, td=32, T=3, dtype=jnp.float32
    )
    tuned = Schedule(mode="grid", math="bitplane", block_t=64, r_chunk=2)
    n = autotune.load_schedules({
        "format": autotune.SCHEDULES_FORMAT,
        "entries": {key: tuned.to_dict()},
    })
    assert n == 1
    assert autotune.resolve("bitlinear", **sig) == tuned
    assert autotune.last_resolutions()[-1]["source"] == "cache"


def test_load_schedules_rejects_unknown_format():
    with pytest.raises(ValueError, match="format"):
        autotune.load_schedules({"format": "bogus/v9", "entries": {}})


def test_export_load_roundtrip():
    key = autotune.schedule_key(
        "bitlinear_grouped", n_r=1, n_c=1, tn=8, K=3, td=16, T=1,
        dtype=jnp.bfloat16, E=4,
    )
    autotune.load_schedules({
        "format": autotune.SCHEDULES_FORMAT,
        "entries": {key: Schedule("decode", "bitplane").to_dict()},
    })
    table = autotune.export_schedules()
    assert table["format"] == autotune.SCHEDULES_FORMAT
    autotune.clear_schedules()
    assert autotune.load_schedules(table) == 1
    sig = dict(n_r=1, n_c=1, tn=8, kb=1, K=3, td=16, T=1,
               dtype=jnp.bfloat16, E=4)
    assert autotune.resolve("bitlinear_grouped", **sig) == \
        Schedule("decode", "bitplane")


# ---------------------------------------------------------------------------
# timed search
# ---------------------------------------------------------------------------


def test_tune_returns_valid_best_and_trials():
    x, mp, C = _operands(jax.random.PRNGKey(0), 2, 2, 16, 4, 32, T=4)
    best, trials = autotune.tune(x, mp, C, repeats=1, iters=2)
    assert best.mode in ("jnp", "grid", "decode")
    timed = [t for t in trials if "seconds" in t]
    assert len(timed) >= 2
    assert best.to_dict() in [t["schedule"] for t in timed]
    # the winner's measured time is the minimum of the timed trials
    assert min(t["seconds"] for t in timed) == \
        [t for t in timed if t["schedule"] == best.to_dict()][0]["seconds"]


def test_tune_grouped_routes_by_ndim():
    x, mp, C = _operands(jax.random.PRNGKey(1), 1, 2, 8, 3, 16, T=2, E=3)
    best, trials = autotune.tune(
        x, mp, C, repeats=1, iters=2,
        schedules=[Schedule("jnp", "dot"), Schedule("grid", "unpack"),
                   Schedule("jnp", "bitplane")],
    )
    # 3D operands time the grouped kernel: every schedule ran, none failed
    assert best.mode in ("jnp", "grid")
    assert [t["schedule"]["mode"] for t in trials] == ["jnp", "grid", "jnp"]
    assert all("seconds" in t for t in trials)
    # a mode no kernel has is refused up front, not skipped in silence
    with pytest.raises(ValueError, match="unknown bitlinear mode"):
        autotune.tune(x, mp, C, repeats=1, iters=1,
                      schedules=[Schedule("stream", "unpack")])


# ---------------------------------------------------------------------------
# probe-then-serve round trip
# ---------------------------------------------------------------------------


def _compressed_model(key, arch="qwen3-32b"):
    from repro import compression as comp

    cfg = reduced_for_smoke(get_config(arch))
    cfg = dataclasses.replace(cfg, dtype="float32")
    vals, _ = split(init_model(key, cfg))
    policy = comp.CompressionPolicy(
        method="alternating", tile_n=16, tile_d=32, rank_ratio=0.5,
        min_size=4096,
    )
    plan = comp.plan_compression(vals, policy)
    cvals, artifact = comp.execute_plan(plan, vals, key=key)
    return cfg, cvals, artifact


def test_tune_artifact_engine_roundtrip(key):
    """The full probe-then-serve contract: tune_artifact persists winners
    into the manifest, a fresh Engine restores them, and the engine's
    prefill/decode traces resolve every fused call from the cache (source
    "cache" in the resolution log) — serving never re-tunes."""
    cfg, cvals, artifact = _compressed_model(key)
    batch, prompt = 3, 8
    # T buckets the engine will hit: decode flattens x to (batch, d) and
    # prefill to (batch*prompt, d) — cover exactly those
    table = autotune.tune_artifact(
        artifact, T_values=(batch, batch * prompt), repeats=1, iters=2,
        schedules=[Schedule("jnp", "dot"), Schedule("jnp", "unpack")],
    )
    assert table["format"] == autotune.SCHEDULES_FORMAT
    assert len(table["entries"]) > 0
    assert artifact.manifest["kernel_schedules"] is table
    for entry in table["entries"].values():
        Schedule.from_dict(entry)   # every entry is a valid schedule

    # a fresh process would start cold: drop the tuner's in-process cache
    # and prove the Engine restores it from the manifest alone
    autotune.clear_schedules()
    eng = Engine(cfg, cvals, max_len=24, batch=batch, artifact=artifact)
    assert eng.kernel_schedules == len(table["entries"])
    assert eng.compression["kernel_schedules"] == len(table["entries"])

    autotune.clear_log()
    prompts = jax.random.randint(key, (batch, prompt), 0, cfg.vocab_size)
    eng.generate(prompts, steps=3)
    log = autotune.last_resolutions()
    assert log, "fused traces resolved no schedules"
    assert all(r["source"] == "cache" for r in log), \
        [r for r in log if r["source"] != "cache"]
    assert {r["key"] for r in log} <= set(table["entries"])


def test_engine_without_schedules_uses_heuristic(key):
    cfg, cvals, artifact = _compressed_model(key)
    assert "kernel_schedules" not in artifact.manifest
    eng = Engine(cfg, cvals, max_len=24, batch=2, artifact=artifact)
    assert eng.kernel_schedules == 0
    autotune.clear_log()
    prompts = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    eng.generate(prompts, steps=2)
    log = autotune.last_resolutions()
    assert log and all(r["source"] == "heuristic" for r in log)
