"""granite-4.0-h-small (Mamba2 + MoE hybrid) at a small size on the CPU,
in float32 on seeded weights, against the plain reference
``bench/hybrid_reference.py``: the forward, prefill then decode through
``Engine``/``Scheduler``, the expert layer that holds a share, the named
scopes of its parts, and granite-moe's forward as it was before the
hybrid's options came in."""

import dataclasses
import json
import pathlib
import re
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_for_smoke
from repro.models import forward, init_cache, init_model, moe
from repro.models.params import split

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import cells, hybrid_reference, serve_hybrid, weights  # noqa: E402

CONF = json.loads((ROOT / "bench/configs/granite-4.0-h-small.json").read_text())


def tiny_conf(**kw) -> dict:
    """The configuration file narrowed to CPU size, every mapped key (the
    multipliers, NoPE, the shared expert's width, the held experts) kept
    in play; widths as ratios of the published ones."""
    conf = json.loads(json.dumps(CONF))
    conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=32, shared_intermediate_size=48,
                mamba_d_head=16, mamba_n_heads=8, mamba_d_state=16,
                mamba_chunk_size=16, vocab_size=97, torch_dtype="float32",
                num_local_experts=8, num_experts_per_tok=3)
    conf.update(kw)
    return conf


def tiny_cfg(conf: dict, router: int = 8, first: int = 0):
    """The program's ModelConfig of ``conf`` (the file's own key map), with
    a router of ``router`` experts of which the file's
    ``num_local_experts`` from ``first`` are held here."""
    cfg = cells.model_config(conf)
    return dataclasses.replace(cfg, num_experts=router, first_expert_held=first,
                               head_dim=conf["hidden_size"] // conf["num_attention_heads"])


def reference_weights(vals, layer_types) -> dict:
    """The reference's weights from the program's dense tree."""
    flat = dict(weights.paths(vals))
    return serve_hybrid.reference_weights(layer_types, lambda p: flat[p][0], flat)


def seeded(cfg, seed: int):
    """The program's tree with every leaf drawn from the seed: norms and
    vectors too, so that no part of a layer is an identity."""
    vals, _ = split(init_model(jax.random.PRNGKey(seed), cfg))
    leaves, treedef = jax.tree_util.tree_flatten(vals)
    key = jax.random.PRNGKey(seed + 1)
    out = []
    for i, x in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if x.ndim == 1 or (x.ndim == 2 and x.shape[0] == 1):   # vectors, norms
            x = x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def rel_err(z, ref):
    z, ref = np.asarray(z, np.float64), np.asarray(ref, np.float64)
    return np.linalg.norm(z - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


# Tolerance of the float32 comparisons: the program computes the Mamba2
# mixer in chunks (the SSD form) and attention blockwise, the reference
# position by position at ``highest``; through ten layers the logits differ
# by ~1e-6 of their norm.  1e-4 leaves room and lies far below what one
# multiplier, the scale or a held expert left out moves (> 1e-2).
TOL = 1e-4


def test_the_forward_matches_the_reference():
    """The whole published period (9 Mamba2 + 1 attention layers) at a
    chip's share of 4 experts of a 16-wide router from expert 8, 35
    positions (the SSD pads 35 to 48 inside)."""
    conf = tiny_conf(num_local_experts=4)
    cfg = tiny_cfg(conf, router=16, first=8)
    assert cfg.block_pattern[5] == "attn_moe" and cfg.num_groups == 1
    vals = seeded(cfg, 3)
    toks = jax.random.randint(jax.random.PRNGKey(4), (35,), 0, cfg.vocab_size)
    z, _, _ = forward(vals, {"tokens": toks[None]}, cfg)
    ref = hybrid_reference.logits(conf, reference_weights(vals, conf["layer_types"]),
                                  np.asarray(toks), 48, first=8)
    assert rel_err(z[0], ref).max() < TOL
    # the reference tells the multipliers apart
    other = dict(conf, residual_multiplier=1.0)
    ref1 = hybrid_reference.logits(other, reference_weights(vals, conf["layer_types"]),
                                   np.asarray(toks), 48, first=8)
    assert rel_err(z[0], ref1).min() > 1e-2


def test_prefill_then_decode_matches_the_references_full_forward():
    """Prefill 20 positions into the cache, then decode 6, against the
    reference's forward over all 26 (logits, not tokens)."""
    conf = tiny_conf()
    cfg = tiny_cfg(conf)
    vals = seeded(cfg, 5)
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 26), 0, cfg.vocab_size)
    cache = init_cache(cfg, 1, 32)
    z, cache, _ = forward(vals, {"tokens": toks[:, :20]}, cfg, cache=cache,
                          pos_offset=0)
    got = [z[0]]
    for t in range(20, 26):
        z, cache, _ = forward(vals, {"tokens": toks[:, t:t + 1]}, cfg,
                              cache=cache, pos_offset=t)
        got.append(z[0])
    ref = hybrid_reference.logits(conf, reference_weights(vals, conf["layer_types"]),
                                  np.asarray(toks[0]), 32)
    assert rel_err(jnp.concatenate(got), ref).max() < TOL


def test_the_shares_of_eight_chips_add_up_to_the_whole_layer():
    """Eight chips of 2 experts each (16 experts, top 4): the sum of their
    layers' outputs, the shared expert counted once, is the uncut
    reference layer's output.  float32 on both sides; 1e-5 of the norm is
    the order of summation."""
    conf = tiny_conf(num_local_experts=16, num_experts_per_tok=4)
    whole = tiny_cfg(conf, router=16)
    vals = seeded(whole, 7)
    p = jax.tree.map(lambda a: a[0], vals["groups"]["0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, whole.d_model))
    shared = moe.layers.mlp(x, p["shared"])
    total = -7 * shared
    for chip in range(8):
        cfg = dataclasses.replace(whole, num_experts_held=2,
                                  first_expert_held=2 * chip)
        part = {**p, **{n: p[n][2 * chip:2 * chip + 2] for n in ("gate", "up", "down")}}
        out, _ = moe.moe_block(x, part, cfg)
        total = total + out
    lw = {"router": p["router"], "gate": p["gate"], "up": p["up"], "down": p["down"],
          **{f"shared_{n}": p["shared"][n]["w"] for n in ("gate", "up", "down")}}
    sz = hybrid_reference._sizes(conf)
    with jax.default_matmul_precision("highest"):
        ref = jax.vmap(lambda r: hybrid_reference._moe(r, lw, sz, 0, False))(x)
    assert rel_err(total, ref).max() < 1e-5
    # one chip alone is not the layer
    assert rel_err(out, ref).min() > 1e-2


SCOPES = ("ssm.mixer", "moe.route", "moe.held_experts", "moe.shared_expert")


def test_the_new_parts_carry_their_scopes_in_the_compiled_module():
    conf = tiny_conf(layer_types=["mamba", "attention"], num_hidden_layers=2)
    cfg = tiny_cfg(conf, router=16)
    vals = seeded(cfg, 9)
    toks = jnp.zeros((1, 8), jnp.int32)
    text = jax.jit(lambda v, t: forward(v, {"tokens": t}, cfg)[0]).lower(
        vals, toks).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert any(scope in n.split("/") for n in names), scope


def test_granite_moe_forward_is_as_it_was():
    """granite-moe keeps every multiplier at identity, RoPE and 1/sqrt(hd):
    its forward reads the numbers it read before the hybrid's options came
    in (recorded from that tree, float32 on the CPU)."""
    cfg = reduced_for_smoke(get_config("granite-moe-1b-a400m"))
    p = init_model(jax.random.PRNGKey(7), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 24), 0, cfg.vocab_size)
    z, _, aux = forward(p, {"tokens": toks}, cfg)
    np.testing.assert_allclose(np.asarray(z[0, :3, :4]), [
        [-0.567411721, 1.08516705, -0.193905294, 0.181515306],
        [1.379416704, 0.100989863, -1.277649999, 0.330008537],
        [-0.075683549, -1.880920768, -0.565469027, 0.046849493]], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(z[1, -1, -4:]), [
        1.600858808, -0.601407349, -1.07498014, 0.842898905], rtol=1e-6)
    np.testing.assert_allclose(float(jnp.abs(z).sum()), 9835.52056636392, rtol=1e-6)
    np.testing.assert_allclose(float(aux), 4.161459922790527, rtol=1e-6)
    cache = init_cache(cfg, 2, 24)
    _, cache, _ = forward(p, {"tokens": toks[:, :23]}, cfg, cache=cache, pos_offset=0)
    dec, _, _ = forward(p, {"tokens": toks[:, 23:]}, cfg, cache=cache, pos_offset=23)
    np.testing.assert_allclose(np.asarray(dec[:, 0, :4]), [
        [1.176939368, 1.014580488, 0.769343972, 0.651496351],
        [-0.556914866, 0.004737476, -1.071681619, -0.394652098]], rtol=1e-6)


def test_the_registry_holds_the_published_sizes():
    cfg = get_config("granite-4.0-h-small")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.vocab_size) == (40, 4096, 32, 8, 128, 100352)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token, cfg.d_ff,
            cfg.d_ff_shared) == (72, 72, 10, 768, 1536)
    assert (cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state,
            cfg.ssm_ngroups, cfg.ssm_dconv, cfg.ssm_chunk) == (8192, 128, 64, 128, 1, 4, 256)
    assert cfg.block_pattern.count("ssm_moe") == 9 and cfg.num_groups == 4
    assert cfg.attention_scale == 1 / 128 and cfg.position_embedding_type == "nope"
    run = cells.model_config(CONF)
    assert (run.num_layers, run.num_groups, run.experts_held, run.num_experts) == \
        (10, 1, 9, 72)


def test_layer_types_is_the_one_source_of_the_block_structure():
    cfg = get_config("granite-4.0-h-small")
    assert dataclasses.replace(cfg, num_layers=10).block_pattern == cfg.block_pattern
    with pytest.raises(ValueError, match="not both"):
        dataclasses.replace(cfg, block_pattern=("ssm",))


def tiny_serve_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A checkout whose bench holds the hybrid narrowed to a Mamba2 and an
    attention layer of 4 held experts of 8, in float32, and the serve-chat
    mix cut to 4 slots of 64 positions."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = tiny_conf(layer_types=["mamba", "attention"], num_hidden_layers=2,
                     num_local_experts=4, hidden_size=128, mamba_n_heads=16)
    (root / "bench/configs/tiny-hybrid.json").write_text(json.dumps(conf))
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
    return root


def test_the_served_hybrid_matches_the_reference(tmp_path, monkeypatch):
    """The hybrid compressed and served through ``Engine`` (fused
    bit-linear kernel, interpret mode) and ``Scheduler`` (paged KV for the
    attention layer beside the resident Mamba2 state, exact-length
    prefill), then every served position of a sample of requests against
    the reference's full forward on the same decoded M C.  float32 on both
    sides: the order of summation and the chunked against the stepwise
    Mamba2, well under 1e-4 of a logit vector's norm; the float8 control
    reads above 1e-2."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    root = tiny_serve_root(tmp_path)
    conf = json.loads((root / "bench/configs/tiny-hybrid.json").read_text())
    mix = json.loads((root / "bench/traffic/tiny-chat.json").read_text())
    cell = cells.Cell(name="tiny-hybrid.tiny-chat", workload={"chips": 1},
                      config=conf, traffic=mix, limits={}, end_to_end=[],
                      per_layer=[], root=root)
    import bench.serve

    monkeypatch.setattr(bench.serve, "model_config",
                        lambda c: tiny_cfg(c, router=8, first=4))
    drv = serve_hybrid.HybridServeCell(cell, seed=21)
    assert drv.cfg.first_expert_held == 4 and drv.cfg.experts_held == 4
    drv.setup(trace=False)
    w = drv.window(1.0, None)
    samples = drv.sample(w)
    drv.free()
    assert sum(len(s[1]) for s in samples) >= 10
    got = drv.check(samples, control=True)
    assert got["logit_err_max"] < TOL, got
    assert got["token_gap"] == 0.0
    assert got["control_logit_err_p50"] > 1e-2
    assert got["c_gap"] < 1e-3
