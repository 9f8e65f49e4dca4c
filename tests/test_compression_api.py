"""Plan/execute compression API: policy rules, pooled execution equivalence,
plan serialisation, manifest artifact."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compression as comp
from repro.compression.plan import tree_paths
from repro.configs.base import CompressionConfig
from repro.core import quantized
from repro.core.compress import compress_matrix, pick_tile
from repro.launch.mesh import make_mesh


def small_values():
    """Mixed tree: two 2D tensors sharing tile geometry, one 3D stack, one
    excluded-by-token tensor, one too-small tensor."""
    return {
        "blk": {
            "attn": {
                "wq": {"w": jax.random.normal(jax.random.PRNGKey(1), (64, 64))},
                "wo": {"w": jax.random.normal(jax.random.PRNGKey(2), (64, 64))},
                "norm": {"scale": jnp.ones((64,))},
            },
            "mlp": {
                "experts": {"w": jax.random.normal(jax.random.PRNGKey(3), (2, 64, 128))},
                "tiny": {"w": jax.random.normal(jax.random.PRNGKey(4), (8, 8))},
            },
        },
    }


def base_policy(**kw):
    kw.setdefault("method", "alternating")
    kw.setdefault("tile_n", 16)
    kw.setdefault("tile_d", 32)
    kw.setdefault("rank_ratio", 0.25)
    kw.setdefault("min_size", 1024)
    return comp.CompressionPolicy(**kw)


# ---------------------------------------------------------------------------
# pick_tile (all-divisor search)
# ---------------------------------------------------------------------------

def test_pick_tile_searches_all_divisors():
    assert pick_tile(48, 32) == 24          # not in the old {32,16,8,64} ladder
    assert pick_tile(12, 8) == 6
    assert pick_tile(100, 32) == 25
    assert pick_tile(64, 32) == 32          # exact divisor still wins
    assert pick_tile(3, 8) is None          # no divisor >= 4
    assert pick_tile(7, 8) == 7             # near-want prime uses the whole dim
    assert pick_tile(96, 8, max_tile=16) in (8,)   # cap honoured
    # candidates stay inside the legacy [want/4, want*4] envelope: a far-off
    # divisor (1018 = 2 * 509) would make K scale with the dim and blow up
    # alternating's 2^K row enumeration -> skip instead
    assert pick_tile(1018, 32) is None
    assert pick_tile(128, 32) == 32
    assert pick_tile(8, 32) == 8            # want/4 boundary still allowed


def test_plan_min_size_gates_on_slice_size():
    """(G, d_in, d_out) stacks are G independent problems: the gate is the
    slice size, exactly as the legacy per-slice compress_matrix applied it."""
    values = {"experts": {"w": jnp.zeros((64, 16, 16))}}   # leaf 16384, slice 256
    plan = comp.plan_compression(values, base_policy(min_size=1024))
    assert plan.tensors == ()
    assert dict(plan.skipped)["experts/w"] == "below min_size"


def test_plan_emits_distinct_skip_reasons():
    """The skip report separates the three miss classes: a matrix the
    policy never targeted, a targeted one below min_size, and a targeted
    one with indivisible dims.  (MoE expert stacks used to fall silently
    into the first class — now they are targets by default and the report
    names whatever still misses.)"""
    values = {
        "blk": {
            "proj": {"kernel": jax.random.normal(jax.random.PRNGKey(0), (64, 64))},
            "tiny": {"w": jnp.zeros((8, 8))},
            "odd": {"w": jnp.zeros((257, 64))},          # 257 prime, no divisor
            "moe": {"gate": jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))},
        },
    }
    plan = comp.plan_compression(values, base_policy(min_size=1024))
    skipped = dict(plan.skipped)
    assert skipped["blk/proj/kernel"] == "not matched by policy"
    assert skipped["blk/tiny/w"] == "below min_size"
    assert skipped["blk/odd/w"].startswith("indivisible dims")
    # the expert stack is a target: planned, not lumped into any miss bucket
    assert [t.path for t in plan.tensors] == ["blk/moe/gate"]
    # skip_summary aggregates the distinct reasons; the printable plan
    # surfaces it plus the predicted-bytes totals (the CLI summary line)
    summary = plan.skip_summary()
    assert summary["not matched by policy"] == 1
    assert summary["below min_size"] == 1
    assert sum(summary.values()) == len(plan.skipped)
    text = plan.summary()
    assert "skips: " in text and "below min_size x1" in text
    assert plan.tensors[0].groups == 2


def test_plan_total_bytes_helpers():
    plan = comp.plan_compression(small_values(), base_policy())
    assert plan.total_bytes() == sum(t.pred_bytes for t in plan.tensors)
    assert plan.compression_ratio == pytest.approx(
        plan.total_orig_bytes / plan.total_bytes()
    )
    _, artifact = comp.execute_plan(plan, small_values(),
                                    key=jax.random.PRNGKey(0))
    assert artifact.total_bytes() == artifact.manifest["totals"]["new_bytes"]
    assert artifact.compression_ratio == artifact.total_ratio
    # plan-predicted bytes equal executed bytes (the budget contract)
    assert plan.total_bytes() == artifact.total_bytes()


def test_plan_covers_bfloat16_and_shape_structs():
    """bfloat16 (the default model dtype — a void type to numpy) must plan,
    including over ShapeDtypeStruct trees (the dry-run planning input)."""
    pol = base_policy(min_size=1024)
    for leaf in (jnp.zeros((64, 64), jnp.bfloat16),
                 jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)):
        plan = comp.plan_compression({"blk": {"wq": {"w": leaf}}}, pol)
        assert [t.path for t in plan.tensors] == ["blk/wq/w"], plan.summary()
        assert plan.tensors[0].dtype == "bfloat16"
    # integer leaves stay silently outside the report universe
    plan = comp.plan_compression(
        {"idx": {"w": jnp.zeros((64, 64), jnp.int32)}}, pol
    )
    assert plan.tensors == () and plan.skipped == ()


def test_policy_targets_are_policy_data():
    """Targets serialise with the policy and scoping them changes
    eligibility without touching code."""
    pol = base_policy(targets=(r"/w$",))
    assert not pol.matches_target("blk/moe/gate")
    assert comp.CompressionPolicy.from_json(pol.to_json()) == pol
    values = {"moe": {"gate": jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64))}}
    plan = comp.plan_compression(values, pol)
    assert plan.tensors == ()
    assert dict(plan.skipped)["moe/gate"] == "not matched by policy"
    # default policy targets expert stacks
    plan2 = comp.plan_compression(values, base_policy(min_size=1024))
    assert [t.path for t in plan2.tensors] == ["moe/gate"]
    with pytest.raises(Exception):
        comp.CompressionPolicy(targets=("[unclosed",))


def test_plan_reports_chosen_tile_for_awkward_dims():
    values = {"odd": {"w": jax.random.normal(jax.random.PRNGKey(0), (48, 96))}}
    plan = comp.plan_compression(values, base_policy(tile_n=32, tile_d=64))
    (t,) = plan.tensors
    assert (t.tile_n, t.tile_d) == (24, 96) or (t.tile_n, t.tile_d) == (24, 48), t
    assert not plan.skipped


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------

def test_policy_rule_precedence_first_match_wins():
    pol = base_policy(rules=(
        comp.CompressionRule(pattern=r"attn/wq", method="greedy", tile_d=16),
        comp.CompressionRule(pattern=r"attn", method="bbo"),
        comp.CompressionRule(pattern=r"experts", method="skip"),
    ))
    s = pol.resolve("blk/attn/wq/w")
    assert s.method == "greedy" and s.tile_d == 16
    assert s.tile_n == 16                   # unset field inherits the default
    assert pol.resolve("blk/attn/wo/w").method == "bbo"
    assert pol.resolve("blk/mlp/experts/w") is None
    assert "skip" in pol.skip_reason("blk/mlp/experts/w")


def test_policy_exclude_tokens():
    pol = base_policy()
    assert pol.resolve("blk/attn/norm/scale") is None
    assert "excluded" in pol.skip_reason("blk/attn/norm/scale")
    # exclusion is itself policy: clearing it re-enables the path
    pol2 = base_policy(exclude=())
    assert pol2.resolve("blk/attn/norm/scale") is not None


def test_policy_json_roundtrip():
    pol = base_policy(rules=(
        comp.CompressionRule(pattern=r"experts", rank_ratio=0.5),
        comp.CompressionRule(pattern=r"wo/w$", method="skip"),
    ))
    assert comp.CompressionPolicy.from_json(pol.to_json()) == pol
    # json form is plain data (editable / checked in)
    d = json.loads(pol.to_json())
    assert d["rules"][0]["pattern"] == "experts"


def test_policy_validation():
    with pytest.raises(ValueError):
        comp.CompressionRule(pattern=r"x", method="annealing")
    with pytest.raises(ValueError):
        comp.CompressionPolicy(method="skip")


def test_config_to_policy_adapter():
    ccfg = CompressionConfig(tile_n=16, tile_d=32, rank_ratio=0.25,
                             min_size=1024, optimizer="greedy")
    pol = ccfg.to_policy()
    assert pol.method == "greedy" and pol.tile_n == 16 and pol.rules == ()


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

def test_plan_is_pure_and_json_roundtrips():
    values = small_values()
    plan = comp.plan_compression(values, base_policy())
    paths = [t.path for t in plan.tensors]
    assert paths == ["blk/attn/wo/w", "blk/attn/wq/w", "blk/mlp/experts/w"]
    assert dict(plan.skipped)["blk/mlp/tiny/w"] == "below min_size"
    # all three tensors share (16, 32, K=4, alternating) -> ONE pool
    pools = plan.pools()
    assert len(pools) == 1
    ((key, members),) = pools.items()
    assert key == (16, 32, 4, "alternating", 0)
    # wq/wo: (64/16)*(64/32) = 8 tiles each; experts: 2*(64/16)*(128/32) = 32
    assert sum(m.num_tiles for m in members) == 8 + 8 + 32
    plan2 = comp.CompressionPlan.from_json(plan.to_json())
    assert plan2 == plan
    assert plan.diff(plan2) == []
    # an attached autotune metadata block survives the round trip (and its
    # absence keeps the JSON form unchanged: no "autotune" key above)
    assert "autotune" not in plan.to_dict()
    import dataclasses as _dc

    tuned = _dc.replace(plan, autotune={"budget_bytes": 123, "engine": "greedy"})
    assert comp.CompressionPlan.from_json(tuned.to_json()) == tuned
    # the printable form tolerates a partial autotune block
    assert "autotune[greedy]" in tuned.summary()


def test_plan_predicted_bytes_match_executed_bytes():
    values = small_values()
    plan = comp.plan_compression(values, base_policy())
    cvals, _ = comp.execute_plan(plan, values)
    leaves = dict(tree_paths(cvals))
    for t in plan.tensors:
        w = {"m_packed": leaves[t.path + "/m_packed"], "C": leaves[t.path + "/C"]}
        assert t.pred_bytes == quantized.compressed_num_bytes(w), t.path
        assert t.orig_bytes == int(np.prod(t.shape)) * np.dtype(t.dtype).itemsize


def test_plan_diff_reports_changes():
    values = small_values()
    a = comp.plan_compression(values, base_policy())
    b = comp.plan_compression(values, base_policy(rank_ratio=0.5))
    d = a.diff(b)
    assert len(d) == 3 and all("K" in line for line in d)


# ---------------------------------------------------------------------------
# execute: pooled == legacy per-tensor, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["greedy", "alternating"])
def test_pooled_execute_bit_exact_vs_per_tensor(method):
    """The acceptance contract: pooling tiles across tensors into one batch
    must not change a single bit vs compressing each tensor alone with the
    legacy ``compress_matrix`` walk (same per-tile keys, same vmapped ops)."""
    values = small_values()
    key = jax.random.PRNGKey(42)
    pol = base_policy(method=method)
    plan = comp.plan_compression(values, pol)
    cvals, _ = comp.execute_plan(plan, values, key=key)
    got = dict(tree_paths(cvals))
    ccfg = CompressionConfig(tile_n=16, tile_d=32, rank_ratio=0.25,
                             min_size=1024, optimizer=method)
    leaves = dict(tree_paths(values))
    for t in plan.tensors:
        k = jax.random.fold_in(key, t.leaf_index)
        leaf = leaves[t.path]
        if len(t.shape) == 2:
            w, _ = compress_matrix(leaf, ccfg, k)
        else:
            ws = [
                compress_matrix(leaf[g], ccfg, jax.random.fold_in(k, g))[0]
                for g in range(t.shape[0])
            ]
            w = jax.tree.map(lambda *xs: jnp.stack(xs), *ws)
        np.testing.assert_array_equal(
            np.asarray(w["m_packed"]), np.asarray(got[t.path + "/m_packed"]),
            err_msg=t.path,
        )
        np.testing.assert_array_equal(
            np.asarray(w["C"]), np.asarray(got[t.path + "/C"]), err_msg=t.path,
        )


def test_compress_params_wrapper_matches_execute_plan():
    values = small_values()
    key = jax.random.PRNGKey(3)
    ccfg = CompressionConfig(enabled=True, tile_n=16, tile_d=32,
                             rank_ratio=0.25, min_size=1024)
    from repro.core.compress import compress_params

    cvals, report = compress_params(values, None, ccfg, key)
    plan = comp.plan_compression(values, ccfg.to_policy())
    cvals2, artifact = comp.execute_plan(plan, values, key=key)
    a, b = dict(tree_paths(cvals)), dict(tree_paths(cvals2))
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert [c[0] for c in report.compressed] == \
        [c[0] for c in artifact.report.compressed]


def test_execute_bbo_seed_deterministic_and_pools():
    """BBO pools run lock-step per pool: deterministic per (plan, seed), and
    the manifest records the pooled solver batch (== tiles in the pool)."""
    values = {
        "a": {"w": jax.random.normal(jax.random.PRNGKey(5), (16, 32))},
        "b": {"w": jax.random.normal(jax.random.PRNGKey(6), (16, 64))},
    }
    pol = comp.CompressionPolicy(method="bbo", tile_d=16, rank_ratio=0.375,
                                 min_size=1, bbo_iters=4)
    plan = comp.plan_compression(values, pol)
    cvals1, art1 = comp.execute_plan(plan, values, key=jax.random.PRNGKey(7))
    cvals2, art2 = comp.execute_plan(plan, values, key=jax.random.PRNGKey(7))
    a, b = dict(tree_paths(cvals1)), dict(tree_paths(cvals2))
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    # one (8, 16, K=3, bbo) pool over both tensors: 2*2 + 2*4 = 12 tiles
    assert art1.solver_batches() == [12]
    assert art1.manifest["pools"][0]["num_tensors"] == 2


def test_chunked_pool_bit_exact_and_recorded():
    """max_pool_tiles bounds the per-solve batch without changing
    greedy/alternating results (per-tile keys make chunking invisible)."""
    values = small_values()
    key = jax.random.PRNGKey(9)
    plan = comp.plan_compression(values, base_policy())
    a, art_a = comp.execute_plan(plan, values, key=key)
    b, art_b = comp.execute_plan(plan, values, key=key, max_pool_tiles=10)
    fa, fb = dict(tree_paths(a)), dict(tree_paths(b))
    for k in fa:
        np.testing.assert_array_equal(np.asarray(fa[k]), np.asarray(fb[k]))
    assert art_a.manifest["pools"][0]["chunks"] == 1
    assert art_b.manifest["pools"][0]["chunks"] == 5      # ceil(48 / 10)


def test_rule_bbo_iters_flows_into_pools():
    """A rule's bbo_iters override must reach the solver: tensors with
    different budgets form different pools, each run at its own budget."""
    values = {
        "a": {"w": jax.random.normal(jax.random.PRNGKey(1), (16, 32))},
        "b": {"w": jax.random.normal(jax.random.PRNGKey(2), (16, 32))},
    }
    pol = comp.CompressionPolicy(
        method="bbo", tile_d=16, rank_ratio=0.375, min_size=1, bbo_iters=2,
        rules=(comp.CompressionRule(pattern=r"a/", bbo_iters=6),),
    )
    plan = comp.plan_compression(values, pol)
    by_path = {t.path: t for t in plan.tensors}
    assert by_path["a/w"].bbo_iters == 6 and by_path["b/w"].bbo_iters == 2
    assert len(plan.pools()) == 2
    _, art = comp.execute_plan(plan, values)
    stats = {p["bbo_iters"]: p for p in art.manifest["pools"]}
    assert set(stats) == {2, 6}
    assert stats[6]["solver_calls"] == 6 and stats[2]["solver_calls"] == 2


def test_ragged_final_chunk_recorded():
    """solver_batches() reports the per-call batch sizes, including a final
    chunk smaller than the bound."""
    values = {"a": {"w": jax.random.normal(jax.random.PRNGKey(3), (24, 32))}}
    pol = comp.CompressionPolicy(method="bbo", tile_d=16, rank_ratio=0.375,
                                 min_size=1, bbo_iters=2)
    plan = comp.plan_compression(values, pol)     # 3 * 2 = 6 tiles
    _, art = comp.execute_plan(plan, values, max_pool_tiles=4)
    assert art.manifest["pools"][0]["chunk_sizes"] == [4, 2]
    assert art.solver_batches() == [4, 2]


def test_auto_pool_chunk_memory_model():
    """max_pool_tiles="auto" sizes BBO solve batches from the surrogate
    memory model: whole pool when it fits the budget, even split when not,
    never below the batched-solver floor."""
    from repro.compression.execute import auto_pool_chunk, surrogate_tile_bytes

    per = surrogate_tile_bytes(8, 3, 64)     # n = 24 spins, p = 301 features
    assert 1_000_000 < per < 1_300_000       # Gram + temporaries ~ 1.1 MB
    # bench pool (512 tiles of 8x16 K=3) fits a 1 GiB budget in one batch
    assert auto_pool_chunk(512, 8, 3, 64, budget_bytes=1 << 30) == 512
    # over budget: even split so at most two chunk shapes compile
    chunk = auto_pool_chunk(1000, 8, 3, 64, budget_bytes=100 << 20)
    n_chunks = -(-1000 // chunk)
    assert chunk < 1000 and chunk * n_chunks >= 1000
    assert chunk * per <= 100 << 20 or chunk == 64
    # a tiny budget still keeps the >=64-problem regime the Ising
    # backends are benched at
    assert auto_pool_chunk(512, 32, 8, 64, budget_bytes=1) == 64


def test_auto_decompose_chunk_memory_model():
    """Greedy/alternating pools chunk by their working set against a
    device-memory budget: whole when it fits, evenly split when not — a
    whole-model pool at tile 32x128 does not fit one 16 GB chip."""
    from repro.compression.execute import (
        auto_decompose_chunk, decompose_tile_bytes,
    )

    per = decompose_tile_bytes(32, 128)
    assert per == 4 * 4 * 32 * 128
    assert auto_decompose_chunk(1000, 32, 128) == 1000
    total = 313_344                          # granite-moe-1b-a400m, 32x128
    chunk = auto_decompose_chunk(total, 32, 128)
    n_chunks = -(-total // chunk)
    assert chunk * per <= 1 << 30 and chunk * n_chunks >= total
    assert chunk * (n_chunks - 1) < total    # an even split, no empty chunk
    assert auto_decompose_chunk(10, 32, 128, budget_bytes=1) == 1


def test_auto_chunk_recorded_in_pool_stats(monkeypatch):
    """execute_plan(max_pool_tiles="auto") chunks BBO pools by the memory
    model (env-overridable budget) and records the policy + model input in
    the pool stats; a small non-BBO pool fits its device budget whole."""
    from repro.compression.execute import POOL_BUDGET_ENV, surrogate_tile_bytes

    values = {"a": {"w": jax.random.normal(jax.random.PRNGKey(3), (24, 32))}}
    pol = comp.CompressionPolicy(method="bbo", tile_d=16, rank_ratio=0.375,
                                 min_size=1, bbo_iters=2)
    plan = comp.plan_compression(values, pol)     # 3 * 2 = 6 tiles
    _, art = comp.execute_plan(plan, values)      # default: "auto"
    pool = art.manifest["pools"][0]
    assert pool["chunk_policy"] == "auto"
    assert pool["surrogate_tile_bytes"] == surrogate_tile_bytes(8, 3, 2)
    assert pool["chunks"] == 1                    # 6 tiles fit any budget

    # the budget env var reaches the chunker (floored at the solver regime)
    monkeypatch.setenv(POOL_BUDGET_ENV, "1")
    _, art_env = comp.execute_plan(plan, values)
    assert art_env.manifest["pools"][0]["chunk_sizes"] == [6]  # 6 < floor 64

    _, art_greedy = comp.execute_plan(
        plan_compression_greedy(values), values
    )
    gpool = art_greedy.manifest["pools"][0]
    assert gpool["chunk_policy"] == "auto" and gpool["chunks"] == 1
    assert "surrogate_tile_bytes" not in gpool


def plan_compression_greedy(values):
    pol = comp.CompressionPolicy(method="greedy", tile_d=16, rank_ratio=0.375,
                                 min_size=1)
    return comp.plan_compression(values, pol)


def test_execute_validates_plan_against_values():
    values = small_values()
    plan = comp.plan_compression(values, base_policy())
    values["blk"]["attn"]["wq"]["w"] = jnp.zeros((32, 32))
    with pytest.raises(ValueError, match="shape mismatch"):
        comp.execute_plan(plan, values)


def test_execute_with_mesh_matches_unsharded():
    values = small_values()
    key = jax.random.PRNGKey(0)
    plan = comp.plan_compression(values, base_policy())
    mesh = make_mesh((1, 1), ("data", "model"))
    a, _ = comp.execute_plan(plan, values, key=key)
    b, _ = comp.execute_plan(plan, values, key=key, mesh=mesh)
    fa, fb = dict(tree_paths(a)), dict(tree_paths(b))
    for k in fa:
        np.testing.assert_array_equal(np.asarray(fa[k]), np.asarray(fb[k]))


# ---------------------------------------------------------------------------
# artifact
# ---------------------------------------------------------------------------

def test_artifact_manifest_save_load_and_template(tmp_path):
    values = small_values()
    plan = comp.plan_compression(values, base_policy())
    cvals, art = comp.execute_plan(plan, values)
    art.save(str(tmp_path))
    art2 = comp.CompressionArtifact.load(str(tmp_path))
    assert art2.manifest == art.manifest
    assert art2.validate_params(cvals) == []
    # the template mirrors the compressed tree's structure and shapes
    template = art2.restore_template(values)
    t_leaves = dict(tree_paths(template))
    c_leaves = dict(tree_paths(cvals))
    assert t_leaves.keys() == c_leaves.keys()
    for k in t_leaves:
        assert tuple(t_leaves[k].shape) == tuple(c_leaves[k].shape), k
    # a dense tree fails validation loudly
    assert art2.validate_params(values) != []
    # so does a dtype drift (manifest pins C's dtype)
    drifted = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        cvals,
    )
    assert any("dtype" in p for p in art2.validate_params(drifted))


def test_artifact_rejects_unknown_format():
    with pytest.raises(ValueError, match="manifest format"):
        comp.CompressionArtifact({"format": "something/else"})


def test_report_totals_match_manifest():
    values = small_values()
    plan = comp.plan_compression(values, base_policy())
    _, art = comp.execute_plan(plan, values)
    rep = art.report
    assert rep.total_ratio == pytest.approx(art.total_ratio)
    assert {p for p, *_ in rep.compressed} == set(art.manifest["tensors"])
