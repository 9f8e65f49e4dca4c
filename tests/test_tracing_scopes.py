"""The names a profiler trace of a compression job carries: the device
scopes of ``compress_tile_batch`` / ``run_bbo_many`` in the compiled
program's ``op_name`` metadata, and ``execute_plan``'s host spans in a
trace taken on the CPU.  The benchmark's per-layer metrics read these
names (``bench/metrics/``), so a rename fails here first."""

import collections
import pathlib
import re
import sys

import jax
import pytest

from repro import compression as comp
from repro.core import bbo as bbo_lib
from repro.core import decomposition as dec
from repro.core.compress import compress_tile_batch

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import trace_scopes  # noqa: E402

BBO_SCOPES = {"bbo.init", "bbo.surrogate", "bbo.ising", "bbo.evaluate",
              "bbo.append"}
COMPRESS_SCOPES = {"compress.init", "compress.bbo", "compress.lstsq"}
OP_NAME = re.compile(r'op_name="([^"]*)"')
# a factorisation or triangular solve, as the CPU compiles it (LAPACK
# calls) and as HLO names it elsewhere
LINALG = re.compile(
    r"\b(cholesky|triangular-solve)\(|custom_call_target=\"[^\"]*"
    r"(potrf|trsm|Cholesky|TriangularSolve)")


def _policy(**kw):
    return comp.CompressionPolicy(method="bbo", tile_n=8, tile_d=64,
                                  rank_ratio=0.25, min_size=1, bbo_iters=2,
                                  solver_backend="jnp", **kw)


@pytest.fixture(scope="module")
def hlo():
    """The compiled text of one BBO chunk: 8 tiles of 8 x 64, K = 2."""
    tiles = jax.random.normal(jax.random.PRNGKey(0), (8, 8, 64))
    keys = jax.random.split(jax.random.PRNGKey(1), 8)
    return compress_tile_batch.lower(
        tiles, keys, jax.random.PRNGKey(2), K=2, method="bbo", bbo_iters=2,
        backend="jnp",
    ).compile().as_text()


def _op_names(text, pattern=None):
    return [m.group(1) for line in text.splitlines()
            if (pattern is None or pattern.search(line))
            for m in [OP_NAME.search(line)] if m]


def test_every_stage_of_a_bbo_chunk_has_its_scope(hlo):
    found = set()
    for name in _op_names(hlo):
        found |= trace_scopes.scopes_of(name)
    assert BBO_SCOPES | COMPRESS_SCOPES <= found


@pytest.fixture(scope="module")
def vbocs_hlo():
    """The compiled text of a vBOCS lock-step run: 4 problems, n = 8."""
    cfg = bbo_lib.BBOConfig(n=8, N=4, K=2, algo="vbocs", iters=2,
                            init_points=8, gibbs_steps=1, backend="jnp")
    W = jax.random.normal(jax.random.PRNGKey(3), (4, 4, 16))

    def f_batch(xs):
        return jax.vmap(lambda w, x: dec.objective_from_x(x, w, 2))(W, xs)

    fn = jax.jit(lambda key: bbo_lib.run_bbo_many(key, cfg, f_batch, 4))
    return fn.lower(jax.random.PRNGKey(4)).compile().as_text()


def test_an_nbocs_chunk_compiles_no_factorisation(hlo):
    """nBOCS carries its posterior's square root, updated by rank 1 per
    point: the compiled chunk holds no Cholesky or triangular solve."""
    assert _op_names(hlo, LINALG) == []
    assert not LINALG.search(hlo)


def test_the_surrogate_linear_algebra_is_under_bbo_surrogate(vbocs_hlo):
    """vBOCS still factors its posterior precision every Gibbs step."""
    names = _op_names(vbocs_hlo, LINALG)
    assert names
    for name in names:
        assert "bbo.surrogate" in trace_scopes.scopes_of(name), name


def test_the_ising_solve_is_under_bbo_ising(hlo):
    names = [n for n in _op_names(hlo) if "solve_many" in n]
    assert names
    for name in names:
        assert {"bbo.ising", "compress.bbo"} <= trace_scopes.scopes_of(name)


@pytest.mark.parametrize("path,scopes", [
    ("jit(f)/compress.bbo/while/body/closed_call/bbo.surrogate/"
     "vmap(jit(cholesky))/cholesky", {"compress.bbo", "bbo.surrogate"}),
    ("jit(f)/compress.bbo/vmap(bbo.append)/dynamic_update_slice",
     {"compress.bbo", "bbo.append"}),
    ("jit(f)/transpose(jvp(bbo.ising/x))/add;bbo.evaluate/mul",
     {"bbo.ising", "bbo.evaluate"}),
    ("jit(run_bbo_many.<locals>.iteration)/while/body/add", set()),
    ("", set()),
])
def test_scopes_are_dotted_components_with_wrappers_unwrapped(path, scopes):
    assert trace_scopes.scopes_of(path) == scopes


def test_execute_plan_leaves_one_span_per_stage(tmp_path):
    values = {
        "a": {"w": jax.random.normal(jax.random.PRNGKey(1), (16, 128))},
        "b": {"w": jax.random.normal(jax.random.PRNGKey(2), (16, 128))},
    }
    plan = comp.plan_compression(values, _policy())
    jax.block_until_ready(comp.execute_plan(plan, values, max_pool_tiles=4))
    with jax.profiler.trace(str(tmp_path)):
        _, artifact = comp.execute_plan(plan, values, max_pool_tiles=4)
    _, spans, _ = trace_scopes.read_trace(
        trace_scopes.trace_reduce.latest_xplane(str(tmp_path)))
    counts = collections.Counter(n for n, _, _ in spans)
    chunks = sum(p["chunks"] for p in artifact.manifest["pools"])
    assert chunks == 2
    assert counts == {
        "repro.execute": 1,
        "repro.execute.assemble": chunks,
        "repro.execute.dispatch": chunks,
        "repro.execute.pack": len(plan.tensors),
        "repro.execute.manifest": 1,
    }
    outer = next((s, s + d) for n, s, d in spans if n == "repro.execute")
    assert all(outer[0] <= s and s + d <= outer[1] for _, s, d in spans)
