"""Compile every main-path Pallas kernel for a TPU v5e, with no chip.

The TPU compiler compiles for a chip that is described, not attached
(``jax.experimental.topologies``), so Mosaic's refusals — unaligned blocks,
unsupported reshapes or slices, VMEM overflow — show here, at granite-moe-
1b-a400m's widths (tile 32x128, K = 4, d_model 1024, 32 experts of d_ff
512, head_dim 64) and at the solver sizes compression hands
``ising.solve_many``.  Nothing runs: these tests say nothing of results or
speed; the interpret-mode tests in test_kernels.py check the values.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitlinear as bl
from repro.kernels.flash_attention import flash_attention
from repro.kernels.sa_sweep import sa_sweep_many, sq_sweep_many
from repro.kernels.sqa_sweep import sqa_sweep_many

D_MODEL, D_FF, EXPERTS = 1024, 512, 32
TILE_N, TILE_D, K = 32, 128, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler here: nothing to rehearse
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` on (shape, dtype) pairs placed on the described chip
    and compile it; returns the compiled program (raises what Mosaic or XLA
    would raise on the chip)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# bf16 is the served dtype; f32 is the chip smoke's comparison dtype, whose
# matmuls take HIGHEST precision (which Mosaic refuses for bf16 operands)
DTYPES = [jnp.bfloat16, jnp.float32]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("math", ["unpack", "bitplane"])
@pytest.mark.parametrize("T", [8, 256])
@pytest.mark.parametrize("mode", ["decode", "grid"])
@pytest.mark.parametrize("d_in,d_out", [(D_MODEL, D_MODEL), (D_MODEL, D_FF)])
def test_bitlinear_compiles(one_chip, d_in, d_out, mode, T, math, dtype):
    n_r, n_c = d_in // TILE_N, d_out // TILE_D

    def fn(x, mp, C):
        return bl.bitlinear(x, mp, C, mode=mode, math=math, interpret=False)

    c = _compile(fn, one_chip, ((T, d_in), dtype),
                 ((n_r, n_c, TILE_N, 1), jnp.uint8),
                 ((n_r, n_c, K, TILE_D), dtype))
    assert _has_kernel(c)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("T", [8, 256])
@pytest.mark.parametrize("mode", ["decode", "grid"])
@pytest.mark.parametrize("d_in,d_out", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_bitlinear_grouped_compiles(one_chip, d_in, d_out, mode, T, dtype):
    n_r, n_c = d_in // TILE_N, d_out // TILE_D

    def fn(x, mp, C):
        return bl.bitlinear_grouped(x, mp, C, mode=mode, interpret=False)

    c = _compile(fn, one_chip, ((EXPERTS, T, d_in), dtype),
                 ((EXPERTS, n_r, n_c, TILE_N, 1), jnp.uint8),
                 ((EXPERTS, n_r, n_c, K, TILE_D), dtype))
    assert _has_kernel(c)


# (P, n, reads, sweeps): the chip smoke's solver batch at the default
# geometry (n = 32 * 4) and the BBO chunk of its paper-scale rule (n = 8 * 2)
SWEEP_SIZES = [(64, TILE_N * K, 10, 64), (280, 16, 4, 24)]


@pytest.mark.parametrize("P,n,C,S", SWEEP_SIZES)
def test_sa_sweep_many_compiles(one_chip, P, n, C, S):
    fn = lambda h, B, x0, u, t: sa_sweep_many(h, B, x0, u, t, interpret=False)
    c = _compile(fn, one_chip, ((P, n), jnp.float32), ((P, n, n), jnp.float32),
                 ((P, C, n), jnp.float32), ((P, C, S, n), jnp.float32),
                 ((P, S), jnp.float32))
    assert _has_kernel(c)


@pytest.mark.parametrize("P,n,C,S", SWEEP_SIZES)
def test_sq_sweep_many_compiles(one_chip, P, n, C, S):
    fn = lambda h, B, x0, u: sq_sweep_many(h, B, x0, u, interpret=False)
    c = _compile(fn, one_chip, ((P, n), jnp.float32), ((P, n, n), jnp.float32),
                 ((P, C, n), jnp.float32), ((P, C, S, n), jnp.float32))
    assert _has_kernel(c)


def test_sqa_sweep_many_compiles(one_chip):
    P, n, C, S, T = 64, TILE_N * K, 10, 48, 8
    fn = lambda h, B, X0, u, j: sqa_sweep_many(h, B, X0, u, j, interpret=False)
    c = _compile(fn, one_chip, ((P, n), jnp.float32), ((P, n, n), jnp.float32),
                 ((P, C, T, n), jnp.float32), ((P, C, S, T, n), jnp.float32),
                 ((S,), jnp.float32))
    assert _has_kernel(c)


@pytest.mark.parametrize("S", [128, 939, 2048])
def test_flash_attention_compiles(one_chip, S):
    fn = lambda q, k, v: flash_attention(q, k, v, interpret=False)
    c = _compile(fn, one_chip, ((1, 16, S, 64), jnp.bfloat16),
                 ((1, 8, S, 64), jnp.bfloat16), ((1, 8, S, 64), jnp.bfloat16))
    assert _has_kernel(c)
