"""Pallas TPU kernel: bit-packed binary matmul with fused real factor.

Computes  y = (x @ M) @ C  for the integer-decomposition compressed layer
(paper Eq. 1): per (row-tile r, col-tile c) of the original weight,
M[r,c] in {-1,+1}^{tn x K} is stored bit-packed (uint8, 8 cols/byte, see
core.decomposition.pack_bits) and C[r,c] is a small real (K x td) factor.

TPU adaptation (DESIGN.md §4): the win is HBM bandwidth — M's bytes-read are
16x smaller than a bf16 dense weight.  The kernel streams packed tiles into
VMEM, unpacks in VREGs, feeds the MXU, and fuses the K-dim intermediate
z = x @ M so it never touches HBM.

Schedules (``mode``) behind one entry point — docs/kernels.md:

  * grid (T/bt, c, r/r_chunk) with r as the reduction ("arbitrary")
    dimension — the prefill/training-shapes path; the (bt, td) output block
    accumulates in f32 VMEM scratch across r-steps.  ``r_chunk`` packs
    several r tiles into one grid step: fewer grid iterations, larger
    contiguous HBM->VMEM copies for the pipeline to overlap with compute.
  * decode, grid (c,): when the whole activation row block plus one output
    column's worth of M and C fit in VMEM (T = batch, e.g. 1..16), the
    r-reduction runs inside a single kernel invocation with C resident in
    VMEM, so every M/C byte is read from HBM exactly once per step and z
    never leaves registers.
  * jnp: no pallas_call — the same fused math as straight-line XLA ops.
    The serving schedule for non-TPU backends, where Pallas interpret-mode
    overhead (~50-100us per call) dwarfs these skinny matmuls; on TPU it
    exists as an autotuner candidate that the timed search rejects.

Bit algebra (``math``):

  * unpack: M is unpacked to a {-1,+1} **int8** plane by a shift/and chain
    built from broadcasts alone (no sublane-to-lane reshape, which Mosaic
    refuses), and widens to the activation dtype only at the MXU operand.
    Integer activations keep the operand int8 and accumulate via
    ``preferred_element_type=int32``.
  * bitplane: M = 2*B - 1 with B in {0,1}, so z = x @ M = 2*(x @ B) - s
    where s = rowsum(x) per r tile.  The affine correction moves from the
    (tn, K) M tile to the (bt, K) z block — cheaper whenever bt < tn (the
    decode regime) — and B feeds the MXU as the raw unpacked bit, one
    int8->dtype widening and no elementwise 2b-1 on the M side at all.

Block alignment: Mosaic takes a block whose last two dims are multiples of
(8, 128) or whole array dims.  So the grid schedule's x block spans
``r_chunk * tn`` lanes that are a multiple of 128 or all of d_in
(:func:`_resolve_r_chunk` raises the chunk until they do), and bt and td
should be multiples of 128 for the MXU; K and tn are tile-level and may be
small.  Schedule selection per (geometry, T, dtype, device) lives in
``repro.kernels.autotune``; ``mode="auto"`` here keeps the static pallas
heuristic (decode when it fits, else grid).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bitlinear", "bitlinear_grouped", "MODES", "MATHS"]

MODES = ("auto", "grid", "decode", "jnp")
MATHS = ("unpack", "bitplane")

# VMEM budget for the decode fast path (x block + all M/C tiles of one
# output column + accumulator/out blocks + the per-r-step unpacked M tile);
# ~16 MB/core physical, stay well under.  Overridable for smaller-VMEM
# targets via the env var below or the ``vmem_budget`` argument.
_DECODE_VMEM_BYTES = 4 * 2**20
_DECODE_VMEM_ENV = "REPRO_DECODE_VMEM_BYTES"
# Bound on the python-unrolled r-reduction of the decode kernel (compile
# size control; past this the grid schedule wins anyway).
_DECODE_MAX_R = 256


def _vmem_budget(override: int | None) -> int:
    if override is not None:
        return int(override)
    return int(os.environ.get(_DECODE_VMEM_ENV, _DECODE_VMEM_BYTES))


# ---------------------------------------------------------------------------
# bit unpacking + math variants
# ---------------------------------------------------------------------------


def _unpack_i8(mp, K: int, signed: bool):
    """uint8 (tn, kb) -> int8 (tn, K): {0,1} bits, or {-1,+1} when signed.

    Column k is bit k % 8 of byte k // 8.  Each byte column is widened to
    int32 and shifted against a lane iota, so the (tn, K) plane is built by
    broadcasting alone: a (tn, kb, 8) -> (tn, 8 kb) reshape would move data
    from sublanes to lanes, which Mosaic refuses."""
    tn, kb = mp.shape
    words = mp.astype(jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    b = jnp.zeros((tn, K), jnp.int32)
    for j in range(kb):
        shift = jnp.clip(col - 8 * j, 0, 7)
        b = jnp.where(col // 8 == j, (words[:, j:j + 1] >> shift) & 1, b)
    b = 2 * b - 1 if signed else b
    return b.astype(jnp.int8)


def _mxu(a, b, acc_t=jnp.float32):
    """a @ b on the MXU, accumulated in ``acc_t``.  f32 operands contract
    at HIGHEST precision, not rounded to bf16 (the TPU default), so an f32
    model matches its XLA reference instead of drifting from it; Mosaic
    takes no precision for bf16 or int8 operands, which are exact anyway."""
    f32 = a.dtype == jnp.float32
    precision = jax.lax.Precision.HIGHEST if f32 else None
    return jnp.dot(a, b, precision=precision, preferred_element_type=acc_t)


def _z_block(x, mp, *, K: int, math: str):
    """z = x @ M for one (bt, tn) x block and one packed (tn, kb) M tile.
    Integer activations run the int8 MXU path (int32 accumulation);
    float activations widen the int8 plane to x.dtype at the MXU operand
    and accumulate in f32."""
    integer = jnp.issubdtype(x.dtype, jnp.integer)
    acc_t = jnp.int32 if integer else jnp.float32
    if math == "bitplane":
        b = _unpack_i8(mp, K, signed=False)
        zb = _mxu(x, b if integer else b.astype(x.dtype), acc_t)
        s = jnp.sum(x.astype(acc_t), axis=-1, keepdims=True)
        return 2 * zb - s
    m = _unpack_i8(mp, K, signed=True)
    return _mxu(x, m if integer else m.astype(x.dtype), acc_t)


def _tile_out(x, mp, c, *, K: int, math: str):
    """One r tile's f32 contribution (x @ M) @ C: z through the selected
    bit algebra, then the small real factor.  Every schedule accumulates
    these over r."""
    z = _z_block(x, mp, K=K, math=math)                           # (bt, K)
    return _mxu(z.astype(c.dtype), c)


def _pad_rows(x, T: int, block_t: int):
    """Pad the token axis (second-to-last) to a sublane-aligned block
    multiple; returns (x, bt, Tp)."""
    bt = min(block_t, -(-T // 8) * 8)
    Tp = -(-T // bt) * bt
    if Tp != T:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, Tp - T), (0, 0)]
        x = jnp.pad(x, pad)
    return x, bt, Tp


# ---------------------------------------------------------------------------
# grid schedule (r_chunk-aware)
# ---------------------------------------------------------------------------


def _kernel(x_ref, mp_ref, c_ref, o_ref, acc_ref, *, K, n_rsteps, r_chunk, tn,
            math):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x (bt, r_chunk*tn), mp (r_chunk, 1, tn, kb) uint8, c (r_chunk, 1, K, td)
    x = x_ref[...]
    for j in range(r_chunk):
        acc_ref[...] += _tile_out(
            x[:, j * tn:(j + 1) * tn], mp_ref[j, 0], c_ref[j, 0], K=K,
            math=math,
        )

    @pl.when(r == n_rsteps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# decode fast path (C resident in VMEM, single invocation per column)
# ---------------------------------------------------------------------------


def _decode_kernel(x_ref, mp_ref, c_ref, o_ref, *, K, n_r, tn, math):
    x = x_ref[...]                       # (Tp, d_in)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for r in range(n_r):                 # static unroll: z stays in VREGs
        acc = acc + _tile_out(x[:, r * tn:(r + 1) * tn], mp_ref[r, 0],
                              c_ref[r, 0], K=K, math=math)  # C in VMEM
    o_ref[...] = acc.astype(o_ref.dtype)


def _decode_path_ok(Tp, d_in, n_r, tn, kb, K, td, x_itemsize, c_itemsize,
                    budget: int):
    vmem = (
        Tp * d_in * x_itemsize                 # activation block
        + n_r * tn * kb                        # packed M column
        + n_r * K * td * c_itemsize            # C column
        + Tp * td * 4                          # f32 accumulator
        + Tp * td * x_itemsize                 # padded-T output slice
        + tn * K * (1 + x_itemsize)            # per-r-step unpacked M tile
                                               # (int8 plane + MXU operand)
    )
    return n_r <= _DECODE_MAX_R and vmem <= budget


# ---------------------------------------------------------------------------
# jnp schedule (no pallas_call): fused math as straight-line XLA
# ---------------------------------------------------------------------------


def _unpack_dense(mp, K: int, dtype, signed: bool):
    """uint8 (..., tn, kb) -> (..., tn, K) bit plane, int8-staged."""
    bits = ((mp[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1)
    bits = bits.astype(jnp.int8)
    b = bits.reshape(*mp.shape[:-1], mp.shape[-1] * 8)[..., :K]
    if signed:
        b = 2 * b - 1
    return b.astype(dtype)


def _jnp_bitlinear(x, mp, C, math: str):
    n_r, n_c, tn, kb = mp.shape
    _, _, K, td = C.shape
    T = x.shape[0]
    xt = x.reshape(T, n_r, tn)
    if math == "bitplane":
        B = _unpack_dense(mp, K, x.dtype, signed=False)
        zb = jnp.einsum("trn,rcnk->trck", xt, B)
        s = xt.sum(-1)                                       # (T, r)
        z = 2.0 * zb - s[..., None, None]
        y = jnp.einsum("trck,rckd->tcd", z, C.astype(x.dtype))
        return y.reshape(T, n_c * td)
    if math == "dot":
        # batched dot_general formulation: transposed operands feed two
        # plain batched matmuls instead of 4D einsums — the fastest CPU
        # lowering at serving batch sizes (BENCH_bitlinear.json)
        M = _unpack_dense(mp, K, x.dtype, signed=True)       # (r, c, tn, K)
        xr = xt.transpose(1, 0, 2)                           # (r, T, tn)
        M2 = M.transpose(0, 2, 1, 3).reshape(n_r, tn, n_c * K)
        z = jax.lax.dot_general(xr, M2, (((2,), (1,)), ((0,), (0,))))
        z2 = z.reshape(n_r, T, n_c, K).transpose(2, 1, 0, 3)
        z2 = z2.reshape(n_c, T, n_r * K)
        C2 = C.astype(x.dtype).transpose(1, 0, 2, 3).reshape(n_c, n_r * K, td)
        y = jax.lax.dot_general(z2, C2, (((2,), (1,)), ((0,), (0,))))
        return y.transpose(1, 0, 2).reshape(T, n_c * td)
    # math == "unpack": the einsum-oracle formulation
    M = _unpack_dense(mp, K, x.dtype, signed=True)
    z = jnp.einsum("trn,rcnk->trck", xt, M)
    y = jnp.einsum("trck,rckd->tcd", z, C.astype(x.dtype))
    return y.reshape(T, n_c * td)


def _jnp_bitlinear_grouped(x, mp, C, math: str):
    E, n_r, n_c, tn, kb = mp.shape
    _, _, _, K, td = C.shape
    T = x.shape[1]
    xt = x.reshape(E, T, n_r, tn)
    if math == "bitplane":
        B = _unpack_dense(mp, K, x.dtype, signed=False)
        zb = jnp.einsum("etrn,ercnk->etrck", xt, B)
        s = xt.sum(-1)                                       # (E, T, r)
        z = 2.0 * zb - s[..., None, None]
        y = jnp.einsum("etrck,erckd->etcd", z, C.astype(x.dtype))
        return y.reshape(E, T, n_c * td)
    if math == "dot":
        M = _unpack_dense(mp, K, x.dtype, signed=True)
        xr = xt.transpose(0, 2, 1, 3).reshape(E * n_r, T, tn)
        M2 = M.transpose(0, 1, 3, 2, 4).reshape(E * n_r, tn, n_c * K)
        z = jax.lax.dot_general(xr, M2, (((2,), (1,)), ((0,), (0,))))
        z2 = z.reshape(E, n_r, T, n_c, K).transpose(0, 3, 2, 1, 4)
        z2 = z2.reshape(E * n_c, T, n_r * K)
        C2 = C.astype(x.dtype).transpose(0, 2, 1, 3, 4).reshape(
            E * n_c, n_r * K, td)
        y = jax.lax.dot_general(z2, C2, (((2,), (1,)), ((0,), (0,))))
        return y.reshape(E, n_c, T, td).transpose(0, 2, 1, 3)
        # -> (E, T, c, td); reshaped by caller
    M = _unpack_dense(mp, K, x.dtype, signed=True)
    z = jnp.einsum("etrn,ercnk->etrck", xt, M)
    y = jnp.einsum("etrck,erckd->etcd", z, C.astype(x.dtype))
    return y.reshape(E, T, n_c * td)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _resolve_r_chunk(n_r: int, tn: int, r_chunk: int) -> int:
    """The r tiles one grid step reduces: the largest divisor of n_r that
    is <= the requested chunk, raised to the next divisor whose x block
    (r_chunk * tn lanes) is a whole number of 128-lane tiles or all of
    d_in — the only x blocks Mosaic accepts."""
    divisors = [d for d in range(1, n_r + 1) if n_r % d == 0]
    rc = max(d for d in divisors if d <= max(1, r_chunk))
    return min(
        d for d in divisors
        if d >= rc and ((d * tn) % 128 == 0 or d == n_r)
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "interpret", "mode", "math", "r_chunk"),
)
def _bitlinear_jit(x, m_packed, C, block_t, interpret, mode, math, r_chunk):
    T, d_in = x.shape
    n_r, n_c, tn, kb = m_packed.shape
    _, _, K, td = C.shape

    if mode == "jnp":
        return _jnp_bitlinear(x, m_packed, C, math)

    x, bt, Tp = _pad_rows(x, T, block_t)

    if mode == "decode":
        out = pl.pallas_call(
            functools.partial(_decode_kernel, K=K, n_r=n_r, tn=tn, math=math),
            grid=(n_c,),
            in_specs=[
                pl.BlockSpec((Tp, d_in), lambda c: (0, 0)),
                pl.BlockSpec((n_r, 1, tn, kb), lambda c: (0, c, 0, 0)),
                pl.BlockSpec((n_r, 1, K, td), lambda c: (0, c, 0, 0)),
            ],
            out_specs=pl.BlockSpec((Tp, td), lambda c: (0, c)),
            out_shape=jax.ShapeDtypeStruct((Tp, n_c * td), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
            ),
            interpret=interpret,
            name="bitlinear_decode",
        )(x, m_packed, C)
        return out[:T]

    rc = _resolve_r_chunk(n_r, tn, r_chunk)
    n_rsteps = n_r // rc
    grid = (Tp // bt, n_c, n_rsteps)
    out = pl.pallas_call(
        functools.partial(
            _kernel, K=K, n_rsteps=n_rsteps, r_chunk=rc, tn=tn, math=math
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, rc * tn), lambda t, c, r: (t, r)),
            pl.BlockSpec((rc, 1, tn, kb), lambda t, c, r: (r, c, 0, 0)),
            pl.BlockSpec((rc, 1, K, td), lambda t, c, r: (r, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, td), lambda t, c, r: (t, c)),
        out_shape=jax.ShapeDtypeStruct((Tp, n_c * td), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, td), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="bitlinear_grid",
    )(x, m_packed, C)
    return out[:T]


def bitlinear(
    x: jax.Array,        # (T, d_in)
    m_packed: jax.Array, # (r, c, tn, kb) uint8
    C: jax.Array,        # (r, c, K, td)
    block_t: int = 128,
    interpret: bool = False,
    mode: str = "auto",  # auto | grid | decode | jnp
    math: str = "unpack",  # unpack | bitplane (jnp mode also: dot)
    r_chunk: int = 1,
    vmem_budget: int | None = None,
) -> jax.Array:
    """y (T, d_out) = x @ decompress(m_packed, C).  Any T: rows are
    zero-padded to a block multiple and sliced back.  ``mode`` pins the
    schedule (module docstring); "auto" picks decode for small T when the
    column working set fits the VMEM budget (``vmem_budget`` argument or
    the REPRO_DECODE_VMEM_BYTES env var), else grid."""
    T, d_in = x.shape
    n_r, n_c, tn, kb = m_packed.shape
    _, _, K, td = C.shape
    assert n_r * tn == d_in, (m_packed.shape, x.shape)
    assert mode in MODES, mode
    assert math in MATHS + ("dot",), math

    if mode == "auto":
        bt = min(block_t, -(-T // 8) * 8)
        Tp = -(-T // bt) * bt
        mode = "decode" if (
            Tp <= bt
            and _decode_path_ok(Tp, d_in, n_r, tn, kb, K, td,
                                x.dtype.itemsize, C.dtype.itemsize,
                                _vmem_budget(vmem_budget))
        ) else "grid"
    if mode != "jnp" and math == "dot":
        math = "unpack"
    return _bitlinear_jit(x, m_packed, C, block_t, interpret, mode, math,
                          r_chunk)


# ---------------------------------------------------------------------------
# grouped (per-expert) kernels
# ---------------------------------------------------------------------------


def _grouped_kernel(x_ref, mp_ref, c_ref, o_ref, acc_ref, *, K, n_rsteps,
                    r_chunk, tn, math):
    r = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # same body as _kernel behind the leading expert block dim of 1
    x = x_ref[0]
    for j in range(r_chunk):
        acc_ref[...] += _tile_out(
            x[:, j * tn:(j + 1) * tn], mp_ref[0, j, 0], c_ref[0, j, 0], K=K,
            math=math,
        )

    @pl.when(r == n_rsteps - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _grouped_decode_kernel(x_ref, mp_ref, c_ref, o_ref, *, K, n_r, tn, math):
    # x (1, Tp, d_in), mp (1, n_r, 1, tn, kb), c (1, n_r, 1, K, td):
    # one (expert, column) pair per invocation, r statically unrolled with
    # C resident in VMEM — the MoE decode regime (T = a few tokens/expert)
    # skips the full (E, T/bt, c, r) grid overhead entirely.
    x = x_ref[0]
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for r in range(n_r):
        acc = acc + _tile_out(x[:, r * tn:(r + 1) * tn], mp_ref[0, r, 0],
                              c_ref[0, r, 0], K=K, math=math)
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "interpret", "mode", "math", "r_chunk"),
)
def _bitlinear_grouped_jit(x, m_packed, C, block_t, interpret, mode, math,
                           r_chunk):
    E, T, d_in = x.shape
    _, n_r, n_c, tn, kb = m_packed.shape
    _, _, _, K, td = C.shape

    if mode == "jnp":
        return _jnp_bitlinear_grouped(x, m_packed, C, math).reshape(
            E, T, n_c * td
        )

    x, bt, Tp = _pad_rows(x, T, block_t)

    if mode == "decode":
        out = pl.pallas_call(
            functools.partial(
                _grouped_decode_kernel, K=K, n_r=n_r, tn=tn, math=math
            ),
            grid=(E, n_c),
            in_specs=[
                pl.BlockSpec((1, Tp, d_in), lambda e, c: (e, 0, 0)),
                pl.BlockSpec((1, n_r, 1, tn, kb), lambda e, c: (e, 0, c, 0, 0)),
                pl.BlockSpec((1, n_r, 1, K, td), lambda e, c: (e, 0, c, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Tp, td), lambda e, c: (e, 0, c)),
            out_shape=jax.ShapeDtypeStruct((E, Tp, n_c * td), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
            ),
            interpret=interpret,
            name="bitlinear_grouped_decode",
        )(x, m_packed, C)
        return out[:, :T]

    rc = _resolve_r_chunk(n_r, tn, r_chunk)
    n_rsteps = n_r // rc
    grid = (E, Tp // bt, n_c, n_rsteps)
    out = pl.pallas_call(
        functools.partial(
            _grouped_kernel, K=K, n_rsteps=n_rsteps, r_chunk=rc, tn=tn,
            math=math,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, rc * tn), lambda e, t, c, r: (e, t, r)),
            pl.BlockSpec((1, rc, 1, tn, kb),
                         lambda e, t, c, r: (e, r, c, 0, 0)),
            pl.BlockSpec((1, rc, 1, K, td),
                         lambda e, t, c, r: (e, r, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, td), lambda e, t, c, r: (e, t, c)),
        out_shape=jax.ShapeDtypeStruct((E, Tp, n_c * td), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, td), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="bitlinear_grouped_grid",
    )(x, m_packed, C)
    return out[:, :T]


def bitlinear_grouped(
    x: jax.Array,        # (E, T, d_in) per-expert token blocks
    m_packed: jax.Array, # (E, r, c, tn, kb) uint8
    C: jax.Array,        # (E, r, c, K, td)
    block_t: int = 128,
    interpret: bool = False,
    mode: str = "auto",  # auto | grid | decode | jnp
    math: str = "unpack",
    r_chunk: int = 1,
    vmem_budget: int | None = None,
) -> jax.Array:
    """Grouped fused bitlinear: y_e (T, d_out) = x_e @ decompress(M_e, C_e)
    for every expert e in one kernel launch — the compressed form of the
    MoE expert einsum ``ebcd,edf->ebcf`` after flattening (B, C) -> T.

    Schedules: grid (E, T/bt, c, r/r_chunk) reuses the 2D block schedule
    per expert slice; decode, grid (E, c), keeps one expert-column's M/C
    resident in VMEM with the r reduction unrolled in-kernel — the MoE
    decode fast path (T = 1..16 tokens per expert previously paid the full
    grid overhead); jnp is the non-TPU serving schedule.  T is padded to a
    sublane-aligned block multiple and sliced back, so ragged per-expert
    capacities (any B*C, including 1) work; E may be anything >= 1.
    ``mode="auto"`` picks decode for small T when one expert column fits
    the VMEM budget.
    """
    E, T, d_in = x.shape
    Em, n_r, n_c, tn, kb = m_packed.shape
    Ec, _, _, K, td = C.shape
    assert Em == E and Ec == E, (x.shape, m_packed.shape, C.shape)
    assert n_r * tn == d_in, (m_packed.shape, x.shape)
    assert mode in MODES, mode
    assert math in MATHS + ("dot",), math

    if mode == "auto":
        bt = min(block_t, -(-T // 8) * 8)
        Tp = -(-T // bt) * bt
        mode = "decode" if (
            Tp <= bt
            and _decode_path_ok(Tp, d_in, n_r, tn, kb, K, td,
                                x.dtype.itemsize, C.dtype.itemsize,
                                _vmem_budget(vmem_budget))
        ) else "grid"
    if mode != "jnp" and math == "dot":
        math = "unpack"
    return _bitlinear_grouped_jit(x, m_packed, C, block_t, interpret, mode,
                                  math, r_chunk)
