"""Jitted public wrappers for the Pallas kernels.

``interpret`` auto-detection: on non-TPU backends the kernels execute in
Pallas interpret mode (kernel body as jnp on CPU) — used by the test suite.
``enable_kernels()`` registers the TPU paths into the model/quantized layers
(model code calls the jnp fallbacks otherwise, which the dry-run lowers).
"""

from __future__ import annotations

import jax

from repro.core import quantized
from repro.kernels import autotune
from repro.kernels.bitlinear import bitlinear as _bitlinear
from repro.kernels.bitlinear import bitlinear_grouped as _bitlinear_grouped
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.sa_sweep import sa_sweep as _sa_sweep
from repro.kernels.sa_sweep import sa_sweep_many as _sa_sweep_many
from repro.kernels.sa_sweep import sq_sweep_many as _sq_sweep_many
from repro.kernels.sqa_sweep import sqa_sweep_many as _sqa_sweep_many
from repro.models import attention as attn_lib

__all__ = [
    "default_interpret",
    "bitlinear",
    "bitlinear_grouped",
    "flash_attention",
    "sa_sweep",
    "sa_sweep_many",
    "sq_sweep_many",
    "sqa_sweep_many",
    "enable_kernels",
    "disable_kernels",
    "apply_compressed_fused",
    "apply_compressed_grouped_fused",
]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def bitlinear(x, m_packed, C, block_t: int = 128, interpret: bool | None = None,
              mode: str = "auto", math: str = "unpack", r_chunk: int = 1,
              vmem_budget: int | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _bitlinear(x, m_packed, C, block_t=block_t, interpret=interpret,
                      mode=mode, math=math, r_chunk=r_chunk,
                      vmem_budget=vmem_budget)


def bitlinear_grouped(x, m_packed, C, block_t: int = 128,
                      interpret: bool | None = None, mode: str = "auto",
                      math: str = "unpack", r_chunk: int = 1,
                      vmem_budget: int | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _bitlinear_grouped(x, m_packed, C, block_t=block_t,
                              interpret=interpret, mode=mode, math=math,
                              r_chunk=r_chunk, vmem_budget=vmem_budget)


def flash_attention(q, k, v, window: int = 0, interpret: bool | None = None, **kw):
    if interpret is None:
        interpret = default_interpret()
    return _flash(q, k, v, window=window, interpret=interpret, **kw)


def sa_sweep(h, B, x0, rand, temps, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _sa_sweep(h, B, x0, rand, temps, interpret=interpret)


def sa_sweep_many(h, B, x0, rand, temps, block_p: int | None = None,
                  interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _sa_sweep_many(h, B, x0, rand, temps, block_p=block_p,
                          interpret=interpret)


def sq_sweep_many(h, B, x0, rand, temperature: float = 0.1,
                  block_p: int | None = None, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _sq_sweep_many(h, B, x0, rand, temperature=temperature,
                          block_p=block_p, interpret=interpret)


def sqa_sweep_many(h, B, X0, rand, jperps, temperature: float = 0.05,
                   interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _sqa_sweep_many(h, B, X0, rand, jperps, temperature=temperature,
                           interpret=interpret)


def enable_kernels(interpret: bool | None = None) -> None:
    """Route model hot paths through the Pallas kernels.

    On TPU this is called by the launchers (and by ``serving.engine.Engine``
    when a compression artifact is present); tests call it with
    interpret=True to exercise the kernels end-to-end inside the models.
    Registers the flash-attention adapter into the attention layer and the
    fused y = (x @ M) @ C bitlinear adapter into the compressed-layer hot
    path.  Hooks are process-global; ``disable_kernels()`` restores the
    pure-jnp fallbacks.
    """
    it = default_interpret() if interpret is None else interpret

    def _flash_adapter(qh, k, v, window, scale):
        # model layout q (B,S,KV,rep,hd), k/v (B,S,KV,hd)
        B, S, KV, rep, hd = qh.shape
        q = qh.reshape(B, S, KV * rep, hd).transpose(0, 2, 1, 3)
        kk = k.transpose(0, 2, 1, 3)
        vv = v.transpose(0, 2, 1, 3)
        o = _flash(q, kk, vv, window=window, interpret=it, scale=scale)
        return o.transpose(0, 2, 1, 3).reshape(B, S, KV, rep, hd)

    def _fused_bitlinear_adapter(x, w):
        return apply_compressed_fused(x, w, interpret=it)

    def _grouped_bitlinear_adapter(x, w):
        return apply_compressed_grouped_fused(x, w, interpret=it)

    attn_lib.register_flash(_flash_adapter)
    quantized.register_bitlinear_fused(_fused_bitlinear_adapter)
    quantized.register_bitlinear_grouped(_grouped_bitlinear_adapter)


def disable_kernels() -> None:
    """Unregister every kernel hook (back to the jnp fallbacks).  Only
    affects callables traced after this call — an already-jitted decode
    step keeps whichever impl it was traced with."""
    attn_lib.clear_flash()
    quantized.clear_bitlinear()


def apply_compressed_fused(x, w, block_t: int = 128,
                           interpret: bool | None = None, mode: str = "auto",
                           schedule: "autotune.Schedule | None" = None):
    """Fused compressed linear: y = (x @ M) @ C via the bitlinear kernel.
    x (..., d_in) -> (..., d_out), any number of leading dims (including
    none); T not divisible by ``block_t`` is padded inside the kernel.

    Schedule selection: an explicit ``schedule`` pins everything; otherwise
    ``mode="auto"`` resolves through the autotune cache at trace time
    (tuned manifest entry when one matches this device/pallas_mode, else
    the heuristic default — see kernels/autotune.py).  A non-auto ``mode``
    bypasses resolution and keeps the kernel's static behaviour."""
    C = w["C"]
    n_r, n_c, K, td = C.shape
    lead = x.shape[:-1]
    T = 1
    for d in lead:
        T *= d
    x2 = x.reshape(T, x.shape[-1])
    if schedule is None and mode == "auto":
        schedule = autotune.resolve_fused(x2, w["m_packed"], C)
    kw = schedule.kwargs() if schedule is not None else {
        "mode": mode, "block_t": block_t,
    }
    y = bitlinear(x2, w["m_packed"], C, interpret=interpret, **kw)
    return y.reshape(*lead, n_c * td)


def apply_compressed_grouped_fused(x, w, block_t: int = 128,
                                   interpret: bool | None = None,
                                   mode: str = "auto",
                                   schedule: "autotune.Schedule | None" = None):
    """Grouped fused compressed linear: y_e = (x_e @ M_e) @ C_e via the
    grouped bitlinear kernel.  x (E, ..., d_in) -> (E, ..., d_out) with the
    leading axis matching the weight's group (expert) axis; any inner lead
    dims (the MoE (B, C) dispatch dims) flatten into the kernel's T axis.
    Schedule selection as in :func:`apply_compressed_fused`."""
    C = w["C"]
    E, n_r, n_c, K, td = C.shape
    lead = x.shape[1:-1]
    T = 1
    for d in lead:
        T *= d
    x3 = x.reshape(E, T, x.shape[-1])
    if schedule is None and mode == "auto":
        schedule = autotune.resolve_grouped(x3, w["m_packed"], C)
    kw = schedule.kwargs() if schedule is not None else {
        "mode": mode, "block_t": block_t,
    }
    y = bitlinear_grouped(x3, w["m_packed"], C, interpret=interpret, **kw)
    return y.reshape(E, *lead, n_c * td)
