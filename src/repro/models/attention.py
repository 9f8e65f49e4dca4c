"""GQA attention with RoPE (or none: ``position_embedding_type="nope"``),
optional qk-norm, sliding window, KV cache.  Scores are scaled by
``cfg.attention_scale`` (1/sqrt(head_dim) unless the config sets
``attention_multiplier``).

Three execution paths:
  * train/prefill: memory-bounded chunked causal attention (online-softmax
    "flash" structure in pure jnp) — peak memory O(q_chunk * kv_chunk) per
    head instead of O(S^2).  On TPU the Pallas ``flash_attention`` kernel
    (repro/kernels) replaces the inner loop; the jnp path is the oracle and
    the CPU / dry-run fallback.
  * decode: single-token query against the cache.  Under pjit the cache may
    be sequence-sharded over the ``model`` mesh axis (SP decode); XLA inserts
    the max/sum all-reduces for the sharded softmax.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.params import param

__all__ = ["init_attention", "attention", "init_kv_cache"]

# Hook set by repro.kernels.ops when running on TPU.
_FLASH_IMPL = None

# Costing/production toggle: when False the unrolled costing twin enumerates
# ALL (q, kv) block pairs — matching the baseline lax.scan schedule, which
# computes masked blocks too; True costs the causal-block-skipping variant
# (hillclimb; see EXPERIMENTS.md §Perf).
CAUSAL_SKIP_UNROLL = False

# Default q/kv chunk for the flash-structured loops; the roofline costing
# overrides it at long sequences (compile-size control; launch/costing.py).
Q_CHUNK_DEFAULT = 512


def register_flash(fn) -> None:
    global _FLASH_IMPL
    _FLASH_IMPL = fn


def clear_flash() -> None:
    global _FLASH_IMPL
    _FLASH_IMPL = None


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": layers.init_dense(k1, d, H * hd, ("embed", "heads"), dtype, cfg.use_bias),
        "wk": layers.init_dense(k2, d, KV * hd, ("embed", "kv"), dtype, cfg.use_bias),
        "wv": layers.init_dense(k3, d, KV * hd, ("embed", "kv"), dtype, cfg.use_bias),
        "wo": layers.init_dense(k4, H * hd, d, ("heads", "embed"), dtype, cfg.use_bias),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": param(jnp.ones((hd,), jnp.float32), (None,))}
        p["k_norm"] = {"scale": param(jnp.ones((hd,), jnp.float32), (None,))}
    return p


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, hd), positions (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, half)
    if ang.ndim == 2:                                             # (S, half)
        ang = ang[None, :, None, :]                               # (1, S, 1, half)
    else:                                                         # (B, S, half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def _head_rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    sc = scale.value if hasattr(scale, "value") else scale
    return (xf * jax.lax.rsqrt(var + eps) * sc).astype(x.dtype)


def _chunked_attention(
    q: jax.Array,       # (B, S, KV, rep, hd)
    k: jax.Array,       # (B, S, KV, hd)
    v: jax.Array,       # (B, S, KV, hd)
    window: int,
    q_chunk: int,
    causal_skip: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Causal (optionally sliding-window) flash-structured attention.

    ``causal_skip=True`` (serving paths): dynamic-bound fori over kv blocks
    skips fully-masked (j > i) pairs — halves causal FLOPs.  Training keeps
    the static scan over all pairs: reverse-mode AD cannot differentiate
    dynamic-trip-count loops (§Perf H11 — on TPU the custom-VJP Pallas flash
    kernel is the train-path answer)."""
    B, S, KV, rep, hd = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    nq = max(S // q_chunk, 1)
    qc = S // nq
    qs = q.reshape(B, nq, qc, KV, rep, hd)

    def q_block(i, qb):
        # qb (B, qc, KV, rep, hd); attend to keys 0..(i+1)*qc-1.  The kv loop
        # is a dynamic-bound fori: fully-masked (j > i) blocks are SKIPPED —
        # halves causal-attention FLOPs vs the scan-over-all-blocks baseline
        # (hillclimb "causal-skip", EXPERIMENTS.md §Perf).
        q_pos = i * qc + jnp.arange(qc)
        m0 = jnp.full((B, KV, rep, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, rep, qc), jnp.float32)
        a0 = jnp.zeros((B, KV, rep, qc, hd), jnp.float32)

        def kv_block(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * qc, qc, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * qc, qc, axis=1)
            k_pos = j * qc + jnp.arange(qc)
            s = jnp.einsum("bqgrh,bkgh->bgrqk", qb, kj).astype(jnp.float32) * scale
            mask = q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows (m_new = -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgh->bgrqh", p.astype(qb.dtype), vj
            ).astype(jnp.float32)
            return (m_new, l, acc)

        if causal_skip:
            j_lo = 0 if window <= 0 else jnp.maximum((i * qc - (window - 1)) // qc, 0)
            m, l, acc = jax.lax.fori_loop(j_lo, i + 1, kv_block, (m0, l0, a0))
        else:
            (m, l, acc), _ = jax.lax.scan(
                lambda c, j: (kv_block(j, c), None), (m0, l0, a0), jnp.arange(nq)
            )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)       # (B, KV, rep, qc, hd)

    outs = jax.lax.map(lambda args: q_block(*args), (jnp.arange(nq), qs.transpose(1, 0, 2, 3, 4, 5)))
    # outs (nq, B, KV, rep, qc, hd) -> (B, S, KV, rep, hd)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, KV, rep, hd)


def _chunked_attention_unrolled(
    q: jax.Array, k: jax.Array, v: jax.Array, window: int, q_chunk: int,
    scale: float | None = None,
) -> jax.Array:
    """Costing variant (see benchmarks/roofline.py): identical math, but the
    chunk loops are *python-unrolled over the causal lower triangle only*, so
    ``compiled.cost_analysis()`` counts exact causal FLOPs (lax.scan bodies
    are counted once by XLA's cost model, hence this twin)."""
    B, S, KV, rep, hd = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    nq = max(S // q_chunk, 1)
    qc = S // nq
    outs = []
    for i in range(nq):
        qb = q[:, i * qc : (i + 1) * qc]
        m = jnp.full((B, KV, rep, qc), -jnp.inf, jnp.float32)
        l = jnp.zeros((B, KV, rep, qc), jnp.float32)
        acc = jnp.zeros((B, KV, rep, qc, hd), jnp.float32)
        if CAUSAL_SKIP_UNROLL:
            j_lo = 0 if window <= 0 else max(0, (i * qc - (window - 1) - qc + 1) // qc)
            j_range = range(j_lo, i + 1)
        else:
            j_range = range(nq)
        for j in j_range:
            kj = k[:, j * qc : (j + 1) * qc]
            vj = v[:, j * qc : (j + 1) * qc]
            s = jnp.einsum("bqgrh,bkgh->bgrqk", qb, kj).astype(jnp.float32) * scale
            q_pos = i * qc + jnp.arange(qc)
            k_pos = j * qc + jnp.arange(qc)
            mask = q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgh->bgrqh", p.astype(qb.dtype), vj
            ).astype(jnp.float32)
            m = m_new
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        outs.append(out.transpose(0, 3, 1, 2, 4))     # (B, qc, KV, rep, hd)
    return jnp.concatenate(outs, axis=1)


def _decode_attention(qh, ck, cv, valid, scale, out_dtype):
    """Single-token attention over the cache.

    ``valid`` is either (Smax,) — every row decodes at the same position —
    or (B, Smax) for per-slot positions (continuous batching: each slot is
    at its own sequence length).

    Flash-decode (hillclimb, EXPERIMENTS.md §Perf): when activation rules
    advertise a sequence-sharding axis for the cache, run under shard_map —
    each device computes partial softmax stats over its local KV slice and
    the results combine with pmax/psum.  Without it, GSPMD all-gathers the
    whole cache per device (llama3-405b decode: 16.9 GiB/device).
    Fallback: plain (replicated-softmax) einsum path.
    """
    from repro.distributed.sharding import current_rule

    axis = current_rule("decode_sp_axis")
    dp = current_rule("dp_axes")
    B, KVh, rep, hd = qh.shape
    Smax = ck.shape[1]

    def _mask(s, val):
        vb = val[:, None, None, :] if val.ndim == 2 else val[None, None, None]
        return jnp.where(vb, s, -jnp.inf)

    def plain(q, k, v, val):
        s = jnp.einsum("bgrh,bkgh->bgrk", q, k).astype(jnp.float32) * scale
        s = _mask(s, val)
        w = jax.nn.softmax(s, axis=-1).astype(out_dtype)
        return jnp.einsum("bgrk,bkgh->bgrh", w, v)

    usable = axis is not None
    if usable:
        try:
            mesh = jax.sharding.get_abstract_mesh()
            sizes = dict(mesh.shape) if mesh is not None else {}
        except Exception:
            sizes = {}
        ax_size = sizes.get(axis, 0)
        dp_size = 1
        for a in (dp or ()):
            dp_size *= sizes.get(a, 1)
        usable = (
            ax_size > 1 and Smax % ax_size == 0 and B % max(dp_size, 1) == 0
        )
    if not usable:
        return plain(qh, ck, cv, valid)

    from jax.sharding import PartitionSpec as P

    def partial_attn(q, k, v, val):
        # local shapes: q (B/dp, KV, rep, hd); k/v (B/dp, S/ax, KV, hd)
        s = jnp.einsum("bgrh,bkgh->bgrk", q, k).astype(jnp.float32) * scale
        s = _mask(s, val)
        m = jnp.max(s, axis=-1, keepdims=True)
        g_m = jax.lax.pmax(m, axis)
        c = jnp.where(jnp.isfinite(m), jnp.exp(m - g_m), 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
        num = jnp.einsum("bgrk,bkgh->bgrh", p.astype(v.dtype), v).astype(jnp.float32)
        num = jax.lax.psum(num * c[..., 0][..., None], axis)
        den = jax.lax.psum(jnp.sum(p, axis=-1) * c[..., 0], axis)
        return (num / jnp.maximum(den, 1e-30)[..., None]).astype(out_dtype)

    valid_spec = P(dp, axis) if valid.ndim == 2 else P(axis)
    fn = jax.shard_map(
        partial_attn,
        in_specs=(P(dp), P(dp, axis), P(dp, axis), valid_spec),
        out_specs=P(dp),
    )
    return fn(qh, ck, cv, valid)


def _chunk_cache_attention(qh, ck, cv, qpos, window, scale, out_dtype):
    """Chunked-prefill attention: a chunk of queries against the FULL cache.

    qh (B, S, KV, rep, hd) are the current chunk's queries at absolute
    positions ``qpos`` ((S,) or (B, S)); ck/cv (B, Smax, KV, hd) is the
    updated cache (the chunk's own k/v already written at those positions).
    Used by continuation chunks (pos_offset > 0), where the chunk-local
    flash path would miss everything prefetched by earlier chunks.  Memory
    is O(S * Smax) per head — bounded by the scheduler's chunk size.
    """
    B, S = qh.shape[:2]
    Smax = ck.shape[1]
    s = jnp.einsum("bqgrh,bkgh->bgrqk", qh, ck).astype(jnp.float32) * scale
    kpos = jnp.arange(Smax)
    qp = qpos if qpos.ndim == 2 else qpos[None]           # (B|1, S)
    mask = kpos[None, None, :] <= qp[:, :, None]          # (B|1, S, Smax)
    if window > 0:
        mask &= kpos[None, None, :] > qp[:, :, None] - window
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
    o = jnp.einsum("bgrqk,bkgh->bqgrh", w, cv)
    return o.astype(out_dtype)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, KV, hd), dtype),
        "v": jnp.zeros((batch, max_len, KV, hd), dtype),
    }


def attention(
    h: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    pos_offset: jax.Array | int = 0,
    cache: dict | None = None,
    window: int | None = None,
    q_chunk: int | None = None,
    unroll: bool = False,
    attend_cache: bool = False,
):
    """Returns (out, new_cache).  Modes:
      cache is None              -> training/prefill without cache
      cache given, S == 1        -> decode step at position pos_offset
      cache given, S > 1         -> prefill writing the cache; with
                                    ``attend_cache=True`` the chunk's queries
                                    attend to the FULL cache (continuation
                                    chunks of a chunked prefill at
                                    pos_offset > 0), otherwise chunk-local
                                    flash attention (a full prefill from 0)

    ``pos_offset`` may be a scalar (every row at the same position — the
    fixed-batch path) or a (B,) vector of per-slot positions (continuous
    batching: each slot is at its own sequence length).  Vector positions
    write the cache via a per-row scatter and mask attention per slot.
    """
    B, S, d = h.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    rep = H // KV
    window = cfg.sliding_window if window is None else window
    q_chunk = Q_CHUNK_DEFAULT if q_chunk is None else q_chunk

    q = layers.apply_dense(h, p["wq"]).reshape(B, S, H, hd)
    k = layers.apply_dense(h, p["wk"]).reshape(B, S, KV, hd)
    v = layers.apply_dense(h, p["wv"]).reshape(B, S, KV, hd)

    if cfg.qk_norm:
        q = _head_rms(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = _head_rms(k, p["k_norm"]["scale"], cfg.norm_eps)

    pos_is_vec = isinstance(pos_offset, jax.Array) and pos_offset.ndim == 1
    if pos_is_vec:
        positions = pos_offset[:, None] + jnp.arange(S)   # (B, S)
    else:
        positions = pos_offset + jnp.arange(S)            # (S,)
    if cfg.position_embedding_type != "nope":
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    scale = cfg.attention_scale

    # Ring-buffer cache: a sliding-window attn layer only ever needs the
    # last `window` KV entries, so its cache may be allocated at window size
    # (zamba2 @ long_500k: 4096 instead of 524288 — this is what keeps the
    # hybrid sub-quadratic in memory too).  Ring mode iff the cache is
    # exactly window-sized and smaller than the write position ever needed.
    cache_len = cache["k"].shape[1] if cache is not None else 0
    ring = cache is not None and window > 0 and cache_len == window

    new_cache = cache
    if cache is not None:
        if pos_is_vec:
            # per-slot write positions: each row lands at its own offset
            if ring and S > 1:
                raise NotImplementedError(
                    "vector pos_offset with a ring (window-sized) cache is "
                    "decode-only (S == 1)"
                )
            wp = jnp.mod(pos_offset, cache_len) if ring else pos_offset

            def _wr(cb, xb, pb):   # cb (Smax, KV, hd), xb (S, KV, hd)
                return jax.lax.dynamic_update_slice_in_dim(cb, xb, pb, axis=0)

            ck = jax.vmap(_wr)(cache["k"], k, wp)
            cv = jax.vmap(_wr)(cache["v"], v, wp)
        elif ring and S >= cache_len:
            # prefill longer than the window: only the last `window` tokens
            # matter; place token (pos_offset + t) at ring slot (pos+t) % w.
            roll = jnp.mod(pos_offset + (S - cache_len), cache_len)
            ck = jnp.roll(k[:, -cache_len:], roll, axis=1)
            cv = jnp.roll(v[:, -cache_len:], roll, axis=1)
        else:
            write_pos = jnp.mod(pos_offset, cache_len) if ring else pos_offset
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, write_pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, write_pos, axis=1)
        new_cache = {"k": ck, "v": cv}

    if S == 1 and cache is not None:
        # ---- decode: one query against the cache ----
        ck, cv = new_cache["k"], new_cache["v"]
        Smax = ck.shape[1]
        qh = q.reshape(B, KV, rep, hd)
        kpos = jnp.arange(Smax)
        pb = pos_offset[:, None] if pos_is_vec else pos_offset
        if ring:
            # entries are the last `window` tokens by construction; only the
            # not-yet-written slots (pos < cache_len) are invalid.
            valid = (kpos <= pb) | (pb >= cache_len)
        else:
            valid = kpos <= pb
            if window > 0:
                valid &= kpos > pb - window
        # valid: (Smax,) scalar pos / (B, Smax) per-slot pos
        o = _decode_attention(qh, ck, cv, valid, scale, h.dtype)
        o = o.reshape(B, 1, H * hd)
    elif cache is not None and attend_cache:
        # ---- chunked prefill: chunk queries vs the full updated cache ----
        qh = q.reshape(B, S, KV, rep, hd)
        o = _chunk_cache_attention(
            qh, new_cache["k"], new_cache["v"], positions, window,
            scale, h.dtype,
        )
        o = o.reshape(B, S, H * hd)
    else:
        qh = q.reshape(B, S, KV, rep, hd)
        if unroll:
            o = _chunked_attention_unrolled(qh, k, v, window, q_chunk, scale)
        elif _FLASH_IMPL is not None:
            o = _FLASH_IMPL(qh, k, v, window, scale)
        else:
            # checkpoint: without it autodiff saves every chunk's fp32 score
            # matrix (2.1 GiB per layer on zamba2 train — §Perf); recomputing
            # the flash forward in backward is the standard trade.
            # causal_skip only on serving paths (cache given): reverse-mode
            # AD rejects the dynamic-bound kv loop.
            attn_fn = jax.checkpoint(
                functools.partial(
                    _chunked_attention, window=window, q_chunk=q_chunk,
                    causal_skip=cache is not None, scale=scale,
                ),
                policy=jax.checkpoint_policies.nothing_saveable,
            )
            o = attn_fn(qh, k, v)
        o = o.reshape(B, S, H * hd)

    return layers.apply_dense(o, p["wo"]), new_cache
