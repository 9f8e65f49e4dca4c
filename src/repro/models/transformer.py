"""Composable decoder backbone covering all assigned architecture families.

A model is a sequence of blocks described by ``cfg.block_pattern`` (e.g.
("attn",) for dense LMs, ("attn", "attn_moe") for llama4-style interleaved
MoE, ("ssm",) for Mamba2, ("ssm",)*5 + ("ssm_attn",) for Zamba2 hybrids,
("ssm_moe",)*5 + ("attn_moe",) + ("ssm_moe",)*4 for granite-4.0-h, whose
Mamba2 and attention mixers are each followed by the MoE FFN).
The pattern repeats ``cfg.num_groups`` times under a ``lax.scan`` (stacked
group parameters -> O(1) compile time in depth) with optional remat;
leftover layers (num_layers % len(pattern)) run unrolled, and Zamba2's
*shared* attention block lives outside the scan so its parameters are reused
by every invocation.

Modes: train/prefill (cache=None / cache given) and single-token decode.
``unroll=True`` produces the python-unrolled costing twin used by the
roofline analysis (lax.scan bodies are counted once by XLA's cost model).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn_lib
from repro.models import layers, moe, ssm
from repro.models.params import Param, split

__all__ = [
    "init_model",
    "forward",
    "train_loss",
    "init_cache",
    "model_dtype",
]


def model_dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _init_block(key, kind: str, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    if kind == "attn":
        return {
            "norm1": layers.init_rms_norm(d, dtype),
            "attn": attn_lib.init_attention(ks[0], cfg, dtype),
            "norm2": layers.init_rms_norm(d, dtype),
            "mlp": layers.init_mlp(ks[1], d, cfg.d_ff_dense or cfg.d_ff, dtype, cfg.use_bias),
        }
    if kind == "attn_moe":
        return {
            "norm1": layers.init_rms_norm(d, dtype),
            "attn": attn_lib.init_attention(ks[0], cfg, dtype),
            "norm2": layers.init_rms_norm(d, dtype),
            "moe": moe.init_moe(ks[1], cfg, dtype),
        }
    if kind in ("ssm", "ssm_attn"):
        return {
            "norm1": layers.init_rms_norm(d, dtype),
            "ssm": ssm.init_ssm(ks[0], cfg, dtype),
        }
    if kind == "ssm_moe":
        return {
            "norm1": layers.init_rms_norm(d, dtype),
            "ssm": ssm.init_ssm(ks[0], cfg, dtype),
            "norm2": layers.init_rms_norm(d, dtype),
            "moe": moe.init_moe(ks[1], cfg, dtype),
        }
    raise ValueError(kind)


def _init_shared_attn(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": layers.init_rms_norm(cfg.d_model, dtype),
        "attn": attn_lib.init_attention(k1, cfg, dtype),
        "norm2": layers.init_rms_norm(cfg.d_model, dtype),
        "mlp": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype, cfg.use_bias),
    }


def _residual(h, x, cfg: ModelConfig):
    """h + residual_multiplier * x (granite scales every residual branch)."""
    if cfg.residual_multiplier != 1.0:
        x = x * cfg.residual_multiplier
    return h + x


def _moe_ffn(h, p, cfg: ModelConfig):
    mo, aux = moe.moe_block(layers.rms_norm(h, p["norm2"], cfg.norm_eps), p["moe"], cfg)
    return _residual(h, mo, cfg), aux


def _apply_block(
    h, p, kind, cfg: ModelConfig, shared, *, cache, pos_offset, window, unroll,
    attend_cache=False,
):
    """Returns (h, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "attn_moe"):
        kv = cache["kv"] if cache is not None else None
        if cfg.parallel_block:
            n = layers.rms_norm(h, p["norm1"], cfg.norm_eps)
            a, new_kv = attn_lib.attention(
                n, p["attn"], cfg, pos_offset=pos_offset, cache=kv,
                window=window, unroll=unroll, attend_cache=attend_cache,
            )
            h = h + a + layers.mlp(n, p["mlp"])
            return h, ({"kv": new_kv} if cache is not None else None), aux
        a, new_kv = attn_lib.attention(
            layers.rms_norm(h, p["norm1"], cfg.norm_eps),
            p["attn"], cfg, pos_offset=pos_offset, cache=kv,
            window=window, unroll=unroll, attend_cache=attend_cache,
        )
        h = _residual(h, a, cfg)
        if kind == "attn":
            h = _residual(
                h, layers.mlp(layers.rms_norm(h, p["norm2"], cfg.norm_eps), p["mlp"]), cfg
            )
        else:
            h, aux = _moe_ffn(h, p, cfg)
        return h, ({"kv": new_kv} if cache is not None else None), aux

    if kind in ("ssm", "ssm_attn", "ssm_moe"):
        sc = cache["ssm"] if cache is not None else None
        with jax.named_scope("ssm.mixer"):
            s, new_sc = ssm.ssm_block(
                layers.rms_norm(h, p["norm1"], cfg.norm_eps), p["ssm"], cfg,
                cache=sc, unroll=unroll,
            )
        h = _residual(h, s, cfg)
        new_cache = {"ssm": new_sc} if cache is not None else None
        if kind == "ssm_moe":
            h, aux = _moe_ffn(h, p, cfg)
        if kind == "ssm_attn":
            kv = cache["kv"] if cache is not None else None
            a, new_kv = attn_lib.attention(
                layers.rms_norm(h, shared["norm1"], cfg.norm_eps),
                shared["attn"], cfg, pos_offset=pos_offset, cache=kv,
                window=window, unroll=unroll, attend_cache=attend_cache,
            )
            h = _residual(h, a, cfg)
            h = _residual(h, layers.mlp(
                layers.rms_norm(h, shared["norm2"], cfg.norm_eps), shared["mlp"]
            ), cfg)
            if cache is not None:
                new_cache["kv"] = new_kv
        return h, new_cache, aux
    raise ValueError(kind)


def _apply_group(h, gp, cfg: ModelConfig, shared, *, cache, pos_offset, window, unroll,
                 attend_cache=False):
    aux = jnp.zeros((), jnp.float32)
    new_cache = {} if cache is not None else None
    for i, kind in enumerate(cfg.block_pattern):
        key = f"{i}"
        h, nc, a = _apply_block(
            h, gp[key], kind, cfg, shared,
            cache=None if cache is None else cache[key],
            pos_offset=pos_offset, window=window, unroll=unroll,
            attend_cache=attend_cache,
        )
        if cache is not None:
            new_cache[key] = nc
        aux = aux + a
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

def _is_param(x):
    return isinstance(x, Param)


def _stack_param_trees(trees):
    """Stack a list of identically-structured Param trees along a new leading
    "layers" axis (mesh-unsharded: None)."""
    return jax.tree.map(
        lambda *ps: Param(jnp.stack([q.value for q in ps]), (None,) + ps[0].axes),
        *trees,
        is_leaf=_is_param,
    )


def init_model(key, cfg: ModelConfig):
    """Returns a Param pytree (values + logical axes). Use params.split."""
    dtype = model_dtype(cfg)
    k_embed, k_groups, k_rem, k_shared, k_head = jax.random.split(key, 5)

    def one_group(k):
        ks = jax.random.split(k, len(cfg.block_pattern))
        return {
            f"{i}": _init_block(ks[i], kind, cfg, dtype)
            for i, kind in enumerate(cfg.block_pattern)
        }

    G = cfg.num_groups
    group_keys = jax.random.split(k_groups, G)
    groups = _stack_param_trees([one_group(group_keys[g]) for g in range(G)])

    p = {
        "embed": layers.init_embedding(k_embed, cfg.vocab_size, cfg.d_model, dtype),
        "groups": groups,
        "final_norm": layers.init_rms_norm(cfg.d_model, dtype),
    }
    if cfg.remainder_pattern:
        ks = jax.random.split(k_rem, len(cfg.remainder_pattern))
        p["rem"] = {
            f"{i}": _init_block(ks[i], kind, cfg, dtype)
            for i, kind in enumerate(cfg.remainder_pattern)
        }
    if cfg.shared_attn:
        p["shared"] = _init_shared_attn(k_shared, cfg, dtype)
    if not cfg.tie_embeddings:
        p["head"] = layers.init_dense(
            k_head, cfg.d_model, cfg.vocab_size, ("embed", "vocab"), dtype
        )
    return p


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _init_block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int, dtype):
    c = {}
    if kind in ("attn", "attn_moe"):
        c["kv"] = attn_lib.init_kv_cache(cfg, batch, max_len, dtype)
    if kind in ("ssm", "ssm_attn", "ssm_moe"):
        c["ssm"] = ssm.init_ssm_cache(cfg, batch, dtype)
    if kind == "ssm_attn":
        kv_len = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
        c["kv"] = attn_lib.init_kv_cache(cfg, batch, kv_len, dtype)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int, stacked: bool = True):
    """Decode cache pytree.  ``stacked=True`` packs per-group caches into
    (G, ...) arrays for the scanned forward; ``stacked=False`` keeps a list
    of per-group caches for the *unrolled* decode path — scan-carried cache
    stacks get 14x copy-duplicated by (CPU) buffer assignment, while
    unrolled per-leaf caches alias in/out via donation (EXPERIMENTS.md
    §Perf H10)."""
    dtype = model_dtype(cfg)
    G = cfg.num_groups

    def one():
        return {
            f"{i}": _init_block_cache(kind, cfg, batch, max_len, dtype)
            for i, kind in enumerate(cfg.block_pattern)
        }

    if stacked:
        groups = jax.tree.map(lambda a: jnp.zeros((G,) + a.shape, a.dtype), one())
    else:
        groups = [one() for _ in range(G)]
    cache = {"groups": groups}
    if cfg.remainder_pattern:
        cache["rem"] = {
            f"{i}": _init_block_cache(kind, cfg, batch, max_len, dtype)
            for i, kind in enumerate(cfg.remainder_pattern)
        }
    return cache


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _values(tree):
    return jax.tree.map(
        lambda p: p.value if isinstance(p, Param) else p,
        tree,
        is_leaf=lambda x: isinstance(x, Param),
    )


def forward(
    params,
    inputs,
    cfg: ModelConfig,
    *,
    cache=None,
    pos_offset=0,
    unroll: bool = False,
    window: int | None = None,
    last_only: bool = False,
    return_hidden: bool = False,
    unroll_groups: bool = False,
    attend_cache: bool = False,
):
    """inputs: {"tokens": (B,S) int32} or {"embeds": (B,S,d)}.
    Returns (logits (B,S,V), new_cache, aux_loss).  ``last_only`` computes
    logits for the final position only (prefill: a (B,S,V) logits tensor
    with an unshardable odd vocab was 12 GiB/device on internvl2 —
    EXPERIMENTS.md §Perf).  ``return_hidden`` skips the head and returns the
    post-final-norm hidden states (the chunked CE path)."""
    p = _values(params)
    dtype = model_dtype(cfg)

    if "tokens" in inputs:
        h = layers.embed_lookup(inputs["tokens"], p["embed"]).astype(dtype)
    else:
        h = inputs["embeds"].astype(dtype)
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    h = constrain(h, "hidden")

    window = cfg.sliding_window if window is None else window
    shared = p.get("shared")

    group_fn = functools.partial(
        _apply_group, cfg=cfg, shared=shared,
        pos_offset=pos_offset, window=window, unroll=unroll,
        attend_cache=attend_cache,
    )
    # remat in costing (unroll) mode too, so autodiff recompute FLOPs are
    # counted the same way the production scan path executes them.
    if cfg.remat and cache is None:
        group_fn = jax.checkpoint(
            group_fn, policy=jax.checkpoint_policies.nothing_saveable
        )

    aux_total = jnp.zeros((), jnp.float32)
    gcache = cache["groups"] if cache is not None else None
    cache_is_list = isinstance(gcache, list)

    if unroll or unroll_groups or cache_is_list:
        new_gcaches = []
        for g in range(cfg.num_groups):
            gp = jax.tree.map(lambda a: a[g], p["groups"])
            if gcache is None:
                gc = None
            elif cache_is_list:
                gc = gcache[g]
            else:
                gc = jax.tree.map(lambda a: a[g], gcache)
            h, nc, aux = group_fn(h, gp, cache=gc)
            aux_total = aux_total + aux
            if nc is not None:
                new_gcaches.append(nc)
        if not new_gcaches:
            new_groups = None
        elif cache_is_list:
            new_groups = new_gcaches
        else:
            new_groups = jax.tree.map(lambda *xs: jnp.stack(xs), *new_gcaches)
    else:
        def body(carry, xs):
            h, aux_acc = carry
            gp, gc = xs
            h, nc, aux = group_fn(h, gp, cache=gc)
            h = constrain(h, "hidden")
            return (h, aux_acc + aux), nc

        (h, aux_total), new_groups = jax.lax.scan(
            body, (h, aux_total), (p["groups"], gcache)
        )

    new_cache = {"groups": new_groups} if cache is not None else None

    if cfg.remainder_pattern:
        rcache = cache["rem"] if cache is not None else None
        new_rem = {}
        remat_rem = cfg.remat and cache is None

        def block_fn(h, bp, sh, kind, bcache):
            def inner(h_, bp_, sh_):
                return _apply_block(
                    h_, bp_, kind, cfg, sh_, cache=bcache,
                    pos_offset=pos_offset, window=window, unroll=unroll,
                    attend_cache=attend_cache,
                )

            if remat_rem:
                # remainder layers run outside the scan — without remat they
                # save every intermediate for backward (zamba2: +GBs, §Perf)
                inner = jax.checkpoint(
                    inner, policy=jax.checkpoint_policies.nothing_saveable
                )
            return inner(h, bp, sh)

        for i, kind in enumerate(cfg.remainder_pattern):
            h, nc, aux = block_fn(
                h, p["rem"][f"{i}"], shared, kind,
                None if rcache is None else rcache[f"{i}"],
            )
            aux_total = aux_total + aux
            if nc is not None:
                new_rem[f"{i}"] = nc
        if cache is not None:
            new_cache["rem"] = new_rem

    if last_only:
        h = h[:, -1:]
    h = layers.rms_norm(h, p["final_norm"], cfg.norm_eps)
    if return_hidden:
        return h, new_cache, aux_total
    if cfg.tie_embeddings:
        table = p["embed"]["table"]
        logits = h @ table.T
    else:
        logits = layers.apply_dense(h, p["head"])
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.logits_softcap > 0:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    logits = constrain(logits, "logits")
    return logits, new_cache, aux_total


def train_loss(params, batch, cfg: ModelConfig, *, unroll: bool = False):
    """Next-token CE (+ z-loss + MoE aux). Returns (loss, metrics).

    The CE is computed from hidden states per sequence chunk so the (B,S,V)
    logits tensor is never materialised (layers.chunked_softmax_cross_entropy)."""
    h, _, aux = forward(params, batch, cfg, unroll=unroll, return_hidden=True)
    p = _values(params)
    if cfg.tie_embeddings:
        head_w = p["embed"]["table"].T
    else:
        head_w = p["head"]["w"]
    if cfg.logits_scaling != 1.0:
        head_w = head_w / cfg.logits_scaling
    if "labels" in batch:
        labels = batch["labels"]
        hh = h
    else:
        labels = batch["tokens"][:, 1:]
        hh = h[:, :-1]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    elif "labels" not in batch:
        mask = mask[:, 1:]
    ce = layers.chunked_softmax_cross_entropy(
        hh, head_w, labels, mask, cfg.z_loss, cfg.logits_softcap
    )
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux, "loss": loss}
