"""Mixture-of-Experts block: top-k routing with per-group capacity.

Baseline path (this file): the classic dispatch/combine einsum formulation
(Switch/GShard style) with the *per-batch-row group* trick so the dispatch
tensor is (B, S, E, C) rather than (T, E, C).  Experts are sharded over the
``model`` (expert-parallel) mesh axis; tokens over ``data``; XLA SPMD inserts
the gather/reduce collectives.  This path is simple and robustly shardable —
its known cost is *dense-dispatch FLOP inflation* (the one-hot einsums count
as real FLOPs), which the roofline analysis quantifies via the
MODEL_FLOPS / HLO_FLOPs ratio and the §Perf hillclimb replaces with a
sort-based shard_map dispatch for the MoE cell (see moe_sorted.py).

Load-balancing auxiliary loss follows Switch Transformers (mean over experts
of fraction-routed * mean-gate).

Expert parallelism: a layer may hold a share of the experts
(``cfg.num_experts_held`` from ``cfg.first_expert_held``).  It routes over
all ``cfg.num_experts`` and computes the part of the output its own
experts give, for the tokens routed to them, with their gate weights; the
shares of all chips, with the shared expert counted once, add up to the
whole layer.  On one chip the layer runs without the exchange.

``capacity_factor`` 0 routes dropless: an expert takes each token at most
once, so a queue as long as the routing group holds every token routed to
it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quantized
from repro.models import layers
from repro.models.params import dense_init

__all__ = ["init_moe", "moe_block", "moe_capacity"]


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    if cfg.capacity_factor <= 0:
        return tokens_per_group
    k = max(cfg.experts_per_token, 1)
    cap = int(cfg.capacity_factor * tokens_per_group * k / max(cfg.num_experts, 1))
    return max(cap, 1)


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.experts_held
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": dense_init(k1, (d, cfg.num_experts), ("embed", None), jnp.float32),
        "gate": dense_init(k2, (E, d, ff), ("experts", "embed", "mlp"), dtype),
        "up": dense_init(k3, (E, d, ff), ("experts", "embed", "mlp"), dtype),
        "down": dense_init(k4, (E, ff, d), ("experts", "mlp", "embed"), dtype),
    }
    if cfg.moe_shared_expert:
        p["shared"] = layers.init_mlp(k5, d, cfg.d_ff_shared or ff, dtype,
                                      cfg.use_bias)
    return p


def _expert_linear(x: jax.Array, w) -> jax.Array:
    """Per-expert linear over the (E, B, C, d_in) dispatch layout.

    Dense expert stacks run the classic ``ebcd,edf->ebcf`` einsum;
    integer-decomposition-compressed stacks ({"m_packed", "C"} with a
    leading expert axis, as produced by ``repro.compression``) route through
    ``quantized.apply_compressed`` — the grouped fused bitlinear kernel when
    one is registered, the grouped two-einsum oracle otherwise."""
    if quantized.is_compressed(w):
        return quantized.apply_compressed(x, w)
    if quantized.is_intquant(w):
        return quantized.apply_intquant(x, w)
    return jnp.einsum("ebcd,edf->ebcf", x, w)


def _route_block(cfg: ModelConfig) -> int:
    """Routing group size: dispatch tensors are (groups, blk, E, C) with
    C ~ cf*k*blk/E — fixed-size token blocks keep them bounded regardless of
    sequence length (a whole 32k sequence as one group made granite's
    prefill dispatch 165 GiB/device; EXPERIMENTS.md §Perf)."""
    return 1024


def moe_block(h: jax.Array, p: dict, cfg: ModelConfig):
    """h (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    B0, S0, d = h.shape
    blk = min(_route_block(cfg), S0)
    while S0 % blk != 0:
        blk //= 2
    h = h.reshape(B0 * (S0 // blk), blk, d)
    B, S, _ = h.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    e0, n = cfg.first_expert_held, cfg.experts_held
    C = moe_capacity(cfg, S)

    with jax.named_scope("moe.route"):
        router_w = p["router"].value if hasattr(p["router"], "value") else p["router"]
        logits = (h.astype(jnp.float32) @ router_w)            # (B, S, E)
        probs = jax.nn.softmax(logits, axis=-1)

        gate_vals, expert_idx = jax.lax.top_k(probs, k)        # (B, S, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )
        # one-hot over all experts per routing slot: (B, S, k, E)
        onehot_all = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)

    with jax.named_scope("moe.held_experts"):
        # the experts held here: (B, S, k, n)
        onehot = onehot_all if n == E else onehot_all[..., e0:e0 + n]
        # position of each routing slot within its expert queue: count over
        # the flattened (S*k) slot order so slots of different tokens never
        # collide in the same capacity slot (causal: earlier tokens
        # unaffected by later).
        flat = onehot.reshape(B, S * k, n)
        pos_in_expert = (jnp.cumsum(flat, axis=1) - flat).reshape(B, S, k, n)
        pos = jnp.sum(pos_in_expert * onehot, axis=-1)         # (B, S, k)
        keep = pos < C
        gate_vals = gate_vals * keep.astype(gate_vals.dtype)

        # dispatch/combine tensors (B, S, n, C)
        pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
        dispatch = jnp.einsum("bske,bskc->bsec", onehot, pos_oh)
        combine = jnp.einsum("bsk,bske,bskc->bsec", gate_vals, onehot, pos_oh)

        xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(h.dtype), h)   # (n,B,C,d)
        gate_w = p["gate"].value if hasattr(p["gate"], "value") else p["gate"]
        up_w = p["up"].value if hasattr(p["up"], "value") else p["up"]
        down_w = p["down"].value if hasattr(p["down"], "value") else p["down"]
        act = jax.nn.silu(_expert_linear(xin, gate_w))
        act = act * _expert_linear(xin, up_w)
        xout = _expert_linear(act, down_w)                                # (n,B,C,d)
        out = jnp.einsum("bsec,ebcd->bsd", combine.astype(h.dtype), xout)

    if "shared" in p:
        with jax.named_scope("moe.shared_expert"):
            out = out + layers.mlp(h, p["shared"])

    # Switch load-balance loss
    frac_routed = jnp.mean(
        onehot_all[..., 0, :] if k == 1 else jnp.max(onehot_all, axis=2), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_routed * mean_prob)
    return out.reshape(B0, S0, d), aux
