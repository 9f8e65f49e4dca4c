"""Compositional roofline costing (EXPERIMENTS.md §Roofline).

``compiled.cost_analysis()`` counts while-loop (lax.scan) bodies ONCE, but
the production programs scan over layer groups and microbatches.  We
reconstruct exact totals by compiling three scan-free subprograms per cell
on the same mesh with the same shardings:

  B  = one layer-group step (fwd+bwd for train; fwd for serve), with the
       model's costing twin (`unroll=True`) so attention/SSD chunk loops are
       python-unrolled — trip counts exact, causal structure controllable;
  A  = a one-group end-to-end step (same kind) -> stem = A - B - C;
  C  = the optimiser update alone (train only; also gives its HBM bytes).

  total = microbatches * (stem + num_groups * B [+ remainder layers]) + C

Collective bytes compose the same way from the per-subprogram HLO text.
This is exact for FLOPs/collectives (linear in trip counts) and a good
approximation for bytes-accessed (fusion boundaries differ only at the
stem/layer seam).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import roofline
from repro.configs import SHAPES, get_config
from repro.distributed import sharding as shd
from repro.launch.cells import _batch_specs, build_cell
from repro.launch.mesh import make_production_mesh
from repro.launch.presets import parallel_preset
from repro.models import transformer as tr
from repro.models.params import split
from repro.serving.engine import cache_shardings, make_decode_step, make_prefill
from repro.training.loop import _axes_trees, make_optimizer, make_train_step, state_shardings
from repro.optim import constant

__all__ = [
    "cost_cell",
    "CellCosts",
    "compressed_weight_bytes",
    "int8_weight_bytes",
    "dense_weight_bytes",
]


# ---------------------------------------------------------------------------
# Weight-compression byte costing (pure; used by repro.compression.plan to
# predict bytes/ratio before any solver runs)
# ---------------------------------------------------------------------------


def dense_weight_bytes(shape, itemsize: int) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n * int(itemsize)


def compressed_weight_bytes(
    d_in: int, d_out: int, tile_n: int, tile_d: int, K: int,
    itemsize: int, groups: int = 1,
) -> int:
    """Stored bytes of the {"m_packed", "C"} form produced by
    ``repro.compression.execute`` — must agree exactly with
    ``quantized.compressed_num_bytes`` on the executed result:
    per tile, M packs to tile_n * ceil(K/8) uint8 and C stays
    (K, tile_d) at the weight's dtype."""
    r, c = d_in // tile_n, d_out // tile_d
    m_bytes = r * c * tile_n * ((K + 7) // 8)
    c_bytes = r * c * K * tile_d * int(itemsize)
    return int(groups) * (m_bytes + c_bytes)


def int8_weight_bytes(
    d_in: int, d_out: int, tile_n: int, tile_d: int, groups: int = 1,
) -> int:
    """Stored bytes of the int8-baseline {"q", "scale"} form — must agree
    exactly with ``quantized.intquant_num_bytes`` on the executed result:
    per tile, tile_n * tile_d int8 values plus one float32 scale."""
    r, c = d_in // tile_n, d_out // tile_d
    return int(groups) * (r * c * tile_n * tile_d + r * c * 4)


class CellCosts(NamedTuple):
    flops: float
    bytes: float
    coll: float
    parts: dict


def _program_costs(compiled) -> tuple[float, float, float]:
    c = roofline.cost_summary(compiled)
    coll = roofline.collective_bytes(compiled.as_text())["total"]
    return c["flops"], c["bytes"], coll


def _strip_lead(tree_axes, tree_shapes):
    axes = jax.tree.map(lambda a: a[1:], tree_axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), tree_shapes
    )
    return axes, shapes


def _group_param_specs(cfg, pcfg, mesh):
    shapes, axes = _axes_trees(cfg)
    g_axes, g_shapes = _strip_lead(axes["groups"], shapes["groups"])
    rules = shd.make_rules(pcfg)
    return g_shapes, shd.param_shardings(g_axes, g_shapes, rules, mesh), shapes, axes


def _hidden_sds_and_spec(cfg, shape, pcfg, mesh, micro: int):
    B = shape.global_batch // micro if shape.kind == "train" else shape.global_batch
    S = 1 if shape.kind == "decode" else shape.seq_len
    sds = jax.ShapeDtypeStruct((B, S, cfg.d_model), tr.model_dtype(cfg))
    dp_names = ("pod", "data", "model") if pcfg.dp_includes_model else ("pod", "data")
    dp = tuple(a for a in dp_names if a in mesh.shape)
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    lead = dp if B % max(size, 1) == 0 else None
    model = "model" if ("model" in mesh.shape and not pcfg.dp_includes_model
                        and cfg.d_model % mesh.shape["model"] == 0) else None
    return sds, NamedSharding(mesh, P(lead, None, model))


def cost_cell(arch: str, shape_name: str, multi_pod: bool = False,
              causal_skip: bool = False, overrides: dict | None = None) -> dict:
    """Compositional roofline terms for one cell.  ``causal_skip`` costs the
    causal-block-skipping attention variant (hillclimb) instead of the
    baseline all-blocks schedule."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pcfg = parallel_preset(cfg, shape, multi_pod=multi_pod)
    if overrides:
        pcfg = dataclasses.replace(pcfg, **overrides)
    micro = pcfg.microbatches
    G = cfg.num_groups
    kind = shape.kind

    g_shapes, g_specs, full_shapes, full_axes = _group_param_specs(cfg, pcfg, mesh)
    h_sds, h_spec = _hidden_sds_and_spec(cfg, shape, pcfg, mesh, micro)

    shared_sds = full_shapes.get("shared")
    shared_spec = None
    if shared_sds is not None:
        rules = shd.make_rules(pcfg)
        shared_spec = shd.param_shardings(full_axes["shared"], shared_sds, rules, mesh)
        shared_sds = jax.tree.map(lambda s: s, shared_sds)

    from repro.models import attention as attn_lib

    attn_lib.CAUSAL_SKIP_UNROLL = bool(causal_skip)
    tr_cfg = cfg
    # coarser costing chunks at long sequence: the unrolled twin at 32k with
    # q_chunk=512 is 2080 block pairs per layer -> XLA-CPU compile blow-up.
    # FLOPs are chunk-size-invariant except the causal diagonal granularity
    # (<= 1/(2*nq) relative overcount with causal_skip).
    attn_lib.Q_CHUNK_DEFAULT = (
        max(shape.seq_len // 8, 512) if shape.seq_len >= 16384 else 512
    )

    def group_fwd(h, gp, shared):
        out, _, aux = tr._apply_group(
            h, gp, tr_cfg, shared, cache=None, pos_offset=0,
            window=cfg.sliding_window, unroll=True,
        )
        return jnp.sum(out.astype(jnp.float32)) + aux

    def group_fwd_raw(h, gp, shared):
        out, _, _ = tr._apply_group(
            h, gp, tr_cfg, shared, cache=None, pos_offset=0,
            window=cfg.sliding_window, unroll=True,
        )
        return out

    parts = {}
    with jax.set_mesh(mesh), shd.activation_rules(pcfg, mesh):
        # ---- B: one layer group ----
        if kind == "train":
            fn = jax.grad(group_fwd, argnums=(0, 1) if shared_sds is None else (0, 1, 2))
            in_sh = (h_spec, g_specs, shared_spec)
            args = (h_sds, g_shapes, shared_sds)
            if shared_sds is None:
                in_sh, args = in_sh[:2] + (None,), args[:2] + (None,)
            comp = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
            # remat executes an extra forward per layer during backward: the
            # layer term is grad-program + one forward (matches production).
            comp_f = jax.jit(group_fwd_raw, in_shardings=in_sh,
                             out_shardings=h_spec).lower(*args).compile()
            parts["layer_fwd"] = _program_costs(comp_f)
        else:
            # serve: forward with cache (decode) or without (prefill)
            if kind == "decode":
                cache_sds = jax.eval_shape(
                    lambda: tr.init_cache(cfg, shape.global_batch, shape.seq_len)
                )
                cache_sh = cache_shardings(cfg, pcfg, mesh, shape.global_batch, shape.seq_len)
                gcache_sds = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                    cache_sds["groups"],
                )
                gcache_sh = jax.tree.map(
                    lambda ns: NamedSharding(mesh, P(*tuple(ns.spec)[1:])),
                    cache_sh["groups"],
                    is_leaf=lambda x: isinstance(x, NamedSharding),
                )

                def g_dec(h, gp, shared, gc):
                    out, nc, _ = tr._apply_group(
                        h, gp, tr_cfg, shared, cache=gc,
                        pos_offset=jnp.asarray(shape.seq_len - 1, jnp.int32),
                        window=cfg.sliding_window, unroll=True,
                    )
                    return out, nc

                comp = jax.jit(
                    g_dec, in_shardings=(h_spec, g_specs, shared_spec, gcache_sh),
                    out_shardings=(h_spec, gcache_sh), donate_argnums=(3,),
                ).lower(h_sds, g_shapes, shared_sds, gcache_sds).compile()
            else:
                def g_pre(h, gp, shared):
                    out, _, _ = tr._apply_group(
                        h, gp, tr_cfg, shared, cache=None, pos_offset=0,
                        window=cfg.sliding_window, unroll=True,
                    )
                    return out

                comp = jax.jit(
                    g_pre, in_shardings=(h_spec, g_specs, shared_spec),
                    out_shardings=h_spec,
                ).lower(h_sds, g_shapes, shared_sds).compile()
        parts["layer"] = _program_costs(comp)

        # ---- A: one-group end-to-end; C: optimizer ----
        one_cfg = dataclasses.replace(cfg, num_layers=len(cfg.block_pattern))
        if kind == "train":
            one_pcfg = dataclasses.replace(pcfg, microbatches=1)
            st_sh = state_shardings(one_cfg, one_pcfg, mesh)
            shapes1, _ = _axes_trees(one_cfg)
            opt = make_optimizer(one_pcfg)
            opt_sds = jax.eval_shape(opt.init, shapes1)
            from repro.training.loop import TrainState
            state_sds = TrainState(jax.ShapeDtypeStruct((), jnp.int32), shapes1, opt_sds)
            micro_shape = dataclasses.replace(shape, global_batch=shape.global_batch // micro)
            b_sds, b_sh = _batch_specs(one_cfg, micro_shape, mesh, one_pcfg)
            step = make_train_step(one_cfg, one_pcfg, constant(1e-4), unroll=True)
            compA = jax.jit(step, in_shardings=(st_sh, b_sh),
                            out_shardings=(st_sh, None),
                            donate_argnums=(0,)).lower(state_sds, b_sds).compile()
            parts["one_group_step"] = _program_costs(compA)

            def opt_only(g, s, p):
                return opt.update(g, s, p, jnp.zeros((), jnp.int32), 1e-4)

            comp = jax.jit(opt_only,
                           in_shardings=(st_sh.params, st_sh.opt, st_sh.params),
                           out_shardings=(st_sh.params, st_sh.opt, None),
                           donate_argnums=(0, 1, 2)).lower(
                shapes1, opt_sds, shapes1).compile()
            parts["opt_one_group"] = _program_costs(comp)

            # full-model optimizer (the real C term)
            st_sh_full = state_shardings(cfg, pcfg, mesh)
            optF = make_optimizer(pcfg)
            opt_sds_full = jax.eval_shape(optF.init, full_shapes)

            def opt_full(g, s, p):
                return optF.update(g, s, p, jnp.zeros((), jnp.int32), 1e-4)

            comp = jax.jit(opt_full,
                           in_shardings=(st_sh_full.params, st_sh_full.opt, st_sh_full.params),
                           out_shardings=(st_sh_full.params, st_sh_full.opt, None),
                           donate_argnums=(0, 1, 2)).lower(
                full_shapes, opt_sds_full, full_shapes).compile()
            parts["opt_full"] = _program_costs(comp)
        else:
            p_shapes1, p_axes1 = _axes_trees(one_cfg)
            rules = shd.make_rules(pcfg)
            p_sh1 = shd.param_shardings(p_axes1, p_shapes1, rules, mesh)
            B = shape.global_batch
            cache_sds1 = jax.eval_shape(lambda: tr.init_cache(one_cfg, B, shape.seq_len))
            cache_sh1 = cache_shardings(one_cfg, pcfg, mesh, B, shape.seq_len)
            if kind == "prefill":
                b_sds, b_sh = _batch_specs(one_cfg, shape, mesh, pcfg)
                fn1 = make_prefill(one_cfg)
                compA = jax.jit(fn1, in_shardings=(p_sh1, b_sh, cache_sh1),
                                out_shardings=(None, cache_sh1),
                                donate_argnums=(2,)).lower(
                    p_shapes1, b_sds, cache_sds1).compile()
            else:
                from repro.models.frontends import needs_embeds
                if needs_embeds(one_cfg):
                    tok_sds = jax.ShapeDtypeStruct((B, cfg.d_model), tr.model_dtype(cfg))
                else:
                    tok_sds = jax.ShapeDtypeStruct((B,), jnp.int32)
                fn1 = make_decode_step(one_cfg)
                compA = jax.jit(fn1, in_shardings=(p_sh1, None, cache_sh1, None),
                                out_shardings=(None, cache_sh1),
                                donate_argnums=(2,)).lower(
                    p_shapes1, tok_sds, cache_sds1,
                    jax.ShapeDtypeStruct((), jnp.int32)).compile()
            parts["one_group_step"] = _program_costs(compA)

    # ---- compose ----
    A = parts["one_group_step"]
    if kind == "train":
        layer = tuple(g + f for g, f in zip(parts["layer"], parts["layer_fwd"]))
        C1 = parts["opt_one_group"]
        CF = parts["opt_full"]
        stem = tuple(max(a - b - c, 0.0) for a, b, c in zip(A, layer, C1))
        total = tuple(
            micro * (s + G * l) + cf
            for s, l, cf in zip(stem, layer, CF)
        )
    else:
        layer = parts["layer"]
        stem = tuple(max(a - b, 0.0) for a, b in zip(A, layer))
        total = tuple(s + G * l for s, l in zip(stem, layer))
    # remainder layers (zamba2) approximated by the group average
    n_rem = len(cfg.remainder_pattern)
    if n_rem:
        per_layer = tuple(l / len(cfg.block_pattern) for l in layer)
        scale = micro if kind == "train" else 1
        total = tuple(t + scale * n_rem * p for t, p in zip(total, per_layer))

    flops, bytes_, coll = total
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": kind, "micro": micro, "groups": G,
        "causal_skip": causal_skip,
        "flops": flops, "bytes": bytes_, "coll_bytes": coll,
        "parts": {k: dict(zip(("flops", "bytes", "coll"), v)) for k, v in parts.items()},
        **roofline.roofline_terms(flops, bytes_, coll),
    }
