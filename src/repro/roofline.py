"""Roofline-term extraction from compiled XLA artifacts (no hardware).

Hardware model (TPU v5e, per chip):
    peak bf16 compute   197 TFLOP/s
    HBM bandwidth       819 GB/s
    ICI link bandwidth  ~50 GB/s per link

Terms per (arch x shape x mesh), all PER DEVICE (cost_analysis and
memory_analysis are post-SPMD per-device on this jax version — verified):

    compute_s    = HLO_FLOPs / peak_FLOPs
    memory_s     = HLO_bytes_accessed / HBM_bw
    collective_s = collective_bytes / ICI_bw

collective_bytes is parsed from the SPMD-partitioned HLO text: the sum of
operand bytes of all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute ops.  KNOWN LIMIT (and why benchmarks/roofline.py exists):
XLA's cost model counts while-loop (lax.scan) bodies ONCE — production
programs scan over layer groups and microbatches, so totals must be
reconstructed compositionally (per-layer costing twins x trip counts); the
raw numbers here are exact for scan-free programs (decode steps) and
lower bounds otherwise.
"""

from __future__ import annotations

import re

__all__ = [
    "PEAK_FLOPS",
    "HBM_BW",
    "ICI_BW",
    "memory_summary",
    "cost_summary",
    "collective_bytes",
    "roofline_terms",
]

PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link (one direction)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g. "bf16[16,1024,128]{2,1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_LINE_RE = re.compile(
    r"=\s*(?P<shapes>\(?[\w\[\],{}\s/*]+?\)?)\s*"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in (per-device) HLO.

    The op's *result* shape is a consistent per-device traffic proxy (for
    all-gather the gathered buffer, for reduce-scatter the scattered one).
    Async pairs are counted once (the -start; -done carries no new traffic).
    Returns totals by collective kind + counts.  NOTE: ops inside while
    bodies appear once — callers scale by trip counts (benchmarks/roofline).
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if m is None:
            continue
        kind = m.group("kind")
        b = sum(_shape_bytes(s) for s in _SHAPE_RE.finditer(m.group("shapes")))
        out[kind] += b
        counts[kind] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


def memory_summary(compiled) -> dict:
    ma = compiled.memory_analysis()
    arg = getattr(ma, "argument_size_in_bytes", 0)
    out = getattr(ma, "output_size_in_bytes", 0)
    tmp = getattr(ma, "temp_size_in_bytes", 0)
    alias = getattr(ma, "alias_size_in_bytes", 0)
    return {
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "alias_bytes": alias,
        # donated (aliased) buffers are counted once
        "per_device_total": arg + out + tmp - alias,
    }


def cost_summary(compiled) -> dict:
    ca = compiled.cost_analysis()
    # older jax returns a per-partition list of dicts (also seen when the
    # program embeds interpret-mode Pallas calls); sum across entries
    if isinstance(ca, (list, tuple)):
        merged: dict = {}
        for entry in ca:
            for k, v in (entry or {}).items():
                try:
                    merged[k] = merged.get(k, 0.0) + float(v)
                except (TypeError, ValueError):
                    continue
        ca = merged
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def analytic_memory_bytes(cfg, shape, pcfg, chips: int = 256) -> float:
    """First-principles per-device HBM traffic per step (napkin model,
    DESIGN.md methodology) assuming VMEM-resident attention/SSD inner tiles
    (i.e. the Pallas kernels) — the counterpart to the HLO-parsed bytes,
    which on the CPU backend include score-matrix traffic that never reaches
    HBM on TPU.

    train:  micro * (3 x gathered-weights + activation stream) + optimiser
    serve:  local weight shards + KV/SSM cache traffic + activations
    """
    p_bytes = cfg.param_count() * 2  # bf16
    mesh_model = 1
    for ax, dim in zip(pcfg.mesh_axes, pcfg.mesh_shape):
        if ax == "model":
            mesh_model = dim
    dp = chips // mesh_model

    d = cfg.d_model
    micro = max(pcfg.microbatches, 1)
    B_loc = max(shape.global_batch // (dp * micro), 1) if shape.kind == "train" \
        else max(shape.global_batch // dp, 1)
    S = 1 if shape.kind == "decode" else shape.seq_len
    # activation stream: ~8 residual-width tensors per layer, fwd(+remat+bwd)
    act_layer = B_loc * S * d * 2 / (mesh_model if not pcfg.dp_includes_model else 1)
    passes = 3 if shape.kind == "train" else 1
    act = 8 * act_layer * cfg.num_layers * passes

    if shape.kind == "train":
        # FSDP gather: each device streams the model-shard of every param
        # 3x per microbatch (fwd, remat re-fwd, bwd)
        w_gathered = p_bytes / (mesh_model if not pcfg.dp_includes_model else 1)
        opt = (2 + 2 + 4 + 4 + 4) * cfg.param_count() / chips  # p,g,m,v r/w
        return micro * (3.0 * w_gathered + act) + opt

    w_local = p_bytes / chips
    cache = 0.0
    hd, KV = cfg.resolved_head_dim, cfg.num_kv_heads
    n_attn = sum(
        1 for k in cfg.block_pattern * cfg.num_groups + cfg.remainder_pattern
        if k in ("attn", "attn_moe")
    ) + (cfg.num_groups if cfg.shared_attn else 0)
    if n_attn:
        seq_span = min(shape.seq_len, cfg.sliding_window or shape.seq_len)
        per_seq = seq_span * KV * hd * 2 * 2  # k+v bf16
        cache = n_attn * per_seq * max(shape.global_batch // chips, B_loc / mesh_model)
    n_ssm = sum(
        1 for k in cfg.block_pattern * cfg.num_groups + cfg.remainder_pattern
        if k in ("ssm", "ssm_attn", "ssm_moe")
    )
    if n_ssm:
        state = cfg.ssm_nheads * cfg.ssm_headdim * cfg.ssm_state * 4
        cache += n_ssm * state * 2 * max(shape.global_batch // chips, 1)
    return w_local + cache + act


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   ici_links: int = 4) -> dict:
    """Seconds per step by each roofline ceiling, per device."""
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    collective_s = coll_bytes / (ICI_BW * ici_links)
    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", collective_s),
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "bound_s": max(compute_s, memory_s, collective_s),
    }
