"""Execute stage: run a :class:`CompressionPlan` with cross-tensor pooling.

The legacy walk compressed one tensor at a time, so the batched Ising
backend (``ising.solve_many``) only ever saw one tensor's tiles per call.
``execute_plan`` instead pools tiles from *every* planned tensor by
(tile_n, tile_d, K, method) and runs each pool as ONE
``compress_tile_batch`` call — one vmapped greedy/alternating
decomposition, and for BBO one ``run_bbo_many`` whose per-iteration
``solve_many`` batch is the whole pool (the ≥64-problem regime where the
Pallas backend wins, BENCH_ising.json).  The pooled tile axis can
optionally be sharded over a mesh, which is how "shard the problem axis of
``solve_many``" lands: GSPMD partitions every per-tile op (and the solver
chain axis) across devices.

Reproducibility contract: per-tile PRNG keys are derived exactly as the
legacy per-tensor walk derived them (fold_in(key, leaf_index) per tensor,
fold_in per group slice, split over tiles), so greedy/alternating pooled
output is bit-identical to per-tensor ``compress_matrix`` with the same
seed.  BBO pools share one lock-step run per pool, so its results are
deterministic per (plan, seed) but not equal to the per-tensor walk —
see docs/compression_api.md.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compression.artifact import CompressionArtifact, MANIFEST_FORMAT
from repro.compression.plan import CompressionPlan, TensorPlan, tree_paths
from repro.core import decomposition as dec
from repro.core import features as feat
from repro.core import quantized
from repro.core.compress import (
    compress_tile_batch, quantize_tile_batch, tile_matrix,
)

__all__ = [
    "execute_plan",
    "surrogate_tile_bytes",
    "auto_pool_chunk",
    "decompose_tile_bytes",
    "auto_decompose_chunk",
    "tile_residuals",
    "POOL_BUDGET_ENV",
]

# Budget for one pooled BBO solve's surrogate state.  The default is NOT
# host RAM: the lock-step solve touches every tile's (p, p) Gram stack
# each iteration, and past ~last-level-cache size the per-tile cost
# climbs (measured on the bench pool: 8 chunks of 64 tiles beat one
# 512-tile batch ~21s vs ~26s despite 8x the compiles).  64 MiB keeps a
# chunk's surrogate state cache-adjacent on CPU compression hosts; raise
# via the env var on hosts where wider batches amortise better.
POOL_BUDGET_ENV = "REPRO_POOL_BUDGET_BYTES"
_DEFAULT_POOL_BUDGET = 64 << 20
_MIN_BBO_CHUNK = 64      # stay in the >=64-problem regime the batched
                         # Ising backends want (BENCH_ising.json)
_MAX_POOL_CHUNK = 4096   # legacy hard bound
# Budget for one greedy/alternating/int8 chunk's device working set: a
# sixteenth of a 16 GB chip, beside the model it compresses.
_DECOMPOSE_BUDGET = 1 << 30


def surrogate_tile_bytes(tile_n: int, K: int, bbo_iters: int) -> int:
    """Per-tile BBO surrogate footprint in bytes — the memory model behind
    ``max_pool_tiles="auto"``.  One tile optimises n = tile_n*K spins with
    p = 1 + n + n(n-1)/2 quadratic features; the lock-step state carries the
    (p, p) Gram matrix, the (p, p) posterior square root and the
    matrix-vector temporaries (~3 p^2 floats) and the acquired dataset
    ((init_points + iters) x (n + 2) floats, init_points = n per
    core/compress.py).  The formula predates the square root, when its
    3 p^2 covered the Gram matrix and its Cholesky temporaries; it is kept
    unchanged on purpose, so the chunking stays as it was."""
    n = tile_n * K
    p = feat.num_features(n)
    max_points = n + max(bbo_iters, 1)
    return 4 * (3 * p * p + 4 * p) + 4 * max_points * (n + 2)


def _even_chunk(total_tiles: int, cap: int) -> int:
    """``total_tiles`` when it fits ``cap``, else an even split into
    ceil(total / cap) chunks, so at most two distinct chunk shapes
    compile."""
    if total_tiles <= cap:
        return total_tiles
    n_chunks = -(-total_tiles // cap)
    return -(-total_tiles // n_chunks)


def auto_pool_chunk(
    total_tiles: int,
    tile_n: int,
    K: int,
    bbo_iters: int,
    budget_bytes: int | None = None,
) -> int:
    """Solver chunk for one BBO pool: as many tiles per lock-step batch as
    the surrogate budget allows (bigger batches amortise compiles and keep
    the batched Ising solve wide), split evenly when the pool exceeds it so
    at most two distinct chunk shapes compile."""
    if budget_bytes is None:
        budget_bytes = int(
            os.environ.get(POOL_BUDGET_ENV, _DEFAULT_POOL_BUDGET)
        )
    per_tile = surrogate_tile_bytes(tile_n, K, bbo_iters)
    cap = max(_MIN_BBO_CHUNK, min(_MAX_POOL_CHUNK, budget_bytes // per_tile))
    return _even_chunk(total_tiles, cap)


def decompose_tile_bytes(tile_n: int, tile_d: int) -> int:
    """Per-tile working set of a greedy/alternating/int8 pool chunk, as
    four f32 tiles — the memory model behind those pools'
    ``max_pool_tiles="auto"`` chunks.  (XLA's temporaries for an
    alternating chunk at 32x128, compiled for a TPU v5e, come to ~2.1 f32
    tiles per tile; the bf16 input, outputs and headroom make up the rest.)"""
    return 4 * 4 * tile_n * tile_d


def auto_decompose_chunk(
    total_tiles: int,
    tile_n: int,
    tile_d: int,
    budget_bytes: int = _DECOMPOSE_BUDGET,
) -> int:
    """Chunk for one greedy/alternating/int8 pool.  These pools run no
    Ising solve, so the only bound is device memory: a whole-model pool
    (granite-moe-1b-a400m at tile 32x128: ~313k tiles, 5 GB of f32 tiles
    before temporaries) would not fit a 16 GB chip in one batch."""
    per_tile = decompose_tile_bytes(tile_n, tile_d)
    return _even_chunk(total_tiles, max(1, budget_bytes // per_tile))


@jax.jit
def tile_residuals(tiles, M, C):
    """Per-tile ``||W_t - M_t C_t||_F`` in f32 over a (T, tn, td) stack.

    This is THE residual metric shared by execute (which records it per
    tile in the manifest as ``tile_resid``) and the delta-recompression
    drift measurement (:mod:`repro.compression.delta`): both reconstruct
    from the *stored* (dtype-cast) ``C``, so a delta run on an unchanged
    checkpoint measures a drift ratio of exactly 1.0."""
    V = jnp.einsum(
        "tnk,tkd->tnd", M.astype(jnp.float32), C.astype(jnp.float32)
    )
    d = tiles.astype(jnp.float32) - V
    return jnp.sqrt(jnp.sum(d * d, axis=(1, 2)))


def _validate(plan: CompressionPlan, leaves: dict) -> None:
    for t in plan.tensors:
        if t.path not in leaves:
            raise ValueError(f"plan tensor {t.path!r} not found in values tree")
        leaf = leaves[t.path]
        if tuple(leaf.shape) != t.shape:
            raise ValueError(
                f"plan/values shape mismatch at {t.path!r}: "
                f"planned {t.shape}, got {tuple(leaf.shape)}"
            )


def _tensor_keys(key, t: TensorPlan):
    """Per-tile keys for one tensor, exactly as the legacy walk drew them.
    Stacked weights (3D layer stacks, 4D MoE expert stacks) fold the
    flattened group-slice index — for 3D this is the legacy per-slice
    derivation bit-for-bit; 4D extends it over the (layer, expert) raster."""
    k = jax.random.fold_in(key, t.leaf_index)
    tiles_per_slice = t.num_tiles // t.groups
    if len(t.shape) > 2:
        slice_keys = [jax.random.fold_in(k, g) for g in range(t.groups)]
    else:
        slice_keys = [k]
    return jnp.concatenate(
        [jax.random.split(sk, tiles_per_slice) for sk in slice_keys]
    )


def _tensor_tiles(leaf, t: TensorPlan):
    """(num_tiles, tn, td) stack across group slices (g-major, r/c-minor).
    Any number of leading stack dims collapses to the flat group axis."""
    if len(t.shape) > 2:
        flat = leaf.reshape(t.groups, t.d_in, t.d_out)
        stacks = [tile_matrix(flat[g], t.tile_n, t.tile_d) for g in range(t.groups)]
        return jnp.concatenate(stacks)
    return tile_matrix(leaf, t.tile_n, t.tile_d)


def _iter_chunks(members, leaves, key, chunk):
    """Assemble (tiles, keys) chunks of at most ``chunk`` tiles, walking the
    pool's tensors in order WITHOUT concatenating the whole pool first —
    at most one tensor's tile stack plus one chunk is in flight, which is
    what keeps ``max_pool_tiles`` an actual memory bound."""
    buf_t, buf_k, n = [], [], 0
    for t in members:
        tiles = _tensor_tiles(leaves[t.path], t)
        keys = _tensor_keys(key, t)
        pos = 0
        while pos < t.num_tiles:
            take = min(chunk - n, t.num_tiles - pos)
            buf_t.append(tiles[pos:pos + take])
            buf_k.append(keys[pos:pos + take])
            n += take
            pos += take
            if n == chunk:
                yield jnp.concatenate(buf_t), jnp.concatenate(buf_k)
                buf_t, buf_k, n = [], [], 0
    if n:
        yield jnp.concatenate(buf_t), jnp.concatenate(buf_k)


def _shard_pool(tiles, keys, mesh):
    """Shard the pooled tile axis over every mesh axis.  Returns
    (tiles, keys, sharded); when the chunk doesn't divide the device count
    it replicates (correctness first) and the caller warns — a silent
    no-op would masquerade as a sharded solve."""
    n_dev = math.prod(mesh.devices.shape)
    if n_dev <= 1 or tiles.shape[0] % n_dev:
        return tiles, keys, n_dev <= 1
    axes = tuple(mesh.axis_names)
    tiles = jax.device_put(tiles, NamedSharding(mesh, P(axes, None, None)))
    keys = jax.device_put(keys, NamedSharding(mesh, P(axes)))
    return tiles, keys, True


def _pack_tensor_int8(t: TensorPlan, q_seg, scale_seg):
    """Pooled rows for one tensor -> the int8-baseline {"q", "scale"} leaf
    (q (..., r, c, tn, td) int8, scale (..., r, c, 1, 1) f32)."""
    r, c = t.d_in // t.tile_n, t.d_out // t.tile_d
    lead = t.shape[:-2]
    q = q_seg.reshape(*lead, r, c, t.tile_n, t.tile_d)
    scale = scale_seg.reshape(*lead, r, c, 1, 1)
    return {"q": q, "scale": scale}


@jax.jit
def _int8_tile_residuals(tiles, q_seg, scale_seg):
    """Per-tile ``||W_t - scale_t q_t||_F`` — the int8 analogue of
    :func:`tile_residuals` against the stored representation."""
    V = q_seg.astype(jnp.float32) * scale_seg.astype(jnp.float32)
    d = tiles.astype(jnp.float32) - V
    return jnp.sqrt(jnp.sum(d * d, axis=(1, 2)))


def _pack_tensor(t: TensorPlan, M_seg, C_seg, dtype):
    """Pooled rows for one tensor -> the {"m_packed", "C"} leaf.  Leading
    stack dims are preserved (a 4D (L, E, d, f) expert stack packs to
    (L, E, r, c, tn, kb) so the layer-group scan slices it to the
    (E, r, c, tn, kb) grouped-kernel layout per layer)."""
    r, c = t.d_in // t.tile_n, t.d_out // t.tile_d
    lead = t.shape[:-2]
    packed = jax.vmap(dec.pack_bits)(M_seg)
    packed = packed.reshape(*lead, r, c, t.tile_n, -1)
    C_out = C_seg.reshape(*lead, r, c, t.K, t.tile_d).astype(dtype)
    return {"m_packed": packed, "C": C_out}


def _pack_leaf(t: TensorPlan, leaf, M_seg, C_seg, err_seg):
    """One planned tensor's pooled rows -> (compressed leaf, its bytes,
    mean rel_err, manifest entry).  The two host reads of the device
    (``rel_err`` and ``tile_resid``) wait for the tensor's pool."""
    err = float(jnp.mean(err_seg))
    # per-tile residual against the STORED representation (cast C /
    # int8 q·scale) — the baseline the delta drift metric compares
    # against
    if t.method == "int8":
        w = _pack_tensor_int8(t, M_seg, C_seg)
        nb = quantized.intquant_num_bytes(w)
        resid = _int8_tile_residuals(_tensor_tiles(leaf, t), M_seg, C_seg)
        leaf_spec = {
            "q": {
                "shape": list(w["q"].shape),
                "dtype": str(w["q"].dtype),
            },
            "scale": {
                "shape": list(w["scale"].shape),
                "dtype": str(w["scale"].dtype),
            },
        }
    else:
        w = _pack_tensor(t, M_seg, C_seg, leaf.dtype)
        nb = quantized.compressed_num_bytes(w)
        resid = tile_residuals(
            _tensor_tiles(leaf, t), M_seg,
            w["C"].reshape(-1, t.K, t.tile_d),
        )
        leaf_spec = {
            "m_packed": {
                "shape": list(w["m_packed"].shape),
                "dtype": str(w["m_packed"].dtype),
            },
            "C": {"shape": list(w["C"].shape), "dtype": str(w["C"].dtype)},
        }
    entry = {
        "shape": list(t.shape),
        "dtype": t.dtype,
        "groups": t.groups,
        "group_dims": list(t.shape[:-2]),
        "tile_n": t.tile_n,
        "tile_d": t.tile_d,
        "K": t.K,
        "method": t.method,
        "rule": t.rule,
        "leaf_index": t.leaf_index,
        "bbo_iters": t.bbo_iters,
        "num_tiles": t.num_tiles,
        "orig_bytes": t.orig_bytes,
        "new_bytes": int(nb),
        "rel_err": err,
        "tile_resid": [float(f"{v:.8g}") for v in np.asarray(resid)],
        **leaf_spec,
    }
    return w, nb, err, entry


# Host spans (``jax.profiler.TraceAnnotation``, ``repro.execute*``) mark
# each host stage of a job in a profiler trace, beside the device scopes of
# ``compress_tile_batch`` (docs/compression_api.md, "Tracing a job").  With
# no profiler running a span costs about a microsecond.
@functools.partial(jax.profiler.annotate_function, name="repro.execute")
def execute_plan(
    plan: CompressionPlan,
    values,
    *,
    key=None,
    mesh=None,
    backend: str | None = None,
    max_pool_tiles: int | str | None = "auto",
    verbose: bool = False,
):
    """Execute ``plan`` over ``values``; returns (new_values, artifact).

    ``backend`` overrides the policy's Ising solver backend
    ("auto" | "pallas" | "jnp"); ``mesh`` shards the pooled tile axis.
    ``max_pool_tiles`` bounds the tiles per batched solve: the legacy walk
    never held more than one tensor's tiles, but a pool concentrates the
    whole model, whose BBO surrogate state scales as
    O(tiles * num_features^2) — chunking keeps memory bounded while every
    chunk is still a large batch.  The default "auto" derives each BBO
    pool's chunk from the surrogate-memory model (:func:`auto_pool_chunk`,
    budget via ``REPRO_POOL_BUDGET_BYTES``) and every other pool's from its
    decomposition working set (:func:`auto_decompose_chunk`, a device-memory
    bound); an int pins the bound for every pool; None disables chunking.  Chunking never changes
    greedy/alternating results (per-tile keys); BBO results depend on the
    chunk boundaries (each chunk is its own lock-step run).
    The artifact's manifest records per-tensor geometry/bytes/errors and
    per-pool solver batch sizes, and is the serving-consumable description
    of the compressed checkpoint (:mod:`repro.compression.artifact`).
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    backend = backend or plan.policy.solver_backend

    leaves = dict(tree_paths(values))
    _validate(plan, leaves)

    # -- pool tiles across tensors -----------------------------------------
    pools = plan.pools()
    results = {}       # path -> (M_seg, C_seg, err_seg)
    pool_stats = []
    for pidx, (pool_key, members) in enumerate(pools.items()):
        tn, td, K, method, bbo_iters = pool_key
        total = sum(t.num_tiles for t in members)
        if max_pool_tiles == "auto":
            chunk = (
                auto_pool_chunk(total, tn, K, bbo_iters)
                if method == "bbo" else auto_decompose_chunk(total, tn, td)
            )
        else:
            chunk = total if not max_pool_tiles else min(total, max_pool_tiles)
        n_chunks = -(-total // chunk)
        bbo_key = jax.random.fold_in(jax.random.fold_in(key, 0x706F6F6C), pidx)
        parts, chunk_sizes = [], []
        chunks = _iter_chunks(members, leaves, key, chunk)
        for ci in range(n_chunks):
            with jax.profiler.TraceAnnotation(
                "repro.execute.assemble", pool=pidx, chunk=ci
            ):
                ct, ck = next(chunks)
            if mesh is not None:
                ct, ck, sharded = _shard_pool(ct, ck, mesh)
                if not sharded:
                    print(
                        f"[compress] pool {method} {tn}x{td} K={K} chunk "
                        f"{ci}: {ct.shape[0]} tiles do not divide the "
                        f"{math.prod(mesh.devices.shape)}-device mesh; "
                        "running replicated"
                    )
            chunk_sizes.append(int(ct.shape[0]))
            with jax.profiler.TraceAnnotation(
                "repro.execute.dispatch", pool=pidx, chunk=ci,
                tiles=chunk_sizes[-1],
            ):
                if method == "int8":
                    # closed-form baseline: no solver, keys unused (the
                    # rounding is deterministic regardless of chunking)
                    parts.append(quantize_tile_batch(ct))
                else:
                    parts.append(compress_tile_batch(
                        ct, ck, jax.random.fold_in(bbo_key, ci), K, method,
                        bbo_iters=max(bbo_iters, 1), backend=backend,
                    ))
        if len(parts) == 1:
            M, C, errs = parts[0]
        else:
            M, C, errs = (jnp.concatenate(xs) for xs in zip(*parts))
        start = 0
        for t in members:
            stop = start + t.num_tiles
            results[t.path] = (M[start:stop], C[start:stop], errs[start:stop])
            start = stop
        pool_stats.append({
            "tile_n": tn, "tile_d": td, "K": K, "method": method,
            "num_tiles": total,
            "num_tensors": len(members),
            # group slices feeding the pool: the E axis of MoE stacks
            # multiplies the batched solve, it never fragments it
            "group_slices": sum(t.groups for t in members),
            "chunks": n_chunks,
            # For BBO every lock-step iteration issues ONE solve_many over a
            # whole chunk: the actual per-call batch sizes (the final chunk
            # may be smaller than the bound).
            "chunk_sizes": chunk_sizes,
            "solver_batch": max(chunk_sizes) if method == "bbo" else None,
            "bbo_iters": bbo_iters,
            "solver_calls": bbo_iters * n_chunks if method == "bbo" else 0,
            # chunk provenance: "auto" rows also record the memory model
            # input so a bench row is self-describing
            "chunk_policy": "auto" if max_pool_tiles == "auto" else "fixed",
            **(
                {"surrogate_tile_bytes": surrogate_tile_bytes(tn, K, bbo_iters)}
                if method == "bbo" else {}
            ),
        })
        if verbose:
            print(
                f"  pool {method} {tn}x{td} K={K}: {total} tiles "
                f"from {len(members)} tensors ({n_chunks} chunk(s))"
            )

    # -- scatter back into the tree ----------------------------------------
    flat, treedef = jax.tree_util.tree_flatten_with_path(values)
    planned = {t.path: t for t in plan.tensors}
    paths = [p for p, _ in tree_paths(values)]
    out, manifest_tensors = [], {}
    compressed, report_skipped = [], list(plan.skipped)
    for path, (_, leaf) in zip(paths, flat):
        t = planned.get(path)
        if t is None:
            out.append(leaf)
            continue
        with jax.profiler.TraceAnnotation(
            "repro.execute.pack", tensor=path, tiles=t.num_tiles
        ):
            w, nb, err, manifest_tensors[path] = _pack_leaf(
                t, leaf, *results[path]
            )
        compressed.append((path, t.orig_bytes, nb, err))
        out.append(w)
        if verbose:
            print(
                f"  compressed {path}: x{t.orig_bytes / max(nb, 1):.1f}, "
                f"rel_err {err:.3f}"
            )

    with jax.profiler.TraceAnnotation("repro.execute.manifest"):
        ob = sum(c[1] for c in compressed)
        nb_total = sum(c[2] for c in compressed)
        manifest = {
            "format": MANIFEST_FORMAT,
            "policy": plan.policy.to_dict(),
            "solver_backend": backend,
            "tensors": manifest_tensors,
            "skipped": {p: r for p, r in report_skipped},
            "pools": pool_stats,
            "totals": {
                "orig_bytes": int(ob),
                "new_bytes": int(nb_total),
                "ratio": ob / max(nb_total, 1),
            },
        }
        if plan.autotune is not None:
            manifest["autotune"] = plan.autotune
        artifact = CompressionArtifact(manifest)
    return jax.tree_util.tree_unflatten(treedef, out), artifact
