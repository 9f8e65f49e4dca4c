"""Delta recompression: warm-started re-solve of drifted tiles.

Production weights drift — fine-tune steps, RLHF, LoRA merges — and a full
cold recompression re-solves every tile of every tensor from scratch.  The
Ising-machine literature on *dynamically changing* problems (PAPERS.md:
2503.23966) shows warm-starting solvers from the previous solution recovers
near-optimal results at a fraction of cold-start cost.  This module maps
that to the tiled integer decomposition (docs/delta.md):

  1. **drift** — per tile, measure ``||W_new_t - M_prev_t C_prev_t||_F``
     (the previous factorisation applied to the new weights) against the
     tile's *recorded* residual ``manifest["tensors"][p]["tile_resid"]``.
     An unchanged tile has ratio exactly 1.0: both sides are computed by
     the same :func:`repro.compression.execute.tile_residuals` against the
     stored (dtype-cast) ``C``.
  2. **plan** — re-solve only tiles whose ratio exceeds ``threshold``
     (default 1.25: "the old solution is at least 25% worse on the new
     weights than it was at compression time"); every other tile reuses the
     parent's packed bytes verbatim.
  3. **solve** — re-solved tiles pool by ``(tile_n, tile_d, K, method,
     bbo_iters)`` exactly like :func:`execute_plan` and run through
     ``compress_tile_batch(M0=M_prev)``: the cold init still runs with the
     tile's own PRNG key (so a re-solved tile can never end worse than a
     cold recompression of it — greedy/alternating cold solves are
     per-tile-key deterministic) and a second candidate descends from the
     previous solution; BBO additionally seeds its surrogate dataset and
     per-iteration Ising solves from the warm point
     (``run_bbo_many(warm_x=...)`` -> ``solve_many(init_state=...)``).

The returned artifact's manifest is the parent manifest with a ``delta``
lineage block (``parent_fingerprint``, generation, tiles reused vs
re-solved), this run's pool stats, and updated entries *only* for tensors
that had tiles re-solved — on an unchanged checkpoint every stored byte
and every tensor entry reproduces the parent (tests/test_delta.py).

Cold start is **forced** (``ColdStartRequired``) when the parent artifact
cannot anchor a delta: a predicted-only manifest, a ``prev_params`` tree
that fails ``validate_params``, or new weights whose shape/dtype no longer
match the manifest geometry.  Callers (``launch/compress.py --delta-from``,
``optim.grad_compress.CompressionCycle``) catch it and fall back to a full
``plan_compression`` + ``execute_plan``.
"""

from __future__ import annotations

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression.artifact import CompressionArtifact
from repro.compression.execute import (
    _tensor_keys,
    _tensor_tiles,
    auto_decompose_chunk,
    auto_pool_chunk,
    tile_residuals,
)
from repro.compression.plan import TensorPlan, tree_paths
from repro.core import decomposition as dec
from repro.core.compress import compress_tile_batch

__all__ = [
    "DEFAULT_DRIFT_THRESHOLD",
    "ColdStartRequired",
    "TensorDrift",
    "DeltaPlan",
    "compute_drift",
    "plan_delta",
    "delta_recompress",
]

# "re-solve once the old solution is >= 25% worse on the new weights than
# it was at compression time" — an unchanged tile sits at ratio 1.0 exactly
DEFAULT_DRIFT_THRESHOLD = 1.25


class ColdStartRequired(ValueError):
    """The parent artifact cannot anchor a delta; run a full cold
    compression (``plan_compression`` + ``execute_plan``) instead."""


@dataclasses.dataclass(frozen=True)
class TensorDrift:
    """Per-tile drift of one manifested tensor against its parent solve."""

    path: str
    drift: np.ndarray         # (num_tiles,) ||W_new_t - M_prev_t C_prev_t||_F
    resid_prev: np.ndarray    # (num_tiles,) parent residual (see `recorded`)
    recorded: bool            # True: manifest tile_resid; False: estimated
                              # as rel_err * ||W_new_t|| (legacy/streaming
                              # manifests without per-tile residuals)

    @property
    def ratio(self) -> np.ndarray:
        return self.drift / np.maximum(self.resid_prev, 1e-30)


@dataclasses.dataclass(frozen=True)
class DeltaPlan:
    """Which tiles re-solve: the drift measurements plus boolean re-solve
    masks per tensor (True = drift ratio above threshold)."""

    drifts: tuple             # TensorDrift per manifested tensor
    masks: dict               # path -> np.ndarray bool (num_tiles,)
    threshold: float
    parent_fingerprint: str

    @property
    def tiles_total(self) -> int:
        return sum(d.drift.size for d in self.drifts)

    @property
    def tiles_resolved(self) -> int:
        return sum(int(m.sum()) for m in self.masks.values())

    @property
    def fraction_resolved(self) -> float:
        return self.tiles_resolved / max(self.tiles_total, 1)

    def summary(self) -> str:
        lines = [
            f"DeltaPlan: {self.tiles_resolved}/{self.tiles_total} tiles "
            f"re-solve ({self.fraction_resolved:.1%}) at threshold "
            f"{self.threshold} (parent {self.parent_fingerprint})"
        ]
        for d in self.drifts:
            m = self.masks[d.path]
            lines.append(
                f"  {d.path:48s} {int(m.sum()):5d}/{m.size:<5d} "
                f"max ratio {float(d.ratio.max()):.2f}"
                + ("" if d.recorded else "  (estimated baseline)")
            )
        return "\n".join(lines)


def _entry_plan(path: str, entry: dict, leaf_order: dict) -> TensorPlan:
    """Rebuild the :class:`TensorPlan` a manifest entry was executed from —
    geometry, pool key and (crucially) ``leaf_index``, which seeds the
    per-tile PRNG chain, so re-solved tiles draw the keys a cold
    ``execute_plan`` would hand the same tiles."""
    leaf_index = entry.get("leaf_index")
    if leaf_index is None:
        # pre-delta manifests: the leaf index is the tensor's position in
        # the flattened dense tree, recoverable from the new values
        leaf_index = leaf_order[path]
    return TensorPlan(
        path=path,
        leaf_index=int(leaf_index),
        shape=tuple(entry["shape"]),
        dtype=entry["dtype"],
        groups=int(entry["groups"]),
        tile_n=int(entry["tile_n"]),
        tile_d=int(entry["tile_d"]),
        K=int(entry["K"]),
        method=entry["method"],
        rule=entry.get("rule", ""),
        num_tiles=int(entry["num_tiles"]),
        orig_bytes=int(entry["orig_bytes"]),
        pred_bytes=int(entry["new_bytes"]),
        bbo_iters=int(entry.get("bbo_iters") or 0),
    )


def _prev_factors(leaves_prev: dict, t: TensorPlan):
    """Stored factors of one tensor as flat per-tile stacks
    (M (num_tiles, tn, K) in {-1,+1} f32, C (num_tiles, K, td))."""
    kb = (t.K + 7) // 8
    mp = jnp.reshape(leaves_prev[f"{t.path}/m_packed"],
                     (t.num_tiles, t.tile_n, kb))
    C = jnp.reshape(leaves_prev[f"{t.path}/C"],
                    (t.num_tiles, t.K, t.tile_d))
    M = jax.vmap(lambda p: dec.unpack_bits(p, t.K))(mp)
    return M, C


def _anchor(artifact: CompressionArtifact, prev_params, new_values):
    """Validate the (parent, prev, new) triple; returns (plans, leaves_prev,
    leaves_new) or raises :class:`ColdStartRequired`."""
    manifest = artifact.manifest
    if manifest.get("predicted_only"):
        raise ColdStartRequired(
            "parent manifest is predicted-only (no solver ran); "
            "cold compression required"
        )
    problems = artifact.validate_params(prev_params)
    if problems:
        raise ColdStartRequired(
            "prev_params does not match the parent manifest; cold "
            "compression required:\n  " + "\n  ".join(problems)
        )
    leaves_new = dict(tree_paths(new_values))
    leaf_order = {p: i for i, (p, _) in enumerate(tree_paths(new_values))}
    plans = []
    for path, entry in manifest["tensors"].items():
        if entry.get("method") == "int8":
            # the closed-form baseline has no warm-startable M/C factors
            # (re-quantising IS the cold solve) — keep delta semantics
            # uniform by forcing the cold path for the whole artifact
            raise ColdStartRequired(
                f"manifested tensor {path!r} uses the int8 baseline, which "
                "has no warm-startable factors; cold compression required"
            )
        leaf = leaves_new.get(path)
        if leaf is None:
            raise ColdStartRequired(
                f"manifested tensor {path!r} missing from the new values "
                "tree; cold compression required"
            )
        if tuple(leaf.shape) != tuple(entry["shape"]):
            raise ColdStartRequired(
                f"shape of {path!r} changed: manifest {tuple(entry['shape'])}"
                f" vs new {tuple(leaf.shape)}; cold compression required"
            )
        plans.append(_entry_plan(path, entry, leaf_order))
    return plans, dict(tree_paths(prev_params)), leaves_new


def compute_drift(
    artifact: CompressionArtifact, prev_params, new_values
) -> list:
    """Per-tile drift of every manifested tensor: the parent factorisation
    applied to the new weights, against the parent's recorded residual.
    Returns a list of :class:`TensorDrift` in manifest (= leaf) order."""
    plans, leaves_prev, leaves_new = _anchor(artifact, prev_params, new_values)
    out = []
    for t in plans:
        entry = artifact.manifest["tensors"][t.path]
        tiles = _tensor_tiles(leaves_new[t.path], t)
        Mp, Cp = _prev_factors(leaves_prev, t)
        drift = np.asarray(tile_residuals(tiles, Mp, Cp), dtype=np.float64)
        recorded = entry.get("tile_resid") is not None
        if recorded:
            resid_prev = np.asarray(entry["tile_resid"], dtype=np.float64)
        else:
            norms = np.asarray(
                jnp.sqrt(jnp.sum(tiles.astype(jnp.float32) ** 2, axis=(1, 2))),
                dtype=np.float64,
            )
            resid_prev = float(entry["rel_err"]) * norms
        out.append(TensorDrift(t.path, drift, resid_prev, recorded))
    return out


def plan_delta(
    artifact: CompressionArtifact,
    prev_params,
    new_values,
    threshold: float = DEFAULT_DRIFT_THRESHOLD,
) -> DeltaPlan:
    """Measure drift and decide which tiles re-solve."""
    drifts = compute_drift(artifact, prev_params, new_values)
    masks = {d.path: d.ratio > threshold for d in drifts}
    return DeltaPlan(
        drifts=tuple(drifts),
        masks=masks,
        threshold=float(threshold),
        parent_fingerprint=artifact.fingerprint(),
    )


def delta_recompress(
    artifact: CompressionArtifact,
    prev_params,
    new_values,
    *,
    key=None,
    threshold: float = DEFAULT_DRIFT_THRESHOLD,
    backend: str | None = None,
    verbose: bool = False,
):
    """Recompress ``new_values`` as a delta against a parent artifact.

    ``prev_params`` is the parent's *compressed* params tree (every
    manifested tensor as ``{"m_packed", "C"}``); ``new_values`` is the
    drifted dense tree.  Returns ``(new_compressed_values, artifact)`` like
    :func:`execute_plan`; the artifact carries the ``delta`` lineage block
    (see module docstring) and the reused tensors' leaves are the parent's
    arrays verbatim.  Raises :class:`ColdStartRequired` when the parent
    cannot anchor a delta.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    backend = backend or artifact.manifest.get("solver_backend", "auto")
    plans, leaves_prev, leaves_new = _anchor(artifact, prev_params, new_values)
    dplan = plan_delta(artifact, prev_params, new_values, threshold)
    if verbose:
        print(dplan.summary())

    # -- pool re-solved tiles across tensors (same pool key as execute) ----
    pools = {}
    for t in plans:
        idx = np.nonzero(dplan.masks[t.path])[0]
        if idx.size:
            pools.setdefault(t.pool_key, []).append((t, idx))

    results = {}       # path -> (idx, M_sel, C_sel)
    pool_stats = []
    for pidx, (pk, members) in enumerate(pools.items()):
        tn, td, K, method, bbo_iters = pk
        sel_t, sel_k, sel_m0 = [], [], []
        for t, idx in members:
            ji = jnp.asarray(idx)
            sel_t.append(_tensor_tiles(leaves_new[t.path], t)[ji])
            sel_k.append(_tensor_keys(key, t)[ji])
            Mp, _ = _prev_factors(leaves_prev, t)
            sel_m0.append(Mp[ji])
        tiles = jnp.concatenate(sel_t)
        keys = jnp.concatenate(sel_k)
        m0 = jnp.concatenate(sel_m0)
        total = int(tiles.shape[0])
        chunk = (
            auto_pool_chunk(total, tn, K, bbo_iters)
            if method == "bbo" else auto_decompose_chunk(total, tn, td)
        )
        # distinct fold ("delt") from execute's pool fold: a delta solve of
        # a bbo pool is a different lock-step run, not a replay
        bbo_key = jax.random.fold_in(jax.random.fold_in(key, 0x64656C74), pidx)
        parts, chunk_sizes = [], []
        for ci, start in enumerate(range(0, total, chunk)):
            sl = slice(start, min(start + chunk, total))
            chunk_sizes.append(sl.stop - sl.start)
            parts.append(compress_tile_batch(
                tiles[sl], keys[sl], jax.random.fold_in(bbo_key, ci),
                K, method, bbo_iters=max(bbo_iters, 1), backend=backend,
                M0=m0[sl],
            ))
        if len(parts) == 1:
            M, C, _ = parts[0]
        else:
            M, C, _ = (jnp.concatenate(xs) for xs in zip(*parts))
        start = 0
        for t, idx in members:
            stop = start + idx.size
            results[t.path] = (idx, M[start:stop], C[start:stop])
            start = stop
        pool_stats.append({
            "tile_n": tn, "tile_d": td, "K": K, "method": method,
            "num_tiles": total,
            "num_tensors": len(members),
            "chunks": len(chunk_sizes),
            "chunk_sizes": chunk_sizes,
            "solver_batch": max(chunk_sizes) if method == "bbo" else None,
            "bbo_iters": bbo_iters,
            "solver_calls": bbo_iters * len(chunk_sizes)
            if method == "bbo" else 0,
            "warm_started": True,
        })
        if verbose:
            print(
                f"  delta pool {method} {tn}x{td} K={K}: {total} tiles "
                f"re-solved from {len(members)} tensors "
                f"({len(chunk_sizes)} chunk(s))"
            )

    # -- splice re-solved tiles into the parent's stored factors -----------
    manifest = copy.deepcopy(artifact.manifest)
    new_leaves = {}
    for t in plans:
        mp_prev = leaves_prev[f"{t.path}/m_packed"]
        C_prev = leaves_prev[f"{t.path}/C"]
        if t.path not in results:
            # fully reused: the parent's arrays verbatim (byte-identical)
            new_leaves[t.path] = {"m_packed": mp_prev, "C": C_prev}
            continue
        idx, M_sel, C_sel = results[t.path]
        kb = (t.K + 7) // 8
        mp_flat = np.array(mp_prev).reshape(t.num_tiles, t.tile_n, kb)
        c_flat = np.array(C_prev).reshape(t.num_tiles, t.K, t.tile_d)
        mp_flat[idx] = np.asarray(jax.vmap(dec.pack_bits)(M_sel))
        c_flat[idx] = np.asarray(C_sel).astype(c_flat.dtype)
        w = {
            "m_packed": jnp.asarray(mp_flat).reshape(mp_prev.shape),
            "C": jnp.asarray(c_flat).reshape(C_prev.shape),
        }
        new_leaves[t.path] = w
        # refresh the entry's residuals against the new weights + spliced
        # factors (reused tensors keep their parent entries verbatim)
        tiles = _tensor_tiles(leaves_new[t.path], t)
        M_full = jax.vmap(lambda p: dec.unpack_bits(p, t.K))(
            jnp.asarray(mp_flat)
        )
        resid = tile_residuals(tiles, M_full, jnp.asarray(c_flat))
        norms = jnp.sqrt(jnp.sum(tiles.astype(jnp.float32) ** 2, axis=(1, 2)))
        entry = manifest["tensors"][t.path]
        entry["rel_err"] = float(jnp.mean(resid / jnp.maximum(norms, 1e-30)))
        entry["tile_resid"] = [float(f"{v:.8g}") for v in np.asarray(resid)]
        entry["leaf_index"] = t.leaf_index
        entry["bbo_iters"] = t.bbo_iters

    manifest["pools"] = pool_stats
    manifest["solver_backend"] = backend
    manifest["delta"] = {
        "parent_fingerprint": dplan.parent_fingerprint,
        "generation": int(
            artifact.manifest.get("delta", {}).get("generation", 0)
        ) + 1,
        "threshold": float(threshold),
        "tiles_total": dplan.tiles_total,
        "tiles_resolved": dplan.tiles_resolved,
        "tiles_reused": dplan.tiles_total - dplan.tiles_resolved,
        "fraction_resolved": dplan.fraction_resolved,
        "tensors_touched": len(results),
        "per_tensor": {
            d.path: {
                "num_tiles": int(d.drift.size),
                "resolved": int(dplan.masks[d.path].sum()),
                "max_ratio": float(d.ratio.max()),
            }
            for d in dplan.drifts
        },
    }

    # -- scatter into the new tree (dense leaves pass through) -------------
    flat, treedef = jax.tree_util.tree_flatten_with_path(new_values)
    paths = [p for p, _ in tree_paths(new_values)]
    out = [
        new_leaves.get(path, leaf) for path, (_, leaf) in zip(paths, flat)
    ]
    return jax.tree_util.tree_unflatten(treedef, out), CompressionArtifact(
        manifest
    )
