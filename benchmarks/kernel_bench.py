"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels run in interpret mode (not
representative of TPU), so wall-clock here measures (a) the jnp reference
paths — meaningful *relative* numbers — and (b) the model-level effect of
compression: bytes moved per matmul, the quantity the bitlinear kernel is
designed around (DESIGN.md §4).
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, emit
from repro.core import quantized
from repro.core.compress import compress_matrix
from repro.configs.base import CompressionConfig
from repro.kernels import ref


def _time(fn, *args, iters=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_compressed_matmul() -> None:
    d_in, d_out, T = 2048, 2048, 256
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (d_in, d_out)) / np.sqrt(d_in)
    x = jax.random.normal(jax.random.fold_in(key, 1), (T, d_in))
    ccfg = CompressionConfig(tile_n=32, tile_d=128, rank_ratio=0.125, min_size=1)
    w, err = compress_matrix(W, ccfg, method="greedy")

    dense = jax.jit(lambda x: x @ W)
    comp = jax.jit(lambda x: quantized.apply_compressed(x, w))
    us_dense = _time(dense, x)
    us_comp = _time(comp, x)

    dense_bytes = W.size * 2                       # bf16 weight read
    comp_bytes = quantized.compressed_num_bytes(w)
    emit("kernel_dense_matmul_2048", us_dense, f"weight_bytes={dense_bytes}")
    emit(
        "kernel_compressed_matmul_2048", us_comp,
        f"weight_bytes={comp_bytes};bytes_ratio=x{dense_bytes/comp_bytes:.1f};rel_err={err:.3f}",
    )


def bench_flash_ref() -> None:
    B, H, KV, S, hd = 1, 8, 2, 2048, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32)
    f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, 0))
    emit("kernel_attention_ref_2k", _time(f, q, k, v, iters=5),
         f"flops={4*B*H*S*S*hd:.2e}")


def bench_sa_throughput() -> None:
    """Ising solves/second in the batched pure-JAX SA (the BBO inner loop)."""
    from repro.core import ising

    n, reads, sweeps = 24, 10, 64
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (n,))
    Bm = jax.random.normal(jax.random.fold_in(key, 1), (n, n)) * 0.1
    Bm = (Bm + Bm.T) / 2
    Bm = Bm - jnp.diag(jnp.diag(Bm))
    f = jax.jit(lambda k: ising.solve_sa(k, h, Bm, num_sweeps=sweeps, num_reads=reads))
    us = _time(f, key, iters=10)
    emit("kernel_sa_solve_n24", us,
         f"reads={reads};sweeps={sweeps};spin_updates_per_s={reads*sweeps*n/(us*1e-6):.2e}")


def _best_of(fn, *args, repeats=5, iters=3):
    """Min-of-``repeats`` mean over ``iters`` calls, in microseconds."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e6


def bench_ising_suite() -> list:
    """jnp vs Pallas backends of ``ising.solve_many`` across (n, problems,
    chains, sweeps) — the batched SA solve that dominates tile-scale
    compression.  Writes BENCH_ising.json at the repo root."""
    from repro.core import ising

    cases = [
        # (n, problems, reads, sweeps)
        (16, 64, 4, 24),
        (32, 64, 4, 24),
        (64, 64, 4, 24),
        (32, 128, 8, 32),
    ]
    interpret = jax.default_backend() != "tpu"
    results = []
    for n, P, reads, sweeps in cases:
        probs = ising.random_problems(jax.random.PRNGKey(n + P), P, n, scale=0.2)
        key = jax.random.PRNGKey(0)
        row = {"solver": "sa", "n": n, "problems": P, "reads": reads,
               "sweeps": sweeps}
        for backend in ("jnp", "pallas"):
            fn = lambda k, b=backend: ising.solve_many(
                "sa", k, probs, num_sweeps=sweeps, num_reads=reads, backend=b
            )
            us = _best_of(fn, key)
            row[f"{backend}_us"] = us
            chains = P * reads
            row[f"{backend}_spin_updates_per_s"] = chains * sweeps * n / (us * 1e-6)
            emit(f"ising_sa_{backend}_n{n}_p{P}", us,
                 f"reads={reads};sweeps={sweeps}")
        row["pallas_speedup"] = row["jnp_us"] / row["pallas_us"]
        results.append(row)

    out = {
        "suite": "ising",
        "device": jax.default_backend(),
        "pallas_mode": "interpret" if interpret else "compiled",
        "results": results,
    }
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_ising.json")
    with open(os.path.abspath(path), "w") as f:
        json.dump(out, f, indent=2)
    return results


def bench_compress_suite() -> dict:
    """Pooled ``execute_plan`` vs the legacy per-tensor walk on a reduced
    config — wall time end-to-end (compiles included: both pipelines are
    offline one-shots and compile count is exactly what pooling amortises)
    plus the pooled ``solve_many`` batch sizes.  Writes BENCH_compress.json."""
    import jax.random as jrandom

    from repro import compression as comp
    from repro.configs import get_config, reduced_for_smoke
    from repro.models import init_model
    from repro.models.params import split
    from repro.compression.plan import tree_paths

    cfg = reduced_for_smoke(get_config("qwen3-32b"))
    values, _ = split(init_model(jrandom.PRNGKey(0), cfg))
    key = jrandom.PRNGKey(1)
    results = []
    # BBO chunk bound: "auto" derives the solver chunk per pool from the
    # surrogate-memory model (execute.auto_pool_chunk — budget via
    # REPRO_POOL_BUDGET_BYTES), replacing the fixed 128 that regressed
    # pooled_speedup to 0.69x: big chunks amortise compiles and keep the
    # batched Ising solve deep in the >=64-problem regime on every backend.
    for method, bbo_iters in (("alternating", 0), ("bbo", 6)):
        policy = comp.CompressionPolicy(
            method=method, tile_n=16, tile_d=16, rank_ratio=0.375,
            min_size=4096, bbo_iters=max(bbo_iters, 1),
        )
        plan = comp.plan_compression(values, policy)
        leaves = dict(tree_paths(values))

        # legacy per-tensor walk: one compress_matrix call per tensor slice
        ccfg = CompressionConfig(
            tile_n=16, tile_d=16, rank_ratio=0.375, min_size=4096,
            optimizer=method, bbo_iters=max(bbo_iters, 1),
        )
        t0 = time.perf_counter()
        for t in plan.tensors:
            k = jrandom.fold_in(key, t.leaf_index)
            leaf = leaves[t.path]
            if len(t.shape) == 2:
                w, _ = compress_matrix(leaf, ccfg, k)
            else:
                w = [
                    compress_matrix(leaf[g], ccfg, jrandom.fold_in(k, g))[0]
                    for g in range(t.shape[0])
                ]
            jax.block_until_ready(w)
        per_tensor_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        cvals, artifact = comp.execute_plan(
            plan, values, key=key, max_pool_tiles="auto",
        )
        jax.block_until_ready(jax.tree.leaves(cvals))
        pooled_s = time.perf_counter() - t0

        row = {
            "method": method,
            "max_pool_tiles": "auto",
            # the chunk the memory model actually picked (None for the
            # unchunked non-BBO pools)
            "solver_chunk": next(
                (p["solver_batch"] for p in artifact.manifest["pools"]
                 if p["method"] == "bbo"), None,
            ),
            "tensors": len(plan.tensors),
            "total_tiles": sum(t.num_tiles for t in plan.tensors),
            "pools": [
                {k: p[k] for k in ("tile_n", "tile_d", "K", "method",
                                   "num_tiles", "num_tensors", "solver_batch")
                 if k in p}
                for p in artifact.manifest["pools"]
            ],
            "solver_batches": artifact.solver_batches(),
            "per_tensor_s": per_tensor_s,
            "pooled_s": pooled_s,
            "pooled_speedup": per_tensor_s / pooled_s,
        }
        results.append(row)
        emit(f"compress_{method}_per_tensor", per_tensor_s * 1e6,
             f"tensors={row['tensors']}")
        emit(f"compress_{method}_pooled", pooled_s * 1e6,
             f"pools={len(row['pools'])};solver_batches={row['solver_batches']}")

    results.append(_bench_streaming_row())
    results.append(_bench_probe_row(values, key))
    results.append(_bench_plan405b_row())

    out = {
        "suite": "compress",
        "device": jax.default_backend(),
        "config": "qwen3-32b/reduced",
        "results": results,
    }
    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_compress.json")
    with open(os.path.abspath(path), "w") as f:
        json.dump(out, f, indent=2)
    return out


def _cpu_child_env(repo: str) -> dict:
    """Environment for a ``launch.compress`` child of this (JAX-holding)
    process.  The child measures host RSS and a metadata-only plan, which
    need no chip, and a chip belongs to one process: this parent already
    holds it, so the child runs on the CPU."""
    return dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
                JAX_PLATFORMS="cpu")


def _bench_streaming_row() -> dict:
    """Streaming execute under a 64 MiB host budget, run as a fresh
    subprocess of the CLI: ru_maxrss is a process-lifetime high-water mark,
    so the in-process benches above would mask the streaming tier's real
    footprint.  Gated on peak host RSS (as headroom, higher is better) and
    stream throughput."""
    import re
    import subprocess
    import sys
    import tempfile

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = _cpu_child_env(repo)
    env.pop("REPRO_STREAM_KILL_AFTER", None)
    with tempfile.TemporaryDirectory() as td:
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.compress",
             "--arch", "qwen3-32b", "--reduced", "--streaming",
             "--method", "alternating", "--stream-budget-mb", "64",
             "--out-dir", os.path.join(td, "out")],
            capture_output=True, text=True, cwd=repo, env=env,
        )
    if proc.returncode:
        raise RuntimeError(
            f"streaming bench subprocess failed:\n{proc.stderr[-2000:]}"
        )
    rss = int(re.search(r"^peak_rss_bytes=(\d+)$", proc.stdout, re.M).group(1))
    wall = float(re.search(r"^stream_wall_s=([\d.]+)$", proc.stdout,
                           re.M).group(1))
    row = {
        "kind": "streaming",
        "method": "alternating",
        "max_pool_tiles": "stream",
        "stream_budget_mb": 64,
        "peak_rss_bytes": rss,
        "stream_wall_s": wall,
    }
    emit("compress_streaming", wall * 1e6,
         f"peak_rss_mb={rss / 2**20:.0f};budget_mb=64")
    return row


def _bench_plan405b_row() -> dict:
    """The ROADMAP acceptance demo as a gated row: autotune a llama3-405b
    compression plan from metadata alone — ~770 GiB of eligible weights,
    no tensor ever materialises — in a fresh subprocess, recording its
    peak host RSS and the synthetic surrogate probe wall-clock."""
    import re
    import subprocess
    import sys

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = _cpu_child_env(repo)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.compress",
         "--arch", "llama3-405b", "--streaming", "--metadata-only",
         "--plan-only", "--budget-mb", "200000", "--method", "bbo",
         "--bbo-iters", "8"],
        capture_output=True, text=True, cwd=repo, env=env,
    )
    if proc.returncode:
        raise RuntimeError(
            f"405b plan bench subprocess failed:\n{proc.stderr[-2000:]}"
        )
    rss = int(re.search(r"^peak_rss_bytes=(\d+)$", proc.stdout, re.M).group(1))
    probe = float(re.search(r"^probe_s=([\d.]+)$", proc.stdout, re.M).group(1))
    row = {
        "kind": "plan405b",
        "method": "bbo",
        "max_pool_tiles": "metadata",
        "budget_mb": 200000,
        "peak_rss_bytes": rss,
        "probe_s": probe,
    }
    emit("compress_plan405b", probe * 1e6,
         f"peak_rss_mb={rss / 2**20:.0f};budget_mb=200000")
    return row


def _bench_probe_row(values, key) -> dict:
    """Surrogate (SVD-tail) vs exact trial-compression RD probing on the
    same reduced tree.  Both sides run in this process, so the speedup
    ratio is common-mode in machine drift; the gate catches the surrogate
    probe regressing back toward exact-probe cost."""
    from repro import compression as comp
    from repro.compression.autotune import probe_tensors
    from repro.compression.streaming import TreeLeafSource, surrogate_probe

    policy = comp.CompressionPolicy(
        method="alternating", tile_n=16, tile_d=16, rank_ratio=0.375,
        min_size=4096,
    )
    plan = comp.plan_compression(values, policy)
    t0 = time.perf_counter()
    sur = surrogate_probe(TreeLeafSource(values), plan, key=key,
                          sample_tiles=8)
    surrogate_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    probe_tensors(values, plan, key=key, max_probe_tiles=8)
    exact_s = time.perf_counter() - t0
    row = {
        "kind": "probe",
        "method": "surrogate",
        "max_pool_tiles": "probe",
        "tensors": len(sur.probes),
        "surrogate_probe_s": surrogate_s,
        "exact_probe_s": exact_s,
        "probe_speedup_vs_exact": exact_s / surrogate_s,
    }
    emit("compress_probe_surrogate", surrogate_s * 1e6,
         f"tensors={row['tensors']};speedup_vs_exact="
         f"{row['probe_speedup_vs_exact']:.1f}x")
    return row


def bench_bitlinear_suite(fast: bool = False) -> dict:
    """Fused bitlinear schedule microbench: per (geometry, T) case, time the
    unpack+einsum oracle against every bitlinear schedule lane (pallas
    grid / decode under the current pallas mode, the jnp
    formulations) plus the autotuned best (kernels/autotune.py).  Rows
    carry ``device``/``pallas_mode``, so a compiled-mode (TPU/GPU) lane
    lands as new rows without schema changes.  Writes BENCH_bitlinear.json.
    """
    from repro.kernels import autotune
    from repro.kernels import bitlinear as bl

    # calls are microsecond-scale: deep iters cost little and are the only
    # de-noiser that works on single-core CI runners
    repeats, iters = (3, 50) if fast else (5, 200)
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)

    def operands(E, n_r, n_c, tn, K, td, T):
        kb = (K + 7) // 8
        xsh = (E, T, n_r * tn) if E else (T, n_r * tn)
        mpsh = (E, n_r, n_c, tn, kb) if E else (n_r, n_c, tn, kb)
        csh = (E, n_r, n_c, K, td) if E else (n_r, n_c, K, td)
        x = jnp.asarray(rng.standard_normal(xsh).astype(np.float32))
        mp = jnp.asarray(rng.integers(0, 256, mpsh).astype(np.uint8))
        C = jnp.asarray(rng.standard_normal(csh).astype(np.float32))
        return x, mp, C

    # (case, E, n_r, n_c, tn, K, td): E=0 -> 2D.  Serving-shaped tiles
    # (reduced configs land near "serve"; "wide" is a TPU-aligned tile).
    cases = [
        ("small", 0, 4, 2, 16, 8, 32),
        ("serve", 0, 8, 4, 16, 16, 32),
        ("wide", 0, 4, 8, 32, 12, 64),
        ("moe", 4, 4, 2, 16, 8, 32),
    ]
    t_values = {0: (1, 16, 128), 4: (1, 8)}

    lanes = {
        "pallas_grid": autotune.Schedule("grid", "unpack"),
        "pallas_decode": autotune.Schedule("decode", "bitplane"),
        "jnp_dot": autotune.Schedule("jnp", "dot"),
        "jnp_bitplane": autotune.Schedule("jnp", "bitplane"),
    }

    results = []
    for case, E, n_r, n_c, tn, K, td in cases:
        for T in t_values[4 if E else 0]:
            x, mp, C = operands(E, n_r, n_c, tn, K, td, T)
            w = {"m_packed": mp, "C": C}
            call = bl.bitlinear_grouped if E else bl.bitlinear
            row = {
                "kind": "grouped" if E else "2d", "case": case,
                "E": E, "n_r": n_r, "n_c": n_c, "tn": tn, "K": K, "td": td,
                "T": T, "dtype": "float32",
            }
            best, _ = autotune.tune(x, mp, C, repeats=2, iters=10)
            ein_fn = (
                quantized.apply_compressed_grouped_einsum if E
                else quantized.apply_compressed_einsum
            )
            fns = {"einsum": jax.jit(lambda x: ein_fn(x, w))}
            for lane, s in lanes.items():
                fns[lane] = jax.jit(
                    lambda x, s=s: call(x, mp, C, interpret=interpret,
                                        **s.kwargs())
                )
            fns["tuned"] = jax.jit(
                lambda x: call(x, mp, C, interpret=interpret, **best.kwargs())
            )
            # interleaved timing windows: every lane sees the same slice of
            # machine drift, so the per-row speedup ratios the gate watches
            # are common-mode de-noised (min-of-windows per lane)
            times = {k: float("inf") for k in fns}
            for fn in fns.values():
                jax.block_until_ready(fn(x))
            for _ in range(repeats):
                for k, fn in fns.items():
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        out = fn(x)
                    jax.block_until_ready(out)
                    times[k] = min(
                        times[k], (time.perf_counter() - t0) / iters * 1e6
                    )
            row.update({f"{k}_us": v for k, v in times.items()})
            row.update(
                tuned_mode=best.mode, tuned_math=best.math,
                tuned_block_t=best.block_t, tuned_r_chunk=best.r_chunk,
                tuned_speedup_vs_einsum=row["einsum_us"] / row["tuned_us"],
            )
            results.append(row)
            emit(
                f"bitlinear_{row['kind']}_{case}_T{T}", row["tuned_us"],
                f"einsum_us={row['einsum_us']:.1f};"
                f"tuned={best.mode}/{best.math};"
                f"speedup=x{row['tuned_speedup_vs_einsum']:.2f}",
            )

    out = {
        "suite": "bitlinear",
        "device": jax.default_backend(),
        "pallas_mode": "interpret" if interpret else "compiled",
        "note": (
            "tuned_* is the autotuner's timed best over the schedule space; "
            "pallas lanes run in interpret mode off-TPU (not representative "
            "of TPU wall-clock)"
        ),
        "results": results,
    }
    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_bitlinear.json")
    with open(os.path.abspath(path), "w") as f:
        json.dump(out, f, indent=2)
    return out


def run_all() -> None:
    bench_compressed_matmul()
    bench_flash_ref()
    bench_sa_throughput()
    bench_ising_suite()
    bench_compress_suite()
    bench_bitlinear_suite()


def main() -> None:
    """CLI for CI: run one suite (refreshing its BENCH_*.json) or all."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=["all", "ising", "compress", "bitlinear"],
                    help="ising/compress/bitlinear refresh their "
                         "BENCH_*.json respectively")
    ap.add_argument("--fast", action="store_true",
                    help="CI mode: fewer timing repeats (same rows)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.suite == "ising":
        bench_ising_suite()
    elif args.suite == "compress":
        bench_compress_suite()
    elif args.suite == "bitlinear":
        bench_bitlinear_suite(fast=args.fast)
    else:
        run_all()


if __name__ == "__main__":
    main()
