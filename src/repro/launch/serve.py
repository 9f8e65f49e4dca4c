"""Serving launcher: batched generation, optionally from a checkpoint and
optionally with integer-decomposition-compressed weights.

When ``--ckpt-dir`` holds a compression manifest (written by
``launch/compress.py``), the compressed checkpoint is restored through the
manifest's template — the manifest, not shape-sniffing, decides which
weights are ``{"m_packed", "C"}`` dicts and with what geometry — and the
engine validates the restored tree against it.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --reduced \
        --compress --steps 32 --batch 4

``--load-curve`` swaps the one-shot fixed-batch generation for the
continuous-batching tier (serving/scheduler.py): ragged prompts arrive as a
Poisson process at each ``--qps`` rate through the async front end, and the
launcher prints per-rate p50/p99 latency, goodput and peak concurrency.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.compression import CompressionArtifact, CompressionPolicy
from repro.compression import execute_plan, plan_compression
from repro.configs import get_config, reduced_for_smoke
from repro.checkpoint.manager import CheckpointManager
from repro.models import init_model
from repro.models.params import split
from repro.serving.engine import Engine


def restore_checkpoint(ckpt_dir: str, values):
    """Restore ``ckpt_dir`` onto the dense ``values`` template.  Returns
    (values, artifact): a directory holding a compression manifest (as
    ``launch/compress.py`` writes it) restores through the manifest's
    template — the manifest, not shape-sniffing, decides which weights are
    ``{"m_packed", "C"}`` dicts and with what geometry — and returns that
    artifact; any other checkpoint restores dense with artifact None."""
    mgr = CheckpointManager(ckpt_dir)
    if CompressionArtifact.exists(ckpt_dir):
        # the checkpoint's tree is compressed (and holds params only, as
        # written by launch/compress.py), so the dense template must be
        # rewritten before restore
        artifact = CompressionArtifact.load(ckpt_dir)
        template = artifact.restore_template(values)
        step, state = mgr.restore_latest({"params": template})
        if state is not None:
            t = artifact.manifest["totals"]
            print(f"[restore] step {step} (compressed: "
                  f"{len(artifact.manifest['tensors'])} tensors, "
                  f"x{t['ratio']:.2f})")
            return state["params"], artifact
        # manifest without a restorable step: serve the dense init rather
        # than crashing manifest validation against it
        print(f"[restore] {ckpt_dir}: manifest present but no checkpoint "
              "step; serving dense init")
        return values, None
    step, state = mgr.restore_latest(
        {"step": jnp.zeros((), jnp.int32), "params": values, "opt": None}
    )
    if state is not None:
        print(f"[restore] step {step}")
        values = state["params"]
    return values, None


def serve_load_curve(eng: Engine, prompts, *, max_tokens: int, rates,
                     num_slots: int = 4, page_size: int = 16):
    """Serve ``prompts`` through the continuous-batching tier once per
    arrival rate in ``rates``: ``Scheduler`` over paged KV, the async
    ``ServeFrontend``, Poisson arrivals from ``run_load``.  Every prefill
    length and the decode step are traced before the first rate.  Yields
    one ``LoadResult`` per rate."""
    from repro.serving import Scheduler, ServeFrontend, run_load

    max_len = max(len(p) for p in prompts) + max_tokens
    page = min(page_size, max_len)
    while max_len % page != 0:
        page //= 2
    sched = Scheduler(eng, num_slots=num_slots, page_size=page,
                      max_len=max_len)
    lens = sorted({len(p) for p in prompts})
    # warm-up traces every prefill bucket + the decode step
    sched.generate_batch([np.full(L, 3, np.int32) for L in lens],
                         max_tokens=2)
    with ServeFrontend(sched, overcommit=2.0,
                       max_pending=4 * len(prompts)) as fe:
        for qps in rates:
            sched.stats.reset()
            yield run_load(fe, prompts, max_tokens=max_tokens, qps=qps,
                           eos_id=10 ** 6)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--tile-n", type=int, default=16)
    ap.add_argument("--tile-d", type=int, default=32)
    ap.add_argument("--rank-ratio", type=float, default=0.5)
    ap.add_argument("--compress-method", default="alternating",
                    choices=["greedy", "alternating", "bbo"])
    ap.add_argument("--no-fused-bitlinear", action="store_true",
                    help="escape hatch: serve compressed weights through the "
                         "unpack+einsum fallback instead of the fused Pallas "
                         "bitlinear kernel")
    ap.add_argument("--autotune-kernels", action="store_true",
                    help="probe kernel schedules for this manifest's "
                         "geometries (timed best-of-N, kernels/autotune.py) "
                         "and persist the winners into "
                         "manifest['kernel_schedules'] before serving")
    ap.add_argument("--load-curve", action="store_true",
                    help="serve a Poisson arrival sweep through the "
                         "continuous-batching scheduler instead of one "
                         "fixed-batch generate() call")
    ap.add_argument("--qps", type=float, nargs="*", default=[2.0, 8.0, 32.0],
                    help="arrival rates for --load-curve")
    ap.add_argument("--requests", type=int, default=16,
                    help="requests per --load-curve rate")
    ap.add_argument("--num-slots", type=int, default=4,
                    help="decode slots for --load-curve")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size (tokens) for --load-curve")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_for_smoke(cfg)
    values, _ = split(init_model(jax.random.PRNGKey(args.seed), cfg))

    artifact = None
    if args.ckpt_dir:
        values, artifact = restore_checkpoint(args.ckpt_dir, values)

    if args.compress and artifact is None:
        policy = CompressionPolicy(
            method=args.compress_method, tile_n=args.tile_n,
            tile_d=args.tile_d, rank_ratio=args.rank_ratio, min_size=4096,
        )
        plan = plan_compression(values, policy)
        t = time.time()
        values, artifact = execute_plan(
            plan, values, key=jax.random.PRNGKey(args.seed), verbose=True
        )
        report = artifact.report
        print(f"[compress] {len(report.compressed)} tensors, "
              f"ratio {report.total_ratio:.2f}x, {time.time()-t:.1f}s; "
              f"skipped {len(report.skipped)}")

    if args.autotune_kernels and artifact is not None:
        from repro.kernels import autotune as kernel_autotune

        t = time.time()
        table = kernel_autotune.tune_artifact(
            artifact,
            T_values=(args.batch, args.batch * args.prompt_len),
            verbose=True,
        )
        print(f"[autotune] {len(table['entries'])} kernel schedule(s) in "
              f"{time.time()-t:.1f}s")

    eng = Engine(cfg, values, max_len=args.prompt_len + args.steps,
                 batch=args.batch, temperature=args.temperature,
                 artifact=artifact,
                 use_fused_bitlinear=False if args.no_fused_bitlinear else None)
    if eng.compression is not None:
        path = "fused bitlinear kernel" if eng.fused_bitlinear else "unpack+einsum"
        print(f"[engine] serving compressed weights via {path}: "
              f"{eng.compression}")

    if args.load_curve:
        rng = np.random.default_rng(args.seed)
        lens = sorted({max(2, args.prompt_len // 2), args.prompt_len})
        prompts = [
            rng.integers(0, cfg.vocab_size, size=int(rng.choice(lens)))
            .astype(np.int32)
            for _ in range(args.requests)
        ]
        print("qps,completed,goodput_toks_per_s,p50_ms,p99_ms,peak,evictions")
        for res in serve_load_curve(
            eng, prompts, max_tokens=args.steps, rates=args.qps,
            num_slots=args.num_slots, page_size=args.page_size,
        ):
            print(f"{res.qps:g},{res.completed},"
                  f"{res.goodput_toks_per_s:.1f},"
                  f"{1e3 * res.p50_latency_s:.1f},"
                  f"{1e3 * res.p99_latency_s:.1f},"
                  f"{res.peak_running},{res.evictions}")
        return

    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    t = time.time()
    out = eng.generate(prompts, args.steps, key=jax.random.PRNGKey(2))
    dt = time.time() - t
    print("generated:", out.shape, f"in {dt:.2f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s)")
    print(out[0, : args.prompt_len + 8])


if __name__ == "__main__":
    main()
