"""Compression launcher — plan/execute pipeline over a whole model.

Plans the workload from a :class:`repro.compression.CompressionPolicy`
(either ``--policy policy.json`` or a one-rule policy built from the flags),
prints the plan, then executes it with tiles pooled across tensors into
batched solves.  The compressed values are saved as a checkpoint together
with the artifact manifest, which ``launch/serve.py`` consumes to restore
and validate the compressed model.

    PYTHONPATH=src python -m repro.launch.compress --arch granite-moe-1b-a400m \
        --reduced --method bbo --rank-ratio 0.375

    PYTHONPATH=src python -m repro.launch.compress --arch qwen3-32b \
        --reduced --policy policy.json --plan-only

With ``--budget-mb`` the flags/policy become the *base* policy of the
rate-distortion autotuner (docs/autotune.md): per-tensor (K, tile) settings
are chosen by probing RD curves and allocating the byte budget
(``--engine greedy|qubo``), optionally weighted by a calibration batch
(``--calibrate``):

    PYTHONPATH=src python -m repro.launch.compress --arch qwen3-32b \
        --reduced --budget-mb 0.125 --engine qubo --calibrate

``--streaming`` switches to the bounded-memory pipeline
(:mod:`repro.compression.streaming`): the plan comes from checkpoint
metadata (or an ``eval_shape`` template with ``--metadata-only`` — a
llama3-405b *plan* fits on a laptop), the RD probe uses SVD-tail
surrogates with exact fallback only at allocation boundaries, and the
execute walks the checkpoint one leaf at a time under
``REPRO_STREAM_BUDGET_BYTES`` (or ``--stream-budget-mb``), checkpointing
job state so a killed run resumes instead of restarting:

    PYTHONPATH=src python -m repro.launch.compress --arch llama3-405b \
        --streaming --metadata-only --budget-mb 200000 --plan-only

    PYTHONPATH=src python -m repro.launch.compress --arch qwen3-32b \
        --reduced --streaming --ckpt-dir /ckpts/run1 --out-dir /ckpts/run1-c

``--delta-from <dir>`` recompresses drifted weights as a *delta* against a
previously compressed checkpoint (docs/delta.md): geometry and method come
from the parent manifest (the policy flags are unused), only tiles whose
drift crossed ``--delta-threshold`` are re-solved — warm-started from the
parent's (M, C) — and the manifest records the delta lineage:

    PYTHONPATH=src python -m repro.launch.compress --arch qwen3-32b \
        --reduced --ckpt-dir /ckpts/run1-more-steps \
        --delta-from /ckpts/run1-c --out-dir /ckpts/run1-c2
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compression import (
    CompressionPolicy,
    autotune_plan,
    execute_plan,
    plan_compression,
)
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced_for_smoke
from repro.checkpoint import checkpointer
from repro.checkpoint.manager import CheckpointManager
from repro.models import init_model
from repro.models.params import split


def build_policy(args) -> CompressionPolicy:
    if args.policy:
        with open(args.policy) as f:
            return CompressionPolicy.from_json(f.read())
    return CompressionPolicy(
        method=args.method,
        tile_n=args.tile_n,
        tile_d=args.tile_d,
        rank_ratio=args.rank_ratio,
        min_size=args.min_size,
        bbo_iters=args.bbo_iters,
        solver_backend=args.backend,
    )


def run_streaming(args, cfg) -> None:
    """The ``--streaming`` pipeline.  Prints machine-parseable
    ``key=value`` lines (``peak_rss_bytes``, ``probe_s``,
    ``stream_wall_s``) that the streaming bench rows and the CI smoke
    consume."""
    from repro.compression.streaming import (
        CheckpointLeafSource,
        TreeLeafSource,
        peak_rss_bytes,
        run_compression_job,
        streaming_autotune_plan,
    )

    key = jax.random.PRNGKey(args.seed)
    if args.ckpt_dir:
        source = CheckpointLeafSource(args.ckpt_dir)
    elif args.metadata_only:
        # Shapes/dtypes of the full model without materialising one byte of
        # weights: eval_shape traces init_model abstractly, so planning
        # llama3-405b (~810 GB dense) costs ~200 MB of host RSS.
        template = jax.eval_shape(
            lambda k: split(init_model(k, cfg))[0],
            jax.random.PRNGKey(args.seed),
        )
        source = TreeLeafSource(template)
    else:
        values, _ = split(init_model(key, cfg))
        source = TreeLeafSource(values)
    print(f"[stream] source {source.describe()}")

    policy = build_policy(args)
    budget_bytes = (
        int(args.stream_budget_mb * 2**20)
        if args.stream_budget_mb is not None else None
    )
    t0 = time.time()
    if args.budget_mb is not None:
        result = streaming_autotune_plan(
            source, policy, int(args.budget_mb * 2**20), key=key,
            engine=args.engine or "greedy",
            sample_tiles=args.sample_tiles or 8,
            backend=args.backend, verbose=True,
        )
        plan = result.plan
        probe = plan.autotune["probe"]
        print(
            f"[autotune/stream] {probe['source']} surrogate probe of "
            f"{len(result.probes)} tensors in {result.probe_s:.2f}s, "
            f"exact fallback on {len(probe['exact_fallback'])} of "
            f"{len(probe['boundary'])} boundary tensor(s), allocated "
            f"{result.allocation.total_bytes / 2**20:.2f} of "
            f"{args.budget_mb:.2f} MiB"
        )
        print(f"probe_s={result.probe_s:.3f}")
    else:
        plan = plan_compression(source.template(), policy)
    print(plan.summary())
    if args.plan_only:
        print(f"[stream] planned in {time.time() - t0:.1f}s")
        print(f"peak_rss_bytes={peak_rss_bytes()}")
        return

    artifact, stats = run_compression_job(
        source, plan, args.out_dir, key=key, backend=args.backend,
        budget_bytes=budget_bytes,
        max_restarts=3 if args.max_restarts is None else args.max_restarts,
        verbose=True,
    )
    print(
        f"\n[stream] {stats['leaves_done_this_run']} leaves this run "
        f"({stats['resumed_leaves']} resumed), {stats['chunks']} solve "
        f"chunk(s), {stats['restarts']} restart(s), {stats['wall_s']:.1f}s"
    )
    print(
        f"compressed tensors: "
        f"{artifact.manifest['totals']['orig_bytes'] / 2**20:.2f} -> "
        f"{artifact.total_bytes() / 2**20:.2f} MiB "
        f"(x{artifact.compression_ratio:.2f})"
    )
    if args.budget_mb is not None:
        over = artifact.total_bytes() > int(args.budget_mb * 2**20)
        print(f"budget: {args.budget_mb:.2f} MiB -> "
              f"{'OVER' if over else 'met'}")
    print(f"saved compressed params to {args.out_dir}")
    print(f"stream_wall_s={stats['wall_s']:.3f}")
    print(f"peak_rss_bytes={stats['peak_rss_bytes']}")


def run_delta(args, values) -> None:
    """The ``--delta-from`` pipeline: anchor on a previously compressed
    checkpoint and re-solve only drifted tiles (docs/delta.md).  Prints
    machine-parseable ``key=value`` lines (``delta_wall_s``,
    ``fraction_resolved``) the delta bench/smoke consume."""
    from repro.compression import (
        ColdStartRequired,
        CompressionArtifact,
        delta_recompress,
        plan_delta,
    )

    parent = CompressionArtifact.load(args.delta_from)
    template = parent.restore_template(values)
    step, state = CheckpointManager(args.delta_from).restore_latest(
        {"params": template}
    )
    if state is None:
        raise SystemExit(
            f"--delta-from {args.delta_from}: manifest found but no "
            "restorable compressed checkpoint"
        )
    prev = state["params"]
    print(f"[delta] parent {parent.fingerprint()} (step {step}, "
          f"{len(parent.manifest['tensors'])} tensors)")

    threshold = args.delta_threshold
    kw = {} if threshold is None else {"threshold": threshold}
    try:
        if args.plan_only:
            print(plan_delta(parent, prev, values, **kw).summary())
            return
        t = time.time()
        cvalues, artifact = delta_recompress(
            parent, prev, values, key=jax.random.PRNGKey(args.seed),
            backend=args.backend, verbose=True, **kw,
        )
        dt = time.time() - t
    except ColdStartRequired as e:
        raise SystemExit(
            f"--delta-from cannot anchor on {args.delta_from}: {e}\n"
            "run a full compression (drop --delta-from) instead"
        )
    d = artifact.delta
    print(
        f"\n[delta] gen {d['generation']}: {d['tiles_resolved']}/"
        f"{d['tiles_total']} tiles re-solved ({d['fraction_resolved']:.1%}) "
        f"across {d['tensors_touched']} tensor(s) in {dt:.1f}s"
    )
    path = checkpointer.save(args.out_dir, 0, {"params": cvalues})
    mpath = artifact.save(args.out_dir)
    print(f"saved compressed params to {path}")
    print(f"saved compression manifest to {mpath}")
    print(f"delta_wall_s={dt:.3f}")
    print(f"fraction_resolved={d['fraction_resolved']:.4f}")


def execute_and_save(plan, values, out_dir: str, *, seed: int = 0,
                     autotune_kernels: bool = False, verbose: bool = True):
    """Execute ``plan`` over ``values`` and save the compressed params
    (checkpoint step 0) with the artifact manifest in ``out_dir`` — the
    layout ``launch/serve.py`` restores.  ``autotune_kernels`` first
    probes the kernel schedules for every geometry the manifest can
    produce and persists the winners into ``manifest["kernel_schedules"]``
    (probe-then-serve: the engine restores them, serving never re-tunes).
    Returns (compressed values, artifact, execute seconds)."""
    t = time.time()
    cvalues, artifact = execute_plan(
        plan, values, key=jax.random.PRNGKey(seed), verbose=verbose
    )
    jax.block_until_ready(cvalues)
    dt = time.time() - t
    if autotune_kernels:
        from repro.kernels import autotune as kernel_autotune

        t = time.time()
        table = kernel_autotune.tune_artifact(artifact, verbose=verbose)
        print(f"[autotune] {len(table['entries'])} kernel schedule(s) in "
              f"{time.time()-t:.1f}s")
    path = checkpointer.save(out_dir, 0, {"params": cvalues})
    mpath = artifact.save(out_dir)
    print(f"saved compressed params to {path}")
    print(f"saved compression manifest to {mpath}")
    return cvalues, artifact, dt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None, help="source checkpoint")
    ap.add_argument("--out-dir", default="/tmp/repro_compressed")
    ap.add_argument("--policy", default=None,
                    help="CompressionPolicy JSON file; overrides the flags below")
    ap.add_argument("--plan-only", action="store_true",
                    help="print the plan (predicted bytes/ratio) and exit")
    ap.add_argument("--method", default="alternating",
                    choices=["greedy", "alternating", "bbo", "int8"])
    ap.add_argument("--tile-n", type=int, default=32)
    ap.add_argument("--tile-d", type=int, default=128)
    ap.add_argument("--rank-ratio", type=float, default=0.125)
    ap.add_argument("--min-size", type=int, default=1 << 16)
    ap.add_argument("--bbo-iters", type=int, default=64)
    ap.add_argument("--backend", default="auto", choices=["auto", "pallas", "jnp"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune-kernels", action="store_true",
                    help="after compressing, probe kernel schedules for the "
                         "manifest's geometries and persist the winners into "
                         "manifest['kernel_schedules'] (kernels/autotune.py)")
    ap.add_argument("--budget-mb", type=float, default=None,
                    help="autotune to this compressed-bytes budget "
                         "(rate-distortion allocation; docs/autotune.md)")
    ap.add_argument("--engine", default=None, choices=["greedy", "qubo"],
                    help="budget allocator engine (default greedy; qubo "
                         "solves the one-hot QUBO encoding through "
                         "ising.solve_many)")
    ap.add_argument("--calibrate", action="store_true",
                    help="weight probed distortion by activation-sensitivity "
                         "second moments from a calibration batch")
    ap.add_argument("--calib-batch", type=int, default=None)
    ap.add_argument("--calib-seq", type=int, default=None)
    ap.add_argument("--calib-batches", type=int, default=None,
                    help="calibration batches averaged into the sensitivity "
                         "weights (default 1; batch count and key land in "
                         "the plan metadata for byte-determinism)")
    ap.add_argument("--objective", default="frobenius",
                    choices=["frobenius", "eval-loss"],
                    help="what the budget allocator minimises: weight-space "
                         "Frobenius distortion, or measured eval-loss "
                         "deltas from the task-metric evaluation subsystem "
                         "(docs/eval.md; requires --budget-mb)")
    ap.add_argument("--eval-batches", type=int, default=None,
                    help="eval harness batches for --objective eval-loss "
                         "(default 4)")
    ap.add_argument("--eval-seq", type=int, default=None,
                    help="eval harness sequence length (default 32)")
    ap.add_argument("--probe-tiles", type=int, default=None,
                    help="trial-compressed tiles per (tensor, candidate); "
                         "0 probes every tile (exact, slower; default 16)")
    ap.add_argument("--streaming", action="store_true",
                    help="bounded-memory pipeline: plan from metadata, "
                         "surrogate RD probe, leaf-at-a-time resumable "
                         "execute (docs/compression_api.md)")
    ap.add_argument("--metadata-only", action="store_true",
                    help="with --streaming: plan/probe from an eval_shape "
                         "template — no weights are ever materialised "
                         "(requires --plan-only)")
    ap.add_argument("--stream-budget-mb", type=float, default=None,
                    help="host-memory budget for streaming solves "
                         "(default REPRO_STREAM_BUDGET_BYTES or 1 GiB)")
    ap.add_argument("--sample-tiles", type=int, default=None,
                    help="surrogate probe sample tiles per (tensor, "
                         "geometry) (default 8)")
    ap.add_argument("--max-restarts", type=int, default=None,
                    help="streaming job supervision restarts (default 3)")
    ap.add_argument("--delta-from", default=None,
                    help="previously compressed checkpoint dir (manifest + "
                         "compressed params): recompress the current "
                         "weights as a warm-started delta against it "
                         "(docs/delta.md)")
    ap.add_argument("--delta-threshold", type=float, default=None,
                    help="drift ratio above which a tile re-solves "
                         "(default 1.25; an unchanged tile sits at 1.0)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.delta_from:
        stray = [
            name for name, val in (
                ("--streaming", args.streaming or None),
                ("--budget-mb", args.budget_mb),
                ("--policy", args.policy),
                ("--autotune-kernels", args.autotune_kernels or None),
            ) if val is not None
        ]
        if stray:
            ap.error(f"{', '.join(stray)} do not apply with --delta-from "
                     "(geometry, method and kernel schedules come from the "
                     "parent manifest)")
    elif args.delta_threshold is not None:
        ap.error("--delta-threshold only applies with --delta-from")
    if not args.streaming:
        stray = [
            name for name, val in (
                ("--metadata-only", args.metadata_only or None),
                ("--stream-budget-mb", args.stream_budget_mb),
                ("--sample-tiles", args.sample_tiles),
                ("--max-restarts", args.max_restarts),
            ) if val is not None
        ]
        if stray:
            ap.error(f"{', '.join(stray)} only apply with --streaming")
    else:
        if args.calibrate:
            ap.error("--calibrate needs the full model in memory; it does "
                     "not compose with --streaming")
        if args.probe_tiles is not None:
            ap.error("--probe-tiles is the in-memory probe knob; use "
                     "--sample-tiles with --streaming")
        if args.metadata_only and not args.plan_only:
            ap.error("--metadata-only has no tensor data to execute on; "
                     "add --plan-only (or drop --metadata-only)")
        if args.metadata_only and args.ckpt_dir:
            ap.error("--metadata-only and --ckpt-dir are mutually "
                     "exclusive sources")
    if args.budget_mb is None:
        stray = [
            name for name, val in (
                ("--engine", args.engine),
                ("--calibrate", args.calibrate or None),
                ("--calib-batch", args.calib_batch),
                ("--calib-seq", args.calib_seq),
                ("--calib-batches", args.calib_batches),
                ("--probe-tiles", args.probe_tiles),
                ("--objective",
                 args.objective if args.objective != "frobenius" else None),
                ("--eval-batches", args.eval_batches),
                ("--eval-seq", args.eval_seq),
            ) if val is not None
        ]
        if stray:
            ap.error(f"{', '.join(stray)} only apply with --budget-mb "
                     "(the autotune path)")
    elif not args.calibrate and (
        args.calib_batch is not None or args.calib_seq is not None
        or args.calib_batches is not None
    ):
        ap.error("--calib-batch/--calib-seq/--calib-batches require "
                 "--calibrate")
    if args.objective == "eval-loss":
        if args.streaming:
            ap.error("--objective eval-loss needs the full model in memory "
                     "to splice candidates; it does not compose with "
                     "--streaming")
    elif args.eval_batches is not None or args.eval_seq is not None:
        ap.error("--eval-batches/--eval-seq require --objective eval-loss")
    if (args.calib_batches or 1) > 1 and (
        args.calib_batch is not None or args.calib_seq is not None
    ):
        ap.error("--calib-batches > 1 draws default-shaped batches; it is "
                 "mutually exclusive with --calib-batch/--calib-seq")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_for_smoke(cfg)
    if args.streaming:
        run_streaming(args, cfg)
        return
    values, _ = split(init_model(jax.random.PRNGKey(args.seed), cfg))
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        step, state = mgr.restore_latest(
            {"step": jnp.zeros((), jnp.int32), "params": values, "opt": None}
        )
        if state is not None:
            values = state["params"]
            print(f"[restore] step {step}")

    if args.delta_from:
        run_delta(args, values)
        return

    policy = build_policy(args)
    if args.budget_mb is not None:
        budget_bytes = int(args.budget_mb * 2**20)
        engine = args.engine or "greedy"
        objective = args.objective.replace("-", "_")
        probe_tiles = 16 if args.probe_tiles is None else args.probe_tiles
        cal_inputs = None
        if args.calibrate and (args.calib_batch or args.calib_seq):
            from repro.compression.autotune import calibration_inputs

            cal_inputs = calibration_inputs(
                cfg, batch=args.calib_batch or 4,
                seq_len=args.calib_seq or 32,
                key=jax.random.PRNGKey(args.seed),
            )
        result = autotune_plan(
            values, policy, budget_bytes,
            key=jax.random.PRNGKey(args.seed),
            engine=engine, objective=objective, cfg=cfg,
            calibration=args.calibrate,
            calibration_inputs=cal_inputs,
            calib_batches=args.calib_batches or 1,
            eval_batches=args.eval_batches or 4,
            eval_seq=args.eval_seq or 32,
            eval_seed=args.seed,
            max_probe_tiles=probe_tiles or None,
            backend=args.backend, verbose=True,
        )
        plan = result.plan
        print(
            f"[autotune/{engine}] probed {len(result.probes)} tensors "
            f"in {result.probe_s:.1f}s, allocated "
            f"{result.allocation.total_bytes / 2**20:.2f} of "
            f"{budget_bytes / 2**20:.2f} MiB "
            f"(solve {result.allocation.solve_s * 1e3:.1f} ms)"
        )
        if result.metric_table is not None:
            table = result.metric_table
            print(
                f"[eval] baseline loss {table.baseline.loss:.4f}, "
                f"{len(table.exact_paths)} tensor(s) spliced exactly, "
                f"surrogate skip rate {table.surrogate_skip_rate:.0%} "
                f"(table {table.build_s:.1f}s)"
            )
        if result.lp_check is not None:
            lp = result.lp_check
            print(
                f"[lp] {lp['status']}: gap {lp['relative_gap']:+.2%} "
                f"({'within' if lp['within_tolerance'] else 'OVER'} "
                f"{lp['tolerance']:.0%} tolerance)"
            )
    else:
        plan = plan_compression(values, policy)
    print(plan.summary())
    if args.plan_only:
        return

    _, artifact, dt = execute_and_save(
        plan, values, args.out_dir, seed=args.seed,
        autotune_kernels=args.autotune_kernels,
    )
    report = artifact.report
    print(f"\n[compress/{policy.method}] {len(report.compressed)} tensors in {dt:.1f}s")
    for path, ob, nb, err in report.compressed:
        print(f"  {path:48s} {ob/2**20:8.2f} -> {nb/2**20:8.2f} MiB "
              f"(x{ob/max(nb,1):4.1f})  rel_err {err:.3f}")
    # (skip reasons were already summarised by plan.summary() above)
    print(
        f"compressed tensors: "
        f"{artifact.manifest['totals']['orig_bytes'] / 2**20:.2f} -> "
        f"{artifact.total_bytes() / 2**20:.2f} MiB "
        f"(x{artifact.compression_ratio:.2f})"
    )
    if args.budget_mb is not None:
        over = artifact.total_bytes() > budget_bytes
        print(f"budget: {args.budget_mb:.2f} MiB -> "
              f"{'OVER' if over else 'met'}")


if __name__ == "__main__":
    main()
