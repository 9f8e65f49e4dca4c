"""Training launcher: supervised, checkpointed, resumable.

    PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
        --reduced --steps 200 --mesh 1x1 --ckpt-dir /tmp/run1

Production invocation uses the real mesh (--mesh 16x16) on TPU; offline the
same code runs a reduced config on (1, 1).  Fault tolerance: the run resumes
from the newest committed checkpoint; ``--max-restarts`` wraps the loop in
the supervision harness (distributed/fault_tolerance.py); ``--fail-at-step``
injects a crash once, to exercise the restart path end-to-end.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import SHAPES, get_config, reduced_for_smoke
from repro.configs.base import ParallelConfig, ShapeConfig
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import make_pipeline
from repro.distributed.fault_tolerance import Heartbeat, StepTimer, run_with_restarts
from repro.distributed.sharding import activation_rules
from repro.launch.mesh import make_mesh
from repro.optim import warmup_cosine
from repro.training import init_train_state, make_train_step, state_shardings


def parse_mesh(s: str):
    dims = tuple(int(x) for x in s.split("x"))
    axes = ("pod", "data", "model")[-len(dims):] if len(dims) <= 3 else None
    assert axes, f"mesh must have <= 3 dims, got {s}"
    return dims, axes


def train_once(args, attempt: int) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_for_smoke(cfg)
    dims, axes = parse_mesh(args.mesh)
    mesh = make_mesh(dims, axes)
    shape = (
        SHAPES[args.shape]
        if args.shape in SHAPES
        else ShapeConfig("custom", "train", args.seq_len, args.batch)
    )
    pcfg = ParallelConfig(
        mesh_shape=dims, mesh_axes=axes, microbatches=args.microbatches,
        optimizer=args.optimizer,
    )

    mgr = CheckpointManager(args.ckpt_dir, keep_last=args.keep_last)
    hb = Heartbeat(f"{args.ckpt_dir}/heartbeat.json", interval_s=5)
    timer = StepTimer()

    state = init_train_state(jax.random.PRNGKey(args.seed), cfg, pcfg, mesh)
    start, restored = mgr.restore_latest(state)
    if restored is not None:
        state = restored
        print(f"[resume] from step {start} (attempt {attempt})")

    sh = state_shardings(cfg, pcfg, mesh)
    step_fn = make_train_step(cfg, pcfg, warmup_cosine(args.lr, args.warmup, args.steps))
    pipe = make_pipeline(cfg, shape, mesh, seed=args.seed)

    with jax.set_mesh(mesh), activation_rules(pcfg, mesh):
        jstep = jax.jit(
            step_fn, in_shardings=(sh, None), out_shardings=(sh, None),
            donate_argnums=0,
        )
        step = int(state.step)
        while step < args.steps:
            timer.start()
            state, metrics = jstep(state, pipe.batch_at(step))
            loss = float(metrics["loss"])
            dt = timer.stop()
            step = int(state.step)
            hb.beat(step, {"loss": loss})
            if step % args.log_every == 0 or step == args.steps:
                tput = shape.tokens_per_step / dt
                print(f"step {step:6d} loss {loss:.4f} "
                      f"| {dt*1e3:6.0f} ms/step | {tput:9.0f} tok/s", flush=True)
            if args.fail_at_step and step == args.fail_at_step and attempt == 0:
                raise RuntimeError("injected failure (--fail-at-step)")
            if step % args.ckpt_every == 0 or step == args.steps:
                mgr.save(step, state)
        mgr.wait()
    print(f"done at step {step}; final loss {loss:.4f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced same-family config (CPU-runnable)")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--shape", default="custom")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep-last", type=int, default=3)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--fail-at-step", type=int, default=0,
                    help="inject one crash at this step (tests restart path)")
    args = ap.parse_args()

    enable_compile_cache()
    restarts = run_with_restarts(
        lambda attempt: train_once(args, attempt),
        max_restarts=args.max_restarts,
        on_failure=lambda a, e: print(f"[supervisor] attempt {a} failed: {e}; restarting"),
    )
    if restarts:
        print(f"[supervisor] recovered after {restarts} restart(s)")


if __name__ == "__main__":
    main()
