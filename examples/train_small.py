"""End-to-end driver (task deliverable): train a ~100M-param LM for a few
hundred steps with the full production stack — sharded train step,
microbatching, checkpointing, auto-resume, heartbeat.

Default budget is CPU-sized (~20M params, 200 steps, ~10 min); pass
--d-model 768 --layers 12 for the full ~100M variant on real hardware.

    PYTHONPATH=src python examples/train_small.py [--steps 200]
"""

import argparse
import dataclasses
import sys

import jax

sys.path.insert(0, "src")

from repro.configs import get_config, reduced_for_smoke
from repro.configs.base import ParallelConfig, ShapeConfig
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import make_pipeline
from repro.distributed.fault_tolerance import StepTimer
from repro.distributed.sharding import activation_rules
from repro.launch.mesh import make_mesh
from repro.optim import warmup_cosine
from repro.training import init_train_state, make_train_step, state_shardings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_small")
    args = ap.parse_args()

    cfg = reduced_for_smoke(get_config("qwen3-32b"))
    cfg = dataclasses.replace(
        cfg, d_model=args.d_model, num_layers=args.layers,
        num_heads=args.d_model // 64, num_kv_heads=max(args.d_model // 128, 1),
        head_dim=64, d_ff=args.d_model * 4, vocab_size=8192, dtype="float32",
    )
    print(f"model: {cfg.param_count()/1e6:.1f}M params "
          f"({args.layers}L x {args.d_model}d)")

    mesh = make_mesh((1, 1), ("data", "model"))
    pcfg = ParallelConfig(mesh_shape=(1, 1), mesh_axes=("data", "model"),
                          microbatches=2)
    shape = ShapeConfig("small", "train", args.seq_len, args.batch)

    state = init_train_state(jax.random.PRNGKey(0), cfg, pcfg, mesh)
    mgr = CheckpointManager(args.ckpt_dir, keep_last=2)
    start, restored = mgr.restore_latest(state)
    if restored is not None:
        state = restored
        print(f"resumed from step {start}")

    sh = state_shardings(cfg, pcfg, mesh)
    step_fn = make_train_step(cfg, pcfg, warmup_cosine(3e-4, 20, args.steps))
    pipe = make_pipeline(cfg, shape, mesh)
    timer = StepTimer()

    with jax.set_mesh(mesh), activation_rules(pcfg, mesh):
        jstep = jax.jit(step_fn, in_shardings=(sh, None),
                        out_shardings=(sh, None), donate_argnums=0)
        step = int(state.step)
        first_loss = None
        while step < args.steps:
            timer.start()
            state, m = jstep(state, pipe.batch_at(step))
            loss = float(m["loss"])
            dt = timer.stop()
            step = int(state.step)
            if first_loss is None:
                first_loss = loss
            if step % 20 == 0 or step == args.steps:
                print(f"step {step:4d}  loss {loss:.4f}  "
                      f"{shape.tokens_per_step/dt:,.0f} tok/s")
            if step % 50 == 0:
                mgr.save(step, state)
        mgr.save(step, state)
        mgr.wait()
    print(f"loss {first_loss:.3f} -> {loss:.3f} over {args.steps} steps "
          f"({'DECREASED' if loss < first_loss else 'check config'})")


if __name__ == "__main__":
    main()
