"""The train -> compress -> serve *cycle*: periodic delta recompression.

examples/compress_then_serve.py shows the one-shot pipeline; this example
closes the loop for weights that keep drifting (continued fine-tuning).
A :class:`repro.optim.grad_compress.CompressionCycle` hook fires every N
steps from the training loop:

  1. first firing — full cold compression (plan + execute),
  2. later firings — ``delta_recompress`` against the previous artifact:
     per-tile drift is measured against the manifest's recorded residuals
     and only tiles past the threshold re-solve, warm-started from the
     previous (M, C); everything else reuses the parent's packed bytes,
  3. the final artifact carries the delta lineage block (parent
     fingerprint, generation, tiles reused vs re-solved) and serves
     through the Engine — fused bitlinear vs unpack+einsum must emit
     identical greedy tokens.

    PYTHONPATH=src python examples/delta_recompress.py \
        [--train-steps 24] [--every 12] [--method alternating]
"""

import argparse
import dataclasses
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, "src")

from repro.compression import CompressionPolicy
from repro.configs import get_config, reduced_for_smoke
from repro.configs.base import ParallelConfig, ShapeConfig
from repro.data.pipeline import make_pipeline
from repro.distributed.sharding import activation_rules
from repro.launch.mesh import make_mesh
from repro.optim import warmup_cosine
from repro.optim.grad_compress import CompressionCycle
from repro.serving.engine import Engine
from repro.training import init_train_state, make_train_step, state_shardings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default="alternating",
                    choices=["greedy", "alternating", "bbo"])
    ap.add_argument("--train-steps", type=int, default=24)
    ap.add_argument("--every", type=int, default=12,
                    help="recompress every N steps (cold first, delta after)")
    ap.add_argument("--threshold", type=float, default=None,
                    help="drift ratio past which a tile re-solves "
                         "(default: repro.compression.delta's 1.25)")
    args = ap.parse_args()
    if args.train_steps < 2 * args.every:
        raise SystemExit("need train-steps >= 2*every so a delta fires "
                         f"(got {args.train_steps} < {2 * args.every})")

    cfg = reduced_for_smoke(get_config("mistral-nemo-12b"))
    cfg = dataclasses.replace(cfg, d_model=128, d_ff=256, num_layers=4,
                              vocab_size=512, dtype="float32")
    mesh = make_mesh((1, 1), ("data", "model"))
    pcfg = ParallelConfig(mesh_shape=(1, 1), mesh_axes=("data", "model"))
    shape = ShapeConfig("s", "train", 128, 8)

    policy = CompressionPolicy(
        method=args.method, tile_n=8 if args.method == "bbo" else 16,
        tile_d=128, rank_ratio=0.5, min_size=8192, bbo_iters=24,
    )
    cycle = CompressionCycle(policy, every=args.every,
                             threshold=args.threshold, verbose=True)

    state = init_train_state(jax.random.PRNGKey(0), cfg, pcfg, mesh)
    sh = state_shardings(cfg, pcfg, mesh)
    fn = make_train_step(cfg, pcfg, warmup_cosine(3e-3, 10, args.train_steps))
    pipe = make_pipeline(cfg, shape, mesh)
    with jax.set_mesh(mesh), activation_rules(pcfg, mesh):
        jstep = jax.jit(fn, in_shardings=(sh, None), out_shardings=(sh, None))
        for i in range(args.train_steps):
            state, m = jstep(state, pipe.batch_at(i))
            fired = cycle.maybe_recompress(i + 1, state.params)
            if fired is not None:
                _, art = fired
                kind = "delta" if art.delta else "cold"
                print(f"step {i + 1}: {kind} recompression "
                      f"(x{art.compression_ratio:.2f}, "
                      f"loss {float(m['loss']):.3f})")
    print(f"trained {args.train_steps} steps, loss {float(m['loss']):.3f}")

    cvals, artifact = cycle.compressed, cycle.artifact
    d = artifact.delta
    assert d is not None, "no delta fired — raise --train-steps or lower --every"
    print(f"delta lineage: parent {d['parent_fingerprint']} "
          f"generation {d['generation']}, re-solved "
          f"{d['tiles_resolved']}/{d['tiles_total']} tiles "
          f"({d['fraction_resolved']:.1%}), reused {d['tiles_reused']}")
    assert d["tiles_reused"] > 0, (
        "delta reused no tiles — drift threshold too low for this run"
    )

    # serve the delta artifact both ways; greedy tokens must be identical.
    # einsum engine first: the fused hook is process-global, bound at trace
    # time (see Engine docstring).
    prompts = jax.random.randint(jax.random.PRNGKey(7), (4, 12), 0,
                                 cfg.vocab_size)
    eng_e = Engine(cfg, cvals, max_len=44, batch=4, artifact=artifact,
                   use_fused_bitlinear=False)
    out_e = eng_e.generate(prompts, steps=24)
    eng_f = Engine(cfg, cvals, max_len=44, batch=4, artifact=artifact,
                   use_fused_bitlinear=True)
    out_f = eng_f.generate(prompts, steps=24)
    assert jnp.array_equal(out_e, out_f), (
        "fused vs einsum greedy tokens diverged on the delta artifact"
    )
    print(f"serving delta artifact: {eng_f.compression}")
    print("fused vs einsum greedy tokens identical on the delta artifact")


if __name__ == "__main__":
    main()
