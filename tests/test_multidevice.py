"""Multi-device behaviour (sharded training, elastic restore, dry-run cell)
via subprocesses — XLA device count is locked at first jax init, so these
must not pollute the main test process (tests see 1 real CPU device)."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.slow
def test_sharded_training_loss_decreases_and_elastic_restore(tmp_path):
    out = run_py(f"""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced_for_smoke
        from repro.configs.base import ParallelConfig, ShapeConfig
        from repro.training import init_train_state, make_train_step, state_shardings
        from repro.distributed.sharding import activation_rules
        from repro.data.pipeline import make_pipeline
        from repro.optim import warmup_cosine
        from repro.checkpoint.manager import CheckpointManager

        mesh = make_mesh((2,4), ("data","model"))
        cfg = reduced_for_smoke(get_config("qwen3-32b"))
        pcfg = ParallelConfig(mesh_shape=(2,4), mesh_axes=("data","model"), microbatches=2)
        shape = ShapeConfig("tiny", "train", 64, 8)
        state = init_train_state(jax.random.PRNGKey(0), cfg, pcfg, mesh)
        sh = state_shardings(cfg, pcfg, mesh)
        step_fn = make_train_step(cfg, pcfg, warmup_cosine(1e-3, 10, 100))
        pipe = make_pipeline(cfg, shape, mesh)
        with jax.set_mesh(mesh), activation_rules(pcfg, mesh):
            jstep = jax.jit(step_fn, in_shardings=(sh, None), out_shardings=(sh, None), donate_argnums=0)
            losses = []
            for i in range(8):
                state, m = jstep(state, pipe.batch_at(i))
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

        mgr = CheckpointManager(r"{tmp_path}", keep_last=2)
        mgr.save(int(state.step), state); mgr.wait()
        mesh2 = make_mesh((4,2), ("data","model"))
        sh2 = state_shardings(cfg, pcfg, mesh2)
        step2, restored = mgr.restore_latest(state, sh2)
        ok = jax.tree.all(jax.tree.map(
            lambda a,b: bool(jnp.allclose(a.astype(jnp.float32), b.astype(jnp.float32))),
            state.params, restored.params))
        assert step2 == 8 and ok
        print("ELASTIC_OK", losses[0], losses[-1])
    """)
    assert "ELASTIC_OK" in out


@pytest.mark.slow
def test_microbatch_accumulation_equivalence():
    """micro=2 and micro=1 produce (numerically close) identical updates."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config, reduced_for_smoke
        from repro.configs.base import ParallelConfig, ShapeConfig
        from repro.training import init_train_state, make_train_step, state_shardings
        from repro.distributed.sharding import activation_rules
        from repro.data.pipeline import make_pipeline
        from repro.optim import constant

        mesh = make_mesh((2,2), ("data","model"))
        cfg = reduced_for_smoke(get_config("mistral-nemo-12b"))
        shape = ShapeConfig("tiny", "train", 32, 8)
        outs = {}
        for micro in (1, 2):
            pcfg = ParallelConfig(mesh_shape=(2,2), mesh_axes=("data","model"), microbatches=micro)
            state = init_train_state(jax.random.PRNGKey(0), cfg, pcfg, mesh)
            sh = state_shardings(cfg, pcfg, mesh)
            fn = make_train_step(cfg, pcfg, constant(1e-3))
            pipe = make_pipeline(cfg, shape, mesh)
            with jax.set_mesh(mesh), activation_rules(pcfg, mesh):
                jstep = jax.jit(fn, in_shardings=(sh, None), out_shardings=(sh, None))
                state, m = jstep(state, pipe.batch_at(0))
            outs[micro] = (float(m["loss"]), state.params)
        l1, p1 = outs[1]; l2, p2 = outs[2]
        assert abs(l1 - l2) < 5e-2, (l1, l2)
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
        md = max(jax.tree.leaves(diffs))
        assert md < 5e-2, md
        print("MICRO_OK", l1, l2, md)
    """)
    assert "MICRO_OK" in out


@pytest.mark.slow
def test_injected_failure_restart_cli(tmp_path):
    """launch.train with --fail-at-step recovers via the supervisor and
    resumes from the checkpoint."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # A persistent compilation cache of its own: the in-process re-jit
    # after the restart (donated buffers) runs against a warm cache.
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "granite-moe-1b-a400m", "--reduced",
         "--steps", "6", "--seq-len", "32", "--batch", "4",
         "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
         "--fail-at-step", "4", "--log-every", "2"],
        capture_output=True, text=True, timeout=560, env=env, cwd=ROOT,
    )
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    assert "injected failure" in out.stdout + out.stderr or "restarting" in out.stdout
    assert "[resume] from step" in out.stdout
    assert "done at step 6" in out.stdout
