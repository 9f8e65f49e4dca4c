"""Where the persistent compilation cache lands (``repro.compile_cache``):
in ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else in the fixed
``.jax_cache/`` at the root of the checkout — never a temp directory."""

import os
import subprocess
import sys
import textwrap
import uuid

import pytest

from repro.compile_cache import CACHE_DIR_ENV, DEFAULT_CACHE_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# compiles one program of a shape drawn per run, so its cache entry is new
# in whichever directory the helper picked
_COMPILE = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(enable_compile_cache())
    n = int(sys.argv[1])
    jax.jit(lambda x: jnp.sin(x) * n).lower(jnp.ones((n, 3))).compile()
""")


def _entries(path) -> set:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_dir(tmp_path, env_set):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop(CACHE_DIR_ENV, None)
    want = (str(tmp_path / "cache") if env_set
            else os.path.join(ROOT, ".jax_cache"))
    assert str(DEFAULT_CACHE_DIR) == os.path.join(ROOT, ".jax_cache")
    if env_set:
        env[CACHE_DIR_ENV] = want
    before = _entries(want)
    shape = str(1000 + uuid.uuid4().int % 100_000)   # a program new to the cache
    out = subprocess.run(
        [sys.executable, "-c", _COMPILE, shape], capture_output=True,
        text=True, timeout=300, env=env, cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == want
    assert _entries(want) - before, f"no cache entry written to {want}"
