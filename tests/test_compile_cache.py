"""The persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
set, else the fixed ``<repo>/.jax_cache`` (launch/compile_cache.py).
Each case runs in a subprocess, so the cache setting never leaks into
the test process."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache

    where = use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == where, where
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def compile_cache_probe(x):
        return jnp.sin(x) * float(sys.argv[1])

    jax.jit(compile_cache_probe)(jnp.ones(3)).block_until_ready()
    print("CACHE_DIR=" + where)
""")


def _run(env_dir, tag):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, tag], env=env,
                          cwd=REPO_ROOT, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.split("CACHE_DIR=")[-1].strip()


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_lands_where_configured(tmp_path, from_env):
    want = str(tmp_path / "jax_cache") if from_env else \
        os.path.join(REPO_ROOT, ".jax_cache")
    tag = f"{os.getpid()}.{int(from_env)}{len(str(tmp_path))}"
    got = _run(want if from_env else None, tag)
    assert os.path.realpath(got) == os.path.realpath(want)
    entries = os.listdir(want)
    assert any(e.startswith("jit_compile_cache_probe") for e in entries), \
        entries
