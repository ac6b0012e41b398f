"""Persistent compile cache placement (``repro.common.compile_cache``):
``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set; otherwise an
accelerator's programs land at one fixed, git-ignored path inside the
checkout, and CPU programs are not cached.

Each case runs in a child process: the cache directory is process-global
JAX state, fixed at the first compile.
"""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"),
               **env_extra)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.strip().splitlines()[-1]


def test_env_var_places_the_cache(tmp_path):
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.common import compile_cache
        path = compile_cache.enable()
        assert path == jax.config.jax_compilation_cache_dir, path
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
        print(path)
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_accelerator_cache_is_the_fixed_checkout_path():
    out = _run("""
        import jax
        from repro.common import compile_cache
        assert compile_cache.enable() is None  # CPU: not cached
        assert jax.config.jax_compilation_cache_dir is None
        jax.default_backend = lambda: "tpu"  # what an accelerator host sees
        print(compile_cache.enable(), jax.config.jax_compilation_cache_dir)
    """)
    want = os.path.join(ROOT, ".jax_cache")
    assert out == f"{want} {want}"
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
