"""Where ``repro.compile_cache.enable`` puts JAX's persistent cache.

Each case runs in a fresh interpreter: the cache location is process-wide
JAX state, and the suite itself must keep JAX's defaults.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = textwrap.dedent("""
    import json, jax, jax.numpy as jnp
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    used = compile_cache.enable()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
    print(json.dumps({
        "before": before, "used": used,
        "config": jax.config.jax_compilation_cache_dir,
        "min_s": jax.config.jax_persistent_cache_min_compile_time_secs,
        "default": str(compile_cache.DEFAULT_DIR)}))
""")


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_environment_directory_stands_and_receives_entries(tmp_path):
    cache = str(tmp_path / "cache")
    got = _probe(cache)
    assert got["before"] == got["used"] == got["config"] == cache
    assert got["min_s"] == 0
    assert os.listdir(cache), "nothing was cached in the environment's dir"


def test_default_is_the_checkouts_fixed_directory():
    got = _probe(None)
    assert got["before"] is None
    assert got["used"] == got["config"] == got["default"] \
        == os.path.join(REPO, ".jax_cache")
    assert got["min_s"] == 0
