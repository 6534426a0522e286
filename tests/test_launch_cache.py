"""``enable_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` wins and nothing
is set in code; otherwise a fixed directory is used, and a second process
reads what the first one wrote.  Each case is a fresh interpreter, since
the cache is process-wide jax config."""
import json
import os
import subprocess
import sys

from conftest import SRC

RUN = """
import json, sys
import jax, jax.numpy as jnp
from repro.launch import cache
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e) if e.endswith("cache_hits") else None)
if len(sys.argv) > 1:
    cache.CACHE_DIR = cache.Path(sys.argv[1])
where = cache.enable_compile_cache()
jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)(jnp.ones((16,))).block_until_ready()
print(json.dumps({"where": where, "hits": len(hits),
                  "dir": jax.config.jax_compilation_cache_dir}))
"""


def _run(*args, env_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:        # jax's own settings, read from the env
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    r = subprocess.run([sys.executable, "-c", RUN, *map(str, args)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_fixed_dir_is_written_then_read(tmp_path):
    d = tmp_path / "cache"
    first = _run(d)
    assert first["where"] == first["dir"] == str(d)
    assert first["hits"] == 0 and any(d.iterdir())
    assert _run(d)["hits"] >= 1                  # the second run reads it


def test_env_dir_wins_and_nothing_is_set(tmp_path):
    env_dir, fixed = tmp_path / "env", tmp_path / "fixed"
    out = _run(fixed, env_dir=env_dir)
    assert out["where"] == out["dir"] == str(env_dir)
    assert any(env_dir.iterdir()) and not fixed.exists()
