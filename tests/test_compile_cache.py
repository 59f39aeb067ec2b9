"""The persistent compilation cache is placed from outside or at one fixed
path inside the checkout — never at a temporary, per-process name."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

# one small compile, as an entry point would make it; prints the cache's
# directory and the hit/miss events this process saw
_PROBE = r"""
import json
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
events = {"hits": 0, "misses": 0}
def listen(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        events["misses"] += 1
jax.monitoring.register_event_listener(listen)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones((4, 4))).block_until_ready()
print("PROBE:" + json.dumps({"path": path, **events}))
"""


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env", [None, "outside"])
def test_enable_compile_cache_places_cache(env, tmp_path, monkeypatch,
                                           restore_cache_dir):
    if env is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(compile_cache.DEFAULT_DIR)
    else:
        want = str(tmp_path / env)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the default sits at the repository root, next to src/
    assert os.path.isdir(compile_cache.DEFAULT_DIR.parent / "src" / "repro")


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_second_process_hits_cache_placed_from_outside(tmp_path):
    """With the variable set, entries land in that directory and nowhere
    else, and a second process finds them there."""
    cache = tmp_path / "outside"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               **{compile_cache.ENV_VAR: str(cache)})
    env["PYTHONPATH"] = os.pathsep.join(
        [str(compile_cache.DEFAULT_DIR.parent / "src"),
         env.get("PYTHONPATH", "")])
    default_before = _listing(compile_cache.DEFAULT_DIR)
    runs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        line = next(l for l in r.stdout.splitlines()
                    if l.startswith("PROBE:"))
        runs.append(json.loads(line[len("PROBE:"):]))
    assert [run["path"] for run in runs] == [str(cache)] * 2
    assert runs[0]["misses"] > 0 and runs[0]["hits"] == 0
    assert runs[1]["hits"] > 0 and runs[1]["misses"] == 0
    assert _listing(cache)
    assert _listing(compile_cache.DEFAULT_DIR) == default_before
