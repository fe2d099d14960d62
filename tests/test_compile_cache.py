"""Where the persistent XLA compile cache lives (``gofr_tpu.config.env``).

One rule, so that warm-up compiles amortize across processes and a
driver can place the cache: ``JAX_COMPILATION_CACHE_DIR`` if the
environment sets it (JAX reads it itself; the code sets no directory),
else the fixed ``<checkout>/.jax_cache``. Children are real processes:
the rule is about what a fresh process does."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from gofr_tpu.config import env as cfg_env

REPO = Path(__file__).resolve().parent.parent

#: builds an engine (the call site that matters), compiles one probe
#: whose cache entries are recognisable by name, and reports what the
#: process ended up with. ``jax.config.update`` is wrapped to record
#: every option the CODE sets.
_CHILD = """
import json, os
import jax
import jax.numpy as jnp
updated = []
real_update = jax.config.update
jax.config.update = lambda k, v: (updated.append(k), real_update(k, v))[1]
from gofr_tpu.config.env import enable_compile_cache
from gofr_tpu.serving.engine import EngineConfig
from gofr_tpu.serving.glue import demo_llama_engine
demo_llama_engine(EngineConfig(max_batch=2, max_seq=64))
path = enable_compile_cache()
def gofr_cache_probe(x):
    return (x @ x + jnp.float32(3)).sum()
jax.jit(gofr_cache_probe)(jnp.ones((32, 32), jnp.float32)).block_until_ready()
print("RESULT " + json.dumps({
    "path": path, "config": jax.config.jax_compilation_cache_dir,
    "updated": updated,
    "probe_entries": sorted(n for n in os.listdir(path)
                            if n.startswith("jit_gofr_cache_probe"))}))
"""


def _child(env_dir=None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    p = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    line = [ln for ln in p.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_env_set_code_sets_no_directory(tmp_path):
    """The driver's variable wins and the code keeps its hands off:
    no ``config.update`` of the directory on that branch, only the two
    thresholds — and entries land where the variable says."""
    got = _child(env_dir=tmp_path)
    assert got["path"] == got["config"] == str(tmp_path)
    assert "jax_compilation_cache_dir" not in got["updated"]
    assert {"jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs"} \
        <= set(got["updated"])
    assert got["probe_entries"]


def test_env_unset_fixed_directory_shared_by_processes():
    """No variable: the fixed ``<checkout>/.jax_cache`` — the same in
    every process, so the second child finds the first one's entries
    and adds none."""
    first, second = _child(), _child()
    for got in (first, second):
        assert got["path"] == got["config"] == str(REPO / ".jax_cache")
    assert first["probe_entries"]
    assert second["probe_entries"] == first["probe_entries"]


def test_default_directory_is_set_in_one_place_and_ignored():
    """The path is derived from the package's location, never from a
    home directory, a temp name, a pid or a clock; the option is set in
    exactly one place in the package; git ignores the directory."""
    assert cfg_env.DEFAULT_COMPILE_CACHE_DIR == str(REPO / ".jax_cache")
    sites = [str(p.relative_to(REPO))
             for p in (REPO / "gofr_tpu").rglob("*.py")
             if re.search(r"update\(\s*[\"']jax_compilation_cache_dir",
                          p.read_text())]
    assert sites == ["gofr_tpu/config/env.py"]
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_unwritable_default_directory_raises(tmp_path, monkeypatch):
    """A cache that is silently off re-pays every compile in every
    process: a default directory that cannot be created is an error
    that names the way out, not a quiet no-op."""
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cfg_env, "_enabled_dir", None)
    monkeypatch.setattr(cfg_env, "DEFAULT_COMPILE_CACHE_DIR",
                        str(blocker / "cache"))
    with pytest.raises(OSError, match="JAX_COMPILATION_CACHE_DIR"):
        cfg_env.enable_compile_cache()
