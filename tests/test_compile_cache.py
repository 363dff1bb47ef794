"""Where compiled programs are cached (``repro.launch.cache``).

Each case runs in its own process: jax's persistent cache is set up once
per process, at the first compile.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

_PROBE = textwrap.dedent("""
    import json, os
    import jax, jax.numpy as jnp
    from repro.launch.cache import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.arange(7.0)).block_until_ready()
    print("RESULT::" + json.dumps({
        "returned": path,
        "configured": jax.config.jax_compilation_cache_dir,
        "files": sorted(os.listdir(path)),
    }))
""")


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                          text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")][0]
    return json.loads(line[len("RESULT::"):])


@pytest.mark.parametrize("from_env", [True, False])
def test_compiled_programs_land_in_the_cache_dir(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the
    cache is ``.jax_cache/`` at the checkout root, a fixed path."""
    expected = tmp_path / "cache" if from_env else ROOT / ".jax_cache"
    got = _probe(expected if from_env else None)
    assert got["returned"] == str(expected)
    assert got["configured"] == str(expected)
    assert got["files"], "no compiled program was written"
