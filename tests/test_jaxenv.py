"""common/jaxenv.py: where the persistent compilation cache lives.

Checked in a subprocess, because the module configures jax at import and
this process imported it long ago.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cache_dir(**extra_env) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-c",
         "import opensearch_tpu.common.jaxenv, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip()


def test_cache_dir_from_the_environment_is_left_alone():
    assert _cache_dir(JAX_COMPILATION_CACHE_DIR="/x") == "/x"


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout():
    assert _cache_dir() == os.path.join(REPO, ".jax_cache")
    # fixed: it does not move with the requested platform
    assert _cache_dir(JAX_PLATFORMS="") == os.path.join(REPO, ".jax_cache")


def test_the_old_cache_knob_is_read_nowhere():
    knob = "OSTPU_" + "XLA_CACHE"
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in (".git", "__pycache__",
                                                ".jax_cache")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path, errors="replace") as f:
                    if knob in f.read():
                        hits.append(path)
    assert hits == []
