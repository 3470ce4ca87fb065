"""Where both device paths keep JAX's persistent compilation cache:
``JAX_COMPILATION_CACHE_DIR`` when it is set (and nothing set in code),
else ``<repo>/.jax_cache``. Each case runs in a fresh interpreter, since
JAX reads the variable once, at import."""
import os
import subprocess
import sys

from repro.compile_cache import CACHE_DIR

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

PROG = """
import jax, numpy as np
from repro.mapreduce import JOBS, corpus, local_mapreduce
from repro.sweep import vmap_fill as vf
vf.batched_fill([{"links": [["wan", 0, 10.0]],
                  "classes": [{"n": 2, "cap": 3.0, "path": [["wan", 0]],
                               "vdone": 0.0, "target": 8.0}]}])
tok, lng = corpus("non-web", 256, seed=1)
jax.block_until_ready(local_mapreduce(JOBS["WC"], tok, lng))
print(jax.config.jax_compilation_cache_dir)
"""


def _run(prog, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300,
                         env=dict(base, PYTHONPATH=SRC, **env))
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_env_dir_receives_both_paths_entries(tmp_path):
    cache = str(tmp_path / "xla")
    # the threshold comes from the environment too: these compiles are
    # quicker than JAX's default minimum of one second
    seen = _run(PROG, JAX_COMPILATION_CACHE_DIR=cache,
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert seen == cache
    entries = os.listdir(cache)
    assert len(entries) >= 2, entries


def test_repo_dir_when_env_unset():
    seen = _run("import jax\n"
                "from repro.compile_cache import enable_compile_cache\n"
                "enable_compile_cache()\n"
                "print(jax.config.jax_compilation_cache_dir)\n")
    assert seen == CACHE_DIR
    assert os.path.basename(CACHE_DIR) == ".jax_cache"
    assert os.path.dirname(CACHE_DIR) == os.path.dirname(SRC)
