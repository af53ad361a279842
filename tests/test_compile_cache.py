"""torchft_tpu.utils.compile_cache: the persistent compile cache is placed
from outside — JAX_COMPILATION_CACHE_DIR wins and no directory is set in
code; otherwise one fixed path inside the checkout, the same for every call
and every process (the path is part of the cache lookup: a directory that
moves never hits)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, jax
from torchft_tpu.utils.compile_cache import enable_compile_cache
before = jax.config.jax_compilation_cache_dir
first, second = enable_compile_cache(), enable_compile_cache()
print(json.dumps({
    "before": before, "first": first, "second": second,
    "dir": jax.config.jax_compilation_cache_dir,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
}))
"""


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, cwd=REPO, check=True,
        capture_output=True, text=True, timeout=120,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_env_var_wins_and_code_sets_no_directory(tmp_path):
    got = _probe(str(tmp_path))
    assert got["first"] is None and got["second"] is None
    # JAX read the variable itself; the helper left the directory alone
    assert got["before"] == got["dir"] == str(tmp_path)
    assert got["min_secs"] == 0.0


def test_unset_gives_one_fixed_path_in_the_checkout():
    a, b = _probe(None), _probe(None)
    expected = os.path.join(REPO, ".jax_cache")
    assert a["before"] is None
    assert a["first"] == a["second"] == a["dir"] == expected
    assert b["dir"] == expected  # a second process lands on the same path
    assert a["min_secs"] == 0.0


def test_cache_path_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
