"""The one compile-cache helper every entry point uses
(`utils.compile_cache.configure_compile_cache`): placed from outside when
``JAX_COMPILATION_CACHE_DIR`` is set — no directory is set in code — and at
one fixed, git-ignored directory inside the checkout otherwise."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import jax;"
    "from pytorch_ps_mpi_tpu.utils import compile_cache as cc;"
    "calls=[];"
    "orig=jax.config.update;"
    "jax.config.update=lambda k,v: (calls.append(k), orig(k,v))[1];"
    "d=cc.configure_compile_cache();"
    "print(d); print(jax.config.jax_compilation_cache_dir); print(calls)")


# What a program is keyed by is set wherever the cache lies: the scope names
# are in the key, the Python frames are not (`KEYED_WITH_SCOPES`).
KEY_POLICY = ("['jax_compilation_cache_include_metadata_in_key', "
              "'jax_traceback_in_locations_limit']")


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO, env=env,
                         capture_output=True, text=True, check=True,
                         timeout=120).stdout.strip().splitlines()
    return out[-3], out[-2], out[-1]


def test_env_set_means_nothing_is_set_in_code(tmp_path):
    want = str(tmp_path / "outside")
    returned, in_effect, calls = _run(want)
    assert returned == want and in_effect == want
    assert calls == KEY_POLICY          # jax read the env's directory itself


def test_env_unset_means_the_fixed_in_checkout_directory():
    returned, in_effect, calls = _run(None)
    fixed = os.path.join(REPO, ".jax_cache")
    assert returned == fixed and in_effect == fixed
    assert calls == KEY_POLICY[:-1] + ", 'jax_compilation_cache_dir']"
    # git would not commit it.
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_entry_point_names_another_cache_directory():
    """``/tmp`` caches, per-benchmark cache variables and in-code paths are
    gone: the helper is the only place the option is set."""
    offenders = []
    for root, _dirs, files in os.walk(REPO):
        if any(part in root for part in (".git", ".jax_cache", "chiprun_out",
                                         ".smoke_checkout", "__pycache__")):
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            if path.endswith(os.path.join("utils", "compile_cache.py")) \
                    or path == os.path.abspath(__file__):
                continue
            with open(path) as f:
                text = f.read()
            if "jax_compilation_cache_dir" in text \
                    or "_JAX_CACHE" in text:
                offenders.append(os.path.relpath(path, REPO))
    assert offenders == []
