"""Where JAX's persistent compilation cache lives — decided in one place.

Every entry point that compiles (`train.main`, ``chip_smoke.py`` phases,
``perfbench/run.py``, ``tests/conftest.py``) calls
`configure_compile_cache` once, before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX already uses
  it, and no directory is set in code — whoever runs the program owns the
  placement (a machine that keeps that directory between runs gets warm
  starts).
* unset: one fixed directory inside the checkout (`CACHE_DIR`,
  git-ignored).  Fixed because the path is part of the cache key — a
  directory named after a pid, a timestamp or a fresh ``/tmp`` entry never
  hits.

And what a program is keyed by, wherever the cache lies
(`KEYED_WITH_SCOPES`): by default JAX strips scope names, file names and
line numbers before it hashes a program, so a program that differs from a
cached one in its `jax.named_scope`s alone is a cache hit, and what comes
back is the *other* program's executable with the other program's
``op_name``s.  This repo reads the scopes out of the compiled text
(`utils.timing.program_scopes`: a checkout with new scopes read none of them
after its parent had filled the cache), so the names go into the key.  The
Python frames under them do not (no traceback in the locations): with the
call stack in the key a step called from another line of the caller's code,
or from a checkout at another path, would compile again; with the names alone
a program compiles again exactly when its operations or their scopes change.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

KEYED_WITH_SCOPES = {
    "jax_compilation_cache_include_metadata_in_key": True,
    "jax_traceback_in_locations_limit": 0,
}


def configure_compile_cache() -> str:
    """Apply the policy above; returns the directory in effect."""
    for name, value in KEYED_WITH_SCOPES.items():
        jax.config.update(name, value)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


class CacheCounter:
    """Counts this process's persistent-cache hits and misses (JAX's own
    monitoring events), so a run can show that a program compiled by an
    earlier process was found again."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
