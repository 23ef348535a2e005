"""Test harness: 8 virtual CPU devices — the ``mpirun -n N`` analogue.

The reference runs its whole suite SPMD under ``mpirun -n 2``
(`/root/reference/Makefile:2-3`), simulating multi-node with local ranks.  We
simulate a TPU mesh with ``--xla_force_host_platform_device_count=8`` CPU
devices; real collectives rendezvous across them inside jitted SPMD programs.

Must run before jax initializes its backends.  The CPU platform is named
here (``jax_platforms=cpu``) on purpose: the library refuses a CPU it was
not told to use (`parallel.mesh.default_devices`).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)

# The transport's byte-sentinel sanitizer rides the whole tier-1 lane
# (flow/failover/hierarchy suites and every spawned CLI subprocess,
# which inherits the env): each parked data frame's checksum is
# re-verified at flush, so any buffer-ownership regression — a caller
# reusing a handed-off buffer, a park that stopped copying — trips a
# typed BufferMutatedError in the suite that exercises it instead of
# silently corrupting gradients (ISSUE 12).
os.environ.setdefault("PS_BUFFER_SENTINEL", "1")
# The race sanitizer rides the same lane (ISSUE 20): every Session's
# ``# pslint: holds(_lock)`` helper probes that the calling thread
# actually holds the session lock, so a lock-discipline regression in
# the threaded data plane trips a typed RaceDetectedError in whichever
# suite exercises the broken interleaving — the dynamic complement of
# pslint's static PSL8xx lockset pass.  Inherited by CLI subprocesses.
os.environ.setdefault("PS_RACE_SANITIZER", "1")
# Persistent compilation cache: the suite's wall-clock is dominated by XLA
# compiles of the many (mesh, feature-combo) step programs, most of which
# are identical run-to-run, and min_compile_time 0 caches even fast
# compiles — there are hundreds of them.  The thresholds ride the ENV so
# the worker/server subprocesses the suite spawns (multihost TCP tests,
# CLI round-trips) inherit them.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

from pytorch_ps_mpi_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

# The directory comes from the one helper every entry point uses (the
# environment's JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout
# path); exported so spawned children that never reach `train.main` (the
# ``-c`` worker scripts) share it instead of compiling from scratch.
os.environ["JAX_COMPILATION_CACHE_DIR"] = configure_compile_cache()

import pytest  # noqa: E402


def _surviving_worker_children() -> "list[tuple[int, str]]":
    """Live child processes of this test process that look like spawned
    PS/worker subprocesses (multihost TCP workers, --serve/--connect CLI
    roles).  Zombies are excluded automatically: an exited-but-unreaped
    process has an empty /proc cmdline, so it can't match the markers."""
    me = os.getpid()
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        pid = int(entry)
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
            ppid = int(stat.rsplit(")", 1)[1].split()[1])
            if ppid != me:
                continue
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except (OSError, ValueError, IndexError):
            continue
        if ("AsyncPSWorker" in cmd or "--connect" in cmd
                or "--serve" in cmd):
            found.append((pid, cmd[:140]))
    return found


@pytest.fixture(autouse=True)
def no_leftover_workers():
    """Every test must reap the worker processes it spawned (a bench run once
    observed a survivor).  Runs after each test: any still-live spawned
    worker/server child fails the test — after being killed, so one leak
    can't cascade into later tests' process accounting."""
    yield
    import signal
    import time as _time

    deadline = _time.monotonic() + 5.0  # grace for natural post-DONE exit
    left = _surviving_worker_children()
    while left and _time.monotonic() < deadline:
        _time.sleep(0.2)
        left = _surviving_worker_children()
    if left:
        for pid, _ in left:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        pytest.fail(f"leftover worker processes survived the test "
                    f"(killed now): {left}")


@pytest.fixture(scope="session")
def mesh8():
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    return make_ps_mesh(8)


@pytest.fixture(scope="session")
def mesh2():
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    return make_ps_mesh(2)
