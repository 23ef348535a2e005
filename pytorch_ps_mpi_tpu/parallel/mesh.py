"""Device-mesh construction — the TPU replacement for the reference's process group.

The reference binds ``MPI.COMM_WORLD`` plus ``rank``/``size`` at import time
(`/root/reference/mpi_comms.py:11-13`) and every collective rides that world
communicator. Here the "world" is a `jax.sharding.Mesh` over the local (or
pod-wide) device set, and "rank"/"size" become the per-shard axis index/size
inside `shard_map` (``jax.lax.axis_index`` / ``jax.lax.axis_size``).

Unlike MPI, mesh construction is explicit and cheap; nothing is captured at
import time, so tests can build meshes of any size over virtual devices.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..errors import NoAcceleratorError

# Canonical axis name for the data-parallel PS "world" axis.
PS_AXIS = "ps"


def default_devices() -> list:
    """``jax.devices()``, minus JAX's silent fall-back to the host: when no
    accelerator answers, JAX logs an error and hands back one CPU device,
    and a run built on it trains, prints a loss and measures nothing.  The
    CPU is a valid world only when named (``JAX_PLATFORMS=cpu``, or
    ``jax.config.update("jax_platforms", "cpu")`` — what the tests and
    ``train.py --force-cpu-devices`` do); otherwise a missing chip is a
    `NoAcceleratorError`."""
    devices = jax.devices()
    named = (jax.config.jax_platforms or "").split(",")
    if devices[0].platform == "cpu" and "cpu" not in named:
        raise NoAcceleratorError(
            "JAX found no accelerator and fell back to the CPU; set "
            "JAX_PLATFORMS=cpu (or pass --force-cpu-devices N to train.py) "
            "to run on the host on purpose")
    return devices


def make_ps_mesh(n_devices: int | None = None, *, axis: str = PS_AXIS,
                 devices=None) -> Mesh:
    """Build a 1-D mesh over ``n_devices`` devices with a single PS axis.

    This is the moral equivalent of launching under ``mpirun -n N``
    (`/root/reference/Makefile:3`): it fixes the SPMD world size. Defaults to
    all visible devices.
    """
    if devices is None:
        devices = default_devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices > len(devices):
        raise ValueError(
            f"requested {n_devices} devices but only {len(devices)} visible")
    return jax.make_mesh((n_devices,), (axis,), devices=devices[:n_devices])


def _make_dp_x_mesh(axis2: str, dp: int | None, k: int, devices) -> Mesh:
    """Shared builder for the 2-D ``(ps, <axis2>)`` meshes: validate the
    inner degree, default ``dp`` to whatever fills the device set, and
    range-check the product."""
    if devices is None:
        devices = default_devices()
    if k < 1:
        raise ValueError(f"{axis2} must be >= 1, got {k}")
    if dp is None:
        dp = len(devices) // k
    n = dp * k
    if n > len(devices) or n < 1:
        raise ValueError(
            f"dp*{axis2} = {dp}*{k} = {n} needs {n} devices, "
            f"have {len(devices)}")
    return jax.make_mesh((dp, k), (PS_AXIS, axis2), devices=devices[:n])


def make_dp_sp_mesh(dp: int | None = None, sp: int = 1, *,
                    devices=None) -> Mesh:
    """2-D ``(ps, sp)`` mesh: data parallelism × sequence parallelism.

    The reference scales only the batch axis (SURVEY §2); ``sp`` adds the
    long-context dimension — attention sequence shards ride `ring_attention`
    ppermute hops over the inner (fast-ICI) mesh axis while gradient sync
    psums over both axes.  ``dp`` defaults to ``len(devices) // sp``.
    """
    return _make_dp_x_mesh("sp", dp, sp, devices)


def make_dp_tp_mesh(dp: int | None = None, tp: int = 1, *,
                    devices=None) -> Mesh:
    """2-D ``(ps, tp)`` mesh: data parallelism × tensor parallelism.

    tp shards transformer *compute* Megatron-style (see
    `models.transformer`); gradients still SUM over ``ps`` only — pass
    ``axis='ps', batch_spec=P('ps')`` to `MPI_PS` (its defaults), tp rides
    along as an extra (averaged) axis.
    """
    return _make_dp_x_mesh("tp", dp, tp, devices)


def make_dp_ep_mesh(dp: int | None = None, ep: int = 1, *,
                    devices=None) -> Mesh:
    """2-D ``(ps, ep)`` mesh: data parallelism × expert parallelism.

    Both axes are **data** axes (tokens shard over ep; the MoE layer's
    all_to_all carries tokens to their expert's rank) — pass
    ``axis=('ps', 'ep')`` and ``batch_spec=P(('ps', 'ep'))`` to `MPI_PS` so
    the gradient sum spans both.
    """
    return _make_dp_x_mesh("ep", dp, ep, devices)


def make_dp_pp_mesh(dp: int | None = None, pp: int = 1, *,
                    devices=None) -> Mesh:
    """2-D ``(ps, pp)`` mesh: data parallelism × pipeline parallelism.

    pp shards transformer *depth* (`parallel.pipeline`): each pp rank runs a
    contiguous block of layers and activations ppermute around the ring.
    Like tp it is a model axis — gradients still SUM over ``ps`` only (the
    `MPI_PS` defaults) — so pass ``batch_spec=P('ps')``.
    """
    return _make_dp_x_mesh("pp", dp, pp, devices)


def make_dp_sp_tp_mesh(dp: int, sp: int, tp: int, *, devices=None) -> Mesh:
    """3-D ``(ps, sp, tp)`` mesh: data × sequence × tensor parallelism,
    composed.  Batch shards over (ps, sp); heads/MLP compute shards over tp;
    gradient sum over ps, mean over sp and tp."""
    if devices is None:
        devices = default_devices()
    n = dp * sp * tp
    if n > len(devices) or min(dp, sp, tp) < 1:
        raise ValueError(
            f"dp*sp*tp = {dp}*{sp}*{tp} = {n} needs {n} devices, "
            f"have {len(devices)}")
    return jax.make_mesh((dp, sp, tp), (PS_AXIS, "sp", "tp"),
                         devices=devices[:n])


def make_dp_pp_tp_mesh(dp: int, pp: int, tp: int, *, devices=None) -> Mesh:
    """3-D ``(ps, pp, tp)`` mesh: data × pipeline × tensor parallelism.
    Batch shards over ps; depth over the pp ring; heads/MLP over tp."""
    if devices is None:
        devices = default_devices()
    n = dp * pp * tp
    if n > len(devices) or min(dp, pp, tp) < 1:
        raise ValueError(
            f"dp*pp*tp = {dp}*{pp}*{tp} needs at least "
            f"{max(n, pp * tp)} devices, have {len(devices)}")
    return jax.make_mesh((dp, pp, tp), (PS_AXIS, "pp", "tp"),
                         devices=devices[:n])


DCN_AXIS = "dcn"


def make_hybrid_mesh(slices: int | None = None, *, axis: str = PS_AXIS,
                     devices=None) -> Mesh:
    """2-D ``(dcn, ps)`` mesh for multi-slice / multi-host data parallelism.

    The inner ``ps`` axis spans the devices of one slice (gradient psum rides
    ICI); the outer ``dcn`` axis spans slices (the cross-slice stage of the
    hierarchical all-reduce rides the data-center network).  Pass
    ``axis=('dcn', 'ps')`` to `MPI_PS` so the gradient sum covers both.

    On a single-controller/single-slice environment this still works (slices
    defaults to 1 per-process granularity) — ``slices`` mainly matters under
    `distributed_init` where ``jax.devices()`` spans processes.
    """
    if devices is None:
        devices = default_devices()
    if slices is None:
        slices = max(1, jax.process_count())
    n = len(devices)
    if n % slices != 0:
        raise ValueError(f"{n} devices do not split into {slices} slices")
    try:
        from jax.experimental import mesh_utils
    except ImportError:  # pragma: no cover - mesh_utils ships with jax
        mesh_utils = None
    if (mesh_utils is not None and slices > 1
            and jax.process_count() == slices):
        # No blanket except here: a failure in hybrid placement is a real
        # topology bug (wrong slice count, non-uniform hosts) and silently
        # falling back would hand the caller a working-but-wrong mesh whose
        # "dcn" axis actually cuts across ICI neighbours.
        dm = mesh_utils.create_hybrid_device_mesh(
            (n // slices,), (slices,), devices=devices)
        return Mesh(dm.reshape(slices, n // slices), (DCN_AXIS, axis))
    return jax.make_mesh((slices, n // slices), (DCN_AXIS, axis),
                         devices=devices)


def distributed_init(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> None:
    """Bring up the multi-host runtime — the ``mpirun`` moment for a TPU pod
    (`/root/reference/Makefile:3` analogue).  On TPU pods all three arguments
    auto-detect from the environment; afterwards ``jax.devices()`` spans every
    host and meshes built from it are pod-wide."""
    import jax.distributed
    jax.distributed.initialize(coordinator_address, num_processes, process_id)


def describe_mesh(mesh: Mesh) -> dict:
    """JSON-able topology fingerprint: axis names, per-axis sizes, device
    count and platform.  Recorded into checkpoint metadata as the SOURCE
    topology so elastic N→M resume can verify (and de-chunk against) the
    mesh a checkpoint was written on — see `MPI_PS.state_dict`."""
    return {"axis_names": list(mesh.axis_names),
            "shape": {a: int(mesh.shape[a]) for a in mesh.axis_names},
            "n_devices": int(mesh.size),
            "platform": mesh.devices.flat[0].platform}


def world_size(mesh: Mesh, axis: str = PS_AXIS) -> int:
    """The number of PS ranks — ``comm.Get_size()`` analogue."""
    return mesh.shape[axis]


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for parameters / optimizer state: replicated on every rank."""
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = PS_AXIS) -> NamedSharding:
    """Sharding for a global batch: leading dim split across PS ranks."""
    return NamedSharding(mesh, P(axis))
