"""TPU-native parameter-server training framework.

Public API mirrors the reference (`/root/reference/__init__.py:1`:
``from .ps import MPI_PS, Adam, SGD``) — a PS-style optimizer constructed from
named parameters, with SGD and Adam variants whose update rules match the
reference's math exactly (`/root/reference/ps.py:195-261`), re-designed
TPU-first: gradient sync is a static-shape XLA collective over an ICI mesh
inside one jitted SPMD step, not host-side MPI (plus an AdamW extension).
"""

from .ps import (MPI_PS, PS, SGD, Adam, AdamW, ElasticResumeError,
                 SDCDetectedError)
from .async_ps import AsyncPS, AsyncSGD, AsyncAdam
from .multihost_async import (AsyncPSServer, AsyncSGDServer,
                              AsyncAdamServer, AsyncPSWorker)
from .shard import (PSFleet, ShardPlan, ShardRouter, build_shard_plan,
                    match_partition_rules)
from .serve import (FleetSubscriber, InferenceFrontend, InferRequest,
                    Subscriber)
from .parallel.mesh import make_ps_mesh
from .ops.codecs import (Codec, IdentityCodec, CastCodec, TopKCodec,
                         QuantizeCodec, BlockQuantizeCodec, SignCodec)
from .utils import checkpoint
from .utils.checkpoint import CheckpointError
from .utils.faults import FaultPlan, SimulatedCrash
from .errors import (PSRuntimeError, NotCompiledError, WorkerFailedError,
                     FleetDeadError, FillStarvedError, NativeToolchainError,
                     AggregatorDeadError, ShardDeadError,
                     BufferMutatedError, TorchUnavailableError,
                     InferShedError, SnapshotRewindError)

__version__ = "0.1.0"

__all__ = [
    "MPI_PS",
    "PS",
    "SGD",
    "Adam",
    "AdamW",
    "AsyncPS",
    "AsyncSGD",
    "AsyncAdam",
    "AsyncPSServer",
    "AsyncSGDServer",
    "AsyncAdamServer",
    "AsyncPSWorker",
    "PSFleet",
    "ShardPlan",
    "ShardRouter",
    "build_shard_plan",
    "match_partition_rules",
    "make_ps_mesh",
    "Codec",
    "IdentityCodec",
    "CastCodec",
    "TopKCodec",
    "QuantizeCodec",
    "BlockQuantizeCodec",
    "SignCodec",
    "checkpoint",
    "CheckpointError",
    "ElasticResumeError",
    "SDCDetectedError",
    "FaultPlan",
    "SimulatedCrash",
    "PSRuntimeError",
    "NotCompiledError",
    "WorkerFailedError",
    "FleetDeadError",
    "FillStarvedError",
    "AggregatorDeadError",
    "ShardDeadError",
    "NativeToolchainError",
    "BufferMutatedError",
    "TorchUnavailableError",
    "InferShedError",
    "SnapshotRewindError",
    "Subscriber",
    "FleetSubscriber",
    "InferenceFrontend",
    "InferRequest",
]
