"""Multi-host asynchronous PS — AsySG-InCon across processes/hosts.

The reference's async design is explicitly multi-node: rank 0 receives
gradients from ``MPI.ANY_SOURCE`` over the cluster network until a quota,
steps, and re-broadcasts params with inconsistent reads
(`/root/reference/README.md:56-77`).  `async_ps.AsyncPS` realizes the
algorithm within one controller (workers = local devices); this module is
the multi-HOST realization the r1 review called for: the PS is a process
serving parameters and consuming gradients over TCP (the DCN analogue of
the reference's MPI-over-ethernet transport), and each worker is an
independent process — on another host, with its own local accelerator —
that pulls params, computes grad+encode on-device, and pushes back only
the *coded* payload, serialized by the in-repo native pipeline
(`native.serializer` — the role pickle+blosc played on the reference's
wire, `/root/reference/mpi_comms.py:186-193`).

AsySG-InCon semantics survive intact (see `async_ps` for the algorithm):
the ANY_SOURCE receive is the fill loop over whichever frames arrive,
the inconsistent read is the leaf-by-leaf serving snapshot a PULL races,
and every gradient carries the param version it was computed from so
staleness stays observable end to end.

Fault tolerance (the part AsySG assumes away and the original
parameter-server work, Li et al. OSDI 2014, treats as a first-class design
constraint) is built into the transport:

* every frame carries a CRC32: a corrupted frame is a counted,
  frame-local drop — one flipped bit costs one gradient, not the
  connection;
* workers heartbeat (``BEAT``); ranks that go silent (or whose
  connections die and stay down) are **evicted** and the effective quota
  clamps to the live fleet, so a fill can always complete;
* a lost connection **reconnects with jittered exponential backoff**
  (`utils.backoff.Backoff`), re-presenting the worker's rank so the PS
  books a reconnect, not a new worker — also how survivors rejoin a
  crashed-and-restarted PS (``--resume``);
* admission control (`AsyncPS._admit`): stale-beyond-clamp and
  non-finite gradients are dropped and counted, never applied;
* the serve loop auto-checkpoints every N updates, so a killed PS
  resumes from its last snapshot via `resume_from`;
* deterministic fault injection hooks (`utils.faults.FaultPlan`) let
  tests and chaos evidence runs prove all of the above.

On a TPU pod the TCP transport can be swapped for device-to-device DMA
(`jax.experimental.transfer`) without touching the PS loop — the transport
surface is just frames in, frames out.  TCP is the honest baseline: the
reference's own transport was MPI over the machine network.

Wire protocol (all messages ``u32 length | u32 crc32(payload) | payload``
frames; a crc mismatch drops the frame, never the stream):

* worker → PS ``HELO | flags(u8) | [prior_rank(u32) if flags&1 |
  assigned_rank(u32) if flags&2] | token``
  → PS replies ``"PSA" | version(u8) | rank(u32) | auth_enforced(u8) |
  shard_index(u16) | num_shards(u16) | plan_digest(u64) |
  credit_window(u32) | wire_flags(u8) | codec_name_utf8`` (the
  magic+version prefix turns a cross-version peer into an explicit
  "incompatible protocol" error; the worker refuses a codec mismatch
  at connect time).  ``wire_flags`` bit 1 (v9) advertises the
  SEGMENTED wire: GRAD/AGGR/PARM payloads are scatter-gathered as
  ``meta_blob + per-leaf buffer frames`` iovecs (byte-identical on the
  wire to the old monolithic blob — the flag is a capability
  statement, and the v9 version byte is what refuses a v8 peer
  loudly).
  ``prior_rank`` is the reconnect path: the PS re-books the same rank
  instead of minting a new worker; ``assigned_rank`` the fleet-identity
  path (`shard.router`): shard 0 minted the rank, every other shard
  books it verbatim so per-rank accounting names the same worker
  fleet-wide.  The shard triple is trivial on an unsharded PS; a fleet
  advertises its slot + `shard.partition.ShardPlan` digest so a split
  disagreement is refused at connect time, before any gradient;
* worker → PS ``PULL | [have(u64)]`` → PS replies ``DONE`` (shut
  down) or ``PARM | version(u64) | credits(u32) | codec(u8) |
  [params_blob]`` — every pull is also a flow-control replenish.
  ``have`` (v9) makes the pull CONDITIONAL: a worker that already
  holds version ``have`` == the served version gets an EMPTY-payload
  PARM ("unchanged" — the tree frame is never empty, so the encoding
  is unambiguous) and reuses its cached params, skipping the multi-MB
  transfer + decode; all-ones ``have`` (or a bare 4-byte PULL) is
  unconditional.  ``codec`` (v12) names the WIRE codec the payload was
  encoded under (`ops.codecs.WIRE_CODEC_IDS`: 0 identity, 1 bf16,
  2 int8) — params are compressed ONCE per version in the encode-once
  cache and every reader decodes from the frame byte alone (no reader
  knob; optimizer state stays f32 server-side, only the wire is
  lossy);
* worker → PS ``GRAD | bucket(u16) | n_buckets(u16) | seq(u64) |
  version(u64) | loss(f64) | codes_blob`` (no reply); ``seq`` is this
  worker's monotone push counter — the PS drops repeats per rank
  (``fault_stats["duplicate_dropped"]``).  ``bucket``/``n_buckets``
  (v11): a whole-tree gradient is the degenerate ``(0, 1)``; a
  BUCKET-STREAMED gradient (`AsyncPSWorker(bucket_bytes=...)`) ships as
  ``n_buckets`` frames sharing one ``seq``, each carrying one bucket's
  code sub-tree, streamed as the backward pass materializes them — the
  PS assembles per ``(rank, seq)`` (any arrival order), dedups per
  ``(seq, bucket)``, and the assembled tree enters the fill loop
  exactly like a whole-tree frame.  A partial assembly (bucket shed or
  connection died mid-gradient) is retired when a newer seq from the
  same rank completes or at connection teardown (counted
  ``bucket_partial_timeouts``) — the missing gradient folds into the
  quorum/late-fold machinery like any straggler.  Flow control charges
  ONE credit per GRADIENT, not per bucket frame
  (`transport.Session.begin_data_parts`): the window meters assembled
  queue slots, and a stalled bucketed gradient parks — and sheds —
  as a unit;
* worker → PS ``BEAT`` (no reply): heartbeat, refreshes the rank's
  last-seen age;
* worker → PS ``SPLN`` → PS replies ``SPLN | plan_json_utf8`` (empty on
  an unsharded PS): the fleet's authoritative shard plan, adopted (and
  digest-cross-checked) by `shard.ShardRouter` at connect time;
* primary → standby ``REPL | step(u64) | codec(u8) | checkpoint_blob``
  → standby replies ``ACKR | step(u64) | credits(u32)``: the
  hot-standby replication stream (v6) — the blob IS the on-disk
  checkpoint format incl. serving-version + rank-alloc extras, so a
  promoted standby serves with continuous versions; a ``PROM``-fenced
  standby refuses later ``REPL`` (counted) so a zombie primary cannot
  write into the successor's past.  ``codec`` (v12): the primary's
  wire codec applied to the checkpoint's ARRAY payload (meta stays
  exact); the standby stashes the byte with the blob and decodes at
  promotion — its on-disk auto-checkpoints and optimizer state remain
  f32;
* supervisor → shard ``SNAP | cut(u64)`` → shard replies
  ``SNAP | armed_cut(u64)`` (0 = refused): the Chandy–Lamport-style
  marker — the shard checkpoints at EXACTLY fill boundary ``cut``, so
  K independently-paced shards cut one consistent fleet snapshot;
* supervisor → standby ``PROM | plan_digest(u64)`` → standby replies
  ``PROM | replicated_step(u64)`` (all-ones = nothing replicated): the
  promotion fence — wrong-fleet digests refused, the standby fenced,
  then rebound onto the dead primary's port;
* aggregator → root ``AGGR | group(u16) | n_contrib(u16) | target(u16)
  | bucket(u16) | n_buckets(u16) | seq(u64) | version(u64) | loss(f64)
  | codes_blob`` (no reply): the v7 hierarchical forward — one
  group-reduced, per-contributor-MEAN gradient standing for
  ``n_contrib`` worker contributions (the root weights it by that
  multiplicity, so a short group fill moves the root pro-rata);
  ``seq`` rides the same per-rank dedup as GRAD, and the v11 bucket
  fields work exactly as on GRAD — a bucket-streaming aggregator
  (`shard.hierarchy.LocalAggregator(bucket_bytes=...)`) pre-reduces
  per bucket and pipelines the AGGR fanout, with ``agg_frames`` and
  the groups view booked per ASSEMBLED gradient, never per frame;
* subscriber → PS ``SUBS | have(u64)`` → PS replies ``DELT |
  version(u64) | read_credits(u32) | flags(u8) | codec(u8) |
  [params_payload]`` (v10, the serve tier's read path —
  `serve.subscribe.Subscriber`): a conditional snapshot read.
  ``have`` == the served version answers head-only UNCHANGED (flags
  bit 1); otherwise a full-payload reply costs one READ TOKEN from the
  per-version read budget (``read_window`` full reads per
  served-version advance, time-floored for idle servers) and fans out
  the encode-once PARM cache; an exhausted budget answers head-only
  SHED (flags bit 2, counted ``read_shed``) — the reader backs off,
  and training traffic never sees the flood.  ``codec`` (v12) is the
  wire codec byte, as on PARM.  Flags bit 4 (v12, ``delta_parm=True``
  servers): the payload is a DELTA vs the subscriber's presented
  ``have`` — sparse changed-index/value leaves diffed from a small
  ring of recent post-decode versions (depth ``_DELTA_RING``), patched
  onto the reader's current tree to land bitwise-identical to the full
  decode.  A ``have`` outside the ring (or a redial, which forces
  ``have=_UNVERSIONED``) falls back to the full compressed snapshot —
  delta is purely a wire-size optimization, never a correctness
  dependency (``delta_hits``/``delta_misses`` counted).  Every DELT
  advertises the remaining READ window, seeding the subscriber's
  sender-side READ gate (`transport.Session.send_read` — a separate
  credit class, so reader frames can never consume or stall
  GRAD/AGGR/REPL credits).

Control connections (the supervisor's SNAP/PROM/REPL client sides) HELO
with flag bit 4: authenticated like a worker but booked as NO rank —
a fleet's own control traffic must not pollute worker identity,
eviction, or the ``workers_seen`` diagnostics.  Flag bit 32 (v10)
books a SUBSCRIBER: authenticated, rank-less like a control conn —
readers must never occupy worker identity or shrink the effective
quota — and tracked in the ``subs_active`` gauge for the connection's
lifetime.  Two more HELO flags
carry hierarchy identity (v7): bit 8 marks the connection as a group
AGGREGATOR (``group(u16) + group_target(u16)`` follow the optional rank
field) — booked as a normal rank, but the root's ``groups`` view names
it as group g's aggregator; bit 16 marks a DIRECT-FALLBACK worker
(``group(u16)``) — a worker whose aggregator died un-restorably and who
re-admitted itself at the root as a plain rank (counted
``direct_fallbacks``, listed under its group in the view).

Flow control (v8): the server advertises a **credit window** —
``max(0, credit_window - queue_depth)`` — in every PSA, PARM, and ACKR
reply; each DATA frame (GRAD/AGGR/REPL, the `transport` module's
sheddable class) consumes one sender-side credit, and at zero credits
the sender stalls-then-sheds oldest-first instead of blocking the
socket (`transport.Session`).  Control frames (HELO/PULL/BEAT/SPLN/
SNAP/PROM/DONE) never shed and never queue behind data, so a flooded
link keeps its heartbeats and a saturated fleet degrades by counted
shedding instead of by spurious evictions or unbounded staleness.
Under queue pressure the server additionally sheds stale-beyond-clamp
and duplicate GRAD/AGGR frames BEFORE decoding them (counted
``admission_shed``) — the cheapest place to drop a frame the admission
policy would reject anyway.  Session/framing/deadline machinery lives
in `transport`; this module keeps the protocol: frame kinds, field
layouts, handshake, and admission policy.

Zero-copy segmented data plane (v9): the blob pipeline
(``serializer.dumps`` → one bytes → ``send_frame`` → ``recv_frame`` →
``serializer.loads``) is replaced end to end.  Senders build
``(meta_blob, per-leaf segments)`` via `serializer.encode_segments`
and gather-send them in ONE ``sendmsg`` (`transport.
send_frame_segments` / `Session.send_data_segments` — copy-on-park per
segment keeps the credit gate's ownership contract); receivers
``recv_into`` per-connection preallocated `transport.RecvArena` rings
(sized from the compiled code tree) and dispatch from HEADER fields
first — dedup and admission shedding burn seqs at receive time, in
wire order, so multi-MB decodes can run on a small off-GIL decode pool
(``decode_offloaded``) without a fresh frame ever reading as a
duplicate.  PARM replies are ENCODED ONCE per served version
(``parm_encodes``) and the same segment set fans out to every puller
at that version (``parm_fanout_reuse``) — PARM encode cost scales with
versions, not requests.  The wire bytes are identical to v8's frames;
v9 exists so a pre-segmented peer is refused at HELO instead of
trusted to have the ownership discipline this plane requires.
"""

from __future__ import annotations

import os
import queue
import socket
import struct
import sys
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable

import numpy as np

from .async_ps import AsyncPS
from .errors import FillStarvedError, FleetDeadError, NotCompiledError
from .native import serializer
from .ops import codecs as _codecs
from .ops.codecs import Codec
# The session layer (transport.py) shares this module's wire vocabulary
# (the pslint frame-drift checkers treat the pair as one unit):
# pslint: frame-vocabulary(ps-wire)
from . import transport as _transport
from .transport import (_CONTROL_RANK, _NO_REPLICA, TRANSPORT_ERRORS,
                        Deadline, DeadlineExpired, FrameCRCError, Session,
                        frame_header, recv_frame, request_promotion,
                        request_snapshot, send_frame)
from .utils.backoff import Backoff
from .utils.bytes import bytes_of

# Legacy aliases — the framing primitives moved to `transport`.
_frame_header = frame_header
_recv_frame = recv_frame
_send_frame = send_frame
_TRANSPORT_ERRORS = TRANSPORT_ERRORS

_U64 = struct.Struct("<Q")
# v8 credit windows (PSA/PARM/ACKR replies) ride a u32.
_U32 = struct.Struct("<I")
# AGGR frame prefix: (group, contributor count, group fill target).
_GRP = struct.Struct("<HHH")
# v11 bucket-stream fields on GRAD/AGGR: (bucket index, bucket count).
# Whole-tree frames pack the degenerate (0, 1).
_BKT = struct.Struct("<HH")
# Per-rank in-flight bucketed-seq bound: at most this many (seq ->
# seen-bucket-set) dedup entries per rank; older ones retire as
# completed-with-missing-buckets would (memory bounded against a
# flooding or seq-skipping peer).
_BUCKET_SEQ_WINDOW = 4
# Per-connection partial-assembly cap: a peer streaming new seqs
# without ever completing one is bounded to this many live assemblies
# (oldest retired + counted).
_ASSEMBLY_CAP = 4

# HELO-reply protocol version.  Bump on any change to message framing or
# field layout; the worker refuses a mismatch explicitly instead of
# mis-parsing later fields (r4 advisor).  History: v3 CRC framing +
# reconnect HELO + heartbeats; v4 per-rank GRAD seq dedup; v5 sharded
# fleet; v6 availability (control conns, REPL/ACKR, SNAP, PROM); v7
# hierarchy (AGGR, aggregator/fallback HELO flags); v8 flow control —
# PSA/PARM/ACKR each advertise the server's remaining credit window
# (u32, layouts in the docstring) and senders gate DATA frames on it;
# v9 segmented data plane — the PSA grows a wire_flags u8 (bit 1 =
# scatter-gather segments), GRAD/AGGR/PARM payloads ride sendmsg
# iovecs into preallocated recv arenas, and PARM encodes once per
# version; v10 serve tier — SUBS/DELT versioned snapshot subscription
# (HELO flag bit 32 books a rank-less SUBSCRIBER), DELT replies carry
# a READ-class credit window with a per-version read-token budget, and
# readers shed (``read_shed``) before they can touch training traffic;
# v11 bucket-streamed gradients — GRAD/AGGR grow ``bucket(u16) |
# n_buckets(u16)`` header fields (whole-tree = ``(0, 1)``), bucketed
# gradients stream one frame per bucket under ONE credit and assemble
# per (rank, seq) at the receiver — a v10 peer mis-parses the layout,
# so the version byte refuses it loudly at HELO; v12 compressed
# parameter wire — PARM/DELT/REPL grow a codec-id u8 (identity/bf16/
# int8, encoded once per version in the ``_parm_cache`` path and
# decoded by every reader from the frame itself), and DELT may carry a
# delta vs the subscriber's presented version (flag bit 4) served from
# a small ring of recent post-decode trees — a v11 peer would misread
# the codec byte as payload, so the version byte refuses it at HELO.
PROTOCOL_VERSION = 12
# PSA wire_flags (v9): bit 1 = this server speaks the segmented wire.
_WIRE_SEGMENTED = 1
# Conditional-PULL "no cached version" sentinel (v9): a pull carrying
# this value (or no body at all) is unconditional.
_UNVERSIONED = (1 << 64) - 1
# DELT reply flags (v10 serve tier): UNCHANGED = the subscriber's
# ``have`` equals the served version (head-only reply, the
# conditional-pull short-circuit applied to the read path); SHED = the
# server's read-token budget for this version is exhausted (head-only,
# READ-class shed — the reader backs off and retries; a zero payload
# with neither flag never occurs, a tree frame is never empty).
_DELT_UNCHANGED = 1
_DELT_SHED = 2
# v12: the payload is a DELTA vs the subscriber's presented ``have``
# version (sparse index/value leaves; apply on top of the reader's
# current tree).  Absent the flag a non-empty payload is a full
# snapshot — the unconditional fallback after a ring miss or redial.
_DELT_DELTA = 4
# v12 codec-id byte on PARM/DELT/REPL frames (see ops.codecs
# WIRE_CODEC_IDS: 0 identity, 1 bf16, 2 int8).  Frames self-describe,
# so readers need no knob and mixed-codec failover stays correct.
_U8 = struct.Struct("B")
# Delta ring depth: how many recent post-decode versions the server
# retains for delta serving.  Small on purpose — a reader more than
# this many versions behind is better served a full (compressed)
# snapshot than an ever-growing delta.
_DELTA_RING = 4
# Read-token time floor: the read budget refills on every served-
# version advance (read bandwidth scales with training progress), but
# an IDLE server (converged, paused, pure-serve) must still serve a
# bounded read rate instead of none — tokens also refill after this
# many seconds at an unchanged version.
_READ_REFILL_S = 0.25
# Worker-side same-version pacing: after this many consecutive
# unchanged pulls (= gradients already computed at the CURRENT served
# version), the worker yields per further iteration, escalating with
# the streak (the streak IS the backlog signal).  On the zero-copy
# wire a worker outruns the server's apply loop by a wide margin, and
# past a couple of in-flight gradients per version every extra one
# only deepens the net-queue backlog — i.e. buys pure applied
# staleness, never throughput (updates consume quota gradients no
# matter who queued them; Lian et al.'s bound is on staleness).  A
# yield — not a block — so quota >> workers configurations still fill.
_SAME_VERSION_PACE = 2
_SAME_VERSION_YIELD_S = 0.002
_SAME_VERSION_YIELD_MAX_S = 0.02
# Frames at/above this payload size route their decode through the
# server's small off-GIL pool (`ps_tree_decode`/`ps_lz_decompress`
# release the GIL); smaller ones decode inline — the pool's dispatch
# overhead would dominate them.  On a single-usable-CPU host nothing
# can run in parallel with the conn thread, so offload is disabled at
# runtime (the pool dispatch would be pure added latency).
_DECODE_OFFLOAD_MIN = 1 << 16
try:
    _USABLE_CPUS = len(os.sched_getaffinity(0))
except (AttributeError, OSError):  # pragma: no cover - non-Linux
    _USABLE_CPUS = os.cpu_count() or 1
# In-flight offloaded decodes per connection.  MUST stay strictly below
# the conn loop's RecvArena ring depth (nbufs=3): an offloaded payload
# is a zero-copy view into the arena, valid until its slot is refilled
# nbufs-1 receives later — the PSL703 rotation discipline.
_DECODE_DEPTH = 2
_F64 = struct.Struct("<d")

# The supervisor's control-plane client helpers (SNAP/PROM markers,
# rank-less control dial) live in `transport` with the rest of the
# session layer; this module's conn loop keeps their decode branches.
def control_connect(host: str, port: int, token: "str | None" = None,
                    timeout: float = 10.0) -> socket.socket:
    """`transport.control_connect` bound to this protocol version."""
    return _transport.control_connect(
        host, port, token=token, timeout=timeout,
        protocol_version=PROTOCOL_VERSION)


class AsyncPSServer(AsyncPS):
    """The rank-0 process of the multi-host async PS.

    Usage (PS host)::

        srv = AsyncSGDServer(named_params, lr=0.1, quota=8, port=5555)
        srv.compile_step(loss_fn)          # builds the jitted decode+update
        history = srv.serve(steps=1000)    # serves until done, then stops
                                           # workers via DONE on their pulls

    Reuses the single-controller `AsyncPS` machinery (codec, torch-parity
    update rules, checkpointing, timing dicts); only the transport differs —
    gradients arrive from sockets instead of local device threads.
    """

    def __init__(self, named_params, *, quota: int,
                 host: str = "127.0.0.1", port: int = 0,
                 wire_level: int = 0, token: str | None = None,
                 conn_timeout: float = 60.0, shard_info=None,
                 standby: bool = False, replica_addr=None,
                 replica_every: int = 1,
                 op_deadline: "float | None" = None,
                 read_window: int = 0, wire_codec: str = "identity",
                 delta_parm: bool = False, **kw):
        super().__init__(named_params, quota=quota, **kw)
        # Credit-based flow control (v8): the window this server
        # advertises in PSA/PARM/ACKR replies is the remaining queue
        # room divided across the live senders (see
        # `_advertised_credits`).  The base class's ``credit_window``
        # knob (0 = auto) sizes it; the net queue is never smaller than
        # the window.
        self._credit_window = self.credit_window or max(quota * 2, 8)
        # READ-class budget (v10, the serve tier): at most this many
        # full-payload DELT replies per served-version advance (with an
        # idle-server time floor, `_READ_REFILL_S`) — reader bandwidth
        # scales with training progress BY CONSTRUCTION, so a reader
        # flood exhausts read tokens and sheds head-only (counted
        # ``read_shed``) instead of competing with GRAD/AGGR service.
        # "Unchanged" replies are token-free: they cost a frame header.
        if read_window < 0:
            raise ValueError(
                f"read_window must be >= 0, got {read_window}")
        self._read_window = int(read_window) or max(4, quota)
        self._read_lock = threading.Lock()
        self._read_tokens = self._read_window  # pslint: guarded-by(_read_lock)
        self._read_tokens_version = -1  # pslint: guarded-by(_read_lock)
        self._read_tokens_t = 0.0  # pslint: guarded-by(_read_lock)
        # Per-op deadline budget for this server's own client-side ops
        # (the REPL round trip to its standby); workers carry their own.
        self.op_deadline = op_deadline
        # Hot-standby replication (ISSUE 7): ``standby=True`` is the
        # RECEIVING side (stash REPL blobs, answer PROM fences, never
        # serve fills until promoted); ``replica_addr`` the SENDING side
        # (stream every ``replica_every``-th update's checkpoint blob —
        # R>1 trades wire cost for <=R-1 rewind, surfaced as repl_lag).
        if standby and replica_addr is not None:
            raise ValueError("a standby cannot itself replicate onward "
                             "(chained replication is not supported)")
        if replica_every < 1:
            raise ValueError(
                f"replica_every must be >= 1, got {replica_every}")
        self._standby = standby
        self.replica_addr = (tuple(replica_addr)
                             if replica_addr is not None else None)
        self.replica_every = int(replica_every)
        self._repl_lock = threading.Lock()
        self._repl_step: "int | None" = None  # pslint: guarded-by(_repl_lock)
        self._repl_blob: "bytes | None" = None  # pslint: guarded-by(_repl_lock)
        # v12: the codec byte that rode the newest REPL frame — promotion
        # decodes the stashed blob's arrays with THIS, not any local
        # knob (the primary may run a different wire codec).
        self._repl_codec = 0  # pslint: guarded-by(_repl_lock)
        self._promoted = False  # pslint: guarded-by(_repl_lock)
        # Sender-side state: serve-loop-only (single thread), unguarded.
        # The replication stream rides a credit-gated `transport.Session`
        # (REPL is a DATA frame): a slow standby stalls-then-sheds
        # replication payloads instead of blocking the primary's serve
        # loop in sendall.
        self._repl_session: "Session | None" = None
        self._last_acked = 0
        # Coordinated-snapshot markers: cuts armed by SNAP frames (conn
        # threads) and consumed at the fill boundary (serve thread).
        self._snap_cuts: "set[int]" = set()  # pslint: guarded-by(_stats_lock)
        self._snap_path = None  # pslint: guarded-by(_stats_lock)
        self._fill_next_step = 0  # pslint: guarded-by(_stats_lock)
        # Fleet identity (`shard.partition.ShardInfo`, duck-typed so this
        # module never imports the shard package): which slice of the
        # plan this server owns.  Advertised in every HELO reply and
        # served in full over SPLN; an unsharded PS advertises the
        # trivial (0, 1, digest=0) triple and an empty plan.
        self.shard_info = shard_info
        if shard_info is not None:
            self._shard_index = int(shard_info.index)
            self._shard_count = int(shard_info.count)
            self._plan_digest = int(shard_info.digest)
            self._plan_json = bytes(shard_info.plan_json)
        else:
            self._shard_index, self._shard_count = 0, 1
            self._plan_digest = 0
            self._plan_json = b""
        # Per-connection recv timeout: a peer that stops mid-frame costs
        # its connection after this long instead of pinning a handler
        # thread forever (healthy workers heartbeat every ~2 s).
        self.conn_timeout = conn_timeout
        # ``wire_level=0``: store-framed (the reference's blosc clevel=0
        # operating point); >=1 adds shuffle+LZ for thin links.
        self.wire_level = wire_level
        # Optional shared-secret admission: with ``token`` set, every
        # message before an authenticated HELO is refused (wrong token →
        # NOAU, connection-local).  Not encryption — just keeps a PS
        # bound beyond loopback from serving strangers.  Empty string
        # normalizes to None (an unset env var interpolated into --token
        # must not silently open the gate while looking enabled).
        self.token = token or None
        self._host = host  # kept: promotion rebinds onto a new port
        self._listener = socket.create_server((host, port))
        self.address: tuple[str, int] = self._listener.getsockname()[:2]
        self._conn_threads: list[threading.Thread] = []
        self._net_queue: "queue.Queue" = queue.Queue(
            maxsize=max(self._credit_window, quota * 2, 8))
        self._net_stop = threading.Event()
        # Permanent-shutdown latch, distinct from `_net_stop` (which
        # every serve() finally sets and the next re-arms): ONLY close()
        # sets it, so a close() landing at any point aborts promptly
        # instead of idling toward the full idle_timeout.
        self._closed = threading.Event()
        # Shared mutable state below carries `pslint: guarded-by` lock
        # annotations (enforced by `tools/pslint`'s lock-discipline
        # checker): conn-handler threads and the serve loop both touch it.
        # Deliberately UNguarded: `_served`/`_served_version` (the
        # leaf-wise inconsistent-read surface — racing a PULL against an
        # update is the AsySG-InCon algorithm, not a bug) and `_dying`
        # (a monotonic latch, set once before shutdown).
        self._next_rank = 0  # pslint: guarded-by(_rank_lock)
        # Established whole-program lock order (enforced by pslint's
        # PSL5xx concurrency checker): rank state may be snapshotted
        # together with the stats counters (`_fault_stats_snapshot`
        # takes both), so the rank lock is OUTER to the stats lock —
        # and the session send lock is outer to the stats lock too (the
        # stall/shed hooks bump `_bump` from under it; declared in
        # `transport`).  Never take `_rank_lock` (or the session lock)
        # while holding `_stats_lock`.
        # pslint: lock-order(_rank_lock < _stats_lock)
        self._rank_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        # Leaf-wise serving snapshot (host arrays) + version — the published
        # surface remote PULLs read; mid-update pulls see mixed leaves.
        # Only the serve loop writes it lock-free (leaf swaps on existing
        # keys — no dict resize, so handler-thread iteration never sees a
        # changed-size error and each leaf swap is one atomic rebind);
        # that leaf-wise inconsistency IS AsySG-InCon, which is why this
        # is single-writer, not guarded-by.
        self._served = {n: np.asarray(p)  # pslint: single-writer(serve-loop)
                        for n, p in self.params.items()}
        self._served_version = 0
        # Encode-once PARM fanout (v9): the segment set for the current
        # served version, built lazily by the FIRST pull at that version
        # and fanned out to every later one — PARM encode cost scales
        # with versions, not requests.  Leaf segments alias the captured
        # `_served` arrays, which the serve loop REBINDS (never mutates
        # in place), so a cached iovec stays the bytes it was encoded
        # from for as long as any puller needs it.
        self._parm_lock = threading.Lock()
        self._parm_cache = None  # pslint: guarded-by(_parm_lock)
        # Compressed parameter wire (v12): the server-side WIRE codec
        # applied inside the encode-once cache — each version pays the
        # cast/quantize ONCE no matter how many pullers, subscribers,
        # or standbys read it.  Optimizer state stays f32; only f32
        # leaves transform (step counters etc. pass through by dtype).
        # Validated loudly here so a typo'd codec fails at construction,
        # not on the first pull.
        self._wire_codec = str(wire_codec)
        self._wire_codec_id = _codecs.wire_codec_id(self._wire_codec)
        # Delta PARM serving (v12, DELT path only): retain a small ring
        # of recent POST-DECODE trees (exactly what readers hold after
        # decoding our frames) and serve subscribers a sparse diff vs
        # their presented version.  Ring + per-(have, version) encoded
        # delta cache both live under `_parm_lock` with the PARM cache
        # they shadow; `load_state_dict` clears all three together.
        self._delta_parm = bool(delta_parm)
        self._delta_ring = OrderedDict()  # pslint: guarded-by(_parm_lock)
        self._delta_cache = {}  # pslint: guarded-by(_parm_lock)
        # Off-GIL decode pool: CRC verify + decompress of multi-MB
        # GRAD/AGGR payloads run through the native lib (GIL released)
        # on these threads, pipelined per connection (depth
        # `_DECODE_DEPTH`), so a conn thread can be back in recv_into
        # while the previous frame decodes.  Threads spawn on first
        # use; a single-usable-CPU host decodes inline instead (None
        # threshold) — the dispatch would be pure added latency there.
        self._decode_pool = ThreadPoolExecutor(
            max_workers=min(2, max(1, _USABLE_CPUS - 1)),
            thread_name_prefix="ps-decode")
        self._decode_offload_min: "int | None" = (
            _DECODE_OFFLOAD_MIN if _USABLE_CPUS > 1 else None)
        # Connection diagnostics: a misbehaving peer only ever costs its own
        # connection; these counters feed the idle-timeout error message.
        # `serve` overwrites the starvation-guard patience with its
        # idle_timeout argument; initialized here so the guard is defined
        # even if the inherited in-process `run` drives the fill loop.
        self._idle_timeout = 300.0
        self._workers_seen = 0  # pslint: guarded-by(_rank_lock)
        self._conn_drops = 0  # pslint: guarded-by(_stats_lock)
        self._last_drop: BaseException | None = None  # pslint: guarded-by(_stats_lock)
        # Live-drop diagnosability (a run-end-only report left an
        # overloaded run silent for its whole life): the last time a
        # queue-full drop warning was printed, rate-limited.
        self._last_drop_warn = 0.0  # pslint: guarded-by(_stats_lock)
        # Serve-loop wall anchor for the drop-RATE gauge in snapshots.
        self._serve_t0: "float | None" = None
        # Set when a FaultPlan kills this PS: shutdown must then be ABRUPT
        # (no DONE courtesy on pending PULLs) — a real killed process sends
        # nothing, and the courtesy would tell workers to exit instead of
        # reconnecting to the restarted PS.
        self._dying = False
        # Per-rank liveness: last-seen monotonic time (refreshed by HELO /
        # PULL / GRAD / BEAT), live connection count, and the live/evicted
        # partition the quota clamps to.
        self._last_seen: dict[int, float] = {}  # pslint: guarded-by(_rank_lock)
        self._conns_for_rank: dict[int, int] = {}  # pslint: guarded-by(_rank_lock)
        self._live_ranks: set[int] = set()  # pslint: guarded-by(_rank_lock)
        self._evicted: set[int] = set()  # pslint: guarded-by(_rank_lock)
        # Per-rank high-water GRAD sequence id: a frame at or below it is
        # a duplicate (wire dup, retransmitting middlebox) and is dropped
        # — without this, WireMangler's `dup` applied the same gradient
        # TWICE as two fresh contributions.
        self._last_seq: dict[int, int] = {}  # pslint: guarded-by(_rank_lock)
        # Bucket-stream dedup (v11): per rank, the seen-bucket set of
        # each in-flight bucketed seq (bounded `_BUCKET_SEQ_WINDOW`).
        # `_last_seq` advances when a bucketed seq completes, so the
        # whole-tree high-water rule keeps covering retired seqs.
        self._bucket_seen: dict[int, dict] = {}  # pslint: guarded-by(_rank_lock)
        # Hierarchy "groups" view (ISSUE 8): per-group detail — which
        # rank is the group's aggregator (HELO flag bit 8), its
        # configured group fill target, AGG frames admitted, the last
        # frame's contributor count, and ranks that re-admitted
        # themselves DIRECT after the aggregator died (flag bit 16).
        self._groups: "dict[int, dict]" = {}  # pslint: guarded-by(_rank_lock)
        # Transport-level fault counters, on top of the admission
        # counters `AsyncPS` installs.  Handler threads bump
        # concurrently with the serve loop, so in THIS class `_bump` is
        # overridden with a locked version (the in-process `AsyncPS` is
        # single-consumer and stays lock-free).
        self.fault_stats.update({  # pslint: guarded-by(_stats_lock)
            "evictions": 0,
            "reconnects": 0,
            "crc_dropped": 0,
            "quarantined_frames": 0,
            "accept_errors": 0,
            "duplicate_dropped": 0,
            "evicted_dropped": 0,
            # Replication / coordinated-snapshot counters (ISSUE 7):
            # REPL frames sent (primary) / applied (standby) / refused
            # after the PROM fence (standby), the primary's unacked-lag
            # gauge, and SNAP-cut checkpoints written at fill boundaries.
            "repl_sent": 0,
            "repl_received": 0,
            "repl_refused": 0,
            "repl_lag": 0,
            "snapshot_barriers": 0,
            # Hierarchical-aggregation counters (ISSUE 8): AGG forward
            # frames admitted into fills, and workers booked as
            # DIRECT-FALLBACK ranks after their group aggregator died.
            "agg_frames": 0,
            "direct_fallbacks": 0,
            "dropped_queue_full": {},
        })

    def compile_step(self, loss_fn) -> None:
        super().compile_step(loss_fn)
        # Reference code structure for validating incoming GRAD payloads: a
        # worker running a different codec would otherwise enqueue a
        # mismatched pytree that only explodes later inside the serve
        # loop's stack/apply — killing the whole job instead of costing the
        # one bad connection.
        import jax.numpy as jnp

        dummy = OrderedDict(
            (n, self.code.encode(jnp.zeros(p.shape, p.dtype)))
            for n, p in self.params.items())
        self._index_code_meta(dummy)

    def _index_code_meta(self, dummy) -> None:
        """Build the incoming-payload validation indexes from one encoded
        zero tree: the whole-tree (treedef, leaf-meta) pair the blob path
        compares, plus the PER-PARAM map bucket sub-trees validate
        against (a bucket's composition is worker-chosen, so the server
        checks each name's code structure individually and completeness
        at assembly).  Shared by `compile_step` and the aggregator's
        `compile_reduce` so the two cannot drift."""
        import jax

        leaves, self._code_treedef = jax.tree_util.tree_flatten(dummy)
        self._code_leaf_meta = [(tuple(l.shape), str(l.dtype))
                                for l in leaves]
        per_name = {}
        for n, c in dummy.items():
            sub_leaves, sub_td = jax.tree_util.tree_flatten(c)
            per_name[n] = (sub_td, [(tuple(l.shape), str(l.dtype))
                                    for l in sub_leaves])
        self._code_meta_by_name = per_name

    def _validate_codes(self, codes) -> None:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(codes)
        meta = [(tuple(np.shape(l)), str(np.asarray(l).dtype))
                for l in leaves]
        if treedef != self._code_treedef or meta != self._code_leaf_meta:
            raise ValueError(
                "gradient payload does not match the server codec's code "
                "structure (worker running a different codec?)")

    def _validate_codes_bucket(self, codes) -> None:
        """Per-bucket payload validation (v11): every name must be a
        parameter this server owns and its code sub-tree must match the
        compiled structure — completeness (every param exactly once
        across the seq's buckets) is checked at assembly."""
        import jax

        if not isinstance(codes, (dict, OrderedDict)) or not codes:
            raise ValueError(
                "bucket payload is not a name-keyed code sub-tree")
        by_name = getattr(self, "_code_meta_by_name", None) or {}
        for n, c in codes.items():
            expected = by_name.get(n)
            if expected is None:
                raise ValueError(
                    f"bucket payload names unknown parameter {n!r}")
            sub_leaves, sub_td = jax.tree_util.tree_flatten(c)
            meta = [(tuple(np.shape(l)), str(np.asarray(l).dtype))
                    for l in sub_leaves]
            if sub_td != expected[0] or meta != expected[1]:
                raise ValueError(
                    f"bucket payload for {n!r} does not match the server "
                    f"codec's code structure (worker running a different "
                    f"codec?)")

    # -- rank liveness bookkeeping --------------------------------------------

    def _register_conn(self, prior: "int | None",
                       assigned: "int | None" = None) -> int:
        """Book an authenticated HELO: a fresh worker gets the next rank; a
        reconnect (``prior`` set) re-books the same rank — un-evicting it if
        a heartbeat gap already cost it its seat.  ``assigned`` is the
        fleet-identity path: shard 0 of a sharded fleet minted the rank
        and every other shard books it verbatim (first sight counts as a
        fresh worker here, never as a reconnect), so per-rank accounting
        — eviction, seq-dedup, scoreboard, latency — names the same
        worker on every shard."""
        now = time.monotonic()
        with self._rank_lock:
            if prior is not None:
                rank = prior
                # Never mint this rank for someone else later.
                self._next_rank = max(self._next_rank, rank + 1)
            elif assigned is not None:
                rank = assigned
                self._next_rank = max(self._next_rank, rank + 1)
                if rank not in self._last_seen:
                    self._workers_seen += 1
            else:
                rank = self._next_rank
                self._next_rank += 1
                self._workers_seen += 1
            self._live_ranks.add(rank)
            self._evicted.discard(rank)
            self._last_seen[rank] = now
            self._conns_for_rank[rank] = \
                self._conns_for_rank.get(rank, 0) + 1
        if prior is not None:
            self._bump("reconnects")
        return rank

    def _release_conn(self, rank: int) -> None:
        with self._rank_lock:
            self._conns_for_rank[rank] = \
                self._conns_for_rank.get(rank, 1) - 1

    def _mark_alive(self, rank: int) -> None:
        """Refresh a rank's last-seen age — and reverse its eviction if
        traffic resumed on a connection that never died (a worker paused
        past the eviction timeout, then unfrozen: it has no reason to
        re-HELO, so the frame handlers must be able to re-admit it)."""
        with self._rank_lock:
            self._last_seen[rank] = time.monotonic()
            if rank in self._evicted:
                self._evicted.discard(rank)
                self._live_ranks.add(rank)
                print(f"async PS: worker rank {rank} resumed after "
                      f"eviction — re-admitted", file=sys.stderr)

    def _bump(self, key: str, n: int = 1) -> None:
        with self._stats_lock:
            self.fault_stats[key] += n

    # -- hierarchy "groups" view bookkeeping ----------------------------------

    # pslint: holds(_rank_lock)
    def _group_entry(self, group: int) -> dict:
        return self._groups.setdefault(int(group), {
            "aggregator_rank": None, "group_target": 0, "agg_frames": 0,
            "last_contributors": 0, "fallback_ranks": []})

    def _note_aggregator(self, group: int, rank: int,
                         target: int) -> None:
        """Book a HELO flag-8 connection: rank ``rank`` is group
        ``group``'s aggregator (a restarted aggregator re-presenting the
        same rank re-claims the entry — no churn in the view either)."""
        with self._rank_lock:
            entry = self._group_entry(group)
            entry["aggregator_rank"] = rank
            entry["group_target"] = int(target)

    def _note_fallback(self, group: int, rank: int) -> None:
        """Book a HELO flag-16 connection: ``rank`` is a worker of group
        ``group`` re-admitting itself DIRECT after its aggregator died."""
        with self._rank_lock:
            entry = self._group_entry(group)
            if rank not in entry["fallback_ranks"]:
                entry["fallback_ranks"].append(rank)
        self._bump("direct_fallbacks")

    def _note_group_frame(self, group: int, rank: int,
                          n_contrib: int) -> None:
        with self._rank_lock:
            entry = self._group_entry(group)
            entry["aggregator_rank"] = rank
            entry["agg_frames"] += 1
            entry["last_contributors"] = int(n_contrib)

    def _evict_dead(self, eviction_timeout: float,
                    dead_conn_grace: float) -> None:
        """Evict live ranks that went silent: past ``eviction_timeout``
        with no frame (hung worker), or past ``dead_conn_grace`` with no
        remaining connection (crashed worker — a reconnecting one re-HELOs
        inside the grace and never trips this)."""
        now = time.monotonic()
        with self._rank_lock:
            dead = []
            for r in list(self._live_ranks):
                age = now - self._last_seen.get(r, now)
                gone = self._conns_for_rank.get(r, 0) <= 0
                if age > eviction_timeout or (gone and age > dead_conn_grace):
                    self._live_ranks.discard(r)
                    self._evicted.add(r)
                    dead.append(r)
        for r in dead:
            self._bump("evictions")
            # Drop the rank's latency state too: a ghost frozen at its
            # pre-death pace would skew the fleet medians driving
            # latency weighting and the adaptive fill-deadline (a
            # rejoining rank re-warms; `_evict_dead` runs only on the
            # serve thread, the same thread that observes latencies).
            self._latency.forget(r)
            print(f"async PS: evicted worker rank {r} "
                  f"(silent/disconnected)", file=sys.stderr)

    def _effective_quota(self) -> int:
        """Quota clamped to the live fleet — but only once an eviction has
        happened: during healthy ramp-up (workers still connecting) the
        configured quota stands, so accounting for fault-free runs is
        exact.  Under rank-distinct fills, quarantined ranks shrink the
        target too (`AsyncPS._fill_target`): they cannot contribute, so
        waiting for their slots would deadlock the fill.  Neither shrink
        may cross the reducer's breakdown size: `_shrink_floor` holds the
        fill there (logged + counted) rather than letting fleet decay
        silently degenerate trimmed_mean/median to a plain mean; while
        the floor binds and fewer eligible distinct ranks remain than it
        needs, fills top up with repeat contributions from eligible
        ranks (`AsyncPS._repeat_allowed`) instead of stalling."""
        with self._rank_lock:
            if not self._evicted:
                q = self.quota
            else:
                q = max(1, min(self.quota, len(self._live_ranks) or 1))
        if self._rank_distinct and self._scoreboard is not None:
            nq = len(self._scoreboard.quarantined_ranks())
            q = max(1, q - nq)
        return self._shrink_floor(q, "eviction/quarantine")

    def _eligible_rank_count(self) -> int:
        """Live, non-evicted, non-quarantined ranks — the set a
        rank-distinct fill can actually draw distinct contributions
        from."""
        with self._rank_lock:
            live = set(self._live_ranks) - self._evicted
        if self._scoreboard is not None:
            live -= set(self._scoreboard.quarantined_ranks())
        return len(live)

    # -- fill-admission hooks (the shared loop is `AsyncPS._fill_gradients`) --

    def _fill_target(self) -> int:
        """The transport deployment's fill target is the effective quota:
        eviction clamp + quarantine shrink + breakdown floor."""
        return self._effective_quota()

    def _fleet_ranks(self) -> "set[int]":
        with self._rank_lock:
            return set(self._live_ranks)

    def _drop_before_admit(self, rank) -> bool:
        """An EVICTED rank's in-flight gradient (enqueued before the
        eviction landed) must not satisfy a fill or a quorum: the rank was
        ruled dead, and re-admission happens on LIVE traffic at the
        connection layer (`_mark_alive`), never via queue leftovers.  A
        rejoining rank's fresh frames re-enter cleanly."""
        if rank is None:
            return False
        with self._rank_lock:
            evicted_now = rank in self._evicted
        if evicted_now:
            self._bump("evicted_dropped")
        return evicted_now

    def _check_fill_starved(self, n_filled: int, t0: float) -> None:
        """Starvation guard: with no quorum to close short, a fill that
        already holds one frame from EVERY eligible rank but still needs
        more distinct ranks can never complete with this fleet — and the
        steady surplus traffic keeps resetting the idle deadline, so the
        generic "fleet dead" error never fires.  Fail loudly after
        ``idle_timeout`` instead of spinning forever (the in-process
        analogue is `run`'s eager quota > num_workers refusal)."""
        eligible = self._eligible_rank_count()
        if (self.quorum is None and eligible > 0
                and n_filled >= eligible
                and time.perf_counter() > t0 + self._idle_timeout):
            raise FillStarvedError(
                f"fill starved for "
                f"{self._idle_timeout:.0f}s: aggregate="
                f"{self.aggregate!r} admits one "
                f"contribution per rank per fill "
                f"and the fill target is "
                f"{self._effective_quota()}, but "
                f"only {eligible} distinct eligible "
                f"rank(s) are connected — add "
                f"workers, lower --quota, or set "
                f"--quorum/--fill-deadline")

    def _fault_stats_snapshot(self) -> dict[str, Any]:
        now = time.monotonic()
        with self._rank_lock, self._stats_lock:
            # Counter copy + admission-audit extras (per-rank latency,
            # anomaly scoreboard) come from the shared base snapshot —
            # a field added there must reach BOTH deployments' histories
            # — only the transport-layer fields are server-specific.
            snap = self._base_fault_snapshot()
            snap["conn_drops"] = self._conn_drops
            snap["workers_seen"] = self._workers_seen
            # Drop RATE, not just count: "40 drops" means nothing without
            # the wall it accrued over — a live overloaded run reads
            # drops/sec here (0.0 before serve starts, or with none).
            drops_total = sum(
                self.fault_stats["dropped_queue_full"].values())
            elapsed = (time.perf_counter() - self._serve_t0
                       if self._serve_t0 is not None else 0.0)
            snap["dropped_queue_full_rate"] = (
                round(drops_total / elapsed, 4) if elapsed > 0 else 0.0)
            snap["live_ranks"] = sorted(self._live_ranks)
            snap["evicted_ranks"] = sorted(self._evicted)
            snap["heartbeat_ages"] = {
                r: round(now - t, 3) for r, t in self._last_seen.items()}
            if self._groups:
                # The hierarchy's per-group detail: aggregator rank, AGG
                # traffic, and direct-fallback ranks — keyed by group id
                # as a string (JSON-history friendly, like "shards").
                snap["groups"] = {str(g): dict(info)
                                  for g, info in sorted(
                                      self._groups.items())}
        return snap

    # -- connection handling --------------------------------------------------

    def _accept_loop(self):
        # The session layer's accept pump: one daemon `_conn_loop`
        # thread per connection, unexpected accept errors counted and
        # survived, listener-close races exited quietly.
        _transport.accept_pump(
            self._listener, self._net_stop, self._conn_loop,
            on_error=lambda: self._bump("accept_errors"),
            threads=self._conn_threads)

    def _advertised_credits(self) -> int:
        """The window advertised right now: the remaining net-queue
        room SHARED across the live senders — N workers each holding a
        full window would legally put N*window frames in flight at a
        queue with room for one window.  While any room exists every
        sender gets at least one credit (aggregate overcommit bounded
        by one frame per sender — livelock-free); a saturated server
        advertises 0 and senders stall-then-shed at their end
        (backpressure as an explicit protocol signal)."""
        room = self._credit_window - self._net_queue.qsize()
        if room <= 0:
            return 0
        with self._rank_lock:
            live = len(self._live_ranks)
        return max(1, room // max(1, live))

    # pslint: holds(_read_lock)
    def _refill_read_tokens(self, version: int, now: float) -> None:
        """Refill the read-token bucket when the served version moved
        (the budget is per version: ``read_window`` full-payload reads
        per unit of training progress) or after the idle-server time
        floor — an idle fleet still serves bounded reads, never none."""
        if (version != self._read_tokens_version
                or now - self._read_tokens_t >= _READ_REFILL_S):
            self._read_tokens_version = version
            self._read_tokens_t = now
            self._read_tokens = self._read_window

    def _take_read_token(self) -> bool:
        """One full-payload DELT permit, or False = shed this read
        (head-only SHED reply, counted).  Conn threads race for tokens
        under ``_read_lock`` alone — never nested with another lock."""
        version = self._served_version
        now = time.monotonic()
        with self._read_lock:
            self._refill_read_tokens(version, now)
            if self._read_tokens <= 0:
                return False
            self._read_tokens -= 1
            return True

    def _advertised_read_credits(self) -> int:
        """The READ window advertised in every DELT reply — what seeds
        the subscriber's sender-side READ gate (`Session.send_read`):
        the tokens still available at the current version.  A zeroed
        window tells the reader to back off at ITS end; the `open_read`
        valve bounds how long it believes a stale zero."""
        version = self._served_version
        now = time.monotonic()
        with self._read_lock:
            self._refill_read_tokens(version, now)
            return max(0, self._read_tokens)

    def _under_pressure(self) -> bool:
        """Queue at >= half the credit window: the threshold past which
        pre-decode admission shedding turns on."""
        return self._net_queue.qsize() * 2 >= self._credit_window

    def _shed_before_decode(self, rank, seq: int, version: int,
                            bucket: int = 0, n_buckets: int = 1) -> bool:
        """Overload admission control: under queue pressure, a GRAD/AGGR
        frame the policy would reject anyway — stale beyond the clamp,
        or a per-rank duplicate (bucket-aware on the v11 stream) — is
        shed from its HEADER fields alone, before paying
        deserialize+validate (counted ``admission_shed``).  Off
        pressure, frames flow to the precise post-decode counters so
        fault attribution stays exact when it is affordable."""
        if rank is None or not self._under_pressure():
            return False
        stale = (self.max_staleness is not None
                 and self._served_version - version > self.max_staleness)
        with self._rank_lock:
            dup = seq <= self._last_seq.get(rank, -1)
            if not dup and n_buckets > 1:
                dup = bucket in self._bucket_seen.get(rank, {}).get(
                    seq, ())
        if stale or dup:
            self._bump("admission_shed")
            return True
        return False

    def _burn_seq(self, rank: int, seq: int, bucket: int = 0,
                  n_buckets: int = 1) -> bool:
        """Per-rank monotone dedup, HEADER-FIRST (v9) and bucket-aware
        (v11): returns True when this frame is FRESH, burning its
        (seq, bucket) at receive time in wire order.  Whole-tree frames
        keep the high-water rule; a bucketed frame is fresh while its
        seq is above the high-water mark and its bucket unseen for that
        seq — when the last bucket of a seq burns, the high-water mark
        advances and the per-seq set retires, so a late wire-duplicated
        bucket still reads as a duplicate through the cheap rule."""
        with self._rank_lock:
            last = self._last_seq.get(rank, -1)
            if seq <= last:
                return False
            if n_buckets <= 1:
                self._last_seq[rank] = seq
                # A whole-tree frame above the mark retires any
                # in-flight bucketed seqs at or below it.
                seen = self._bucket_seen.get(rank)
                if seen:
                    for s in [s for s in seen if s <= seq]:
                        del seen[s]
                return True
            seen = self._bucket_seen.setdefault(rank, {})
            got = seen.setdefault(seq, set())
            if bucket in got:
                return False
            got.add(bucket)
            if len(got) >= n_buckets:
                # Seq complete: fold into the high-water rule.
                self._last_seq[rank] = max(last, seq)
                del seen[seq]
            elif len(seen) > _BUCKET_SEQ_WINDOW:
                # Bounded in-flight seq memory: retire the oldest.
                del seen[min(seen)]
            return True

    def _recv_arena_hint(self) -> int:
        """Pre-size each per-connection recv-arena slot to the expected
        GRAD frame: the compiled code tree's per-leaf bytes (a fleet
        shard's plan already sliced the tree, so this is the SHARD's
        expectation) plus framing slack.  Before compile — a standby's
        accept surface — the arena starts small and grows to the
        largest frame seen."""
        meta = getattr(self, "_code_leaf_meta", None)
        if not meta:
            return 1 << 16
        total = sum(
            int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
            for shape, dt in meta)
        return int(total) + 256 * len(meta) + 4096

    def _parm_payload(self):
        """Encode-once PARM fanout (v9): ``(version, meta_blob,
        segments)`` for the CURRENT served version — encoded by the
        first pull that lands at that version (counted
        ``parm_encodes``), reused by every later one at the same
        version (``parm_fanout_reuse``).  The snapshot read races the
        serve loop's leaf-wise publish exactly like the old per-PULL
        ``dumps`` did: the inconsistent read IS the AsySG-InCon
        algorithm, now paid once per version instead of once per
        request."""
        with self._parm_lock:
            version = self._served_version
            cache = self._parm_cache
            fresh = cache is None or cache[0] != version
            if fresh:
                leaves = OrderedDict(
                    (n, self._served[n]) for n in self._served)
                # v12: the wire codec runs HERE, inside the encode-once
                # cache — one cast/quantize per version, fanned out to
                # every reader.  Identity returns `leaves` unchanged
                # (same aliasing as before; zero-copy segments hold).
                wire = _codecs.encode_wire_tree(self._wire_codec, leaves)
                meta_blob, segs = serializer.encode_segments(
                    wire, level=self.wire_level)
                cache = (version, meta_blob, segs)
                self._parm_cache = cache
                raw = _codecs.tree_raw_nbytes(leaves)
                if self._delta_parm:
                    # Ring entry = the POST-DECODE tree (what a reader
                    # holds after decoding this frame) so server-side
                    # diffs match reader-side patches bitwise.  Identity
                    # aliases the served leaves (the serve loop rebinds,
                    # never mutates).
                    if self._wire_codec_id == 0:
                        decoded = leaves
                    else:
                        decoded = _codecs.decode_wire_tree(
                            self._wire_codec_id, wire)
                    ring = self._delta_ring
                    ring[version] = decoded
                    while len(ring) > _DELTA_RING:
                        old, _ = ring.popitem(last=False)
                        for key in [k for k in self._delta_cache
                                    if k[0] == old]:
                            del self._delta_cache[key]
        if fresh:
            self._bump("parm_encodes")
            self._bump("parm_bytes_raw", raw)
            self._bump("parm_bytes_wire", cache[2].wire_len)
        else:
            self._bump("parm_fanout_reuse")
        return cache

    def _delta_payload(self, have: int):
        """One encoded DELTA (``meta_blob, segs``) for a subscriber at
        version ``have``, or None = ring miss / not-worth-it (caller
        serves the full compressed snapshot).  Rides the same
        encode-once discipline as `_parm_payload`: the diff for a given
        (have, version) pair is computed once and fanned out."""
        version, meta_blob, segs = self._parm_payload()
        cached = (None, None)
        with self._parm_lock:
            base = self._delta_ring.get(have)
            cur = self._delta_ring.get(version)
            if (version == self._served_version and base is not None
                    and cur is not None and have != version):
                cached = self._delta_cache.get((have, version))
                if cached is None:
                    delta, nbytes = _codecs.diff_wire_delta(base, cur)
                    # A delta bigger than the full frame serves nobody.
                    if nbytes >= segs.wire_len:
                        cached = (None, None)
                    else:
                        cached = serializer.encode_segments(
                            delta, level=self.wire_level)
                    self._delta_cache[(have, version)] = cached
        hit = cached[0] is not None
        self._bump("delta_hits" if hit else "delta_misses")
        return (version, *cached) if hit else None

    # -- the per-connection decode pipeline (v9) ------------------------------

    def _decode_codes(self, payload):
        """CRC-verify + decompress + validate one GRAD/AGGR payload —
        the work the decode pool runs off the conn thread (the native
        tree decode releases the GIL)."""
        codes = serializer.loads(payload)
        self._validate_codes(codes)
        return codes

    def _decode_codes_bucket(self, payload):
        """The bucket-frame decode (v11): same CRC/decompress pipeline,
        validated as a PARTIAL tree (per-name structure; completeness is
        the assembler's job)."""
        codes = serializer.loads(payload)
        self._validate_codes_bucket(codes)
        return codes

    def _finish_decode(self, decodes) -> None:
        """Complete the OLDEST in-flight decode and enqueue its item —
        FIFO, so enqueue order stays receive order per connection.  A
        bucket frame (``binfo`` set) routes through the assembler
        instead: it enqueues only when its (rank, seq) completes."""
        fut, tail, rank, _frame, binfo = decodes.popleft()
        try:
            codes = fut.result()
        except Exception:
            self._bump("quarantined_frames")
            raise
        if binfo is None:
            self._enqueue_grad((codes, *tail), rank)
        else:
            self._assemble_bucket(binfo, codes, tail, rank)

    def _assemble_bucket(self, binfo, codes, tail, rank) -> None:
        """Fold one decoded bucket into its (rank, seq) assembly; when
        every bucket of the seq has landed, merge the sub-trees in
        canonical param order and enqueue the gradient — which then
        enters `_fill_gradients` exactly like a whole-tree frame (so
        interleaved streams from many ranks fill rank-distinct, quorum
        and staleness admission unchanged).  Decode of bucket b runs
        while bucket b+1 is still on the wire (the `_dispatch_decode`
        pipeline); assembly itself is dict bookkeeping.

        Partial-assembly retirement (the bucket-stream analogue of the
        quorum's late-fold): completing a NEWER seq retires any older
        incomplete assembly from the same rank (its missing buckets
        were shed or lost — they can never arrive now that `_burn_seq`
        advanced the high-water mark), counted
        ``bucket_partial_timeouts``; the absent gradient is exactly a
        straggler the quorum/deadline machinery already absorbs, and
        the rank's next completed gradient late-folds."""
        assembler, seq, bucket, n_buckets, on_complete = binfo
        key = (rank, seq)
        entry = assembler.get(key)
        if entry is None:
            entry = assembler[key] = {"n": int(n_buckets), "parts": {},
                                      "tail": tail}
            if len(assembler) > _ASSEMBLY_CAP:
                oldest = min(assembler,
                             key=lambda k: (k[1], k[0] is None, k[0]))
                if oldest != key:
                    del assembler[oldest]
                    self._bump("bucket_partial_timeouts")
        entry["parts"][bucket] = codes
        if len(entry["parts"]) < entry["n"]:
            return
        del assembler[key]
        for stale_key in [k for k in assembler
                          if k[0] == rank and k[1] < seq]:
            del assembler[stale_key]
            self._bump("bucket_partial_timeouts")
        flat: dict = {}
        for sub in entry["parts"].values():
            flat.update(sub)
        if set(flat) != set(self.params):
            # Structurally valid buckets whose union is not the tree:
            # worker bucket plan disagrees with this server's params.
            self._bump("quarantined_frames")
            raise ValueError(
                f"assembled bucket stream covers {len(flat)} parameter(s) "
                f"but this server owns {len(self.params)} — worker bucket "
                f"plan does not match the served tree")
        merged = OrderedDict((n, flat[n]) for n in self.params)
        self._bump("buckets_filled", entry["n"])
        if on_complete is not None:
            # Deferred per-GRADIENT bookkeeping (the AGGR groups view /
            # agg_frames contract counts assembled gradients, never
            # bucket frames).
            on_complete()
        self._enqueue_grad((merged, *entry["tail"]), rank)

    def _dispatch_decode(self, decodes, payload, tail,
                         rank: "int | None", frame_idx: int,
                         binfo=None) -> None:
        """Decode one admitted GRAD/AGGR payload and enqueue
        ``(codes, *tail)``: multi-MB frames go through the off-GIL
        decode pool (counted ``decode_offloaded``), pipelined at most
        `_DECODE_DEPTH` deep per connection; small frames decode inline
        (pool dispatch would dominate them).  ``frame_idx`` is the
        arena's receive count at dispatch — the conn loop's pre-receive
        drain uses it to finish any in-flight decode whose payload view
        is about to fall out of the RecvArena rotation window (depth
        alone is not enough: control frames rotate the ring too).
        ``binfo`` (v11) marks a bucket frame: ``(assembler, seq,
        bucket, n_buckets, on_complete)`` — decoded like any frame
        (pipelined, so bucket b decodes while b+1 is in flight), then
        routed through `_assemble_bucket` instead of enqueued."""
        decode = (self._decode_codes if binfo is None
                  else self._decode_codes_bucket)
        if (self._decode_offload_min is not None
                and payload.nbytes >= self._decode_offload_min):
            while len(decodes) >= _DECODE_DEPTH:
                self._finish_decode(decodes)
            decodes.append(
                (self._decode_pool.submit(decode, payload),
                 tail, rank, frame_idx, binfo))
            self._bump("decode_offloaded")
            while decodes and decodes[0][0].done():
                self._finish_decode(decodes)
            return
        while decodes:  # keep per-connection enqueue order
            self._finish_decode(decodes)
        try:
            codes = decode(payload)
        except Exception:
            # The v8 blob path counted every corrupt payload; the
            # inline decode must too (the offloaded path counts in
            # `_finish_decode`) — the conn teardown that follows is
            # otherwise invisible in the quarantine accounting.
            self._bump("quarantined_frames")
            raise
        if binfo is None:
            self._enqueue_grad((codes, *tail), rank)
        else:
            self._assemble_bucket(binfo, codes, tail, rank)

    # The queued item's decoded code tree is zero-copy views into the
    # serializer's decode arena — ownership rides INTO the queue with
    # the item (the conn thread never touches the arena again), which
    # is exactly why the serve loop may consume it at any later fill.
    # pslint: transfers-ownership
    def _enqueue_grad(self, item, rank: "int | None",
                      patience: "float | None" = None) -> bool:
        """Bounded put with backpressure; a gradient abandoned because
        the run is shutting down — or stuck behind a full queue past
        the patience budget (an overloaded consumer) — is COUNTED,
        surfaced LIVE via a rate-limited warning, and reported once per
        worker at run end (with the drop RATE in the snapshot).  The
        default patience is ``conn_timeout`` — the same budget a silent
        PEER gets before costing its connection — so a benign serve-loop
        pause (a long checkpoint write) never drops gradients that mere
        blocking would have delivered."""
        wait = Deadline(self.conn_timeout if patience is None
                        else patience)
        while not self._net_stop.is_set() and not wait.expired():
            try:
                self._net_queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        now = time.monotonic()
        with self._stats_lock:
            d = self.fault_stats["dropped_queue_full"]
            key = -1 if rank is None else rank
            d[key] = d.get(key, 0) + 1
            total = sum(d.values())
            warn = now - self._last_drop_warn > 5.0
            if warn:
                self._last_drop_warn = now
        if warn:
            # At DROP time, not only at run end: a live overloaded run
            # must be diagnosable while it is overloaded.
            print(f"async PS warning: net queue full — {total} "
                  f"gradient(s) dropped so far (consumer overloaded or "
                  f"shutting down; see dropped_queue_full_rate in "
                  f"fault_stats)", file=sys.stderr)
        return False

    def _conn_loop(self, conn: socket.socket):
        """Serve one connection.  Any failure — disconnect, malformed frame,
        stray port-scanner bytes — is connection-LOCAL: it closes this
        socket, bumps the drop counters, and never aborts the training run
        (a bad peer must not be able to kill the whole job).  A frame that
        fails its CRC is even cheaper on an authenticated worker
        connection: the frame is dropped and counted, the connection
        lives on (up to a bounded consecutive streak)."""
        authed = self.token is None  # no token -> every connection served
        rank: "int | None" = None
        is_sub = False  # subscriber conn (HELO flag 32): subs_active gauge
        crc_streak = 0
        # Preallocated recv ring (v9): every frame recv_into one of the
        # arena's rotating slots — `msg`/`body` below are zero-copy
        # VIEWS into it, valid for nbufs-1 further receives (anything
        # retained longer — the REPL blob — is bytes()-materialized;
        # GRAD/AGGR decode views are bounded by `_DECODE_DEPTH`).
        arena = _transport.RecvArena(self._recv_arena_hint())
        decodes: "deque" = deque()
        # Bucket-stream assemblies (v11), conn-local like the decode
        # pipeline: (rank, seq) -> {n, parts{bucket: codes}, tail}.
        assembler: dict = {}
        try:
            with conn:
                if self.conn_timeout:
                    conn.settimeout(self.conn_timeout)
                try:
                    # Small control frames (PULL, credit replenishes)
                    # must not wait out Nagle behind a multi-MB reply.
                    conn.setsockopt(socket.IPPROTO_TCP,
                                    socket.TCP_NODELAY, 1)
                except OSError:
                    pass  # non-TCP test sockets (socketpair)
                while True:
                    # Rotation-window guard: an offloaded decode's
                    # payload view is valid for nbufs-1 further
                    # receives, and EVERY frame rotates the ring —
                    # control frames (PULL/BEAT/REPL) included, which
                    # never pass through `_dispatch_decode`'s depth
                    # bound.  Finish any in-flight decode whose slot
                    # the upcoming recv_into would overwrite.
                    while (decodes and arena.frames - decodes[0][3]
                            >= arena.window):
                        self._finish_decode(decodes)
                    try:
                        msg = arena.recv_frame(conn)
                    except FrameCRCError:
                        # Frame-local quarantine (the length prefix kept
                        # the stream aligned) — but only for a BOOKED
                        # worker's link and only up to a bounded streak:
                        # an unauthenticated peer or a long run of bad
                        # CRCs is a broken/hostile client, not a bit
                        # flip, and must not pin this handler thread.
                        self._bump("crc_dropped")
                        crc_streak += 1
                        if rank is None or crc_streak > 16:
                            raise
                        continue
                    crc_streak = 0
                    kind, body = bytes(msg[:4]), msg[4:]
                    if kind == b"HELO":
                        flags = body[0] if body else 0
                        off = 1 if body else 0
                        prior: "int | None" = None
                        assigned: "int | None" = None
                        agg_group: "int | None" = None
                        agg_target = 0
                        fb_group: "int | None" = None
                        if flags & 1:
                            (prior,) = struct.unpack_from("<I", body, off)
                            off += 4
                        elif flags & 2:
                            (assigned,) = struct.unpack_from(
                                "<I", body, off)
                            off += 4
                        if flags & 8:
                            # Aggregator identity: this connection IS
                            # group g's local aggregator (v7).
                            agg_group, agg_target = struct.unpack_from(
                                "<HH", body, off)
                            off += 4
                        if flags & 16:
                            # Direct-fallback identity: a worker of group
                            # g whose aggregator died un-restorably,
                            # re-admitting itself as a plain rank (v7).
                            (fb_group,) = struct.unpack_from(
                                "<H", body, off)
                            off += 2
                        if self.token is not None:
                            import hmac

                            if not hmac.compare_digest(
                                    bytes(body[off:]),
                                    self.token.encode()):
                                _send_frame(conn, b"NOAU")
                                raise ValueError("bad admission token")
                        authed = True
                        if flags & 32 and not is_sub:
                            # Subscriber identity (v10): a serve-tier
                            # READER.  Rank-less like a control conn
                            # (readers must not pollute worker identity,
                            # eviction, or the effective quota), tracked
                            # in the ``subs_active`` gauge for the
                            # lifetime of the connection.
                            is_sub = True
                            self._bump("subs_active")
                        if flags & (4 | 32):
                            # Control connection (fleet supervisor's
                            # SNAP/PROM markers, the primary's REPL
                            # stream) or a v10 subscriber: authenticated
                            # but RANK-LESS — it must not pollute worker
                            # identity, eviction, or the workers_seen
                            # diagnostics.
                            rank = None
                        else:
                            rank = self._register_conn(prior, assigned)
                            if agg_group is not None:
                                self._note_aggregator(agg_group, rank,
                                                      agg_target)
                            if fb_group is not None and prior is None:
                                # A fallback RECONNECT (prior set) is the
                                # same worker riding a blip — only the
                                # first direct admission counts.
                                self._note_fallback(fb_group, rank)
                        # The PSA reply (layout in the module docstring):
                        # the magic/version prefix gives a cross-version
                        # peer an explicit error instead of a misleading
                        # parse of later fields (r4 advisor); the auth
                        # flag lets a token-bearing worker detect a
                        # non-enforcing server; the shard triple lets a
                        # plain worker refuse a fleet shard and a router
                        # refuse a digest-mismatched fleet; the credit
                        # window (v8) seeds the sender's flow gate.
                        _send_frame(conn, b"PSA"
                                    + bytes([PROTOCOL_VERSION])
                                    + struct.pack("<I",
                                                  _CONTROL_RANK
                                                  if rank is None else rank)
                                    + (b"\x01" if self.token is not None
                                       else b"\x00")
                                    + struct.pack("<HHQ",
                                                  self._shard_index,
                                                  self._shard_count,
                                                  self._plan_digest)
                                    + _U32.pack(self._advertised_credits())
                                    + bytes([_WIRE_SEGMENTED])
                                    + self.code.name.encode())
                    elif not authed:
                        # Handshake-skipping peer: the token must gate
                        # EVERY message, not just HELO.
                        raise ValueError(
                            f"{kind!r} before authenticated HELO")
                    elif kind == b"BEAT":
                        if rank is not None:
                            self._mark_alive(rank)
                    elif kind == b"SPLN":
                        # Shard-plan fetch (`shard.ShardRouter` at connect
                        # time): the fleet's full plan, so the worker
                        # adopts the authoritative split instead of
                        # recomputing one that could silently differ.
                        # Empty reply on an unsharded PS.
                        if rank is not None:
                            self._mark_alive(rank)
                        _send_frame(conn, b"SPLN" + self._plan_json)
                    elif kind == b"REPL":
                        # Hot-standby replication: stash the newest blob
                        # as BYTES (no jax on a handler thread —
                        # promotion deserializes) and ack.  Refused on a
                        # non-standby and after the PROM fence (a zombie
                        # primary across a partition must not write into
                        # the promoted standby's past).
                        (step,) = _U64.unpack_from(body, 0)
                        # v12: the primary's wire-codec byte rides the
                        # frame; stashed WITH the blob so promotion
                        # decodes the arrays it actually received even
                        # across a primary restart with a new codec.
                        (repl_codec,) = _U8.unpack_from(body, _U64.size)
                        with self._repl_lock:
                            fenced = self._promoted
                            if not fenced and self._standby:
                                self._repl_step = step
                                self._repl_codec = repl_codec
                                # Materialized: the stash outlives this
                                # frame's recv-arena slot (the PSL703
                                # refill discipline — a retained view
                                # would silently become a LATER frame).
                                self._repl_blob = bytes(
                                    body[_U64.size + _U8.size:])
                        if fenced:
                            # Checked FIRST: a promoted successor is no
                            # longer a standby, but its zombie primary's
                            # stream must still count as the fence
                            # refusal it is, not as a stray peer.
                            self._bump("repl_refused")
                            raise ValueError(
                                "standby already promoted — replication "
                                "stream fenced off")
                        if not self._standby:
                            self._bump("quarantined_frames")
                            raise ValueError(
                                "REPL sent to a non-standby server")
                        self._bump("repl_received")
                        # The ack doubles as the replication stream's
                        # credit replenish (v8) — REPL is a DATA frame.
                        _send_frame(conn, b"ACKR" + _U64.pack(step)
                                    + _U32.pack(self._advertised_credits()))
                    elif kind == b"SNAP":
                        # Coordinated-snapshot marker: arm a checkpoint
                        # at EXACTLY fill boundary `cut` (consumed by
                        # `_at_fill_boundary` on the serve thread).  A
                        # cut this shard has already reached cannot be
                        # honored — ack 0 so the supervisor re-proposes
                        # a later one instead of waiting forever.
                        (cut,) = _U64.unpack_from(body, 0)
                        with self._stats_lock:
                            armable = (not self._standby
                                       and self._snap_path is not None
                                       and cut > self._fill_next_step)
                            if armable:
                                self._snap_cuts.add(cut)
                        _send_frame(conn, b"SNAP"
                                    + _U64.pack(cut if armable else 0))
                    elif kind == b"PROM":
                        # Promotion fence: only a standby of the SAME
                        # fleet (plan digest) may be promoted; the reply
                        # carries the replicated step the supervisor
                        # resumes serving from.  Fencing is permanent —
                        # every later REPL is refused.
                        if not self._standby:
                            self._bump("quarantined_frames")
                            raise ValueError(
                                "PROM sent to a non-standby server")
                        (digest,) = _U64.unpack_from(body, 0)
                        if digest != self._plan_digest:
                            raise ValueError(
                                f"PROM plan digest {digest:#x} does not "
                                f"match this standby's "
                                f"{self._plan_digest:#x} — wrong fleet")
                        with self._repl_lock:
                            self._promoted = True
                            step = self._repl_step
                        _send_frame(conn, b"PROM" + _U64.pack(
                            _NO_REPLICA if step is None else step))
                    elif kind == b"PULL":
                        if rank is not None:
                            self._mark_alive(rank)
                        if self._net_stop.is_set():
                            if self._dying:
                                return  # crash: vanish, like a real kill -9
                            _send_frame(conn, b"DONE")
                            return
                        # Conditional pull (v9): a worker already at the
                        # served version gets a head-only "unchanged"
                        # reply — no encode, no multi-MB transfer, no
                        # decode at its end.
                        have = None
                        if len(body) >= _U64.size:
                            (have,) = _U64.unpack_from(body, 0)
                        version_now = self._served_version
                        if have is not None and have == version_now:
                            _send_frame(conn, b"PARM"
                                        + _U64.pack(version_now)
                                        + _U32.pack(
                                            self._advertised_credits())
                                        + _U8.pack(self._wire_codec_id))
                            self._bump("parm_unchanged")
                            continue
                        # Encode-once fanout (v9): the served snapshot
                        # is encoded per VERSION (`_parm_payload`), and
                        # this pull gather-sends the cached segment set
                        # — only the tiny head (version + the per-reply
                        # credit field: each pull is also a flow-control
                        # replenish) is built per request.
                        version, meta_blob, segs = self._parm_payload()
                        head = (b"PARM" + _U64.pack(version)
                                + _U32.pack(self._advertised_credits())
                                + _U8.pack(self._wire_codec_id))
                        _transport.send_frame_segments(
                            conn, [head, meta_blob, *segs],
                            cached=(segs.wire_crc, segs.wire_len))
                        self._bump("segments_sent", len(segs) + 2)
                    elif kind == b"SUBS":
                        # Versioned snapshot subscription (v10, the
                        # serve tier's read path): conditional like a
                        # PULL — ``have`` at the served version answers
                        # head-only "unchanged" — but READ-class: a
                        # full-payload reply costs a read token, and an
                        # exhausted budget sheds head-only (the reader
                        # flood pays HERE, never in the GRAD path).
                        # Payload replies fan out the encode-once PARM
                        # cache: N subscribers cost one encode per
                        # version, like N pulling workers.
                        if self._standby:
                            self._bump("quarantined_frames")
                            raise ValueError(
                                "SUBS sent to a standby server — "
                                "standbys hold replicated blobs, not a "
                                "served snapshot; subscribe to the "
                                "primary")
                        if self._net_stop.is_set():
                            if self._dying:
                                return  # crash: vanish, like a real kill
                            _send_frame(conn, b"DONE")
                            return
                        have = _UNVERSIONED
                        if len(body) >= _U64.size:
                            (have,) = _U64.unpack_from(body, 0)
                        # Counters bump BEFORE the reply hits the wire:
                        # a reader acts on the reply the instant it
                        # lands, and its view of the server's counters
                        # must never lag its own observation of the
                        # event (the conn thread may be descheduled
                        # between send and bump on a busy host).
                        version_now = self._served_version
                        if have == version_now:
                            self._bump("reads_served")
                            _send_frame(
                                conn, b"DELT" + _U64.pack(version_now)
                                + _U32.pack(self._advertised_read_credits())
                                + bytes([_DELT_UNCHANGED])
                                + _U8.pack(self._wire_codec_id))
                            continue
                        if not self._take_read_token():
                            # READ-class shed: head-only, token-free —
                            # under a reader flood this reply is the
                            # cheap path, and it re-advertises the live
                            # (zero) window so the reader's sender-side
                            # gate closes too.
                            self._bump("read_shed")
                            _send_frame(
                                conn, b"DELT" + _U64.pack(version_now)
                                + _U32.pack(0) + bytes([_DELT_SHED])
                                + _U8.pack(self._wire_codec_id))
                            continue
                        # Delta serving (v12): a subscriber whose
                        # presented version is still in the ring gets a
                        # sparse diff instead of the full snapshot —
                        # bytes proportional to change.  Any miss (ring
                        # evicted, redial's _UNVERSIONED, raced publish,
                        # delta not smaller) falls through to the full
                        # compressed frame; correctness never depends
                        # on the ring.
                        dpay = None
                        if self._delta_parm and have != _UNVERSIONED:
                            dpay = self._delta_payload(have)
                        if dpay is not None:
                            version, meta_blob, segs = dpay
                            dflags = _DELT_DELTA
                        else:
                            version, meta_blob, segs = self._parm_payload()
                            dflags = 0
                        # A DISTINCT local for the segmented head: the
                        # drift checker resolves iovec head bindings
                        # per enclosing function, and `_conn_loop`
                        # already binds `head` for the PARM reply.
                        dhead = (b"DELT" + _U64.pack(version)
                                 + _U32.pack(self._advertised_read_credits())
                                 + bytes([dflags])
                                 + _U8.pack(self._wire_codec_id))
                        self._bump("reads_served")
                        self._bump("delta_frames")
                        self._bump("segments_sent", len(segs) + 2)
                        _transport.send_frame_segments(
                            conn, [dhead, meta_blob, *segs],
                            cached=(segs.wire_crc, segs.wire_len))
                    elif kind == b"GRAD":
                        if rank is not None:
                            self._mark_alive(rank)
                        try:
                            bucket, n_buckets = _BKT.unpack_from(body, 0)
                            seq = _U64.unpack_from(body, _BKT.size)[0]
                            version = _U64.unpack_from(
                                body, _BKT.size + _U64.size)[0]
                            loss = _F64.unpack_from(
                                body, _BKT.size + 2 * _U64.size)[0]
                            if n_buckets < 1 or bucket >= n_buckets:
                                raise ValueError(
                                    f"bad bucket header "
                                    f"({bucket}/{n_buckets})")
                        except Exception:
                            self._bump("quarantined_frames")
                            raise
                        if self._shed_before_decode(rank, seq, version,
                                                    bucket, n_buckets):
                            continue
                        if rank is not None:
                            # Per-rank monotone dedup, HEADER-FIRST (v9)
                            # and bucket-aware (v11): the (seq, bucket)
                            # burns at RECEIVE time, in wire order, so
                            # pipelined decodes may complete out of
                            # order without a fresh frame ever reading
                            # as a duplicate — and a duplicate never
                            # pays a decode at all.
                            if not self._burn_seq(rank, seq, bucket,
                                                  n_buckets):
                                self._bump("duplicate_dropped")
                                continue
                        binfo = None
                        if n_buckets > 1:
                            binfo = (assembler, seq, int(bucket),
                                     int(n_buckets), None)
                        self._dispatch_decode(
                            decodes,
                            body[_BKT.size + 2 * _U64.size + _F64.size:],
                            (version, rank, loss), rank, arena.frames,
                            binfo)
                    elif kind == b"AGGR":
                        # Hierarchical forward (v7): admitted like a
                        # GRAD (same validation/dedup/fill loop) but the
                        # item carries the contributor multiplicity, so
                        # the root weights it by the gradients it folds.
                        if rank is not None:
                            self._mark_alive(rank)
                        try:
                            group, n_contrib, gtarget = _GRP.unpack_from(
                                body, 0)
                            bucket, n_buckets = _BKT.unpack_from(
                                body, _GRP.size)
                            seq = _U64.unpack_from(
                                body, _GRP.size + _BKT.size)[0]
                            version = _U64.unpack_from(
                                body, _GRP.size + _BKT.size + _U64.size)[0]
                            loss = _F64.unpack_from(
                                body,
                                _GRP.size + _BKT.size + 2 * _U64.size)[0]
                            if n_buckets < 1 or bucket >= n_buckets:
                                raise ValueError(
                                    f"bad bucket header "
                                    f"({bucket}/{n_buckets})")
                        except Exception:
                            self._bump("quarantined_frames")
                            raise
                        if self._shed_before_decode(rank, seq, version,
                                                    bucket, n_buckets):
                            continue
                        if rank is not None:
                            # Header-first dedup, like GRAD (v9/v11).
                            if not self._burn_seq(rank, seq, bucket,
                                                  n_buckets):
                                self._bump("duplicate_dropped")
                                continue
                        binfo = None
                        if n_buckets > 1:
                            # Per-GRADIENT bookkeeping defers to
                            # assembly completion: agg_frames and the
                            # groups view count assembled forwards,
                            # never bucket frames (the root-traffic
                            # contract: one AGGR per group fill).
                            def _aggr_done(g=group, r=rank,
                                           nc=n_contrib):
                                if r is not None:
                                    self._note_group_frame(g, r, nc)
                                self._bump("agg_frames")
                            binfo = (assembler, seq, int(bucket),
                                     int(n_buckets), _aggr_done)
                        else:
                            if rank is not None:
                                self._note_group_frame(group, rank,
                                                       n_contrib)
                            self._bump("agg_frames")
                        self._dispatch_decode(
                            decodes,
                            body[_GRP.size + _BKT.size + 2 * _U64.size
                                 + _F64.size:],
                            (version, rank, loss,
                             float(max(int(n_contrib), 1))), rank,
                            arena.frames, binfo)
                    else:
                        self._bump("quarantined_frames")
                        raise ValueError(f"unknown message kind {kind!r}")
        except ConnectionError:
            pass  # normal worker departure (DONE'd or finished its pushes)
        except Exception as exc:
            # Locked: handler threads drop concurrently, and the serve
            # loop reads these for its idle-timeout diagnostic — an
            # unlocked += here can lose increments.
            with self._stats_lock:
                self._conn_drops += 1
                self._last_drop = exc
        finally:
            # Best-effort drain of in-flight decodes: gradients already
            # received (and seq-burned) should reach the queue even when
            # the connection died right after delivering them.
            while decodes:
                try:
                    self._finish_decode(decodes)
                except Exception:
                    break
            if assembler:
                # Partial bucket assemblies die with the connection: the
                # missing buckets can never arrive on a new socket (a
                # reconnecting worker computes a FRESH gradient with a
                # fresh seq, never resends old frames).  Counted — the
                # absent gradient is a straggler the quorum machinery
                # absorbs.
                self._bump("bucket_partial_timeouts", len(assembler))
            if rank is not None:
                self._release_conn(rank)
            if is_sub:
                # The subs_active gauge tracks LIVE subscriber conns.
                self._bump("subs_active", -1)

    # -- checkpoint / resume --------------------------------------------------

    def load_state_dict(self, sd: dict) -> None:
        super().load_state_dict(sd)
        # Republish: remote PULLs read the serving snapshot, which must
        # reflect the restored params, not the construction-time ones.
        self._served = {n: np.asarray(p) for n, p in self.params.items()}
        # The encode-once PARM cache is stale now even if the restored
        # version NUMBER matches (resume/promotion replaced the bytes).
        # The delta ring and its encoded-diff cache go with it: their
        # trees describe PRE-restore versions, and serving a diff across
        # the restore would patch a reader onto bytes the server never
        # published — every subscriber's next read must be a full frame
        # (the forced-full-after-failover rule, server side).
        with self._parm_lock:
            self._parm_cache = None
            self._delta_ring.clear()
            self._delta_cache.clear()

    def _resume_extra(self) -> dict:
        """The serve-continuity extras every durable copy of this server
        carries — auto-checkpoints AND the replication stream: the
        serving version counter (continuous staleness accounting) and the
        rank-allocation state (no post-takeover rank collisions)."""
        # Rank-allocation state is written by handler threads (HELO
        # booking) — snapshot it under its lock so a checkpoint cut
        # mid-handshake can't persist a torn pair.
        with self._rank_lock:
            next_rank, workers_seen = self._next_rank, self._workers_seen
        return {"served_version": self._served_version,
                "next_rank": next_rank,
                "workers_seen": workers_seen}

    def _apply_resume_extra(self, extra: dict) -> None:
        """Apply `_resume_extra` output — shared by checkpoint resume and
        standby promotion, so the two recovery paths cannot drift on what
        serve-continuity state they restore."""
        # Restoring the version counter keeps reconnecting workers'
        # staleness accounting continuous across the crash (a restart from
        # 0 would make every surviving gradient look future-dated).
        self._served_version = int(extra.get("served_version") or 0)
        # Rank allocation survives too: a fresh worker must not be
        # minted a rank a survivor is about to re-book via prior_rank
        # (a shared rank conflates per-rank accounting), and the
        # idle-timeout diagnostic keeps its worker history.
        with self._rank_lock:
            self._next_rank = max(self._next_rank,
                                  int(extra.get("next_rank") or 0))
            self._workers_seen = max(self._workers_seen,
                                     int(extra.get("workers_seen") or 0))

    def resume_from(self, path) -> int:
        """Restore optimizer state + the serving version counter from an
        auto-checkpoint (see ``serve(checkpoint_every=...)``).  Returns the
        global step to continue from — pass it back as ``start_step``."""
        from .utils import checkpoint as _checkpoint

        info = _checkpoint.load_optimizer(path, self)
        self._apply_resume_extra(info.get("extra") or {})
        return int(info.get("step") or 0)

    def _auto_checkpoint(self, path, step: int) -> None:
        from .utils import checkpoint as _checkpoint

        _checkpoint.save_optimizer(path, self, step=step,
                                   extra=self._resume_extra())

    # -- hot-standby replication (primary side) -------------------------------

    def _replicate(self, step: int) -> None:
        """Stream the post-update state to the standby as one REPL frame
        and consume the ACKR.  Best-effort by design: a dead standby
        costs a growing ``repl_lag`` gauge and a redial next cadence,
        never the serve loop.  The stream rides a credit-gated session
        (REPL is a DATA frame): a standby that stops acking stops
        granting credits, and the primary sheds replication payloads
        (counted) instead of blocking in sendall."""
        from .utils import checkpoint as _checkpoint

        # v12: the wire codec rides the replication stream too — the
        # array payload (the multi-MB part) compresses, the pickled meta
        # stays exact, and the codec byte tells the standby how to
        # decode at promotion.  On-disk auto-checkpoints stay f32.
        wire_encode = None
        if self._wire_codec_id != 0:
            wire_encode = (lambda tree: _codecs.encode_wire_tree(
                self._wire_codec, tree))
        blob = _checkpoint.dump_optimizer_bytes(
            self, step=step, extra=self._resume_extra(),
            wire_encode=wire_encode)
        dl = Deadline(self.op_deadline)
        try:
            if self._repl_session is None:
                host, port = self.replica_addr
                sock = control_connect(host, port, token=self.token,
                                       timeout=5.0)
                self._repl_session = Session(
                    sock, io_timeout=5.0, max_pending=1,
                    stall_hook=lambda: self._bump("credits_stalled"),
                    shed_hook=lambda: self._bump("shed_data_frames"))
            sent = self._repl_session.send_data(
                b"REPL" + _U64.pack(step)
                + _U8.pack(self._wire_codec_id) + blob, deadline=dl)
            if sent:
                reply = self._repl_session.recv(dl)
                if reply[:4] == b"ACKR":
                    (acked,) = _U64.unpack_from(reply, 4)
                    (credits,) = _U32.unpack_from(reply, 4 + _U64.size)
                    self._last_acked = max(self._last_acked, acked)
                    self._repl_session.replenish(credits)
                self._bump("repl_sent")
            else:
                # A zero-credit stall has NO in-band recovery on a
                # request/response stream: no REPL sent means no ACKR,
                # so no replenish would ever arrive and replication
                # would stay dead for the process lifetime (and a
                # parked frame flushed later would desync the send/ack
                # pairing).  Drop the session; the next cadence redials
                # and arrives ungated.
                self._repl_session.close()
                self._repl_session = None
        except _TRANSPORT_ERRORS + (ValueError,):
            # ValueError covers a fenced standby dropping the stream
            # (this primary is a zombie past a promotion) and protocol
            # refusals — none of them may kill the serve loop.
            # DeadlineExpired rides the same ladder (it IS an OSError),
            # with the expiry counted like every blown transport budget.
            if sys.exc_info()[0] is DeadlineExpired:
                self._bump("deadline_expired")
            if self._repl_session is not None:
                self._repl_session.close()
                self._repl_session = None
        with self._stats_lock:
            self.fault_stats["repl_lag"] = step - self._last_acked

    # -- hot-standby promotion (standby side; driven by shard.PSFleet) --------

    def replica_step(self) -> "int | None":
        """The newest replicated step this standby holds (None before the
        first REPL lands) — what the supervisor consults to decide
        promotion vs checkpoint-restore."""
        with self._repl_lock:
            return self._repl_step

    def promote_from_replica(self) -> "int | None":
        """Apply the replicated checkpoint blob to this (standby) server
        and fence the replication stream.  Returns the step to resume
        serving from, or None when nothing was ever replicated.  Called
        by the fleet supervisor AFTER the wire-level PROM fence; fencing
        here too keeps the latch correct even on the in-process fallback
        path."""
        with self._repl_lock:
            self._promoted = True
            step, blob = self._repl_step, self._repl_blob
            repl_codec = self._repl_codec
        if blob is None:
            return None
        from .utils import checkpoint as _checkpoint

        # v12: the blob's array payload rode the primary's wire codec
        # (the frame's codec byte, stashed with the blob) — decode it
        # back to f32 BEFORE applying, so the promoted server's
        # optimizer state is plain arrays like any resumed one.
        arrays, meta = _checkpoint.loads_tree(
            blob, with_meta=True, source="<replication stream>")
        arrays = _codecs.decode_wire_tree(repl_codec, arrays)
        info = _checkpoint.apply_optimizer(
            self, arrays, meta, source="<replication stream>")
        self._apply_resume_extra(info.get("extra") or {})
        # The successor IS a primary now: it must serve fills, arm SNAP
        # cuts (a fleet that promoted once must not silently lose its
        # coordinated snapshots), and replicate onward to its own fresh
        # standby.  Late REPL from the zombie primary stays refused via
        # the `_promoted` fence, which outlives the role change.
        self._standby = False
        return int(info.get("step") or 0)

    def rebind(self, port: int) -> None:
        """Move the listener to ``port`` — the promotion takeover step:
        reconnecting workers land on the successor without re-pointing.
        Call with the accept loop stopped."""
        try:
            self._listener.close()
        except OSError:  # pragma: no cover - close best-effort
            pass
        self._listener = socket.create_server((self._host, port))
        self.address = self._listener.getsockname()[:2]

    def _start_accept_thread(self) -> threading.Thread:
        """Run the accept loop without serve() — the standby's frame
        surface (REPL/PROM are conn-thread work); promotion stops it,
        rebinds, and serve() starts a fresh one."""
        t = threading.Thread(target=self._accept_loop, daemon=True,
                             name="async-ps-standby-accept")
        t.start()
        return t

    # -- coordinated snapshots (SNAP markers) ---------------------------------

    def applied_updates(self) -> int:
        """Updates applied so far (the current fill boundary) — what the
        fleet supervisor reads to propose a snapshot cut every shard is
        still short of."""
        with self._stats_lock:
            return self._fill_next_step

    # pslint: only-called-by(_fill_gradients)
    def _at_fill_boundary(self) -> None:
        """The snapshot hook: at the boundary before filling for update
        g, an armed cut == g means "g updates applied" is the agreed
        fleet-wide cut — write the step-tagged checkpoint NOW, before any
        new gradient moves this shard past it."""
        with self._stats_lock:
            boundary = self._fill_next_step
            due = boundary in self._snap_cuts
            if due:
                self._snap_cuts.discard(boundary)
            path = self._snap_path
        if due and path is not None:
            from .utils import checkpoint as _checkpoint

            self._auto_checkpoint(_checkpoint.step_path(path, boundary),
                                  boundary)
            self._bump("snapshot_barriers")

    # -- the PS loop ----------------------------------------------------------

    def serve(self, steps: int, log_every: int = 0,
              idle_timeout: float = 300.0, *,
              eviction_timeout: float = 30.0,
              dead_conn_grace: float = 2.0,
              checkpoint_path=None, checkpoint_every: int = 0,
              start_step: int = 0,
              warmup_steps: int = 0) -> dict[str, Any]:
        """Serve until ``steps`` updates have been applied, then stop (every
        subsequent PULL answers ``DONE``, shutting workers down).

        ``idle_timeout``: maximum seconds to wait between gradients —
        a dead (or never-started) fleet errors out loudly instead of
        hanging, the error-never-hang contract of the single-host
        variant.  ``eviction_timeout`` / ``dead_conn_grace``: a rank
        past the timeout with no frame, or past the grace with no live
        connection, is evicted and the effective quota clamps to the
        live fleet; a reconnecting worker re-books its rank and the
        quota grows back.  ``checkpoint_every``/``checkpoint_path``:
        atomic auto-checkpoint every N updates — a killed PS restarts,
        calls `resume_from`, and serves the remaining updates while
        surviving workers reconnect.  ``warmup_steps`` (benchmarking
        aid): updates counted before the steady-state clock starts —
        ``history["steady_wall_time"]`` then measures only the updates
        AFTER it (worker jit compilation and connection ramp-up land in
        the warmup window); all ``steps`` updates still run and appear
        in the history.

        Named ``serve`` rather than overriding `AsyncPS.run` — remote
        workers own their data, so the single-controller ``batch_fn``
        contract does not apply here."""
        if self._apply_fn is None:
            raise NotCompiledError(
                "call compile_step(loss_fn) before serve()")
        if checkpoint_every and not checkpoint_path:
            raise ValueError("checkpoint_every needs a checkpoint_path")
        import jax

        # A fresh serve un-latches the stop flag (reuse-after-serve); a
        # PERMANENT close() must win even against a serve() entered
        # after it fired (supervisor closing a sick fleet mid-restore),
        # so it rides the separate `_closed` latch honored promptly.
        if self._closed.is_set():
            raise FleetDeadError(
                "serve() called on a closed server — this PS was shut "
                "down permanently")
        self._net_stop.clear()
        accept = threading.Thread(target=self._accept_loop, daemon=True,
                                  name="async-ps-accept")
        accept.start()
        # Sub-second idle timeouts need a finer poll than the 0.5 s default.
        poll = min(0.5, max(idle_timeout / 4.0, 0.02))
        # The starvation guard (`_check_fill_starved`) fires on the same
        # patience budget as the fleet-dead diagnostic.
        self._idle_timeout = idle_timeout
        # Arm the coordinated-snapshot surface: SNAP markers write their
        # cut checkpoints as step-tagged siblings of the auto-checkpoint
        # path (no path = markers are refused with ack 0).
        with self._stats_lock:
            self._snap_path = checkpoint_path
            self._fill_next_step = start_step

        # One bounded receive attempt for the shared fill loop: sweep
        # evictions on quiet intervals, and error out loudly — never
        # hang — once the fleet has been silent past the idle
        # `Deadline` (restarted on every frame and fill boundary).
        idle = Deadline(idle_timeout)
        plan = self.fault_plan

        def receive(timeout):
            try:
                item = self._net_queue.get(timeout=timeout)
            except queue.Empty:
                if self._closed.is_set():
                    # close() mid-serve: fail NOW — new gradients are
                    # already refused; waiting out the idle deadline
                    # would only delay the error.
                    raise FleetDeadError(
                        "PS closed while serving — shutdown requested "
                        "before the run completed")
                self._evict_dead(eviction_timeout, dead_conn_grace)
                if idle.expired():
                    self._bump("deadline_expired")
                    with self._stats_lock:
                        conn_drops = self._conn_drops
                        last_drop = self._last_drop
                    with self._rank_lock:
                        workers_seen = self._workers_seen
                    detail = (f"; last dropped connection: {last_drop!r}"
                              if last_drop else "")
                    raise FleetDeadError(
                        f"no gradient received for "
                        f"{idle_timeout:.0f}s "
                        f"({workers_seen} workers ever "
                        f"connected, "
                        f"{conn_drops} connections "
                        f"dropped"
                        f"{detail}) — fleet dead or never "
                        f"started"
                    ) from last_drop
                return None
            idle.restart()
            if plan is not None and plan.slow_consumer > 0:
                # Overload injector: a slow consumer — the queue fills,
                # so the flow-control machinery under test engages.
                time.sleep(plan.slow_consumer)
                self._bump("slow_consumed")
            return item

        def drain_nowait():
            try:
                return self._net_queue.get_nowait()
            except queue.Empty:
                return None

        history: dict[str, Any] = {"losses": [], "staleness": [],
                                   "versions": [], "contributors": [],
                                   "grads_consumed": 0}
        t_start = time.perf_counter()
        t_steady = t_start
        self._serve_t0 = t_start
        try:
            for update in range(steps):
                if update == warmup_steps and warmup_steps > 0:
                    t_steady = time.perf_counter()
                gstep = start_step + update
                # The kill fires only if THIS serve() started before the
                # planned step: a supervised relaunch with --resume
                # lands at start_step == kill_ps_at, and re-firing there
                # would be an infinite crash loop — the plan means "die
                # once AT step k", not on every incarnation reaching k.
                if (self.fault_plan is not None
                        and self.fault_plan.should_kill_ps(gstep)
                        and (gstep > start_step or start_step == 0)):
                    from .utils.faults import SimulatedCrash
                    self._dying = True
                    raise SimulatedCrash(
                        f"FaultPlan: PS killed before update {gstep}")
                data: dict[str, float] = {}
                t0 = time.perf_counter()
                # Publish the fill boundary: `gstep` updates are applied,
                # the fill for update gstep starts now — what SNAP-marker
                # armability checks against, and what `_at_fill_boundary`
                # consumes inside the shared fill loop.
                with self._stats_lock:
                    self._fill_next_step = gstep
                # Sweep once per update too (not only on empty-queue ticks):
                # a busy queue must not starve eviction bookkeeping.
                self._evict_dead(eviction_timeout, dead_conn_grace)
                # Each update gets the full idle budget (a fill served
                # entirely from held-over frames must not inherit a stale
                # deadline from long ago).
                idle.restart()
                # Fill to the EFFECTIVE quota (`_fill_target`, re-read
                # per iteration so a mid-fill eviction shrinks it) with
                # quorum+deadline short-fill semantics — the shared
                # `AsyncPS._fill_gradients` loop.
                (batch_codes, stalenesses, losses, ranks, contribs,
                 fill_target, _short) = self._fill_gradients(
                    receive, drain_nowait,
                    current_version=lambda: self._served_version,
                    base_timeout=poll)
                data["comm_wait"] = time.perf_counter() - t0

                t0 = time.perf_counter()
                # Stack on the HOST (numpy), one device_put for the
                # whole tree: the per-leaf ``jnp.stack`` dispatch this
                # replaces cost ~1 ms of op-by-op jax overhead PER LEAF
                # per update — pure serve-loop tax on the wire path.
                stacked = jax.tree.map(
                    lambda *xs: np.stack([np.asarray(x) for x in xs]),
                    *batch_codes)
                self.params, self.state = self._apply_weighted(
                    jax.device_put(stacked, self.ps_device), stalenesses,
                    ranks, data, n_target=fill_target, contribs=contribs)
                data["optim_step_time"] = time.perf_counter() - t0

                t0 = time.perf_counter()
                # One device_get for the whole tree, then the leaf-wise
                # (InCon) publish — readers may still see mixed leaves
                # mid-loop; the fetch itself needs no per-leaf dispatch.
                host_params = jax.device_get(self.params)
                for n, p in host_params.items():
                    self._served[n] = np.asarray(p)
                self._served_version += 1
                data["isend_time"] = time.perf_counter() - t0
                data["msg_bytes"] = float(bytes_of(batch_codes[0]))

                mean_loss = float(np.mean(losses))
                mean_stale = float(np.mean(stalenesses))
                history["losses"].append(mean_loss)
                history["staleness"].append(mean_stale)
                history["versions"].append(self._served_version)
                history["contributors"].append(list(ranks))
                history["grads_consumed"] += len(batch_codes)
                self.timings.append(data)
                if checkpoint_every and (gstep + 1) % checkpoint_every == 0:
                    self._auto_checkpoint(checkpoint_path, gstep + 1)
                if (self.replica_addr is not None
                        and (gstep + 1) % self.replica_every == 0):
                    # Stream this update to the hot standby: with the
                    # default cadence (1) the standby is never behind, so
                    # a promotion rewinds ZERO updates — shard death
                    # stops costing a checkpoint rewind.
                    self._replicate(gstep + 1)
                if log_every and (update + 1) % log_every == 0:
                    print(f"async update {update + 1:5d}  loss "
                          f"{mean_loss:.4f}  staleness {mean_stale:.2f}")
        finally:
            self._net_stop.set()
            self._listener.close()
            accept.join(timeout=5.0)
            if self._repl_session is not None:
                self._repl_session.close()
                self._repl_session = None
            # The once-per-worker report of silently-lost gradients
            # (satellite of the fault-tolerance PR: a queue-full drop at
            # shutdown used to vanish without a trace).
            with self._stats_lock:
                drops = dict(self.fault_stats["dropped_queue_full"])
            for r in sorted(drops):
                who = "unranked conn" if r == -1 else f"worker rank {r}"
                print(f"async PS warning: {who}: {drops[r]} gradient(s) "
                      f"dropped (net queue full at shutdown)",
                      file=sys.stderr)
        history["wall_time"] = time.perf_counter() - t_start
        history["steady_wall_time"] = time.perf_counter() - t_steady
        history["warmup_steps"] = warmup_steps
        history["fault_stats"] = self._fault_stats_snapshot()
        return history

    def close(self):
        self._closed.set()
        self._net_stop.set()
        self._decode_pool.shutdown(wait=False)
        try:
            self._listener.close()
        except OSError as exc:  # pragma: no cover - close rarely fails
            # Surfaced instead of swallowed: an unclosable listener is
            # worth a trace in the final stats.
            self._bump("accept_errors")
            with self._stats_lock:
                self._last_drop = exc

    def join(self, timeout: float = 10.0) -> None:
        """Once `serve` has returned: wait until nothing this server
        started still runs — the connection handlers (each answers its
        peer's next PULL with DONE and ends when the peer hangs up) and
        the decode pool.  Terminal, like `close`, which follows it.  A
        role that returns to interpreter exit while a handler is inside a
        native or JAX call aborts the process (rc 134) AFTER the work is
        done; the CLI roles join before they return.  Separate from
        `close` because the fleet supervisor closes a dead shard mid-run
        and must not wait on its peers."""
        deadline = time.monotonic() + timeout
        for t in list(self._conn_threads):
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        self._decode_pool.shutdown(wait=True)
        alive = sum(t.is_alive() for t in self._conn_threads)
        if alive:
            print(f"async PS warning: {alive} connection handler(s) "
                  f"still running {timeout:.0f}s after serve() ended",
                  file=sys.stderr)


class AsyncSGDServer(AsyncPSServer):
    def __init__(self, named_params, **kw):
        kw["optim"] = "sgd"
        super().__init__(named_params, **kw)


class AsyncAdamServer(AsyncPSServer):
    def __init__(self, named_params, **kw):
        kw["optim"] = "adam"
        super().__init__(named_params, **kw)


class AsyncPSWorker:
    """A worker process: pull params, grad+encode on the local device, push
    coded gradients.  Run one per host (or per accelerator)::

        w = AsyncPSWorker("ps-host", 5555, code="blockq")
        w.run(loss_fn, batch_fn)     # returns when the PS answers DONE

    ``batch_fn(rank, it)`` supplies this worker's ``it``-th local batch —
    rank is assigned by the server at connect time, so the same worker
    binary can be launched identically on every host.

    Transport faults heal instead of killing the worker: a lost connection
    (PS restart, network blip, dropped reply) triggers reconnection with
    exponential backoff + jitter, re-presenting this worker's rank so the
    PS books it as a reconnect rather than a new worker.  A PS that stays
    gone past ``reconnect_retries`` attempts ends the run cleanly, exactly
    as a DONE would.  ``fault_plan`` (`utils.faults.FaultPlan`) injects
    deterministic chaos — planned death, NaN gradients, wire mangling on
    outbound GRAD frames — for tests and chaos evidence runs.
    """

    def __init__(self, host: str, port: int,
                 code: "Codec | str | None" = None,
                 device=None, wire_level: int = 0,
                 token: str | None = None,
                 fault_plan=None,
                 io_timeout: float = 60.0,
                 reconnect_retries: int = 3,
                 backoff_base: float = 0.1,
                 backoff_max: float = 1.0,
                 heartbeat_interval: float = 2.0,
                 assigned_rank: "int | None" = None,
                 expect_shard: "int | None" = None,
                 agg_group: "int | None" = None,
                 agg_target: int = 0,
                 fallback_group: "int | None" = None,
                 op_deadline: "float | None" = None,
                 credit_cap: "int | None" = None,
                 max_pending: int = 4,
                 stall_hook=None, pace_hook=None,
                 bucket_bytes: "int | None" = None,
                 fused_encode: bool = False):
        from .ops.codecs import get_codec
        from .parallel.mesh import default_devices

        # Bucket-streamed gradient production (v11): None = whole-tree
        # pushes (the legacy path, still the degenerate (0, 1) frame);
        # an int enables bucket streaming at that size (0 = auto-tune
        # by `parallel.overlap.auto_bucket_bytes`).
        # ``fused_encode`` selects the per-bucket encode compiled INTO
        # the grad program (`parallel.overlap.make_async_bucket_step`)
        # vs the host-boundary per-bucket encode fallback; it is the
        # encode half of bucket streaming, so it requires the plan.
        if bucket_bytes is not None and bucket_bytes < 0:
            raise ValueError(
                f"bucket_bytes must be >= 0 (0 = auto) or None, got "
                f"{bucket_bytes}")
        if fused_encode and bucket_bytes is None:
            raise ValueError(
                "fused_encode fuses the PER-BUCKET encode into the grad "
                "program — it needs bucket streaming (set bucket_bytes; "
                "0 auto-tunes); without a plan the flag would be "
                "silently inert")
        self.bucket_bytes = bucket_bytes
        self.fused_encode = bool(fused_encode)
        self._bucket_plan = None
        # Device 0 of what THIS PROCESS was given: on a TPU host every
        # worker process is started with its own chip (the launcher sets
        # TPU_VISIBLE_CHIPS etc. before the child imports jax — README
        # "Running on the chip"), so this is never a shared chip 0.
        self.device = (device if device is not None
                       else default_devices()[0])
        self.code = get_codec(code, self.device.platform)
        self.wire_level = wire_level
        self.token = token or None  # "" must behave exactly like unset
        self.host, self.port = host, port
        self.io_timeout = io_timeout
        self.reconnect_retries = reconnect_retries
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.heartbeat_interval = heartbeat_interval
        self.fault_plan = fault_plan
        self.reconnects = 0
        # Unified per-operation budget (v8): each pull round trip runs
        # under ``Deadline(op_deadline)``; a blown budget is counted and
        # heals through the same reconnect ladder as any transport blip.
        self.op_deadline = op_deadline
        # Sender-side flow control: the server's advertised window,
        # clamped by ``credit_cap`` (CLI --credit-window on a worker
        # role); ``max_pending`` bounds the stall queue before
        # oldest-first shedding.
        self._credit_cap = credit_cap
        self._max_pending = max_pending
        self._stall_hook = stall_hook
        self._pace_hook = pace_hook
        # Worker-side counters; session stall/shed counts merge in via
        # `fault_snapshot` — same render vocabulary as the PS side.
        self.fault_stats: "dict[str, int]" = {
            "deadline_expired": 0, "flood_injected": 0,
            "burst_injected": 0, "parm_unchanged": 0,
            # Bucket streaming (v11): bucket frames handed to the
            # transport (gate-entered, like `push`) and fused bucketed
            # grad+encode steps run.
            "buckets_sent": 0, "fused_encodes": 0}
        # Fleet identity (`shard.ShardRouter` links): ``assigned_rank``
        # books shard 0's minted rank verbatim; ``expect_shard`` pins
        # which fleet slot this connection must land on (endpoint-order
        # mistakes refused at connect time).  A plain worker (both
        # None) refuses any sharded server: it would push full-tree
        # gradients at a slice owner.
        self._assigned_rank = assigned_rank
        self._expect_shard = expect_shard
        # Hierarchy identity (v7): ``agg_group`` presents this link as
        # group g's AGGREGATOR (HELO flag bit 8, with the group's fill
        # target for the root's view); ``fallback_group`` marks a
        # direct-fallback worker re-admitting itself after its group
        # aggregator died (flag bit 16, counted once at the root).
        self._agg_group = agg_group
        self._agg_target = int(agg_target)
        self._fallback_group = fallback_group
        self.shard_index = 0
        self.num_shards = 1
        self.plan_digest = 0
        # Monotone per-rank GRAD sequence id (v4): survives reconnects, so
        # the PS can tell a wire-duplicated frame from a fresh gradient.
        self._push_seq = 0
        self.rank: "int | None" = None
        # The hardened per-connection state — send lock, heartbeat,
        # link-partition latch, credit gate — is one `transport.Session`
        # shared across reconnects (a redial swaps the socket in via
        # `Session.adopt`, keeping credit/pending state).
        self._session: "Session | None" = None
        # v9 segmented wire: set from the server's PSA wire_flags at
        # connect; when set, GRAD/AGGR payloads go out as scatter-gather
        # segment lists and PARM replies land in the preallocated recv
        # ring (decoded inline before the next receive, so nbufs=2).
        self._wire_segmented = False
        self._recv_arena = _transport.RecvArena(nbufs=2)
        # Conditional-pull cache (v9): the last decoded (version,
        # host_params) — presented as ``have`` on every PULL so an
        # unchanged server answers head-only and this worker skips the
        # multi-MB transfer + decode entirely.
        self._parm_cache: "tuple[int, Any] | None" = None
        self._connect(prior_rank=None)
        self._rng = np.random.default_rng(np.random.SeedSequence(
            [fault_plan.seed if fault_plan is not None else 0,
             self.rank, 0xB0FF]))
        self._mangler = (fault_plan.wire_mangler(self.rank)
                         if fault_plan is not None
                         and fault_plan.any_wire_faults() else None)

    # -- connection management ------------------------------------------------

    # -- back-compat surface over the session ---------------------------------

    @property
    def sock(self) -> "socket.socket | None":
        return self._session.sock if self._session is not None else None

    @property
    def link_down(self) -> bool:
        return (self._session.link_down
                if self._session is not None else False)

    @link_down.setter
    def link_down(self, value: bool) -> None:
        if self._session is not None:
            self._session.link_down = bool(value)

    def fault_snapshot(self) -> "dict[str, int]":
        """This worker's counters plus its session's stall/shed counts —
        one dict the shared `format_fault_stats` renders."""
        snap = dict(self.fault_stats)
        if self._session is not None:
            for k, v in self._session.stats.items():
                snap[k] = snap.get(k, 0) + v
        return snap

    def _connect(self, prior_rank: "int | None") -> None:
        """Dial the PS and run the HELO handshake; on success the live
        socket replaces any previous one (the session adopts it —
        credit/pending state and the heartbeat survive the redial).
        ``prior_rank`` marks this as a reconnect so the PS re-books the
        same rank.  The whole dial+handshake runs under one
        ``Deadline(io_timeout)`` budget."""
        dial = Deadline(self.io_timeout)
        sock = socket.create_connection((self.host, self.port),
                                        timeout=dial.timeout())
        try:
            sock.settimeout(dial.timeout())
            try:
                # PULL and BEAT are bytes-small and latency-critical:
                # never queue them behind Nagle.
                sock.setsockopt(socket.IPPROTO_TCP,
                                socket.TCP_NODELAY, 1)
            except OSError:  # pragma: no cover - non-TCP transports
                pass
            if prior_rank is not None:
                flags, extra = 1, struct.pack("<I", prior_rank)
            elif self._assigned_rank is not None:
                # Fleet-identity join: book shard 0's minted rank here
                # too (not a reconnect — the server must not count it).
                flags, extra = 2, struct.pack("<I", self._assigned_rank)
            else:
                flags, extra = 0, b""
            if self._agg_group is not None:
                # Aggregator identity composes with prior/assigned rank
                # (a restarted aggregator re-claims both its rank and
                # its group in one HELO — no churn anywhere).
                flags |= 8
                extra += struct.pack("<HH", self._agg_group,
                                     self._agg_target)
            if self._fallback_group is not None:
                flags |= 16
                extra += struct.pack("<H", self._fallback_group)
            _send_frame(sock, b"HELO" + bytes([flags]) + extra
                        + (self.token.encode() if self.token else b""))
            reply = _recv_frame(sock)
            if reply == b"NOAU":
                raise ValueError(
                    "server refused the admission token (launch the worker "
                    "with the server's --token)")
            if reply[:3] != b"PSA":
                raise ValueError(
                    "incompatible protocol: the server's HELO reply carries "
                    "no PSA magic — it speaks a pre-versioning (or foreign) "
                    "protocol; upgrade both peers to the same release")
            if reply[3] != PROTOCOL_VERSION:
                raise ValueError(
                    f"incompatible protocol version: server speaks "
                    f"{reply[3]}, this worker speaks {PROTOCOL_VERSION} — "
                    f"run matching releases on both ends")
            (rank,) = struct.unpack_from("<I", reply, 4)
            auth_enforced = reply[8:9] == b"\x01"
            if self.token and not auth_enforced:
                raise ValueError(
                    "this worker was given an admission token but the "
                    "server is not enforcing one — refusing to run against "
                    "an open PS port (launch the server with --token)")
            shard_index, num_shards, plan_digest = struct.unpack_from(
                "<HHQ", reply, 9)
            if self._expect_shard is None and num_shards > 1:
                raise ValueError(
                    f"this server is shard {shard_index} of a "
                    f"{num_shards}-shard PS fleet; a plain worker would "
                    f"push full-tree gradients at a slice owner — connect "
                    f"through shard.ShardRouter (CLI: --connect with all "
                    f"{num_shards} endpoints)")
            if (self._expect_shard is not None
                    and shard_index != self._expect_shard):
                raise ValueError(
                    f"endpoint order mismatch: expected fleet shard "
                    f"{self._expect_shard} at {self.host}:{self.port} but "
                    f"the server identifies as shard {shard_index} of "
                    f"{num_shards} — list --connect endpoints in shard "
                    f"order")
            self.shard_index, self.num_shards = shard_index, num_shards
            self.plan_digest = plan_digest
            # v8: the server's advertised credit window follows the
            # shard triple — the sender's initial flow-control balance.
            (credits,) = _U32.unpack_from(reply, 21)
            # v9: the wire_flags byte — bit 1 advertises the segmented
            # scatter-gather plane (a capability statement; the version
            # byte above already refused any pre-segmented peer).
            self._wire_segmented = bool(reply[25] & _WIRE_SEGMENTED)
            server_codec = reply[26:].decode()
            if server_codec and server_codec != self.code.name:
                raise ValueError(
                    f"codec mismatch: the server decodes {server_codec!r} "
                    f"codes but this worker encodes {self.code.name!r} — "
                    f"launch the worker with the server's codec")
        except BaseException:
            sock.close()
            raise
        if self._session is None:
            self._session = Session(
                sock, io_timeout=self.io_timeout,
                heartbeat_interval=self.heartbeat_interval,
                max_pending=self._max_pending,
                credit_cap=self._credit_cap,
                stall_hook=self._stall_hook,
                pace_hook=self._pace_hook)
        else:
            self._session.adopt(sock)
        self.rank = rank
        # Version numbers are only comparable within one server
        # lifetime: a redial may land on a server that RESTORED to an
        # earlier version number with different bytes (checkpoint
        # resume, standby promotion), and a conditional pull against
        # the pre-dial cache would be answered head-only "unchanged" —
        # silently training on stale params.  The server invalidates
        # its encode cache at restore for exactly this reason; the
        # worker's read cache must not survive the dial either.
        self._parm_cache = None
        self._session.replenish(credits)

    def _reconnect(self) -> bool:
        """Jittered backoff redial (`utils.backoff.Backoff` — THE one
        ladder; router link redials and hierarchy aggregator redials
        both arrive here), re-presenting our rank.  ValueError refusals
        propagate: a configuration error does not heal by retrying."""
        ladder = Backoff(base=self.backoff_base, maximum=self.backoff_max,
                         retries=self.reconnect_retries, rng=self._rng)
        for _attempt in ladder.sleeps():
            try:
                self._connect(prior_rank=self.rank)
            except _TRANSPORT_ERRORS:
                continue
            self.reconnects += 1
            return True
        return False

    def _send(self, payload: bytes) -> None:
        """One frame through the session: control frames go straight
        out, data frames ride the credit gate (stall-then-shed, never a
        blocking sendall that starves the heartbeat)."""
        self._session.send(payload)

    def _recv(self, deadline: "Deadline | None" = None, *, into=None):
        return self._session.recv(deadline, into=into)

    def _push_grad(self, payload: bytes) -> None:
        """Send a GRAD frame, routed through the fault plan's wire
        mangler when one is configured (GRAD only: control traffic
        stays clean).  The mangler path bypasses the credit gate — it
        owns the raw framing so it can corrupt it."""
        if self._mangler is None:
            self._send(payload)
            return
        wire = _frame_header(payload) + payload
        chunks, close_after = self._mangler(wire)
        self._session.raw_send(chunks)
        if close_after:
            try:
                self.sock.close()
            except OSError:  # pragma: no cover - close best-effort
                pass
            raise ConnectionResetError(
                "FaultPlan: frame truncated, connection killed")

    # -- protocol round trips (shared by run() and `shard.ShardRouter`) -------

    def pull(self, force: bool = False) -> "tuple[int, Any] | None":
        """One PULL round trip under the op `Deadline` budget:
        ``(version, host_params)``, or None on DONE.  The PARM credit
        field replenishes the session's flow-control window (flushing
        stalled data frames).  Transport errors — a blown deadline
        included, counted — propagate for the caller's reconnect
        policy.  The reply lands in this worker's preallocated recv
        ring (v9) and is decoded before the next receive — no
        per-frame payload allocation, no copy between socket and
        decode arena.  The pull is CONDITIONAL on the cached version:
        an unchanged server answers head-only (counted
        ``parm_unchanged``) and the cached host params are returned
        again — the transfer + decode cost scales with VERSIONS, like
        the server's encode cost.  ``force=True`` pulls
        unconditionally (a fresh full transfer even at the served
        version — what a fanout benchmark or an integrity re-read
        wants)."""
        dl = Deadline(self.op_deadline)
        have = (self._parm_cache[0]
                if self._parm_cache is not None and not force
                else _UNVERSIONED)
        self._send(b"PULL" + _U64.pack(have))
        try:
            reply = self._recv(dl, into=self._recv_arena)
        except DeadlineExpired:
            self.fault_stats["deadline_expired"] += 1
            raise
        kind = bytes(reply[:4])
        if kind == b"DONE":
            return None
        if kind == b"PARM":
            version = _U64.unpack_from(reply, 4)[0]
            credits = _U32.unpack_from(reply, 4 + _U64.size)[0]
            # v12: the codec byte names the wire encoding — the frame
            # self-describes, so this worker needs no codec knob and
            # survives a failover onto a differently-configured server.
            codec = _U8.unpack_from(reply, 4 + _U64.size + _U32.size)[0]
            self._session.replenish(credits)
            payload = reply[4 + _U64.size + _U32.size + _U8.size:]
            if len(payload) == 0:
                # "Unchanged": only ever answered to a conditional pull
                # at the served version (a real tree frame is never
                # empty), so the cache is authoritative by construction.
                if (self._parm_cache is None
                        or self._parm_cache[0] != version):
                    raise ValueError(
                        "empty PARM payload for a version this worker "
                        "never decoded — protocol violation")
                self.fault_stats["parm_unchanged"] += 1
                return self._parm_cache
            params = _codecs.decode_wire_tree(
                codec, serializer.loads(payload))
            self._parm_cache = (version, params)
            return self._parm_cache
        raise ValueError(f"unexpected reply {kind!r}")

    def push(self, codes_host, version: int, loss: float) -> None:
        """Serialize and hand one (host-side) code pytree to the
        transport as a GRAD frame tagged with the param ``version`` it
        was computed from.  Under the v8 credit gate "pushed" means
        gate-entered, not wire-confirmed: at zero credits the frame
        parks (flushed at the next replenish) and may be shed
        oldest-first — exact accounting lives in the session's
        ``credits_stalled``/``shed_data_frames`` counters
        (`fault_snapshot`).  The per-rank seq is burned even if the
        send fails or sheds: a lost gradient's seq must never be reused
        by a later one (the PS would drop the fresh gradient as a
        duplicate).  Ownership: the caller KEEPS ``codes_host`` — on
        the segmented wire (v9) the leaf segments are zero-copy views
        of its arrays, gather-sent inside this call or copied per
        segment on park (`Session.send_data_segments`), so reusing the
        code tree for the next step is always safe."""
        seq = self._push_seq
        self._push_seq += 1
        head = (b"GRAD" + _BKT.pack(0, 1) + _U64.pack(seq)
                + _U64.pack(version) + _F64.pack(float(loss)))
        if self._mangler is None and self._wire_segmented:
            # Scatter-gather: header + meta + per-leaf buffer views in
            # one sendmsg through the credit gate — no blob assembly,
            # and the frame crc rides the encode pass's chained crc
            # (one combine, not a second multi-MB read).
            meta_blob, segs = serializer.encode_segments(
                codes_host, level=self.wire_level)
            self._session.send_data_segments(
                [head, meta_blob, *segs],
                cached=(segs.wire_crc, segs.wire_len))
            return
        # Blob path: the wire mangler owns its framing (it corrupts
        # it), and a pre-segmented server never advertised the flag.
        blob = serializer.dumps(codes_host, level=self.wire_level)
        self._push_grad(head + blob)

    def push_agg(self, codes_host, version: int, loss: float, *,
                 group: int, n_contrib: int, target: int) -> None:
        """Forward one group-reduced code pytree as an AGGR frame (the
        hierarchy's per-fill forward — `shard.hierarchy.LocalAggregator`
        calls this so the frame literal stays in THIS module, balanced
        against its decoder).  ``n_contrib`` is how many worker
        gradients the pre-reduced frame stands for; the seq is burned
        like a GRAD push, and the payload rides the same segmented
        scatter-gather path (v9)."""
        seq = self._push_seq
        self._push_seq += 1
        head = (b"AGGR"
                + _GRP.pack(int(group), int(n_contrib), int(target))
                + _BKT.pack(0, 1)
                + _U64.pack(seq) + _U64.pack(version)
                + _F64.pack(float(loss)))
        if self._mangler is None and self._wire_segmented:
            meta_blob, segs = serializer.encode_segments(
                codes_host, level=self.wire_level)
            self._session.send_data_segments(
                [head, meta_blob, *segs],
                cached=(segs.wire_crc, segs.wire_len))
            return
        blob = serializer.dumps(codes_host, level=self.wire_level)
        self._push_grad(head + blob)

    def push_buckets(self, buckets, n_buckets: int, version: int,
                     loss: float) -> None:
        """Stream one gradient as ``n_buckets`` GRAD-bucket frames
        sharing one burned seq (v11).  ``buckets`` is an ITERABLE whose
        items are host-side code sub-trees — or LISTS of them: a list
        is a READY GROUP, coalesced into one gather-send
        (`Session.send_data_parts`).  The run loop hands in a generator
        that yields each bucket as the device produces it and groups
        consecutive already-ready buckets — so a bucket whose backward
        is still running buys genuine wire/compute overlap (its
        predecessors are on the wire while it computes), while buckets
        that are already materialized cost one syscall for the run, not
        one thread wakeup each.

        Flow control: the first bucket consults the credit gate ONCE
        for the whole gradient (`Session.begin_data_parts`); a closed
        gate collects every bucket and parks the gradient as one entry
        (park/shed as a unit — see the module docstring).  Ownership:
        as in `push`, the caller keeps every buffer it hands in.  With
        a wire mangler armed (or a non-segmented peer) each bucket
        rides the blob path as its own mangled frame."""
        seq = self._push_seq
        self._push_seq += 1
        direct: "bool | None" = None
        parked: list = []
        b = 0
        for item in buckets:
            group = item if isinstance(item, (list, tuple)) else [item]
            batch: list = []
            for codes_host in group:
                head = (b"GRAD" + _BKT.pack(b, int(n_buckets))
                        + _U64.pack(seq) + _U64.pack(version)
                        + _F64.pack(float(loss)))
                b += 1
                self.fault_stats["buckets_sent"] += 1
                if (self._mangler is not None
                        or not self._wire_segmented):
                    blob = serializer.dumps(codes_host,
                                            level=self.wire_level)
                    self._push_grad(head + blob)
                    continue
                meta_blob, segs = serializer.encode_segments(
                    codes_host, level=self.wire_level)
                batch.append((head, meta_blob, segs))
            if not batch:
                continue
            if direct is None:
                direct = self._session.begin_data_parts()
            if not direct:
                parked.extend([h, m, *s] for h, m, s in batch)
            elif len(batch) == 1:
                head, meta_blob, segs = batch[0]
                self._session.send_data_part(
                    [head, meta_blob, *segs],
                    cached=(segs.wire_crc, segs.wire_len))
            else:
                self._session.send_data_parts(
                    [([h, m, *s], (s.wire_crc, s.wire_len))
                     for h, m, s in batch])
        if parked:
            self._session.park_data_parts(parked)

    def push_agg_buckets(self, buckets, n_buckets: int, version,
                         loss: float, *, group: int, n_contrib: int,
                         target: int) -> None:
        """`push_buckets` for the hierarchy's AGGR forward: the
        aggregator pre-reduces per bucket and streams each reduced
        sub-tree upstream as its own AGGR-bucket frame (ready runs
        coalesced, like the worker), one credit for the whole forward —
        so the fanout of bucket b overlaps the reduce of bucket b+1
        (`shard.hierarchy.LocalAggregator`).

        The gate/batch/park loop is DELIBERATELY duplicated with
        `push_buckets` rather than factored behind a head-builder
        closure: the pslint drift harvester resolves a frame kind's
        pack-arity through the ``head`` binding in the ENCLOSING
        function of the send call, so hoisting the send into a shared
        helper would silently drop both bucketed kinds out of the
        PSL304 encode/decode balance."""
        seq = self._push_seq
        self._push_seq += 1
        direct: "bool | None" = None
        parked: list = []
        b = 0
        for item in buckets:
            bgroup = item if isinstance(item, (list, tuple)) else [item]
            batch: list = []
            for codes_host in bgroup:
                head = (b"AGGR"
                        + _GRP.pack(int(group), int(n_contrib),
                                    int(target))
                        + _BKT.pack(b, int(n_buckets))
                        + _U64.pack(seq) + _U64.pack(version)
                        + _F64.pack(float(loss)))
                b += 1
                self.fault_stats["buckets_sent"] += 1
                if (self._mangler is not None
                        or not self._wire_segmented):
                    blob = serializer.dumps(codes_host,
                                            level=self.wire_level)
                    self._push_grad(head + blob)
                    continue
                meta_blob, segs = serializer.encode_segments(
                    codes_host, level=self.wire_level)
                batch.append((head, meta_blob, segs))
            if not batch:
                continue
            if direct is None:
                direct = self._session.begin_data_parts()
            if not direct:
                parked.extend([h, m, *s] for h, m, s in batch)
            elif len(batch) == 1:
                head, meta_blob, segs = batch[0]
                self._session.send_data_part(
                    [head, meta_blob, *segs],
                    cached=(segs.wire_crc, segs.wire_len))
            else:
                self._session.send_data_parts(
                    [([h, m, *s], (s.wire_crc, s.wire_len))
                     for h, m, s in batch])
        if parked:
            self._session.park_data_parts(parked)

    def _start_heartbeat(self) -> None:
        # The heartbeat lives on the session (CONTROL class: it never
        # queues behind credit-stalled data frames — a flooded worker
        # must keep its liveness signal).
        self._session.start_heartbeat()

    def close(self) -> None:
        if self._session is not None:
            self._session.close()

    # -- the worker loop ------------------------------------------------------

    def run(self, loss_fn: Callable, batch_fn: Callable[[int, int], Any],
            max_iters: int | None = None) -> int:
        """Work until the PS says DONE (or ``max_iters``).  Returns the
        number of gradients pushed."""
        import jax

        from .async_ps import make_worker_step

        plan = self.fault_plan
        # Byzantine injection compiles INTO this worker's step: the attack
        # mangles raw gradients pre-encode, so it rides any codec (and,
        # below, any bucket plan — it transforms the RAW whole tree).
        transform = (plan.byzantine_transform(self.rank)
                     if plan is not None else None)
        # Bucket streaming (v11) builds its step LAZILY: the plan needs
        # the param shapes, which arrive with the first pull.
        fn = (make_worker_step(loss_fn, self.code, transform)
              if self.bucket_bytes is None else None)
        pushed = 0
        it = 0
        # Device-side params cache for the conditional pull, keyed by
        # the IDENTITY of the pulled host tree, not its version number:
        # an "unchanged" conditional pull returns the same cached
        # object, a fresh decode is a new one — and after a reconnect
        # (cache cleared in `_connect`) a re-served version NUMBER with
        # different bytes is a new object too, where a version compare
        # would silently keep the pre-dial device params.
        dev_params = None
        dev_src = None
        unchanged_streak = 0
        self._start_heartbeat()
        try:
            while max_iters is None or it < max_iters:
                if (plan is not None
                        and plan.should_kill_worker(self.rank, it)):
                    from .utils.faults import SimulatedCrash
                    raise SimulatedCrash(
                        f"FaultPlan: worker {self.rank} killed at "
                        f"iteration {it}")
                if plan is not None and plan.should_slow(self.rank):
                    # Deterministic straggler: this worker pays the delay
                    # before every pull+grad round trip.
                    time.sleep(plan.slow_delay_s)
                try:
                    pulled = self.pull()
                except _TRANSPORT_ERRORS:
                    # Server unreachable (restarting PS, network blip, or
                    # the shutdown race where its DONE is lost).  Backoff
                    # and redial; a server that stays gone means the run
                    # is over — exit cleanly as a DONE would have us do.
                    if self._reconnect():
                        continue
                    break
                if pulled is None:  # DONE
                    break
                version, params = pulled
                if fn is None:
                    # First pull of a bucket-streaming worker: size the
                    # plan from the served tree and compile the
                    # per-bucket grad+encode step (fused or
                    # host-boundary per `fused_encode`).  One program
                    # covers every bucket — steady state never
                    # retraces.
                    from .parallel.overlap import (make_async_bucket_step,
                                                   plan_overlap)
                    self._bucket_plan = plan_overlap(
                        params, self.bucket_bytes)
                    fn = make_async_bucket_step(
                        loss_fn, self.code, self._bucket_plan, transform,
                        fused=self.fused_encode)
                if params is not dev_src:
                    # A fresh tree: one device_put.  An "unchanged"
                    # conditional pull reuses the previous device
                    # arrays outright — same bytes, zero transfer (the
                    # v9 conditional-pull win extends all the way to
                    # the accelerator copy).
                    dev_params = jax.device_put(params, self.device)
                    dev_src = params
                    unchanged_streak = 0
                else:
                    # Same-version pacing: several gradients are already
                    # in flight at this version — yield (escalating
                    # with the streak) so the serve loop drains instead
                    # of deepening the backlog: bounded staleness over
                    # raw production rate.
                    unchanged_streak += 1
                    over = unchanged_streak - _SAME_VERSION_PACE
                    if over >= 0:
                        time.sleep(min(
                            _SAME_VERSION_YIELD_S * (over + 1),
                            _SAME_VERSION_YIELD_MAX_S))
                batch = jax.device_put(batch_fn(self.rank, it), self.device)
                if self._bucket_plan is not None:
                    # Bucket-streamed production: the step returns one
                    # encoded sub-tree per bucket; each is device_get
                    # as it completes and pushed IMMEDIATELY, so bucket
                    # 0's transfer+serialize+send overlaps the later
                    # buckets' remaining backward/encode compute.
                    loss, bucket_codes = fn(dev_params, batch)
                    if self.fused_encode:
                        self.fault_stats["fused_encodes"] += 1
                    loss_f = float(loss)
                    poison = (plan is not None
                              and plan.inject_nonfinite(self.rank, it))
                    host_parts: list = []

                    def to_host(cb, poison=poison,
                                host_parts=host_parts):
                        h = jax.tree.map(np.asarray,
                                         jax.device_get(cb))
                        if poison and not host_parts:
                            from .utils.faults import poison_nonfinite
                            h = poison_nonfinite(h)
                        host_parts.append(h)
                        return h

                    # REVERSE plan order = backward-production order:
                    # the output layers' cotangents (tail of the
                    # param-ordered plan) materialize first, so
                    # streaming tail-first puts the first-ready bucket
                    # on the wire while the input layers' backward is
                    # still running.  Bucket ids are stream-positional;
                    # assembly merges by NAME, so arrival order is
                    # free.  `iter_ready_groups` coalesces runs of
                    # already-materialized buckets into one gather-send
                    # and flushes the pending run before blocking on a
                    # bucket still computing — the overlap window.
                    from .parallel.overlap import iter_ready_groups
                    stream = iter_ready_groups(
                        reversed(bucket_codes), to_host)

                    try:
                        self.push_buckets(stream,
                                          self._bucket_plan.n_buckets,
                                          version, loss_f)
                    except _TRANSPORT_ERRORS:
                        if self._reconnect():
                            continue  # this gradient is lost
                        break
                    self._inject_overload_buckets(plan, it, host_parts,
                                                  version, loss_f)
                    pushed += 1
                    it += 1
                    continue
                loss, codes = fn(dev_params, batch)
                # One device_get for the tree (per-leaf dispatch is
                # measurable serve-rate tax), then cheap np views.
                codes_host = jax.tree.map(np.asarray,
                                          jax.device_get(codes))
                if (plan is not None
                        and plan.inject_nonfinite(self.rank, it)):
                    from .utils.faults import poison_nonfinite
                    codes_host = poison_nonfinite(codes_host)
                try:
                    self.push(codes_host, version, float(loss))
                except _TRANSPORT_ERRORS:
                    if self._reconnect():
                        continue  # this gradient is lost; pull afresh
                    break
                self._inject_overload(plan, it, codes_host, version,
                                      float(loss))
                pushed += 1
                it += 1
        finally:
            self.close()
        return pushed

    def _inject_overload(self, plan, it: int, codes_host, version: int,
                         loss: float) -> None:
        """Overload injectors (flood_rank / burst_at): push EXTRA copies
        of this gradient — fresh seqs, genuine wire+queue load — so the
        flow-control machinery under test actually engages.  Send
        failures are swallowed: injected overload must not change the
        run's failure semantics."""
        if plan is None:
            return
        flood, burst = plan.overload_extras(self.rank, it)
        for i in range(flood + burst):
            try:
                self.push(codes_host, version, loss)
            except _TRANSPORT_ERRORS:
                return
            self.fault_stats["flood_injected" if i < flood
                             else "burst_injected"] += 1

    def _inject_overload_buckets(self, plan, it: int, host_parts,
                                 version: int, loss: float) -> None:
        """`_inject_overload` for the bucket-streamed path: each extra
        copy re-streams the already-materialized host buckets under a
        fresh seq — genuine wire, assembly, and queue load."""
        if plan is None:
            return
        flood, burst = plan.overload_extras(self.rank, it)
        for i in range(flood + burst):
            try:
                # One ready group: the extras are already materialized.
                self.push_buckets(iter([list(host_parts)]),
                                  len(host_parts), version, loss)
            except _TRANSPORT_ERRORS:
                return
            self.fault_stats["flood_injected" if i < flood
                             else "burst_injected"] += 1
