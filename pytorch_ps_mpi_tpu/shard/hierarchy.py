"""Hierarchical fault-contained aggregation: the group-local tier.

Every robustness mechanism the repo earned so far (quorum fills,
rank-distinct trims, scoreboard quarantine, eviction) runs at ONE level:
the root PS sees every worker directly, so straggler patience, Byzantine
breakdown points, and fill-admission cost all scale linearly with fleet
size.  Li et al. (OSDI 2014) scale the server group by interposing
aggregation between workers and servers; Lian et al. (NeurIPS 2015,
AsySG-InCon) show the bounded-staleness semantics survive such re-timing.
This module is that middle tier:

* `LocalAggregator` — one per host group: a full `AsyncPSServer` facing
  its workers (same HELO/PULL/GRAD protocol, same shared
  `AsyncPS._fill_gradients` admission loop, its OWN
  quorum/fill-deadline/robust-reducer/scoreboard policy), but instead of
  applying updates it PRE-REDUCES each fill to one per-contributor-mean
  gradient, re-encodes it with the codec, and forwards ONE ``AGGR``
  frame to the root — a single PS or a PR 6 `PSFleet` (the upstream
  side splits the re-encoded tree along the fleet's `ShardPlan`, so
  hierarchy x sharding composes).  A Byzantine or straggling rank is
  contained INSIDE its group: the group's trim/quarantine eats it, and
  the root only ever sees G well-behaved frames instead of W raw ones —
  straggler and Byzantine tolerance scale sub-linearly with fleet size;
* `GroupWorker` — a worker wired to its group's aggregator with
  first-class failover: a dead aggregator is re-dialed with bounded
  backoff (``agg_redials``), and once the budget is spent the worker
  falls back to a DIRECT root connection (``agg_failovers`` here,
  ``direct_fallbacks`` at the root booking the flagged HELO) — the
  group degrades to flat topology instead of dying with its middle box;
* `Hierarchy` — the supervisor: spawns G aggregators, and restarts one
  killed by a `FaultPlan` (``kill_agg_at``) on the SAME port with the
  SAME upstream rank (``agg_restarts``), so workers still inside their
  redial budget reconnect with their prior local ranks and the group is
  reclaimed with zero rank churn at either level.

Scale contract (what makes mixed fills honest): a forwarded frame
carries the group's **per-contributor mean** gradient plus its
contributor count n; the root folds n into the contribution weight
(`AsyncPS._contrib_weights`), so an AGGR frame standing for 4 gradients
moves the root exactly 4x a plain worker's GRAD — a fill mixing
aggregated groups with direct-fallback workers sums to the honest total,
and a group that closed short moves the root pro-rata.

No wire-frame literals live in this module: the AGGR encode
(`AsyncPSWorker.push_agg`) and its decoder stay in `multihost_async`,
balanced for the pslint PSL301/PSL304 drift checkers.
"""

from __future__ import annotations

import dataclasses
import queue
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Callable

import numpy as np

from ..errors import (AggregatorDeadError, FleetDeadError, NotCompiledError)
from ..multihost_async import (AsyncPSServer, AsyncPSWorker,
                               _TRANSPORT_ERRORS)
from ..utils.faults import SimulatedCrash
from .router import ShardRouter

_DEAD = object()


class _Upstream:
    """The aggregator's root-facing side: one `AsyncPSWorker` link per
    root endpoint (1 = a plain PS, K = a `PSFleet`), every link HELOing
    with the aggregator flag (group id + group fill target) and — on a
    supervised restart — the previous incarnation's rank, so the root's
    per-rank accounting (eviction, seq dedup, scoreboard, the ``groups``
    view) never churns.  For a fleet the authoritative `ShardPlan` is
    fetched from shard 0 and every link's digest cross-checked, exactly
    the `ShardRouter` agreement contract."""

    def __init__(self, endpoints, *, group: int, target: int,
                 code=None, token=None, assigned_rank: "int | None" = None,
                 initial_seq: int = 0,
                 io_timeout: float = 60.0, reconnect_retries: int = 8,
                 backoff_base: float = 0.1, backoff_max: float = 1.0,
                 pace_hook=None, pace: "int | None" = None,
                 op_deadline: "float | None" = None):
        endpoints = [(h, int(p)) for h, p in endpoints]
        if not endpoints:
            raise ValueError("the aggregator needs at least one root "
                             "endpoint")
        self.endpoints = endpoints
        # ``pace``: the forward-ahead bound, reimplemented on the
        # session's credit machinery (ISSUE 10) — at most ``pace`` AGGR
        # frames per observed root-version epoch (`new_epoch`), stalls
        # counted through ``pace_hook`` (the aggregator mirrors PACE
        # stalls into ``agg_paced``, preserving PR 8's continuity;
        # credit stalls stay in the session's own ``credits_stalled``
        # so one stall lands in exactly one counter).
        link_kw = dict(code=code, token=token, io_timeout=io_timeout,
                       reconnect_retries=reconnect_retries,
                       backoff_base=backoff_base, backoff_max=backoff_max,
                       agg_group=group, agg_target=target,
                       pace_hook=pace_hook, max_pending=2,
                       op_deadline=op_deadline)
        self.links: "list[AsyncPSWorker]" = []
        self.plan = None
        try:
            if len(endpoints) == 1:
                h, p = endpoints[0]
                self.links.append(AsyncPSWorker(
                    h, p, assigned_rank=assigned_rank, **link_kw))
            else:
                h0, p0 = endpoints[0]
                first = AsyncPSWorker(h0, p0, expect_shard=0,
                                      assigned_rank=assigned_rank,
                                      **link_kw)
                self.links.append(first)
                for k, (h, p) in enumerate(endpoints[1:], start=1):
                    self.links.append(AsyncPSWorker(
                        h, p, expect_shard=k, assigned_rank=first.rank,
                        **link_kw))
                if first.num_shards != len(endpoints):
                    raise ValueError(
                        f"the root fleet has {first.num_shards} shards "
                        f"but {len(endpoints)} endpoints were given")
                self.plan = ShardRouter._fetch_plan(first)
                digest = self.plan.digest()
                for k, link in enumerate(self.links):
                    if link.plan_digest != digest:
                        raise ValueError(
                            f"root shard {k} advertises plan digest "
                            f"{link.plan_digest:#x}, the fleet's plan "
                            f"hashes to {digest:#x} — mixed fleets")
        except BaseException:
            self.close()
            raise
        self.rank = self.links[0].rank
        # A restarted aggregator re-presents the SAME rank upstream, so
        # its GRAD-seq stream must CONTINUE past the dead incarnation's
        # high-water — a fresh counter would have the root silently drop
        # its first forwards as duplicates (observed in the verify
        # drive: duplicate_dropped == the crashed incarnation's fills).
        for link in self.links:
            link._push_seq = int(initial_seq)
            if pace is not None:
                link._session.set_pace(pace)
        self._shard_names = (None if self.plan is None else
                             [self.plan.names_for(k)
                              for k in range(len(self.links))])
        # Per-link DONE state (the ShardRouter `done[k]` contract): a
        # fleet shard that reaches its step budget first sends DONE and
        # tears down — the OTHER shards may still be filling, and this
        # aggregator may be the only thing feeding them.  A done link
        # freezes at its last pulled (version, slice) and stops taking
        # pushes; the run is over only when EVERY shard said DONE.  (On
        # the v9 wire the shards' completion points genuinely drift:
        # conditional pulls make the aggregator loop fast enough that
        # per-link pace sheds land asymmetrically, and treating the
        # FIRST DONE as run-over starved the slower shard's last fill
        # into a 120 s FleetDeadError.)
        self._link_done = [False] * len(self.links)
        self._last_pull: "list[tuple[int, dict] | None]" = (
            [None] * len(self.links))

    def push_seq(self) -> int:
        """The highest per-link push seq — what a supervised restart
        seeds the successor's links with."""
        return max(link._push_seq for link in self.links)

    def start_heartbeats(self) -> None:
        for link in self.links:
            link._start_heartbeat()

    def new_epoch(self) -> None:
        """The root's version vector advanced: re-arm each link's pace
        allowance (and flush what it admits) — one observed root
        version buys ``pace`` more forwards, the forward_ahead
        contract on credit machinery."""
        for k, link in enumerate(self.links):
            if not self._link_done[k]:
                link._session.new_epoch()

    def open_pace(self) -> None:
        """The pace_timeout valve: a stalled root has cost its bounded
        wait — let queued forwards flow (credits still gate)."""
        for k, link in enumerate(self.links):
            if not self._link_done[k]:
                link._session.open_pace()

    def pending_frames(self) -> int:
        return sum(link._session.pending_count()
                   for k, link in enumerate(self.links)
                   if not self._link_done[k])

    def session_stats(self) -> "dict[str, int]":
        out: "dict[str, int]" = {}
        for link in self.links:
            for k, v in link._session.stats.items():
                out[k] = out.get(k, 0) + v
        return out

    def pull(self):
        """One root round trip: ``(per-link versions, full param dict)``
        — or None when the root's run is over: EVERY shard said DONE
        (or a single root stayed gone past the reconnect budget: the
        plain-worker contract).  A shard that finishes its step budget
        FIRST freezes at its last pulled slice while the rest keep
        serving — the router's per-shard ``done[k]`` contract — so the
        aggregator keeps feeding the slower shards their final fills.
        A PARTIALLY-unreachable fleet (dead, not done) raises loudly
        instead of serving a tree with frozen slices."""
        versions: "list[int]" = []
        params: "dict[str, Any]" = {}
        dead = 0
        for k, link in enumerate(self.links):
            if self._link_done[k]:
                version, slice_params = self._last_pull[k]
                versions.append(version)
                params.update(slice_params)
                continue
            while True:
                try:
                    pulled = link.pull()
                    break
                except _TRANSPORT_ERRORS:
                    if not link._reconnect():
                        pulled = _DEAD
                        break
            if pulled is None:
                if self._last_pull[k] is None:
                    # DONE before this link ever served a slice: there
                    # is nothing to freeze — the run is over for us.
                    return None
                # This shard's run is over; freeze its final slice and
                # stop dialing it (its listener is being torn down —
                # a redial would misread teardown as partial death).
                self._link_done[k] = True
                link.close()
                version, slice_params = self._last_pull[k]
                versions.append(version)
                params.update(slice_params)
                continue
            if pulled is _DEAD:
                dead += 1
                versions.append(0)
                continue
            version, slice_params = pulled
            self._last_pull[k] = (version, slice_params)
            versions.append(version)
            params.update(slice_params)
        if all(self._link_done):
            return None  # every shard completed = the run is over
        if dead:
            # Count still-serving links NOW, after the pass: a link
            # that said DONE during THIS call no longer serves, and a
            # pre-loop snapshot would make the all-dead exit
            # unreachable for a cluster state that one pull later ends
            # the run cleanly.
            remaining = sum(1 for d in self._link_done if not d)
            if dead == remaining:
                return None  # whole (remaining) root gone = run over
            raise FleetDeadError(
                f"{dead} of {remaining} still-serving root shards "
                f"became unreachable (reconnect budget spent) while "
                f"the rest still serve — refusing to aggregate against "
                f"a partial root")
        return versions, params

    def push(self, codes_host, versions, loss: float, *, group: int,
             n_contrib: int, target: int) -> None:
        """Forward one reduced code tree as AGGR frame(s) — split along
        the fleet plan when the root is sharded.  A failed push is a
        lost forward (the seq is burned); the root's own
        quorum/fill-deadline absorbs the short fill, and the next pull
        owns any dead-link escalation.  The aggregator KEEPS owning
        ``codes_host`` (serialize-before-gate + copy-on-park, the
        PSL7xx ownership contract) — load-bearing here more than
        anywhere: the pacing gate parks AGGR frames for whole epochs,
        and the next fill's reduce would otherwise scribble over a
        parked forward."""
        for k, link in enumerate(self.links):
            if self._link_done[k]:
                continue  # this shard's run is complete — nothing to move
            if self._shard_names is None:
                sub = codes_host
            else:
                sub = OrderedDict((n, codes_host[n])
                                  for n in self._shard_names[k])
            try:
                link.push_agg(sub, versions[k], loss, group=group,
                              n_contrib=n_contrib, target=target)
            except _TRANSPORT_ERRORS:
                link._reconnect()

    def push_bucketed(self, buckets, n_buckets: int, versions,
                      loss: float, *, group: int, n_contrib: int,
                      target: int) -> None:
        """Stream one pre-reduced forward as AGGR-bucket frames (v11,
        single-root — `LocalAggregator` refuses bucketing on a sharded
        root at construction).  Failure semantics as `push`: a failed
        stream is a lost forward (seq burned, partial assembly retired
        at the root), the next pull owns escalation."""
        link = self.links[0]
        if self._link_done[0]:
            return
        try:
            link.push_agg_buckets(buckets, n_buckets, versions[0], loss,
                                  group=group, n_contrib=n_contrib,
                                  target=target)
        except _TRANSPORT_ERRORS:
            link._reconnect()

    def close(self) -> None:
        for link in self.links:
            link.close()


class LocalAggregator(AsyncPSServer):
    """One host group's aggregation tier.

    Usage::

        agg = LocalAggregator(named_params, group=0,
                              upstream=[("root-host", 5555)],
                              group_size=4, quorum=3, fill_deadline=0.1,
                              aggregate="trimmed_mean", anomaly_z=4.0)
        agg.compile_reduce()
        hist = agg.serve_group()     # until the root says DONE

    Workers connect to ``agg.address`` with the UNCHANGED worker
    protocol (a plain `AsyncPSWorker` — or `GroupWorker` for failover);
    the aggregator relays the root's params (versioned by its own pull
    counter), runs the shared fill-admission loop with the group's OWN
    policy, pre-reduces each fill to a per-contributor mean, re-encodes,
    and forwards one AGGR frame per fill upstream.  It applies no
    updates and owns no optimizer: ``named_params`` supply the tree
    shape the codec meta and validation need.
    """

    def __init__(self, named_params, *, group: int, upstream,
                 group_size: int, host: str = "127.0.0.1", port: int = 0,
                 upstream_rank: "int | None" = None,
                 upstream_seq: int = 0,
                 upstream_retries: int = 8,
                 upstream_backoff_base: float = 0.1,
                 upstream_backoff_max: float = 1.0,
                 forward_ahead: int = 1,
                 pace_timeout: float = 5.0,
                 bucket_bytes: "int | None" = None, **kw):
        if group_size < 1:
            raise ValueError(f"group_size must be >= 1, got {group_size}")
        # Bucket-streamed AGGR fanout (ISSUE 15, v11): pre-reduce each
        # fill PER BUCKET (coordinate-wise reducers only —
        # `ops.robust.bucket_streamable`; else whole-tree reduce, split
        # for sending) and stream the reduced sub-trees upstream as
        # AGGR-bucket frames, so the send of bucket b overlaps the
        # reduce of bucket b+1.  None = whole-tree forwards (legacy);
        # 0 = auto-size.  Single root only: a sharded root already
        # slices the tree per link, and bucketing the slices again
        # multiplies the frame count for no extra overlap.
        if bucket_bytes is not None and bucket_bytes < 0:
            raise ValueError(
                f"bucket_bytes must be >= 0 (0 = auto) or None, got "
                f"{bucket_bytes}")
        # Materialize once: the single-root guard must not CONSUME an
        # iterator `_Upstream` still needs to walk.
        upstream = list(upstream)
        if bucket_bytes is not None and len(upstream) > 1:
            raise ValueError(
                "bucket_bytes composes with a SINGLE root endpoint — a "
                "sharded root already splits the forward per shard "
                "slice")
        self._bucket_bytes = bucket_bytes
        self._bucket_plan = None
        self._reduce_bucket_fn = None
        super().__init__(named_params, quota=int(group_size), host=host,
                         port=port, **kw)
        self.group = int(group)
        self.group_size = int(group_size)
        # Forward pacing, reimplemented on the v8 credit machinery
        # (ISSUE 10; PR 8 shipped it as a bespoke wait loop): the
        # upstream session admits at most ``forward_ahead`` AGGR frames
        # per observed ROOT-version epoch (`Session.set_pace` /
        # `_Upstream.new_epoch`), ON TOP of the root's advertised
        # credit window.  A plain worker is implicitly paced — its
        # blocking PULL round trip caps it at ~one in-flight gradient —
        # but a group fills from its own workers' free-running pushes,
        # so an unpaced aggregator outruns the root and piles frames
        # into the root's queue/TCP buffers; applied many versions
        # late, those are exactly the stale updates async runs diverge
        # on (observed in PR 8's verify drive: mean staleness ~5 and a
        # rising loss, vs ~1 paced).  The default of ONE forward per
        # root version balances supply to demand exactly at the
        # designed operating point (root quota == G groups).  A paced-
        # out forward stalls into the session's pending queue (counted
        # ``agg_paced`` via the stall hook — PR 8 counter continuity —
        # and shed oldest-first if the root stays gone); ``pace_timeout``
        # bounds the stall: past it `Session.open_pace` lets queued
        # frames flow and the root's own admission policy owns the
        # staleness.  0 disables pacing (credits alone still gate).
        if forward_ahead < 0:
            raise ValueError(
                f"forward_ahead must be >= 0, got {forward_ahead}")
        self.forward_ahead = int(forward_ahead)
        if pace_timeout <= 0:
            raise ValueError(
                f"pace_timeout must be > 0, got {pace_timeout}")
        self.pace_timeout = float(pace_timeout)
        self.fault_stats.update({
            # Fills pre-reduced and handed to the upstream transport as
            # AGGR frames (gate-entered — a paced/credit-stalled
            # forward may park and shed, exact in the session's
            # shed_data_frames), and forwards stalled by the pacing
            # gate.
            "agg_forwards": 0,
            "agg_paced": 0,
        })
        self._reduce_fn = None
        # Local pull counter -> the upstream per-shard version vector at
        # that pull, so forwarded frames carry honest ROOT versions (the
        # staleness the root accounts is real, not re-based).  Bounded.
        self._version_map: "dict[int, list[int]]" = {0: []}
        try:
            self._upstream = _Upstream(
                upstream, group=self.group, target=self.group_size,
                code=self.code, token=self.token,
                assigned_rank=upstream_rank, initial_seq=upstream_seq,
                reconnect_retries=upstream_retries,
                backoff_base=upstream_backoff_base,
                backoff_max=upstream_backoff_max,
                pace_hook=lambda: self._bump("agg_paced"),
                pace=(self.forward_ahead or None),
                # The aggregator's own op budget rides its upstream
                # pulls too — --op-deadline must not be silently inert
                # on the hierarchy role.
                op_deadline=self.op_deadline)
        except BaseException:
            # The base server already bound its listener; an unreachable
            # root (or a plan-digest refusal) must not leak it — a fixed
            # -port retry after fixing the root would die on EADDRINUSE.
            super().close()
            raise
        self._version_map[0] = [0] * len(self._upstream.links)

    @property
    def upstream_rank(self) -> int:
        """This aggregator's rank at the root — what a supervised
        restart re-presents so the root books the same identity."""
        return self._upstream.rank

    # -- program construction -------------------------------------------------

    def compile_reduce(self) -> None:
        """Build the jitted group-reduce program: decode the fill's
        contributions, reduce them with the group policy to ONE
        per-contributor-mean gradient (`ops.robust.robust_reduce` with
        ``n_target=1`` — the same statistic the root would run, at mean
        scale so the root's contribution-count weighting recovers the
        honest sum), apply any `FaultPlan` aggregator attack, and
        re-encode with the codec.  Also builds the incoming-GRAD
        validation meta and pre-warms the quarantine-scoring probe,
        exactly like `compile_step` (which this replaces: an aggregator
        has no loss function and applies no update)."""
        import jax
        import jax.numpy as jnp

        from ..ops.robust import check_reducer_codec, robust_reduce

        code = self.code
        dummy = OrderedDict(
            (n, code.encode(jnp.zeros(p.shape, p.dtype)))
            for n, p in self.params.items())
        # The shared validation indexes (whole-tree + per-name): group
        # workers may themselves stream bucketed GRADs at this
        # aggregator, and the inherited conn loop assembles them.
        self._index_code_meta(dummy)
        self._itemwise = check_reducer_codec(
            self.aggregate, code,
            anomaly_scoring=self._scoreboard is not None)
        meta = {n: (p.shape, p.dtype) for n, p in self.params.items()}
        aggregate, trim_k = self.aggregate, self.trim_k
        itemwise = self._itemwise
        transform = (self.fault_plan.agg_byzantine_transform(self.group)
                     if self.fault_plan is not None else None)

        def decode_stack(stacked_codes, name):
            shape, dtype = meta[name]
            codes_n = stacked_codes[name]
            n_contrib = jax.tree_util.tree_leaves(codes_n)[0].shape[0]
            items = [code.decode(jax.tree.map(lambda x: x[i], codes_n),
                                 shape=shape, dtype=dtype)
                     for i in range(n_contrib)]
            return jnp.stack(items)

        def agg_reduce(stacked_codes, weights, clip_norm):
            n = weights.shape[0]
            if itemwise:
                decoded = OrderedDict(
                    (nm, decode_stack(stacked_codes, nm)) for nm in meta)
                reduced, info = robust_reduce(
                    aggregate, decoded, weights,
                    n_target=jnp.float32(1.0), trim_k=trim_k,
                    clip_norm=clip_norm)
            else:
                # Fused decode_sum fast path (mean + no scoring): fold
                # the 1/n mean scale into the per-code weights so even a
                # decode_sum-only sketch codec aggregates hierarchically.
                reduced = OrderedDict()
                w = (weights / jnp.float32(n))
                for nm, (shape, dtype) in meta.items():
                    codes_n = jax.vmap(code.scale_code)(
                        stacked_codes[nm], w)
                    reduced[nm] = code.decode_sum(codes_n, shape=shape,
                                                  dtype=dtype)
                info = {"contrib_norms": jnp.zeros((n,), jnp.float32),
                        "clipped": jnp.zeros((), jnp.int32)}
            if transform is not None:
                reduced = transform(reduced)
            codes_out = OrderedDict(
                (nm, code.encode(reduced[nm].astype(meta[nm][1])))
                for nm in meta)
            return codes_out, info

        self._reduce_fn = jax.jit(agg_reduce)

        def contrib_norm(codes):
            sq = jnp.zeros((), jnp.float32)
            for nm in codes:
                shape, dtype = meta[nm]
                d = code.decode(codes[nm], shape=shape, dtype=dtype)
                sq = sq + jnp.sum(d.astype(jnp.float32) ** 2)
            return jnp.sqrt(sq)

        self._norm_fn = jax.jit(contrib_norm)
        if self._scoreboard is not None:
            # Same pre-warm rationale as `compile_step`: the first
            # quarantined submission must hit a compile-cache HIT, not a
            # mid-fill compile racing worker dispatch.
            dummy_host = OrderedDict(
                (n, jax.tree.map(np.asarray,
                                 code.encode(jnp.zeros(p.shape, p.dtype))))
                for n, p in self.params.items())
            float(self._norm_fn(dummy_host))

        # Bucket-streamed AGGR fanout (ISSUE 15): the bucket plan over
        # the param tree, plus — when the group policy is
        # coordinate-wise (`bucket_streamable`) and no aggregator fault
        # transform is armed — ONE jitted per-bucket reduce program.
        # The jit cache keys on the sub-tree structure, so B buckets
        # cost B traces once and steady state never retraces; the
        # per-bucket statistics compose bitwise to the whole-tree
        # reduce (coordinate-wise property, `ops.robust`).  Non-
        # streamable policies (norm_clip's global-norm clip, anomaly
        # scoring's whole-gradient norms, a byzantine_agg transform)
        # keep the whole-tree reduce and only SPLIT for sending — the
        # fanout still pipelines, the statistic never changes.
        self._reduce_bucket_fn = None
        self._bucket_plan = None
        if self._bucket_bytes is not None:
            from ..ops.robust import bucket_streamable
            from ..parallel.overlap import plan_overlap

            self._bucket_plan = plan_overlap(
                OrderedDict((n, np.asarray(p))
                            for n, p in self.params.items()),
                self._bucket_bytes, record=False)
            if (transform is None
                    and bucket_streamable(
                        self.aggregate,
                        anomaly_scoring=self._scoreboard is not None)):
                def agg_reduce_bucket(stacked_sub, weights):
                    n = weights.shape[0]
                    if itemwise:
                        decoded = OrderedDict(
                            (nm, decode_stack(stacked_sub, nm))
                            for nm in stacked_sub)
                        reduced, _info = robust_reduce(
                            aggregate, decoded, weights,
                            n_target=jnp.float32(1.0), trim_k=trim_k,
                            clip_norm=jnp.float32(float("nan")))
                    else:
                        reduced = OrderedDict()
                        w = weights / jnp.float32(n)
                        for nm in stacked_sub:
                            shape, dtype = meta[nm]
                            codes_n = jax.vmap(code.scale_code)(
                                stacked_sub[nm], w)
                            reduced[nm] = code.decode_sum(
                                codes_n, shape=shape, dtype=dtype)
                    return OrderedDict(
                        (nm, code.encode(reduced[nm].astype(meta[nm][1])))
                        for nm in stacked_sub)

                self._reduce_bucket_fn = jax.jit(agg_reduce_bucket)

    # -- the group reduce (mirrors `AsyncPS._apply_weighted`) -----------------

    def _reduce_weighted(self, stacked, stalenesses, ranks, contribs):
        import jax
        import jax.numpy as jnp

        w = self._contrib_weights(stalenesses, ranks, contribs)
        clip = float("nan")
        if self.aggregate == "norm_clip" and self._norm_window:
            clip = float(np.median(np.asarray(self._norm_window)))
        codes_out, info = self._reduce_fn(
            jax.device_put(stacked, self.ps_device), jnp.asarray(w),
            jnp.float32(clip))
        if self._itemwise:
            self._post_apply_scoring(ranks, info)
        return codes_out

    def _forward_bucketed(self, stacked, stalenesses, ranks, contribs,
                          versions_vec, mean_loss: float,
                          fill_target: int, n_codes: int) -> None:
        """Bucket-streamed forward: reduce per bucket (one jitted
        program per bucket STRUCTURE, dispatched back-to-back so jax's
        async dispatch runs bucket b+1's reduce while bucket b is
        fetched and sent), then stream each reduced sub-tree upstream
        as an AGGR-bucket frame — one credit, one seq, one assembled
        forward at the root.  Non-streamable policies reduce whole-tree
        first and only the SENDING is split."""
        import jax
        import jax.numpy as jnp

        from ..parallel.overlap import split_tree

        plan = self._bucket_plan
        if self._reduce_bucket_fn is not None:
            w = jnp.asarray(
                self._contrib_weights(stalenesses, ranks, contribs))
            outs = [self._reduce_bucket_fn(
                        jax.device_put(sub, self.ps_device), w)
                    for sub in split_tree(stacked, plan)]
        else:
            outs = split_tree(
                self._reduce_weighted(stacked, stalenesses, ranks,
                                      contribs), plan)

        # Ready-group coalescing (the shared flush-before-blocking
        # rule, `parallel.overlap.iter_ready_groups`): a reduce still
        # in flight flushes what is already encoded — the fanout/reduce
        # overlap — and finished runs go out as one gather-send.
        from ..parallel.overlap import iter_ready_groups

        stream = iter_ready_groups(
            outs, lambda sub: jax.tree.map(np.asarray,
                                           jax.device_get(sub)))
        self._upstream.push_bucketed(
            stream, plan.n_buckets, versions_vec, mean_loss,
            group=self.group, n_contrib=n_codes, target=fill_target)

    def _fault_stats_snapshot(self) -> "dict[str, Any]":
        """The server snapshot plus the upstream sessions' flow-control
        counters (credit stalls / oldest-first sheds on the AGGR
        forward path) — read lock-free: snapshot-grade int reads, and
        taking the session lock under the stats lock would invert the
        declared ``lock-order(_lock < _stats_lock)`` (the stall/pace
        hooks bump `_bump` from UNDER the session lock; pslint's PSL501
        convicts the inversion if anyone ever 'fixes' this by locking)."""
        snap = super()._fault_stats_snapshot()
        for k, v in self._upstream.session_stats().items():
            snap[k] = snap.get(k, 0) + v
        return snap

    # -- the aggregator loop --------------------------------------------------

    def _pull_and_publish(self) -> "list[int] | None":
        """One upstream pull, published leaf-wise to the group's serving
        snapshot (the InCon relay).  The LOCAL version advances only
        when the ROOT's version vector actually moved: bumping per
        re-pull would inflate worker staleness against a frozen root —
        tripping max_staleness rejections and collapsing staleness
        weights on perfectly fresh gradients.  An actual advance is
        also the pacing EPOCH signal: it re-arms the upstream sessions'
        forward allowance and flushes any paced-out forwards.
        None = root DONE/gone."""
        pulled = self._upstream.pull()
        if pulled is None:
            return None
        versions, params = pulled
        for n in self._served:
            self._served[n] = np.asarray(params[n])
        if self._version_map.get(self._served_version) != list(versions):
            self._served_version += 1
            self._version_map[self._served_version] = list(versions)
            if len(self._version_map) > 128:
                self._version_map.pop(min(self._version_map))
            self._upstream.new_epoch()
        return versions

    def serve_group(self, max_fills: "int | None" = None,
                    log_every: int = 0, idle_timeout: float = 300.0, *,
                    eviction_timeout: float = 30.0,
                    dead_conn_grace: float = 2.0) -> "dict[str, Any]":
        """Serve the group until the root says DONE (or ``max_fills``):
        pull the root's params, publish them to the group, run one
        shared-loop fill under the GROUP's admission policy, pre-reduce,
        forward one AGGR frame, repeat.  Worker-facing failure semantics
        are the server's own: eviction, re-admission, quorum short
        fills, starvation/idle errors — a group is a PS whose "update"
        is a forward."""
        if self._reduce_fn is None:
            raise NotCompiledError(
                "call compile_reduce() before serve_group()")
        if self._closed.is_set():
            raise FleetDeadError(
                "serve_group() called on a closed aggregator")
        import jax
        import jax.numpy as jnp

        self._net_stop.clear()
        accept = threading.Thread(target=self._accept_loop, daemon=True,
                                  name=f"agg-{self.group}-accept")
        accept.start()
        poll = min(0.5, max(idle_timeout / 4.0, 0.02))
        self._idle_timeout = idle_timeout
        idle_deadline = [time.perf_counter() + idle_timeout]

        def receive(timeout):
            try:
                item = self._net_queue.get(timeout=timeout)
            except queue.Empty:
                if self._closed.is_set():
                    raise FleetDeadError(
                        f"aggregator group {self.group} closed while "
                        f"serving") from None
                self._evict_dead(eviction_timeout, dead_conn_grace)
                if time.perf_counter() > idle_deadline[0]:
                    raise FleetDeadError(
                        f"group {self.group}: no worker gradient for "
                        f"{idle_timeout:.0f}s — group fleet dead or "
                        f"never started") from None
                return None
            idle_deadline[0] = time.perf_counter() + idle_timeout
            return item

        def drain_nowait():
            try:
                return self._net_queue.get_nowait()
            except queue.Empty:
                return None

        history: "dict[str, Any]" = {"fills": 0, "losses": [],
                                     "contributors": [],
                                     "grads_consumed": 0}
        plan = self.fault_plan
        t_start = time.perf_counter()
        fill = 0
        # The pace_timeout valve: armed while paced-out forwards sit in
        # the upstream sessions' pending queues; expired, it opens the
        # pace gate so a stalled/short-filling root costs seconds,
        # never a deadlock (`transport.Deadline` — the unified budget
        # type; PR 8 ran this as a bespoke re-pull wait loop).
        from ..transport import Deadline
        pace_valve: "Deadline | None" = None
        try:
            self._upstream.start_heartbeats()
            while max_fills is None or fill < max_fills:
                if plan is not None and plan.should_kill_agg(self.group,
                                                             fill):
                    self._dying = True
                    raise SimulatedCrash(
                        f"FaultPlan: aggregator group {self.group} "
                        f"killed before fill {fill}")
                if plan is not None and plan.should_slow_agg(self.group):
                    # A straggling AGGREGATOR: the whole group's forward
                    # lags — only the ROOT's quorum/deadline absorbs it.
                    time.sleep(plan.slow_agg_delay_s)
                versions = self._pull_and_publish()
                if versions is None:
                    break  # root DONE: propagate to the group via DONE
                pending = self._upstream.pending_frames()
                if pending == 0:
                    pace_valve = None
                elif pace_valve is None:
                    pace_valve = Deadline(self.pace_timeout)
                elif pace_valve.expired():
                    self._upstream.open_pace()
                    pace_valve = None
                self._evict_dead(eviction_timeout, dead_conn_grace)
                idle_deadline[0] = time.perf_counter() + idle_timeout
                (codes_list, stalenesses, losses, ranks, contribs,
                 fill_target, _short) = self._fill_gradients(
                    receive, drain_nowait,
                    current_version=lambda: self._served_version,
                    base_timeout=poll)
                # Host-side stack + one device_get: per-leaf jnp
                # dispatch is pure serve-rate tax on the fill path
                # (same move as the root's serve loop, v9).
                stacked = jax.tree.map(
                    lambda *xs: np.stack(
                        [np.asarray(x) for x in xs]), *codes_list)
                # The frame's version: the OLDEST contributing pull,
                # mapped back to the root's version vector — staleness
                # stays honest through the tier.
                v_old = self._served_version - (int(max(stalenesses))
                                                if stalenesses else 0)
                vmap = self._version_map.get(
                    v_old, self._version_map[min(self._version_map)])
                mean_loss = float(np.mean([float(l) for l in losses]))
                if self._bucket_plan is not None:
                    self._forward_bucketed(stacked, stalenesses, ranks,
                                           contribs, vmap, mean_loss,
                                           fill_target, len(codes_list))
                else:
                    codes_out = self._reduce_weighted(
                        stacked, stalenesses, ranks, contribs)
                    codes_host = jax.tree.map(np.asarray,
                                              jax.device_get(codes_out))
                    self._upstream.push(
                        codes_host, vmap, mean_loss, group=self.group,
                        n_contrib=len(codes_list), target=fill_target)
                self._bump("agg_forwards")
                history["fills"] += 1
                history["losses"].append(mean_loss)
                history["contributors"].append(list(ranks))
                history["grads_consumed"] += len(codes_list)
                fill += 1
                if log_every and fill % log_every == 0:
                    print(f"group {self.group} fill {fill:5d}  loss "
                          f"{mean_loss:.4f}  n={len(codes_list)}")
        finally:
            self._net_stop.set()
            self._listener.close()
            accept.join(timeout=5.0)
            self._upstream.close()
        history["wall_time"] = time.perf_counter() - t_start
        history["fault_stats"] = self._fault_stats_snapshot()
        return history

    def close(self) -> None:
        super().close()
        self._upstream.close()


class GroupWorker:
    """A hierarchy worker: computes against its group's aggregator, and
    FAILS OVER to a direct root connection when the aggregator dies
    un-restorably.

    Failure ladder on a lost aggregator link: (1) bounded re-dial with
    exponential backoff, re-presenting the local rank
    (``fault_stats["agg_redials"]``) — this is what rides a supervised
    aggregator restart with zero rank churn; (2) once the budget is
    spent, fall back to the ROOT (``fault_stats["agg_failovers"]``; the
    root books the flagged HELO under ``direct_fallbacks`` and lists the
    rank in its ``groups`` view) and finish the run as a plain worker —
    a `ShardRouter` when the root is a fleet, so failover composes with
    sharding too.  No root endpoints configured = the plain worker's
    clean-exit contract."""

    def __init__(self, agg_host: str, agg_port: int, *,
                 root_endpoints=None, group: int = 0,
                 code=None, token: "str | None" = None, fault_plan=None,
                 device=None, wire_level: int = 0,
                 io_timeout: float = 60.0, reconnect_retries: int = 3,
                 backoff_base: float = 0.1, backoff_max: float = 1.0,
                 heartbeat_interval: float = 2.0):
        self.group = int(group)
        self.root_endpoints = ([(h, int(p)) for h, p in root_endpoints]
                               if root_endpoints else None)
        self.fault_stats: "dict[str, int]" = {"agg_failovers": 0,
                                              "agg_redials": 0}
        self._link_kw = dict(code=code, token=token, fault_plan=fault_plan,
                             device=device, wire_level=wire_level,
                             io_timeout=io_timeout,
                             reconnect_retries=reconnect_retries,
                             backoff_base=backoff_base,
                             backoff_max=backoff_max,
                             heartbeat_interval=heartbeat_interval)
        self.link = AsyncPSWorker(agg_host, agg_port, **self._link_kw)
        self.rank = self.link.rank  # LOCAL rank, minted by the aggregator
        self.direct_rank: "int | None" = None

    @property
    def reconnects(self) -> int:
        return self.link.reconnects

    def close(self) -> None:
        self.link.close()

    def _redial(self) -> bool:
        if self.link._reconnect():
            self.fault_stats["agg_redials"] += 1
            return True
        return False

    def _fallback(self, loss_fn, batch_fn,
                  max_iters: "int | None") -> int:
        """The direct-root leg: re-admit at the root as a plain (but
        group-flagged) worker and finish the run there.  Root gone too —
        or refusing the config — means the run is over; 0 pushes, clean
        exit, exactly a plain worker's contract."""
        self.fault_stats["agg_failovers"] += 1
        kw = dict(self._link_kw)
        try:
            if len(self.root_endpoints) > 1:
                direct = ShardRouter(self.root_endpoints,
                                     fallback_group=self.group, **kw)
            else:
                (h, p), = self.root_endpoints
                direct = AsyncPSWorker(h, p, fallback_group=self.group,
                                       **kw)
        except _TRANSPORT_ERRORS:
            return 0
        self.direct_rank = direct.rank
        print(f"group {self.group} worker (local rank {self.rank}): "
              f"aggregator gone — direct fallback to the root as rank "
              f"{direct.rank}", file=sys.stderr)
        try:
            return direct.run(loss_fn, batch_fn, max_iters)
        finally:
            direct.close()

    def run(self, loss_fn: Callable,
            batch_fn: "Callable[[int, int], Any]",
            max_iters: "int | None" = None) -> int:
        """Work until the aggregator (or, post-failover, the root) says
        DONE.  Returns gradients pushed across both legs."""
        import jax

        from ..async_ps import make_worker_step

        plan = self._link_kw["fault_plan"]
        transform = (plan.byzantine_transform(self.rank)
                     if plan is not None else None)
        fn = make_worker_step(loss_fn, self.link.code, transform)
        pushed = 0
        it = 0
        failover = False
        self.link._start_heartbeat()
        try:
            while max_iters is None or it < max_iters:
                if (plan is not None
                        and plan.should_kill_worker(self.rank, it)):
                    raise SimulatedCrash(
                        f"FaultPlan: group {self.group} worker "
                        f"{self.rank} killed at iteration {it}")
                if plan is not None and plan.should_slow(self.rank):
                    time.sleep(plan.slow_delay_s)
                try:
                    pulled = self.link.pull()
                except _TRANSPORT_ERRORS:
                    if self._redial():
                        continue
                    failover = True
                    break
                if pulled is None:
                    break  # DONE rode down from the root
                version, params = pulled
                params = jax.device_put(params, self.link.device)
                batch = jax.device_put(batch_fn(self.rank, it),
                                       self.link.device)
                loss, codes = fn(params, batch)
                codes_host = jax.tree.map(np.asarray,
                                          jax.device_get(codes))
                if (plan is not None
                        and plan.inject_nonfinite(self.rank, it)):
                    from ..utils.faults import poison_nonfinite
                    codes_host = poison_nonfinite(codes_host)
                try:
                    self.link.push(codes_host, version, float(loss))
                except _TRANSPORT_ERRORS:
                    if self._redial():
                        continue  # the gradient is lost; pull afresh
                    failover = True
                    break
                # Overload injectors ride the link's own machinery; the
                # link's counters fold into this worker's below.
                self.link._inject_overload(plan, it, codes_host, version,
                                           float(loss))
                pushed += 1
                it += 1
        finally:
            for k, v in self.link.fault_snapshot().items():
                if v:
                    self.fault_stats[k] = self.fault_stats.get(k, 0) + v
            self.link.close()
        if failover and self.root_endpoints:
            remaining = None if max_iters is None else max_iters - it
            pushed += self._fallback(loss_fn, batch_fn, remaining)
        return pushed


class Hierarchy:
    """Spawn and supervise G group-local aggregators against one root.

    Usage (the root — an `AsyncPSServer` or `PSFleet` — must already be
    accepting connections)::

        hier = Hierarchy(named_params, groups=3, group_size=4,
                         upstream=[("127.0.0.1", root_port)],
                         quorum=3, fill_deadline=0.1,
                         aggregate="trimmed_mean", anomaly_z=4.0)
        hier.compile()
        view = hier.serve()          # returns when the root says DONE

    Every keyword argument beyond the topology reaches each
    `LocalAggregator` unchanged, so per-GROUP policy is exactly
    single-PS policy.  An aggregator killed by ``kill_agg_at`` is
    restarted (bounded by ``max_restarts`` per group) on the SAME port
    with the SAME upstream rank — workers inside their redial budget
    reconnect with their prior local ranks, the root books the same
    aggregator rank, and the group is reclaimed with zero rank churn;
    past the budget the group stays down and its workers' own failover
    (direct root fallback) takes over."""

    def __init__(self, named_params, *, groups: int, group_size: int,
                 upstream, host: str = "127.0.0.1", ports=None,
                 fault_plan=None, max_restarts: int = 2, **agg_kw):
        if groups < 1:
            raise ValueError(f"groups must be >= 1, got {groups}")
        self._named_params = list(
            named_params.items() if hasattr(named_params, "items")
            else named_params)
        self.groups = int(groups)
        self.group_size = int(group_size)
        self.upstream = [(h, int(p)) for h, p in upstream]
        self.host = host
        self.fault_plan = fault_plan
        self.max_restarts = int(max_restarts)
        self._agg_kw = dict(agg_kw)
        if ports is None:
            port_list = [0] * groups
        elif isinstance(ports, int):
            port_list = ([0] * groups if ports == 0
                         else [ports + g for g in range(groups)])
        else:
            port_list = list(ports)
            if len(port_list) != groups:
                raise ValueError(
                    f"{len(port_list)} ports for {groups} groups")
        self.aggregators: "list[LocalAggregator]" = []
        try:
            for g in range(groups):
                self.aggregators.append(
                    self._make_agg(g, port_list[g], upstream_rank=None,
                                   consume_kill=False))
        except BaseException:
            self.close()
            raise
        self.fault_stats: "dict[str, int]" = {"agg_restarts": 0}
        self._slots = [{"hist": None, "error": None, "restarts": 0}
                       for _ in range(groups)]
        # Crashed-and-replaced incarnations' final snapshots: their
        # counters must keep counting in the tier view, not vanish with
        # the object swap (the `PSFleet` retired-incarnation contract).
        self._retired: "list[tuple[int, dict]]" = []

    def _make_agg(self, g: int, port: int, *, upstream_rank,
                  consume_kill: bool,
                  upstream_seq: int = 0) -> LocalAggregator:
        plan = self.fault_plan
        if consume_kill and plan is not None and g in plan.kill_agg_at:
            # The restarted incarnation must not crash-loop on the same
            # injection — the restore contract `PSFleet` established.
            remaining = dict(plan.kill_agg_at)
            remaining.pop(g)
            plan = dataclasses.replace(plan, kill_agg_at=remaining)
        return LocalAggregator(
            self._named_params, group=g, upstream=self.upstream,
            group_size=self.group_size, host=self.host, port=port,
            upstream_rank=upstream_rank, upstream_seq=upstream_seq,
            fault_plan=plan, **self._agg_kw)

    @property
    def addresses(self) -> "list[tuple[str, int]]":
        """(host, port) per group, in group order — what each group's
        workers connect to."""
        return [agg.address for agg in self.aggregators]

    def compile(self) -> None:
        for agg in self.aggregators:
            agg.compile_reduce()

    def _serve_agg(self, g: int, serve_kw: dict) -> None:
        slot = self._slots[g]
        try:
            slot["hist"] = self.aggregators[g].serve_group(**serve_kw)
        except BaseException as exc:  # recorded; supervisor decides
            slot["error"] = exc

    def serve(self, log_every: int = 0,
              idle_timeout: float = 300.0, *,
              eviction_timeout: float = 30.0,
              dead_conn_grace: float = 2.0,
              max_fills: "int | None" = None) -> "dict[str, Any]":
        """Run every group's aggregator until the root finishes.  On a
        planned aggregator death (`SimulatedCrash` via ``kill_agg_at``)
        the group is restarted in place — same port, same upstream rank
        (``agg_restarts``) — bounded by ``max_restarts``; past the
        budget (or on restart being disabled with ``max_restarts=0``)
        the group stays down and its workers' direct fallback owns
        recovery.  Any other per-group failure is recorded, printed,
        and survived by the rest of the tier; only a tier that NEVER
        functioned (every group failed before forwarding one fill)
        raises the typed `AggregatorDeadError`."""
        serve_kw = dict(log_every=log_every, idle_timeout=idle_timeout,
                        eviction_timeout=eviction_timeout,
                        dead_conn_grace=dead_conn_grace,
                        max_fills=max_fills)
        threads: "dict[int, threading.Thread]" = {}

        def launch(g: int) -> None:
            t = threading.Thread(target=self._serve_agg,
                                 args=(g, serve_kw), daemon=True,
                                 name=f"hier-agg-{g}")
            threads[g] = t
            t.start()

        t_start = time.perf_counter()
        for g in range(self.groups):
            launch(g)
        while True:
            alive = False
            for g, t in list(threads.items()):
                t.join(timeout=0.1)
                if t.is_alive():
                    alive = True
                    continue
                slot = self._slots[g]
                err, slot["error"] = slot["error"], None
                if err is None:
                    continue
                if (isinstance(err, SimulatedCrash)
                        and slot["restarts"] < self.max_restarts):
                    old = self.aggregators[g]
                    port = old.address[1]
                    rank = old.upstream_rank
                    seq = old._upstream.push_seq()
                    self._retired.append((g, old._fault_stats_snapshot()))
                    old.close()
                    agg = self._make_agg(g, port, upstream_rank=rank,
                                         consume_kill=True,
                                         upstream_seq=seq)
                    agg.compile_reduce()
                    self.aggregators[g] = agg
                    slot["restarts"] += 1
                    self.fault_stats["agg_restarts"] += 1
                    print(f"hierarchy: restarted aggregator for group "
                          f"{g} on port {port} (upstream rank {rank} "
                          f"reclaimed)", file=sys.stderr)
                    launch(g)
                    alive = True
                else:
                    # Gone for good: the group's WORKERS own recovery
                    # from here (bounded redial, then direct fallback to
                    # the root) — a dead middle box must degrade the
                    # topology, not kill the run.
                    slot["error_final"] = err
                    print(f"hierarchy: aggregator for group {g} is down "
                          f"for good ({err!r}) — its workers fail over "
                          f"to direct root connections", file=sys.stderr)
            if not alive:
                break
        wall = time.perf_counter() - t_start
        per_group = [slot["hist"] for slot in self._slots]
        forwarded = sum(h["fills"] for h in per_group if h)
        if forwarded == 0:
            failures = [s.get("error_final") for s in self._slots
                        if s.get("error_final") is not None]
            if len(failures) == self.groups:
                raise AggregatorDeadError(
                    "every group aggregator failed before forwarding a "
                    "single fill — the hierarchy tier never functioned "
                    "(is the root reachable?)") from failures[0]
        view = self.hierarchy_fault_stats()
        return {"per_group": per_group, "fills_total": forwarded,
                "wall_time": wall, "fault_stats": view}

    # -- the one tier view ----------------------------------------------------

    def hierarchy_fault_stats(self) -> "dict[str, Any]":
        """Aggregate the per-group aggregator snapshots: integer
        counters summed tier-wide (rendered by the same
        `format_fault_stats` line), full per-group snapshots — the
        group-level scoreboard/quarantine detail the containment story
        is about — under ``"groups"`` keyed by group id."""
        agg: "dict[str, Any]" = dict(self.fault_stats)
        groups: "dict[str, Any]" = {}
        retired = [(f"{g}:retired{i}", snap)
                   for i, (g, snap) in enumerate(self._retired)]
        live = [(str(g), (a._fault_stats_snapshot()
                          if self._slots[g]["hist"] is None
                          else self._slots[g]["hist"]["fault_stats"]))
                for g, a in enumerate(self.aggregators)]
        for name, snap in retired + live:
            groups[name] = snap
            for key, value in snap.items():
                if isinstance(value, bool):
                    continue
                if key == "workers_seen":
                    agg[key] = agg.get(key, 0) + value  # disjoint groups
                elif key == "repl_lag":
                    continue
                elif isinstance(value, int):
                    agg[key] = agg.get(key, 0) + value
        agg["groups"] = groups
        return agg

    def close(self) -> None:
        for a in self.aggregators:
            a.close()

    def join(self, timeout: float = 10.0) -> None:
        """Once `serve` has returned: `AsyncPSServer.join` on every
        aggregator; `close` follows."""
        for a in self.aggregators:
            a.join(timeout)
