"""PS optimizer layer (L3) — TPU-native `MPI_PS` / `SGD` / `Adam`.

Reference behavior contract (`/root/reference/ps.py:53-193`):

* constructed from **named parameters** plus optimizer hyperparameters; names
  must be unique (`ps.py:118-119,150-153` — validated here at construction);
* each step: every rank computes gradients on its local batch shard, encodes
  them with the pluggable codec, all ranks exchange the encoded gradients,
  decode all ``world_size`` codes, **sum** them (`ps.py:176` — sum, not mean),
  and apply an identical SGD/Adam update (`ps.py:195-261`), leaving parameters
  replicated — every rank is its own parameter server;
* ``step()`` returns ``(loss, metrics_dict)`` (`ps.py:193`) with per-phase
  timing and byte counts.

TPU-native redesign: the entire step — forward, backward, encode, exchange,
decode-sum, update — is **one jitted SPMD program** over a
`jax.sharding.Mesh`, via `jax.shard_map`.  The reference's machinery dissolves:

* backward hooks + a 200-thread encode pool (`ps.py:63-66,85,98-101`) existed
  to overlap encoding with backward; here the step has one gradient
  exchange, after the backward, bucketed (`bucket_mb`,
  `parallel/collectives.py`) into a few large flat transfers (a handful of
  collectives where the per-parameter lowering, ``bucket_mb=0``, has 130
  for ResNet-18), which XLA's scheduler may run beside the backward pass —
  the thread pool's overlap, left to the compiler.  On the chip it does so
  for collective-permutes and not for all-reduces, so on several TPU chips
  each bucket's sum is a ring of hops, chosen from the mesh
  (`MPI_PS._exchange_ring`; PERF.md, `gpt2m-sync-dp4`);
* the ``Iallgather``-of-sizes protocol (`ps.py:140-147`) existed because
  pickled payloads have unknown sizes; codec outputs have static shapes, so
  gradient exchange is a single ``all_gather`` (or, for the identity codec, a
  fused ``psum`` all-reduce) over the ICI mesh;
* pickle+blosc serialization (`mpi_comms.py:186-193`) is replaced by pytree
  leaves living in HBM end-to-end — the zero-copy design
  `serialization.py` was reaching for.

Gradients are computed *inside* ``step`` via ``jax.value_and_grad`` of a
user-supplied ``loss_fn(params, batch)`` — the JAX analogue of
``loss.backward()`` followed by ``opt.step()``.
"""

from __future__ import annotations

import sys
from collections import OrderedDict
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ops.codecs import Codec, IdentityCodec, get_codec
from .optim.rules import RULES
from .parallel.mesh import PS_AXIS, make_ps_mesh, replicated
from .parallel import collectives
from .utils.bytes import bytes_of
from .utils.timing import (STEP_METRIC_KEYS, BoundedList, counter_log,
                           register_program, span, step_scope)

Params = "OrderedDict[str, jax.Array]"

# Hyperparameters accepted per optimizer — the analogue of the reference's
# kwargs filtering at dispatch (`/root/reference/ps.py:181-190`).
_HYPER_KEYS = {
    "sgd": {"lr", "momentum", "dampening", "weight_decay", "nesterov"},
    "adam": {"lr", "betas", "eps", "weight_decay", "amsgrad"},
    "adamw": {"lr", "betas", "eps", "weight_decay", "amsgrad"},
}
_HYPER_DEFAULTS = {
    "sgd": dict(lr=0.01, momentum=0.0, dampening=0.0, weight_decay=0.0,
                nesterov=False),
    "adam": dict(lr=1e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                 amsgrad=False),
    "adamw": dict(lr=1e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=1e-2,
                  amsgrad=False),
}


class ElasticResumeError(ValueError):
    """A checkpoint that cannot be remapped onto this optimizer's topology.

    Elastic N→M resume de-chunks/re-chunks ZeRO shards and remaps the
    error-feedback residual across device counts; when a component is
    GENUINELY topology-bound (or reflects a model change, not a topology
    change), this names it instead of loading a silently-wrong tree."""


class SDCDetectedError(RuntimeError):
    """The replica-consensus guard found data-parallel replicas that are
    not bitwise identical — silent data corruption or a desync bug.
    Raised under ``consensus_policy="abort"``; the message names the first
    diverging parameter leaf."""


def find_param(params: Params, name: str):
    """Lookup-by-name helper (`/root/reference/ps.py:46-50` parity; names are
    unique by construction so this cannot hit the >1-match error path)."""
    if name not in params:
        raise KeyError(name)
    return params[name]


def tree_all_finite(*trees) -> bool:
    """Host-side all-finite check over pytrees (float leaves only).

    The sync PS's ``skip_nonfinite`` machinery runs *inside* the jitted
    step with cross-rank consensus (`_make_spmd_step`); the async paths
    consume gradients one at a time on the host, so their quarantine gate
    is this materialized check instead — same contract (a non-finite
    gradient must never reach the update), different execution site.
    Integer leaves (quantized codecs) are finite by construction and
    skipped."""
    import numpy as _np

    for t in trees:
        for leaf in jax.tree_util.tree_leaves(t):
            a = _np.asarray(leaf)
            if a.dtype.kind == "V" and "float" in a.dtype.name:
                # ml_dtypes extension floats (bfloat16 codecs): numpy's
                # isfinite refuses the raw dtype; widen first.
                a = a.astype(_np.float32)
            if (_np.issubdtype(a.dtype, _np.floating)
                    or _np.issubdtype(a.dtype, _np.complexfloating)):
                if not _np.isfinite(a).all():
                    return False
    return True


def init_ps_core(named_params, optim: str, hyper: dict, place):
    """Shared construction for the sync and async PS variants: validate the
    optimizer name and hyperparameters, enforce name uniqueness
    (`/root/reference/ps.py:150-153`), place params via ``place`` and build
    per-parameter optimizer state.  Returns ``(params, state, merged_hyper,
    update_fn)``."""
    if optim not in RULES:
        raise ValueError(
            f"optimizer {optim!r} not supported; have {sorted(RULES)}")
    unknown = set(hyper) - _HYPER_KEYS[optim]
    if unknown:
        raise TypeError(f"unexpected {optim} hyperparameters: {sorted(unknown)}")
    merged = dict(_HYPER_DEFAULTS[optim])
    merged.update(hyper)

    pairs = list(named_params)
    names_list = [n for n, _ in pairs]
    if len(set(names_list)) != len(names_list):
        raise ValueError("parameter names must be unique")
    params: Params = OrderedDict(
        (n, place(jnp.asarray(p))) for n, p in pairs)

    init_fn, update_fn = RULES[optim]
    init_kwargs = ({"amsgrad": merged["amsgrad"]}
                   if optim in ("adam", "adamw") else {})
    state = OrderedDict(
        (n, jax.tree.map(place, init_fn(p, **init_kwargs)))
        for n, p in params.items())
    return params, state, merged, update_fn


class MPI_PS:
    """Replicated-state parameter-server optimizer over a TPU mesh.

    Usage::

        mesh = make_ps_mesh()                      # the mpirun -n N analogue
        opt = SGD(model_named_params, lr=0.1, momentum=0.9, mesh=mesh)
        opt.compile_step(loss_fn)                  # loss_fn(params, batch)
        for batch in data:
            loss, metrics = opt.step(batch)

    ``code=`` plugs a gradient codec (`ops.codecs`).
    """

    def __init__(self, named_params, *, optim: str = "sgd",
                 code: Codec | str | None = None, mesh: Mesh | None = None,
                 axis: "str | tuple" = PS_AXIS, batch_spec: P | None = None,
                 zero: bool = False,
                 skip_nonfinite: bool = False, clip_norm: float | None = None,
                 error_feedback: bool = False, ema_decay: float | None = None,
                 bucket_mb: float | None =
                 collectives.DEFAULT_BUCKET_BYTES / (1 << 20),
                 consensus_every: int = 0,
                 consensus_policy: str = "abort",
                 **hyper):
        self.optim = optim
        self.mesh = mesh if mesh is not None else make_ps_mesh()
        # The codec's kernels follow the mesh's devices, not the process's
        # default backend: Mosaic on TPUs, the jnp reference on the CPU mesh.
        self.code = get_codec(code, self.mesh.devices.flat[0].platform)
        # ``axis`` may name several mesh axes that are all data-parallel —
        # e.g. ('dcn', 'ps') on a multi-slice hybrid mesh, where the inner
        # axis rides ICI and the outer rides DCN.  Collectives take the
        # tuple directly; XLA lowers the reduction hierarchically.
        self.axes = (axis,) if isinstance(axis, str) else tuple(axis)
        for a in self.axes:
            if a not in self.mesh.axis_names:
                raise ValueError(
                    f"axis {a!r} not in mesh axes {self.mesh.axis_names}")
        self.axis = self.axes  # collectives accept axis-name tuples directly
        # Reduction semantics: gradients SUM across the data-parallel axes
        # (reference `ps.py:176` — every rank contributes its gradient), but
        # AVERAGE across any extra axes (e.g. sequence-parallel 'sp' from
        # make_dp_sp_mesh): an sp shard holds the gradient of its *local
        # mean* loss, and the rank's true gradient is the mean of those —
        # sp is an execution detail that must not rescale the update.
        self.reduce_axes = tuple(self.mesh.axis_names)
        self.extra_axes = tuple(a for a in self.mesh.axis_names
                                if a not in self.axes)
        # How batches shard over the mesh. Default: leading (batch) dim over
        # the combined data axes. A (dp, sp) run passes P('ps', 'sp') to also
        # shard the sequence dim.
        self.batch_spec = (batch_spec if batch_spec is not None
                           else P(self.axes))
        # Gradient bucketing: the cross-rank exchange concatenates same-dtype
        # code leaves into flat buckets of <= bucket_mb MiB and runs ONE
        # collective per bucket instead of one per parameter (the reference's
        # per-param Iallgather loop, `/root/reference/ps.py:140-147`,
        # transliterated to XLA was ~130 small synchronous all-gathers for
        # ResNet-18).  Few large transfers saturate ICI and give XLA's
        # latency-hiding scheduler pieces it can overlap with compute.
        # Bitwise-identical update math (packing is pure data movement);
        # ``bucket_mb=None``/0 restores the per-parameter lowering.
        if bucket_mb is not None and bucket_mb < 0:
            raise ValueError(f"bucket_mb must be >= 0, got {bucket_mb}")
        self.bucket_bytes = (int(bucket_mb * (1 << 20))
                             if bucket_mb else None)
        # ZeRO-style sharded optimizer state: each data-parallel rank owns
        # 1/world of every elementwise state buffer (momentum, Adam
        # moments).  Gradients reduce-scatter straight to the owning chunk,
        # each rank updates only its chunk, and the updated parameter
        # chunks all-gather back to replicated params.  The win is MEMORY:
        # optimizer state drops by world_size with bitwise-identical update
        # math.  Net per-step traffic is unchanged (~2x payload: the
        # all-reduce it replaces is itself reduce-scatter + all-gather).
        self.zero = zero

        # Skip-on-NaN: when any rank's local gradient contains a non-finite
        # value (divergent loss, bad batch), the whole world skips the
        # update in consensus — params/state/aux carry forward unchanged
        # and the step reports ``nonfinite_skip=1``.  The check runs on the
        # raw per-rank gradients BEFORE encode, so a NaN cannot first be
        # laundered into a finite-looking quantized code.  The failure-
        # detection subsystem the reference declares out of scope
        # (README.md:7 "communication is reliable" — but gradients aren't).
        # Global-norm gradient clipping, applied to the cross-rank SUMMED
        # gradient (the quantity the update rules consume) so every rank
        # scales identically — the torch.nn.utils.clip_grad_norm_ knob the
        # reference leaves to the user's loop.
        if clip_norm is not None and not clip_norm > 0:
            # `not >` (rather than `<=`) also rejects NaN, which would
            # otherwise scale every gradient to NaN on the first step.
            raise ValueError(f"clip_norm must be positive, got {clip_norm}")
        self.clip_norm = clip_norm
        self.skip_nonfinite = skip_nonfinite

        # Error feedback (EF-SGD, Karimireddy et al.): each rank keeps the
        # residual its lossy codec dropped and adds it back before the next
        # encode, so compression error accumulates into the update stream
        # instead of being lost — the fix that makes aggressive topk/sign
        # compression converge.  The residual is genuinely PER-RANK state
        # (the one rank-varying tensor in this replicated-state design); it
        # lives as a [world, ...] leaf sharded over the data axes.
        self.error_feedback = error_feedback
        if error_feedback:
            if isinstance(self.code, IdentityCodec):
                raise ValueError(
                    "error_feedback needs a lossy codec: the identity "
                    "codec decodes exactly, so the residual is always 0")

        # Polyak/EMA weight averaging: the step also maintains
        # ema = decay*ema + (1-decay)*params inside the same program —
        # `ema_params` is the evaluation-quality weight set, standard for
        # vision/LM training.  Stored replicated like params.
        if ema_decay is not None and not 0.0 < ema_decay < 1.0:
            raise ValueError(f"ema_decay must be in (0, 1), got {ema_decay}")
        self.ema_decay = ema_decay

        rep = replicated(self.mesh)
        # jnp.array(copy=True) before placement: device_put aliases (no copy)
        # when the input already has the target sharding, and the donated step
        # would then delete buffers the *caller* may still hold.
        self.params, self.state, self.hyper, self._update_fn = init_ps_core(
            named_params, optim, hyper,
            place=lambda x: jax.device_put(jnp.array(x, copy=True), rep))

        self.world_size = int(np.prod([self.mesh.shape[a] for a in self.axes]))
        if zero:
            # Per-param flat size and per-rank chunk length (zero-padded up
            # to world_size * chunk).
            self._zero_meta = {
                n: (int(np.prod(p.shape)),
                    -(-int(np.prod(p.shape)) // self.world_size))
                for n, p in self.params.items()}
            self.state = self._chunk_and_place_state(self.state)
        # Optional per-step carried state beyond params/state/aux, one
        # extras tree so the jitted step's signature stays fixed: "ef" is
        # the per-rank EF residual ([world, ...], sharded over the data
        # axes), "ema" the replicated averaged weights.
        self.extras: "OrderedDict[str, Any]" = OrderedDict()
        if error_feedback:
            sharded = NamedSharding(self.mesh, P(self.axes))
            self.extras["ef"] = OrderedDict(
                (n, jax.device_put(
                    jnp.zeros((self.world_size,) + p.shape, jnp.float32),
                    sharded))
                for n, p in self.params.items())
        if ema_decay is not None:
            self.extras["ema"] = OrderedDict(
                (n, jax.device_put(jnp.array(p, copy=True), rep))
                for n, p in self.params.items())
        # Replica-consensus SDC guard: every ``consensus_every`` steps the
        # parameter tree is fingerprinted per replica and compared across
        # the mesh (data-parallel replicas must be bitwise identical — any
        # mismatch is silent data corruption or a desync bug).  Policy
        # "abort" raises `SDCDetectedError`; "rebroadcast" restores
        # consensus from replica 0's copy and keeps training.  0 = off.
        if consensus_every < 0:
            raise ValueError(
                f"consensus_every must be >= 0, got {consensus_every}")
        if consensus_policy not in ("abort", "rebroadcast"):
            raise ValueError(f"consensus_policy must be 'abort' or "
                             f"'rebroadcast', got {consensus_policy!r}")
        self.consensus_every = int(consensus_every)
        self.consensus_policy = consensus_policy
        self._consensus_fn = None
        self._rebroadcast_fn = None
        # Failure-path observability for the sync trainer — the sync
        # analogue of the async server's fault_stats section: SDC-guard
        # counters here, rollback events appended by the training loop.
        self.fault_stats: dict[str, Any] = {
            "sdc_checks": 0, "sdc_mismatches": 0, "sdc_rebroadcasts": 0,
            "sdc_first_leaf": None, "sdc_events": [], "rollbacks": []}
        # `ps.py:80` accumulator: one dict a step, the newest 65,536.
        self.timings: list[dict[str, float]] = BoundedList()
        self._byte_metrics: "dict[str, float] | None" = None
        # Incremented the moment a step's NEW params become visible on self
        # (i.e. with the post-dispatch reassignment, before the blocking
        # wait).  An interrupt-triggered checkpoint must record the step
        # count matching the params it snapshots: the training loop's own
        # counter advances only after step() returns, so a Ctrl-C landing
        # inside the wait would otherwise save post-step-N+1 params labeled
        # step N and a resume would re-apply batch N+1 (r4 advisor).
        self.steps_completed = 0
        self.aux = {}            # model aux state (e.g. BatchNorm batch_stats)
        self._has_aux = False
        self._has_counters = False   # aux carries a "counters" sub-tree
        self._step_programs = {}     # batch signature -> compiled step
        self._accum = 1
        self._remat = False
        self._step_fn = None
        self._loss_fn = None

    # -- ZeRO state layout ----------------------------------------------------

    def _chunk_and_place_state(self, state):
        """Full elementwise state buffers → ``(world, chunk)`` arrays
        sharded over the data axes (each rank holds one row); scalar leaves
        (step counters) stay replicated."""
        sharded = NamedSharding(self.mesh, P(self.axes))
        rep = replicated(self.mesh)
        world = self.world_size
        out = OrderedDict()
        for n, st in state.items():
            sz, chunk = self._zero_meta[n]
            shape = self.params[n].shape

            def leaf(v, *, sz=sz, chunk=chunk, shape=shape):
                v = np.asarray(v)
                if v.shape != tuple(shape):  # scalar step counter etc.
                    return jax.device_put(jnp.asarray(v), rep)
                flat = np.zeros((world * chunk,), v.dtype)
                flat[:sz] = v.reshape(-1)
                return jax.device_put(
                    jnp.asarray(flat.reshape(world, chunk)), sharded)

            out[n] = jax.tree.map(leaf, st)
        return out

    def _dechunk_state(self, state):
        """Inverse of `_chunk_and_place_state`: host tree with full-shape
        elementwise buffers, world-size independent (so zero-mode
        checkpoints interchange freely with replicated-mode ones)."""
        world = self.world_size
        out = OrderedDict()
        for n, st in state.items():
            sz, chunk = self._zero_meta[n]
            shape = self.params[n].shape

            def leaf(v, *, sz=sz, chunk=chunk, shape=shape):
                a = np.array(jax.device_get(v))
                if a.shape == (world, chunk):
                    return a.reshape(-1)[:sz].reshape(shape)
                return a
            out[n] = jax.tree.map(leaf, st)
        return out

    def _state_specs(self):
        """Per-leaf PartitionSpecs for the optimizer state pytree."""
        if not self.zero:
            return P()
        return jax.tree.map(
            lambda v: P(self.axes) if v.ndim > 0 else P(), self.state)

    # -- step construction ---------------------------------------------------

    def _encode_all(self, grads):
        return OrderedDict((n, self.code.encode(g)) for n, g in grads.items())

    def _sync_codes(self, codes, grads_meta):
        """all_gather the code leaves across the PS axis (bucketed when
        ``bucket_mb`` is set — one flat transfer per ~bucket_mb of same-dtype
        payload across ALL parameters), then decode-sum per parameter."""
        with step_scope("exchange"):
            gathered = collectives.allgather_tree_bucketed(
                codes, self.axis, bucket_bytes=self.bucket_bytes)
        d_ps = OrderedDict()
        for n, code in gathered.items():
            shape, dtype = grads_meta[n]
            d_ps[n] = self.code.decode_sum(code, shape=shape, dtype=dtype)
        return d_ps

    def _resolved_hyper(self, state_n):
        """``lr`` may be a schedule — a callable of the step count
        (`optim.schedules`); resolve it against this param's (traced) step
        counter so the schedule compiles into the update and stays aligned
        across checkpoint/resume (the count lives in optimizer state)."""
        from .optim.schedules import resolve_hyper
        return resolve_hyper(self.hyper, state_n["step"])

    def _apply_updates(self, params, state, d_ps):
        new_params, new_state = OrderedDict(), OrderedDict()
        with step_scope("update"):
            for n, p in params.items():
                if n not in d_ps:  # grad-is-None skip (`ps.py:178-179` parity)
                    new_params[n], new_state[n] = p, state[n]
                    continue
                new_params[n], new_state[n] = self._update_fn(
                    p, d_ps[n], state[n], **self._resolved_hyper(state[n]))
        return new_params, new_state

    def _grads_and_aux(self, loss_fn, has_aux: bool, params, aux, batch):
        """Per-rank gradients + synced aux — the front half of the step.

        Gradients here are *per-rank* (each rank grads its own batch shard);
        the cross-rank sum happens later, explicitly, like the reference's
        decode-then-sum (`ps.py:165-176`).  This relies on check_vma=False:
        with replication typing on, shard_map would auto-psum the cotangent
        of the replicated params.  Returns ``(loss, grads, new_aux)`` with
        loss/grads already collapsed over the extra (non-data) axes — an sp
        shard holds the gradient of its *local mean* loss, and the rank's
        true gradient is the mean of those.

        With ``accum_steps > 1`` the per-rank batch shard splits into that
        many microbatches swept by a ``lax.scan`` — activation memory is
        one microbatch's worth, gradients average across microbatches (==
        the full-shard gradient for mean losses), and aux (BN stats)
        threads through sequentially."""
        accum = self._accum
        if accum > 1:
            leaf = jax.tree.leaves(batch)[0]
            if leaf.shape[0] % accum:
                raise ValueError(
                    f"per-rank batch of {leaf.shape[0]} does not split "
                    f"into accum_steps={accum} microbatches")
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)
            acc0 = jax.tree.map(jnp.zeros_like, params)

            def body(carry, mb):
                aux_c, acc = carry
                with step_scope("grad"):
                    if has_aux:
                        (loss, aux_c), g = jax.value_and_grad(
                            loss_fn, has_aux=True)(params, aux_c, mb)
                    else:
                        loss, g = jax.value_and_grad(loss_fn)(params, mb)
                acc = jax.tree.map(jnp.add, acc, g)
                return (aux_c, acc), loss

            (new_aux, acc), losses = lax.scan(body, (aux, acc0), micro)
            grads = jax.tree.map(lambda a: a / accum, acc)
            loss = jnp.mean(losses)
        elif has_aux:
            with step_scope("grad"):
                (loss, new_aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, aux, batch)
        else:
            with step_scope("grad"):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_aux = aux
        with step_scope("exchange"):
            if has_aux:
                # Batch stats are per-rank; average them so aux stays
                # replicated (the standard cross-replica BN-stats sync).
                new_aux = collectives.pmean_tree(new_aux, self.reduce_axes)
            if self.extra_axes:
                # Collapse the intra-rank axes first: after this, every sp
                # shard holds its rank's full gradient, replicated.
                grads = collectives.pmean_tree(grads, self.extra_axes)
                loss = lax.pmean(loss, self.extra_axes)
        return loss, grads, new_aux

    def _exchange_ring(self) -> bool:
        """Which lowering the identity codec's bucketed gradient sum takes,
        from what can be seen of the mesh when the step is traced: the ring
        of asynchronous hops (`collectives._ring_tree`) where the default
        exchange runs over several TPU chips on one data axis, XLA's
        all-reduce, and the program it always made, everywhere else (the
        CPU, one chip, ``zero``, ``bucket_mb=0``, a codec).  On the v5e a
        bucket's `lax.psum` compiles to a synchronous ``all-reduce`` that
        stops the core (28.4 ms of a 201.6 ms step in `gpt2m-sync-dp4`, in
        the ledger); the ring's hops run beside the backward (PERF.md §6)."""
        return (isinstance(self.code, IdentityCodec) and len(self.axes) == 1
                and self.world_size > 1 and bool(self.bucket_bytes)
                and not self.zero
                and all(d.platform == "tpu" for d in self.mesh.devices.flat))

    def _summed_grads(self, grads):
        """Cross-rank gradient sum, full tensors: the identity codec fuses
        to bucketed all-reduces; codecs ride all_gather + fused decode-sum."""
        if isinstance(self.code, IdentityCodec):
            with step_scope("exchange"):
                return collectives.psum_tree_bucketed(
                    grads, self.axis, bucket_bytes=self.bucket_bytes,
                    ring=self._exchange_ring())
        meta = {n: (g.shape, g.dtype) for n, g in grads.items()}
        codes = self._encode_all(grads)
        return self._sync_codes(codes, meta)

    def _summed_grads_ef(self, grads, ef):
        """Error-feedback sync: add this rank's residual to the raw
        gradient, encode/exchange/decode-sum as usual, and keep what the
        codec dropped (``d - decode(encode(d))``) as the next residual.
        Returns ``(summed, new_ef)``; ``ef`` leaves are per-rank blocks
        ``[1, ...]`` of the sharded ``[world, ...]`` residual."""
        meta = {n: (g.shape, g.dtype) for n, g in grads.items()}
        d = OrderedDict(
            (n, g + ef[n][0].astype(g.dtype)) for n, g in grads.items())
        codes = self._encode_all(d)
        new_ef = OrderedDict(
            (n, (d[n] - self.code.decode(
                codes[n], shape=meta[n][0], dtype=meta[n][1])
                ).astype(jnp.float32)[None])
            for n in d)
        return self._sync_codes(codes, meta), new_ef

    def _clip_tree(self, d_ps, *, psum_axis=None):
        """Global-norm clip of the summed gradient.  With ``psum_axis`` the
        leaves are disjoint per-rank chunks (the ZeRO layout, pads zero)
        and the global sq-norm assembles via one scalar psum; without it
        the leaves are the full replicated tensors."""
        with step_scope("update"):
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(d_ps))
            if psum_axis is not None:
                with step_scope("exchange"):
                    sq = lax.psum(sq, psum_axis)
            scale = jnp.minimum(1.0, self.clip_norm / (jnp.sqrt(sq) + 1e-6))
            return jax.tree.map(lambda g: (g * scale).astype(g.dtype), d_ps)

    def _extras_specs(self):
        """Per-key PartitionSpecs for the extras tree: the EF residual is
        per-rank sharded over its leading world dim; EMA weights are
        replicated like params."""
        table = {"ef": P(self.axes), "ema": P()}
        return OrderedDict((k, table[k]) for k in self.extras)

    def _make_spmd_step(self, loss_fn, has_aux: bool):
        identity = isinstance(self.code, IdentityCodec)
        use_ef = self.error_feedback
        ema_decay = self.ema_decay
        counters = self._has_counters

        # The phases below and in the helpers they call run under
        # `step_scope(...)` (`ps.grad`, `ps.exchange`, `ps.update`): names
        # on the operations, which `utils.timing.step_phase` reads back out
        # of the compiled text to split a device trace by phase (PERF.md §3).
        def core(params, state, aux, batch, extras):
            loss, grads, new_aux = self._grads_and_aux(
                loss_fn, has_aux, params, aux, batch)
            if self.skip_nonfinite:
                # Checked on the RAW gradients, before the residual mixes
                # in: a NaN batch must not poison the carried residual.
                bad = sum(jnp.sum(~jnp.isfinite(g)).astype(jnp.float32)
                          for g in jax.tree.leaves(grads))
                with step_scope("exchange"):
                    ok = lax.psum(bad, self.reduce_axes) == 0
            new_extras = OrderedDict(extras)
            if use_ef:
                d_sum, new_extras["ef"] = self._summed_grads_ef(
                    grads, extras["ef"])
            else:
                d_sum = None
            if self.zero:
                # Identity + zero skips the full sum entirely: the
                # reduce-scatter inside _zero_sync IS the sync.
                if not use_ef:
                    d_sum = None if identity else self._summed_grads(grads)
                d_chunks = self._zero_sync(grads, d_sum)
                new_params, new_state = self._zero_apply(
                    params, state, d_chunks)
            else:
                d_ps = d_sum if use_ef else self._summed_grads(grads)
                if self.clip_norm is not None:
                    d_ps = self._clip_tree(d_ps)
                new_params, new_state = self._apply_updates(
                    params, state, d_ps)
            if ema_decay is not None:
                with step_scope("update"):
                    new_extras["ema"] = jax.tree.map(
                        lambda e, p: (ema_decay * e
                                      + (1.0 - ema_decay) * p.astype(e.dtype)),
                        extras["ema"], new_params)
            if self.skip_nonfinite:
                keep = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b), new, old)
                new_params = keep(new_params, params)
                new_state = keep(new_state, state)
                new_aux = keep(new_aux, aux)
                new_extras = keep(new_extras, extras)
                skipped = 1.0 - ok.astype(jnp.float32)
            else:
                skipped = jnp.float32(0.0)
            # Among the exchange: the compiler may combine this all-reduce
            # with the gradients'.
            with step_scope("exchange"):
                loss = lax.pmean(loss, self.reduce_axes)
            out = (new_params, new_state, new_aux, loss, skipped, new_extras)
            # The counters once more, as an output nothing donates: the
            # copy in `new_aux` is handed to the next step and dies there.
            return out + ((new_aux["counters"],) if counters else ())

        state_specs = self._state_specs()
        # Donating params/state/aux (and the carried extras) lets XLA update
        # parameters in place — without it every step writes a second full
        # copy of the model + optimizer state to HBM before the old one is
        # freed.  Safe because step() replaces self.params/state/aux with
        # the outputs.
        if self.extras:
            extras_specs = self._extras_specs()
            spmd_step = core
            in_specs = (P(), state_specs, P(), self.batch_spec, extras_specs)
            out_specs = (P(), state_specs, P(), P(), P(), extras_specs)
            donate = (0, 1, 2, 4)
        else:
            def spmd_step(params, state, aux, batch):
                out = core(params, state, aux, batch, OrderedDict())
                return out[:5] + out[6:]
            in_specs = (P(), state_specs, P(), self.batch_spec)
            out_specs = (P(), state_specs, P(), P(), P())
            donate = (0, 1, 2)
        if counters:
            out_specs += (P(),)
        return jax.jit(jax.shard_map(
            spmd_step, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        ), donate_argnums=donate)

    def _zero_pad_flat(self, x, sz, chunk):
        return jnp.zeros((self.world_size * chunk,), x.dtype).at[:sz].set(
            x.reshape(-1))

    def _zero_sync(self, grads, d_full):
        """Gradient sync INTO per-rank chunks (the ZeRO sync phase):
        reduce-scatter when ``d_full is None`` — the identity path, the
        cross-rank sum lands directly on the owner (ZeRO-2), bucketed like
        every other exchange; slice the already-decoded sum otherwise.
        Clip (if configured) applies here — the chunks jointly are the
        summed gradient the update consumes."""
        with step_scope("exchange"):
            if d_full is None:
                flats = OrderedDict(
                    (n, self._zero_pad_flat(grads[n], *self._zero_meta[n]))
                    for n in grads)
                d_chunks = collectives.reduce_scatter_flats_bucketed(
                    flats, self.axis, world=self.world_size,
                    bucket_bytes=self.bucket_bytes)
            else:
                my = lax.axis_index(self.axis)
                d_chunks = OrderedDict()
                for n in d_full:
                    sz, chunk = self._zero_meta[n]
                    d_chunks[n] = lax.dynamic_slice(
                        self._zero_pad_flat(d_full[n], sz, chunk),
                        (my * chunk,), (chunk,))
        if self.clip_norm is not None:
            d_chunks = self._clip_tree(d_chunks, psum_axis=self.axis)
        return d_chunks

    def _zero_apply(self, params, state, d_chunks):
        """Sharded-optimizer update (the ZeRO update phase): update only the
        local chunk against the local state row, and all-gather the updated
        chunks back to replicated params (bucketed — one flat gather per
        ~bucket_mb of same-dtype chunks, not one per parameter).  Update
        math is bitwise the replicated rule applied elementwise."""
        my = lax.axis_index(self.axis)
        new_chunks, new_state = OrderedDict(), OrderedDict()
        with step_scope("update"):
            for n, p in params.items():
                sz, chunk = self._zero_meta[n]
                p_chunk = lax.dynamic_slice(
                    self._zero_pad_flat(p, sz, chunk), (my * chunk,),
                    (chunk,))
                # Per-shard chunked state rows arrive as (1, chunk); scalars
                # (step counters) replicated as-is.
                st = {k: (v[0] if v.ndim > 0 else v)
                      for k, v in state[n].items()}
                new_chunks[n], new_st = self._update_fn(
                    p_chunk, d_chunks[n].astype(p.dtype), st,
                    **self._resolved_hyper(st))
                new_state[n] = {k: (v[None] if v.ndim > 0 else v)
                                for k, v in new_st.items()}
        # Untiled gather -> (world, chunk) leaves; the flatten restores the
        # tiled (world*chunk,) layout the de-pad slice expects.
        with step_scope("exchange"):
            gathered = collectives.allgather_tree_bucketed(
                new_chunks, self.axis, bucket_bytes=self.bucket_bytes)
            new_params = OrderedDict(
                (n, gathered[n].reshape(-1)[:self._zero_meta[n][0]]
                 .reshape(p.shape))
                for n, p in params.items())
        return new_params, new_state

    def compile_step(self, loss_fn: Callable, *, has_aux: bool = False,
                     aux=None, accum_steps: int = 1,
                     remat: bool = False) -> None:
        """Bind the loss function and build the jitted SPMD step.

        ``has_aux=True`` means ``loss_fn(params, aux, batch) -> (loss,
        new_aux)`` — for models carrying non-trained state (BatchNorm batch
        statistics), which the step cross-rank averages and threads through.

        **Counters.**  An ``aux`` that is a dict with a ``"counters"`` entry
        says that this sub-tree is not state but what the step counted
        (``new_aux["counters"]``, e.g. the expert load of each MoE layer).
        The fused step then returns it once more as an output of its own,
        which no later step donates, and `step()` appends it to
        `utils.timing.counter_log()` as device arrays, unread (source
        ``"MPI_PS.step"``): no host sync on the training path; a reader
        fetches them afterwards.  Like all of ``aux`` the counters are
        **averaged over the ranks** (`_grads_and_aux`), so on several chips
        a count read there is the mean over the chips, not their sum.

        ``accum_steps=K`` enables gradient accumulation: each rank's batch
        shard splits into K microbatches swept sequentially by a
        ``lax.scan``, trading K× more steps of compute latency for 1/K the
        activation memory — how large effective batches fit in HBM.  The
        update equals the full-shard gradient for mean losses (BN stats,
        if any, update sequentially per microbatch).

        ``remat=True`` wraps the loss in ``jax.checkpoint``: the backward
        pass recomputes forward activations instead of keeping them live
        across the whole forward — ~1/depth the activation memory for one
        extra forward of FLOPs (the standard HBM-for-MXU trade; composes
        with ``accum_steps``, which shrinks the *batch* dimension of the
        same buffers).  Update math is unchanged.
        """
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self._accum = int(accum_steps)
        self._loss_fn = loss_fn  # raw: wrapping happens at build time only
        self._remat = remat
        self._has_aux = has_aux
        self._step_programs = {}    # the next step's dispatch compiles
        if aux is not None:
            rep = replicated(self.mesh)
            # copy=True for the same donation-aliasing reason as params.
            self.aux = jax.tree.map(
                lambda x: jax.device_put(jnp.array(x, copy=True), rep), aux)
        self._has_counters = (has_aux and isinstance(self.aux, dict)
                              and "counters" in self.aux)
        built = jax.checkpoint(loss_fn) if remat else loss_fn
        self._step_fn = self._make_spmd_step(built, has_aux)

    # -- the step ------------------------------------------------------------

    def _shard_batch(self, batch):
        sharding = NamedSharding(self.mesh, self.batch_spec)
        return jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), sharding), batch)

    def _static_byte_metrics(self) -> dict[str, float]:
        msg = sum(bytes_of(p) for p in self.params.values())
        packaged = sum(self.code.wire_bytes(p.shape, p.dtype)
                       for p in self.params.values())
        return {"msg_bytes": float(msg), "packaged_bytes": float(packaged)}

    def step(self, batch=None, closure=None, loss_fn: Callable | None = None,
             block: bool = True):
        """Run one synchronous PS step.  Returns ``(loss, metrics)`` matching
        the reference contract (`/root/reference/ps.py:193`).

        ``block=False`` returns immediately after dispatch with the loss as a
        device future (JAX async dispatch pipelines successive steps on the
        TPU — the analogue of the reference's non-blocking ``I``-collectives,
        but across whole steps); ``comm_wait`` is then reported as 0 and the
        loss is a jax scalar, not a float.
        """
        if loss_fn is not None and loss_fn is not self._loss_fn:
            # Rebinding keeps the established aux/accum contract (a 3-arg
            # aux-style loss stays aux-style).
            self.compile_step(loss_fn, has_aux=self._has_aux,
                              accum_steps=self._accum, remat=self._remat)
        if self._loss_fn is None:
            from .errors import NotCompiledError
            raise NotCompiledError("call compile_step(loss_fn) before step()")
        if batch is None:
            raise ValueError("step() needs a batch")

        if self._byte_metrics is None:   # fixed by the shapes and the codec
            self._byte_metrics = self._static_byte_metrics()
        data: dict[str, float] = {k: 0.0 for k in STEP_METRIC_KEYS}
        data.update(self._byte_metrics)
        # The host path in spans (`utils.timing.span`; PERF.md has the
        # table): the dict's times are the spans' durations, a second view
        # of the same clock reads.
        with span("sync.step", step=self.steps_completed, block=block):
            with span("sync.shard_batch"):
                batch = self._shard_batch(batch)

            if closure is not None:  # API parity with `ps.py:110-112`
                closure()

            args = (self.params, self.state, self.aux, batch) + (
                (self.extras,) if self.extras else ())
            with span("sync.dispatch") as dispatch:
                program, compiled = self._step_program(args, batch)
                out = program(*args)
            del args    # donated: the new values are in `out`
            if self._has_counters:
                *out, counters = out
                counter_log().append("MPI_PS.step", self.steps_completed,
                                     counters)
            if compiled:
                # This call lowered and compiled the SPMD program; that
                # one-time cost is the TPU analogue of the reference's
                # collective "prepare" (`ps.py:140`) — keep it out of
                # isend_time so the per-step dispatch metric stays
                # meaningful.
                dispatch.set(compiled=True)
                data["iallgather_prepare_time"] = dispatch.duration
            else:
                data["isend_time"] = dispatch.duration
            # Reassign BEFORE blocking: the dispatch donated the old
            # params/state buffers, so between dispatch and reassignment
            # `self.params` points at deleted arrays — and block_until_ready
            # is where nearly all step wall-time is spent.  Holding the NEW
            # futures during the wait means an interrupt-triggered
            # state_dict() (Ctrl-C checkpointing) always sees live buffers.
            if self.extras:
                (self.params, self.state, self.aux, loss, skipped,
                 self.extras) = out
            else:
                self.params, self.state, self.aux, loss, skipped = out
            self.steps_completed += 1
            if block:
                with span("sync.block") as wait:
                    jax.block_until_ready(out)
                data["comm_wait"] = wait.duration
                # Only when synced: with block=False the flag is still a
                # device future, and storing a live array would break the
                # dict[str, float] timings contract (and pin the buffer).
                data["nonfinite_skip"] = float(skipped)
                loss = float(loss)
            # Consensus cadence AFTER the step's reassignments: the
            # fingerprint program reads (does not donate) the new params, so
            # it composes with async dispatch — though a firing check does
            # synchronize.
            self._maybe_check_consensus(data)
        self.timings.append(data)
        return loss, data

    def _step_program(self, args, batch):
        """The fused step compiled for this batch's shapes: lowered and
        compiled once, ahead of its first call, and called from then on (a
        batch of other shapes gets a program of its own, as under
        `jax.jit`).  Compiling here, and not inside the jitted call, leaves
        the compiled program in hand: its text is what says under which
        `jax.named_scope` each HLO instruction was traced, and a device
        trace names an operation by its instruction only (see
        `utils.timing.program_scopes`).  The registry is given the
        program's `as_text`, not this object: it holds no parameters.
        Returns the program and whether this call compiled it."""
        leaves, tree = jax.tree.flatten(batch)
        key = (tree, tuple((x.shape, x.dtype) for x in leaves))
        program = self._step_programs.get(key)
        compiled = program is None
        if compiled:
            program = self._step_fn.lower(*args).compile()
            self._step_programs[key] = program
            register_program("MPI_PS.step", program.as_text)
        return program, compiled

    # -- replica-consensus SDC guard -----------------------------------------

    def _make_consensus_fn(self):
        """One jitted SPMD program that fingerprints every parameter leaf
        per replica (wrapping uint32 sum + xor-fold of the raw bit
        pattern — any single flipped bit perturbs both) and cross-rank
        compares via pmax/pmin over the whole mesh: params are replicated
        on every device, so ALL axes must agree.  Returns a per-leaf
        ``ok`` bool vector, identical on every rank."""
        axes = self.reduce_axes
        names = list(self.params)

        bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

        def body(params):
            sums, xors = [], []
            for n in names:
                p = params[n]
                u = lax.bitcast_convert_type(p, bits[p.dtype.itemsize])
                u = u.astype(jnp.uint32).reshape(-1)
                sums.append(jnp.sum(u))  # uint32 wraps: a mod-2^32 checksum
                xors.append(lax.reduce(u, jnp.uint32(0),
                                       lax.bitwise_xor, (0,)))
            fp = jnp.stack(sums + xors)
            same = lax.pmax(fp, axes) == lax.pmin(fp, axes)
            return jnp.logical_and(same[:len(names)], same[len(names):])

        return jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))

    def _make_rebroadcast_fn(self):
        """Restore consensus from replica 0: each leaf becomes
        ``psum(where(replica == 0, p, 0))`` — one all-reduce of the params,
        after which every device provably holds rank 0's copy."""
        axes = self.reduce_axes

        def body(params):
            idx = jnp.int32(0)
            for a in axes:
                idx = idx * lax.axis_size(a) + lax.axis_index(a)

            def fix(p):
                contrib = jnp.where(idx == 0, p, jnp.zeros_like(p))
                return lax.psum(contrib, axes).astype(p.dtype)

            return jax.tree.map(fix, params)

        return jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))

    def check_consensus(self) -> dict:
        """Run the replica-consensus SDC guard once (also runs on the
        ``consensus_every`` cadence inside `step`).  Returns ``{"ok",
        "mismatched", "first_leaf"}``; counts into ``fault_stats`` and,
        on mismatch, either raises `SDCDetectedError` (policy "abort") or
        re-broadcasts replica 0's params (policy "rebroadcast").

        Detection windows differ by state layout.  Replicated-state mode:
        a corrupted replica updates its own divergent copy every step, so
        the divergence PERSISTS and any later cadence check catches it.
        ZeRO mode: each step re-materializes params from the all-gather of
        per-rank chunks, so a flipped param byte either heals at the next
        step (element owned by another rank) or propagates to every
        replica consistently (element in the corrupted rank's own chunk —
        it has become state corruption, invisible to a replica compare).
        There the guard sees param SDC only in the window before the next
        update — a small ``consensus_every`` matters more."""
        if self._consensus_fn is None:
            self._consensus_fn = self._make_consensus_fn()
        leaf_ok = np.asarray(jax.device_get(self._consensus_fn(self.params)))
        self.fault_stats["sdc_checks"] += 1
        names = list(self.params)
        bad = [n for n, ok in zip(names, leaf_ok) if not ok]
        if not bad:
            return {"ok": True, "mismatched": [], "first_leaf": None}
        first = bad[0]
        self.fault_stats["sdc_mismatches"] += 1
        if self.fault_stats["sdc_first_leaf"] is None:
            self.fault_stats["sdc_first_leaf"] = first
        self.fault_stats["sdc_events"].append(
            {"step": self.steps_completed, "leaves": bad[:8],
             "n_leaves": len(bad), "policy": self.consensus_policy})
        msg = (f"replica consensus violated at step {self.steps_completed}:"
               f" {len(bad)}/{len(names)} parameter leaves differ across "
               f"data-parallel replicas (first diverging leaf: {first!r}) "
               f"— silent data corruption or a desync bug")
        print(msg, file=sys.stderr)
        if self.consensus_policy == "abort":
            raise SDCDetectedError(msg)
        if self._rebroadcast_fn is None:
            self._rebroadcast_fn = self._make_rebroadcast_fn()
        self.params = self._rebroadcast_fn(self.params)
        self.fault_stats["sdc_rebroadcasts"] += 1
        print(f"re-broadcast replica 0's params over {len(names)} leaves "
              f"(policy=rebroadcast); training continues", file=sys.stderr)
        return {"ok": False, "mismatched": bad, "first_leaf": first}

    def _maybe_check_consensus(self, data: dict) -> None:
        """The in-step cadence hook, at the tail of `step`."""
        if (self.consensus_every
                and self.steps_completed % self.consensus_every == 0):
            out = self.check_consensus()
            data["sdc_mismatch"] = 0.0 if out["ok"] else 1.0

    # -- checkpoint / resume -------------------------------------------------

    def topology(self) -> dict:
        """The source-topology record every checkpoint carries: what
        elastic N→M resume verifies (and de-chunks raw ZeRO shards
        against) at load."""
        from .parallel.mesh import describe_mesh
        return {"world_size": self.world_size,
                "axes": list(self.axes),
                "mesh": describe_mesh(self.mesh),
                "zero": bool(self.zero),
                "error_feedback": bool(self.error_feedback)}

    def state_dict(self, *, raw_shards: bool = False) -> dict:
        """Torch-style snapshot: params, per-param optimizer state, aux
        (BatchNorm stats), hyperparameters, and the source topology —
        host copies, safe to serialize.  The subsystem the reference
        leaves unbuilt (SURVEY §5 "Checkpoint/resume — absent").

        ``raw_shards=True`` keeps ZeRO optimizer state in its live
        ``(world, chunk)`` layout instead of de-chunking to full buffers
        — the fast path for a preemption-deadline save; `load_state_dict`
        de-chunks against the recorded topology, so the checkpoint still
        loads on any device count.

        Copies, not views: on the CPU backend ``device_get`` can return a
        zero-copy view into a live device buffer, and the donated step
        function recycles those buffers — a snapshot aliasing them would
        mutate under the caller on the next ``step()``.  Copy only in that
        view case; on accelerator backends device_get already materializes
        a fresh host array and a second copy would transiently double host
        RAM for the whole params+state tree."""
        def fetch(x):
            a = np.asarray(jax.device_get(x))
            return a if a.flags["OWNDATA"] else a.copy()
        host = partial(jax.tree.map, fetch)
        from .optim.schedules import hyper_for_checkpoint
        return {
            "optim": self.optim,
            "hyper": hyper_for_checkpoint(self.hyper),
            "topology": {**self.topology(),
                         "raw_zero_shards": bool(raw_shards and self.zero)},
            "params": host(self.params),
            # ZeRO state de-chunks to full buffers so checkpoints stay
            # world-size independent and interchange with replicated mode
            # (raw_shards defers that de-chunk to load time).
            "state": (self._dechunk_state(self.state)
                      if self.zero and not raw_shards
                      else host(self.state)),
            "aux": host(self.aux),
            # EF residual is per-rank state: store the full [world, ...]
            # array so a same-world resume is BITWISE-faithful (r3 VERDICT
            # #6: the sum-only format preserved the aggregate but not the
            # trajectory).  A world-size-changed load sums over ranks and
            # splits evenly — aggregate-exact, trajectory-approximate (the
            # only option once per-rank identity is gone); see
            # `load_state_dict`.
            "ef": (OrderedDict((n, fetch(v))
                               for n, v in self.extras["ef"].items())
                   if self.error_feedback else None),
            "ema": (host(self.extras["ema"])
                    if self.ema_decay is not None else None),
        }

    def _normalize_state_leaf(self, a, *, name: str, src_world: int):
        """One optimizer-state leaf from a checkpoint → full-shape host
        array on THIS topology: full buffers and scalars pass through; a
        ``(src_world, chunk)`` ZeRO shard row from the recorded source
        topology de-chunks (strip the zero pad, restore the parameter
        shape) so the caller can re-chunk it for this mesh.  Anything else
        is genuinely unmappable and refused by name."""
        a = np.asarray(a)
        shape = tuple(self.params[name].shape)
        if a.ndim == 0 or a.shape == shape:
            return a
        sz = int(np.prod(shape))
        if (src_world and a.ndim == 2
                and a.shape == (src_world, -(-sz // src_world))):
            return a.reshape(-1)[:sz].reshape(shape)
        raise ElasticResumeError(
            f"optimizer state for {name!r} has shape {a.shape}, which is "
            f"neither the full parameter shape {shape} nor a "
            f"(world={src_world or 'unrecorded'}, chunk) ZeRO shard layout "
            f"from the checkpoint's recorded source topology — this "
            f"component is topology-bound; re-save it de-chunked "
            f"(state_dict() without raw_shards) on the source mesh")

    def load_state_dict(self, sd: dict) -> None:
        """Restore from `state_dict` output; re-places everything on this
        optimizer's mesh — ANY mesh size.  PS params are replicated, so
        they are world-size-independent outright; ZeRO optimizer shards
        de-chunk from the checkpoint's recorded source topology and
        re-chunk (re-padded flats) onto this mesh; the error-feedback
        residual remaps per-rank state (bitwise on the same world size,
        aggregate-exact on a changed one).  A component that genuinely
        cannot be remapped raises `ElasticResumeError` naming it."""
        if sd["optim"] != self.optim:
            raise ValueError(
                f"checkpoint is for optim={sd['optim']!r}, this is {self.optim!r}")
        if set(sd["params"]) != set(self.params):
            missing = set(self.params) ^ set(sd["params"])
            raise ElasticResumeError(
                f"parameter name mismatch: {sorted(missing)}")
        for n, p in self.params.items():
            have = tuple(np.shape(sd["params"][n]))
            if have != tuple(p.shape):
                raise ElasticResumeError(
                    f"parameter {n!r}: checkpoint shape {have} does not "
                    f"match model shape {tuple(p.shape)} — a model change, "
                    f"not a topology change; elastic resume cannot remap it")
        src = sd.get("topology") or {}
        src_world = int(src.get("world_size") or 0)
        from .optim.schedules import hyper_from_checkpoint
        rep = replicated(self.mesh)
        place = lambda x: jax.device_put(jnp.array(x, copy=True), rep)
        self.hyper = hyper_from_checkpoint(sd["hyper"], self.hyper)
        self.params = OrderedDict(
            (n, place(sd["params"][n])) for n in self.params)
        state_full = OrderedDict(
            (n, jax.tree.map(
                partial(self._normalize_state_leaf, name=n,
                        src_world=src_world),
                sd["state"][n]))
            for n in self.params)
        if self.zero:
            self.state = self._chunk_and_place_state(state_full)
        else:
            self.state = OrderedDict(
                (n, jax.tree.map(place, state_full[n]))
                for n in self.params)
        self.aux = jax.tree.map(place, sd["aux"])
        if self.error_feedback:
            sharded = NamedSharding(self.mesh, P(self.axes))
            world = self.world_size
            saved = sd.get("ef") or {}

            def ef_leaf(n, p):
                if n not in saved:  # was trained without EF: restart
                    full = np.zeros((world,) + p.shape, np.float32)
                else:
                    a = np.asarray(saved[n], np.float32)
                    if (a.shape != tuple(p.shape)
                            and a.shape[1:] != tuple(p.shape)):
                        raise ElasticResumeError(
                            f"error-feedback residual for {n!r}: shape "
                            f"{a.shape} is neither the parameter shape "
                            f"{tuple(p.shape)} (legacy sum format) nor "
                            f"(world,) + parameter shape — cannot remap "
                            f"it to ({world},) + {tuple(p.shape)}")
                    if a.shape == (world,) + tuple(p.shape):
                        # Same world size: restore each rank's residual
                        # exactly — resume is bitwise-faithful.
                        full = a
                    else:
                        # World changed (or legacy sum-format checkpoint):
                        # collapse to the cross-rank sum and split evenly —
                        # the aggregate un-applied error is preserved
                        # exactly, per-rank identity cannot be.
                        total = (a.sum(axis=0)
                                 if a.shape != tuple(p.shape) else a)
                        full = np.broadcast_to((total / world)[None],
                                               (world,) + p.shape)
                return jax.device_put(jnp.array(full, copy=True), sharded)

            self.extras["ef"] = OrderedDict(
                (n, ef_leaf(n, p)) for n, p in self.params.items())
        if self.ema_decay is not None:
            saved_ema = sd.get("ema") or {}
            # Missing in the checkpoint (trained without EMA): restart the
            # average from the restored params.
            self.extras["ema"] = OrderedDict(
                (n, place(saved_ema.get(n, sd["params"][n])))
                for n in self.params)
        if self._loss_fn is not None:
            # Hyperparameters are trace-time constants in the compiled step;
            # rebuild it so restored hyper actually takes effect.
            self.compile_step(self._loss_fn, has_aux=self._has_aux,
                              accum_steps=self._accum, remat=self._remat)

    def rescale_lr(self, scale: float) -> None:
        """Multiply the learning rate by ``scale`` (wrapping a schedule if
        lr is one) and rebuild the compiled step — the rollback
        guardrail's LR backoff after restoring a pre-divergence
        checkpoint.  Checkpoint-safe: a wrapped schedule serializes as the
        standard schedule marker."""
        if not scale > 0:
            raise ValueError(f"lr scale must be positive, got {scale}")
        lr = self.hyper["lr"]
        self.hyper["lr"] = ((lambda step, _lr=lr: scale * _lr(step))
                            if callable(lr) else scale * lr)
        if self._loss_fn is not None:
            self.compile_step(self._loss_fn, has_aux=self._has_aux,
                              accum_steps=self._accum, remat=self._remat)

    # -- conveniences --------------------------------------------------------

    @property
    def ef_state(self):
        """The per-rank EF residual tree ([world, ...] leaves), or None."""
        return self.extras.get("ef")

    @property
    def ema_params(self):
        """The EMA-averaged weights (evaluation-quality), or None."""
        return self.extras.get("ema")

    def named_parameters(self):
        return list(self.params.items())

    def print_summary(self):
        from .utils.timing import print_summary
        print_summary(self.timings)


class PS(MPI_PS):
    """Alias with the TPU-honest name."""


class SGD(MPI_PS):
    """SGD variant — update math parity with `/root/reference/ps.py:195-214`
    (momentum buffer first-step asymmetry, nesterov, weight decay)."""

    def __init__(self, named_params, **kwargs):
        kwargs["optim"] = "sgd"
        super().__init__(named_params, **kwargs)


class Adam(MPI_PS):
    """Adam variant — update math parity with `/root/reference/ps.py:217-261`
    (old-torch eps placement, bias-corrected step size, amsgrad)."""

    def __init__(self, named_params, **kwargs):
        kwargs["optim"] = "adam"
        super().__init__(named_params, **kwargs)


class AdamW(MPI_PS):
    """AdamW variant — decoupled weight decay (`optim/rules.py:adamw_update`,
    torch.optim.AdamW math); beyond the reference's optimizer pair."""

    def __init__(self, named_params, **kwargs):
        kwargs["optim"] = "adamw"
        super().__init__(named_params, **kwargs)
