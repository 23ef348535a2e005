"""Continuous-batching inference front-end on the in-tree transformer.

The serving half of the "one fleet that trains and serves" scenario
(ROADMAP item 2): requests enter a BOUNDED admission queue (the
fault-stats/bounded-queue idiom the training side runs on — an
unbounded queue converts overload into unbounded tail latency for
every request behind it), and an engine loop assembles a fresh batch
EVERY decode step:

* **continuous batching**: the batch is ``max_batch`` slots; a request
  joins the running batch the step after it is admitted and leaves the
  step it finishes — short requests never wait for long ones to drain,
  and freed slots re-fill from the queue at step granularity (the
  static-shape analogue of slot-level continuous batching: one jitted
  decode program, zero recompiles);
* **greedy decode, full-forward**: one jitted step runs the in-tree
  `models.transformer.TransformerLM` over the fixed ``[max_batch,
  buf_len]`` token buffer and emits each active row's next token at
  its own length — per-request lengths are data, not shapes, so
  admission/retirement never retraces;
* **typed shed at overload**: a full admission queue refuses the
  request with `errors.InferShedError` (counted ``infer_shed``) — the
  caller backs off or balances elsewhere, and requests already
  admitted keep their latency bound;
* **per-request p50/p95** via `utils.timing.RequestLatency` — the SLO
  observability the run history gets from ``rank_latency`` on the
  training side, extended to the serve side;
* **zero-dropped-request hot-swap**: between steps the engine polls a
  ``params_source`` (a `serve.subscribe.Subscriber` — anything with
  ``poll() -> (version, params, changed)``); a version advance swaps
  the device params for the NEXT step while the in-flight step
  finishes on the old tree.  A transport blip in the source is
  swallowed: the front-end keeps serving its last snapshot (bounded
  staleness beats an outage) while the subscriber heals itself —
  construct the subscriber with ``nonblock_heal=True`` so a dead PS
  costs the swap poll one bounded dial probe per backoff window, never
  the full redial ladder inside the decode loop.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any

import numpy as np

from ..errors import InferShedError
from ..transport import TRANSPORT_ERRORS
from ..utils.timing import RequestLatency


class InferRequest:
    """One admitted inference request: prompt tokens in, greedily
    decoded continuation out.  ``result(timeout)`` blocks until the
    engine retires the request (or the timeout) and returns the
    generated token list; ``latency_s`` is the submit-to-finish wall
    span the front-end's p50/p95 aggregates."""

    __slots__ = ("prompt", "max_new", "generated", "done", "t0",
                 "latency_s")

    def __init__(self, prompt, max_new: int):
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        self.generated: "list[int]" = []
        self.done = threading.Event()
        self.t0 = time.perf_counter()
        self.latency_s: "float | None" = None

    def result(self, timeout: "float | None" = None) -> "list[int]":
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"inference request not finished within {timeout}s")
        return list(self.generated)

    @property
    def tokens(self) -> "list[int]":
        return self.prompt + self.generated


class InferenceFrontend:
    """Bounded-admission, continuous-batching greedy decoder.

    Usage::

        fe = InferenceFrontend(model, params, max_batch=4, buf_len=64,
                               max_queue=16, params_source=subscriber)
        req = fe.submit([1, 2, 3], max_new=8)   # InferShedError at overload
        while fe.pending:
            fe.step()
        print(req.result(0), fe.stats())

    ``submit`` is thread-safe (many producer threads, the evidence
    harness's request drivers); ``step``/``drain`` belong to ONE engine
    thread — the decode buffers are engine-local state.
    """

    def __init__(self, model, params, *, max_batch: int = 4,
                 buf_len: int = 64, max_queue: int = 16,
                 params_source=None, device=None,
                 latency_window: int = 128):
        import jax
        import jax.numpy as jnp

        from ..parallel.mesh import default_devices
        from ..utils.flatten import unflatten_params

        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if buf_len < 2:
            raise ValueError(f"buf_len must be >= 2, got {buf_len}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.model = model
        self.max_batch = int(max_batch)
        self.buf_len = int(buf_len)
        self.max_queue = int(max_queue)
        self._queue: "queue.Queue[InferRequest]" = queue.Queue(
            maxsize=max_queue)
        self._slots: "list[InferRequest | None]" = [None] * max_batch
        self._tokens = np.zeros((max_batch, buf_len), np.int32)
        self._lengths = np.ones((max_batch,), np.int32)
        self._positions = np.broadcast_to(
            np.arange(buf_len, dtype=np.int32),
            (max_batch, buf_len)).copy()
        self.latency = RequestLatency(window=latency_window)
        self.steps = 0
        # Admission counters (`format_fault_stats` vocabulary; merged
        # into evidence/run reports next to the PS-side serve counters).
        self.fault_stats: "dict[str, int]" = {
            "infer_requests": 0, "infer_shed": 0, "param_swaps": 0}
        self._stats_lock = threading.Lock()
        # Device 0 of what this process was given (one chip-holding
        # process per chip; see AsyncPSWorker).
        self._device = (device if device is not None
                        else default_devices()[0])
        self._dev_params = jax.device_put(params, self._device)
        self._params_source = params_source

        def decode_step(p, tokens, positions, lengths):
            logits = model.apply({"params": unflatten_params(p)},
                                 tokens, positions)
            idx = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]
            return jnp.argmax(last, axis=-1).astype(jnp.int32)

        # ONE jitted program for every step: shapes are static
        # ([max_batch, buf_len]), per-request lengths are data — the
        # continuous batch never retraces as requests come and go.
        self._step_fn = jax.jit(decode_step)

    # -- admission (thread-safe) ----------------------------------------------

    def submit(self, prompt, max_new: int = 8) -> InferRequest:
        """Admit one request, or shed it with typed `InferShedError`
        when the bounded queue is full — graceful overload degradation:
        the refusal is immediate and costs the caller a retry, while an
        unbounded queue would cost every queued request its latency
        bound."""
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) + max_new > self.buf_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds "
                f"the decode buffer ({self.buf_len})")
        req = InferRequest(prompt, max_new)
        with self._stats_lock:
            self.fault_stats["infer_requests"] += 1
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            with self._stats_lock:
                self.fault_stats["infer_shed"] += 1
            raise InferShedError(
                f"inference admission queue full ({self.max_queue} "
                f"pending): request shed — back off and retry (the "
                f"bounded queue is what keeps admitted requests' "
                f"p50/p95 meaningful under overload)") from None
        return req

    @property
    def pending(self) -> int:
        """Requests not yet retired: queued + active batch slots."""
        return (self._queue.qsize()
                + sum(1 for s in self._slots if s is not None))

    # -- the engine loop (single engine thread) -------------------------------

    def _maybe_swap(self) -> None:
        """Parameter hot-swap between steps: poll the subscription; a
        version advance installs the new tree for the NEXT step (the
        in-flight batch already finished on the old one — zero dropped
        requests by construction).  Transport blips are swallowed: the
        subscriber heals itself, and serving the last snapshot at
        bounded staleness beats refusing every request meanwhile."""
        src = self._params_source
        if src is None:
            return
        try:
            _version, params, changed = src.poll()
        except TRANSPORT_ERRORS:
            return
        if changed and params is not None:
            import jax

            self._dev_params = jax.device_put(params, self._device)
            with self._stats_lock:
                self.fault_stats["param_swaps"] += 1

    def _admit_into_slots(self) -> None:
        for i in range(self.max_batch):
            if self._slots[i] is not None:
                continue
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            self._slots[i] = req
            n = len(req.prompt)
            self._tokens[i, :] = 0
            self._tokens[i, :n] = req.prompt
            self._lengths[i] = n

    def step(self) -> int:
        """One continuous-batching decode step: swap params if the
        subscription advanced, admit queued requests into free slots,
        run the jitted step, append each active row's next token, and
        retire finished requests (latency observed at retirement).
        Returns the number of active requests this step served."""
        self._maybe_swap()
        self._admit_into_slots()
        active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active:
            return 0
        nxt = np.asarray(self._step_fn(
            self._dev_params, self._tokens, self._positions,
            self._lengths))
        self.steps += 1
        for i in active:
            req = self._slots[i]
            tok = int(nxt[i])
            req.generated.append(tok)
            n = int(self._lengths[i])
            if n < self.buf_len:
                self._tokens[i, n] = tok
                self._lengths[i] = n + 1
            if (len(req.generated) >= req.max_new
                    or int(self._lengths[i]) >= self.buf_len):
                req.latency_s = time.perf_counter() - req.t0
                self.latency.observe(req.latency_s)
                req.done.set()
                self._slots[i] = None
        return len(active)

    def drain(self, max_steps: int = 100000) -> int:
        """Run steps until every admitted request retired (or the step
        budget — a loud bound, never a hang).  Returns steps run.

        A blown budget raises ``TimeoutError`` (the same type
        `InferRequest.result` uses), NOT `InferShedError`: a wedged
        engine with admitted-but-never-retired requests is the
        semantic opposite of a healthy-but-full admission queue, and a
        load balancer that backs off-and-retries on the typed shed
        must not be told to retry against a wedge."""
        ran = 0
        while self.pending and ran < max_steps:
            if self.step() == 0:
                # Queue raced empty between pending and admit: yield.
                time.sleep(0.001)
            ran += 1
        if self.pending:
            raise TimeoutError(
                f"drain() exceeded its {max_steps}-step budget with "
                f"{self.pending} request(s) still pending — the engine "
                f"is wedged or the budget is too small for the queue")
        return ran

    def stats(self) -> "dict[str, Any]":
        """One report dict: admission counters + the p50/p95 request-
        latency window (`RequestLatency.snapshot`) + engine gauges."""
        with self._stats_lock:
            out: "dict[str, Any]" = dict(self.fault_stats)
        out["steps"] = self.steps
        out["queued"] = self._queue.qsize()
        out["active"] = sum(1 for s in self._slots if s is not None)
        out["request_latency"] = self.latency.snapshot()
        return out
