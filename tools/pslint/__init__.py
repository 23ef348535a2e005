"""pslint — project-native static analysis for the async-PS codebase.

Pure-stdlib (``ast`` + ``tokenize``) checkers for the invariant classes the
bug log shows chaos testing catches *late* and review catches *by luck*:

* **lock-discipline** (PSL1xx) — attributes annotated
  ``# pslint: guarded-by(_lock)`` must only be touched under
  ``with self._lock`` (the ``GUARDED_BY`` idea from Clang's thread-safety
  analysis, scoped to this codebase's handler-thread/serve-loop split);
* **jit-hygiene** (PSL2xx) — recompile/stall hazards: ``jax.jit``/``pmap``
  constructed inside loop bodies (the mid-fill-compile bug class) and
  host-sync calls inside jitted functions and the hot serve/step loops;
* **protocol/stats-drift** (PSL3xx) — wire-frame kinds/field layouts must
  match between encoder and decoder, every bumped fault counter must be
  initialized and rendered, fault snapshots must build on the shared
  base, and fill-admission primitives must stay inside the one shared
  helper;
* **typed-error policy** (PSL4xx) — library code raises the project's
  typed errors (`pytorch_ps_mpi_tpu.errors`), not bare ``RuntimeError``;
* **concurrency/deadlock** (PSL5xx) — the whole-program lock graph:
  ABBA cycles against declared ``# pslint: lock-order(a < b)`` edges,
  blocking calls under locks (``blocking-allowed`` opts a designated
  send lock out), and undeclared cross-thread nestings;
* **protocol model checking** (PSL6xx) — the v8 credit gate's
  transition rules extracted from the session source and exhaustively
  model-checked (``model.py``) at 2 senders x window 2 x queue 2:
  deadlock-freedom, control-frame liveness, replenish reachability,
  oldest-first shedding;
* **buffer-ownership** (PSL7xx) — value-flow over byte-carrying
  buffers for the zero-copy wire: caller-owned buffers parked by
  reference or mutated after hand-off, zero-copy views escaping the
  scope that owns their backing buffer (``transfers-ownership``
  declares the deliberate transfers), recv buffers refilled under live
  views, and reads after jax donation — the static half of the
  ``PS_BUFFER_SENTINEL`` runtime sanitizer;
* **thread-races** (PSL8xx) — the whole-program lockset pass
  (``races.py``): every ``self.attr`` access is recorded with its
  thread roles and held locks, and cross-thread state reached through
  disjoint locksets (801), unlocked compound RMW (802),
  publish-then-fill (803), or torn multi-field snapshots (804) is
  convicted; ``# pslint: single-writer(role)`` declares the one
  legitimate lock-free writer — the static half of the
  ``PS_RACE_SANITIZER`` runtime sanitizer (owner-tracked session lock
  + ``holds(_lock)`` probes raising ``RaceDetectedError``).

Run ``python -m tools.pslint pytorch_ps_mpi_tpu`` (exits non-zero on any
unsuppressed finding; ``--format json`` for machines; ``--changed``
gates only files dirty vs the git index), or ``make lint``
/ ``make lint-json`` / ``make lint-fast``.  Suppress a single line with
``# pslint: allow(rule)``; park an intentional legacy finding in
``tools/pslint/baseline.txt`` (``--write-baseline``).  The annotation
vocabulary is documented in the README section "Static analysis
(`pslint`)".
"""

from .core import Finding, SourceModule, lint_paths, load_corpus  # noqa: F401
