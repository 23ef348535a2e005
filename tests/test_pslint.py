"""Tier-1 gate for `tools.pslint` — the project-native static analyzer.

Three layers:

1. **The real tree is clean**: every checker runs over
   ``pytorch_ps_mpi_tpu`` and must report zero unsuppressed findings —
   this is what makes pslint a merge gate without new CI plumbing (the
   tier-1 lane already runs this file).
2. **The checkers actually detect**: a fixture corpus of known-bad
   snippets under ``tests/fixtures/pslint/`` asserts EXACT
   (checker id, line) findings per rule, and that the
   ``# pslint: allow(...)`` escape hatch suppresses exactly the lines it
   annotates.
3. **Runtime belt-and-suspenders** for the drift checker: the
   `AsyncPS`/`AsyncPSServer` fault-stats snapshots expose a consistent
   key set, and every integer counter either deployment carries is
   actually rendered by `format_fault_stats`.
"""

from __future__ import annotations

import json
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "pslint"
BASELINE = REPO / "tools" / "pslint" / "baseline.txt"

sys.path.insert(0, str(REPO))

from tools.pslint.core import (Finding, SourceModule, lint_paths,  # noqa: E402
                               load_corpus, read_baseline, run_checkers,
                               split_suppressed, write_baseline)

FIXTURE_FILES = ["bad_lock.py", "bad_jit.py", "bad_drift.py",
                 "bad_raise.py", "bad_shard_drift.py",
                 "bad_repl_drift.py", "bad_agg_drift.py",
                 "bad_flow_drift.py", "bad_deadlock.py",
                 "bad_protocol_model.py", "bad_buffer_flow.py",
                 "bad_serve_drift.py", "bad_bucket_drift.py",
                 "bad_codec_wire_drift.py", "bad_races.py"]

# `# [PSL101]` marks an expected active finding on that line;
# `# [allowed:PSL101]` marks an expected suppressed one (the line also
# carries the real allow() directive).
_MARKER = re.compile(r"#\s*\[(allowed:)?(PSL\d{3})\]")


def _expected(path: Path):
    active, suppressed = set(), set()
    for i, line in enumerate(path.read_text().splitlines(), 1):
        for m in _MARKER.finditer(line):
            (suppressed if m.group(1) else active).add((m.group(2), i))
    return active, suppressed


# ---------------------------------------------------------------------------
# 1. the real tree is clean
# ---------------------------------------------------------------------------

def test_real_tree_has_zero_unsuppressed_findings():
    active, _ = lint_paths([REPO / "pytorch_ps_mpi_tpu"],
                           baseline_path=BASELINE)
    assert not active, (
        "pslint found unsuppressed issues in the library — fix them (or "
        "allow() with a rationale):\n"
        + "\n".join(f.render() for f in active))


def test_linting_is_importless():
    """pslint must never import the code it lints (it has to stay fast
    enough to gate every PR, and fixtures contain deliberately-broken
    code) — guard that the toolchain itself never grew a jax/numpy
    dependency."""
    banned = re.compile(r"^\s*(import|from)\s+(jax|numpy|torch)\b", re.M)
    for f in sorted((REPO / "tools" / "pslint").glob("*.py")):
        assert not banned.search(f.read_text()), \
            f"{f.name} imports a runtime library"


# ---------------------------------------------------------------------------
# 2. each checker detects its seeded fixture violations, exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", FIXTURE_FILES)
def test_fixture_findings_exact(name):
    path = FIXTURES / name
    corpus = load_corpus([path])
    active, suppressed = split_suppressed(corpus, run_checkers(corpus))
    exp_active, exp_suppressed = _expected(path)
    assert exp_active, f"{name} has no seeded markers — fixture rotted"
    assert {(f.checker, f.line) for f in active} == exp_active
    # The escape hatch suppresses exactly the annotated lines.
    assert {(f.checker, f.line) for f in suppressed} == exp_suppressed


def test_fixture_corpus_covers_all_eight_checkers():
    corpus = load_corpus([FIXTURES])
    families = {f.rule for f in run_checkers(corpus)}
    assert families == {"lock-discipline", "jit-hygiene", "drift",
                        "raw-raise", "concurrency", "protocol-model",
                        "buffer-ownership", "thread-races"}


def test_findings_carry_location_rule_and_hint():
    corpus = load_corpus([FIXTURES / "bad_raise.py"])
    active, _ = split_suppressed(corpus, run_checkers(corpus))
    f = next(x for x in active if x.checker == "PSL401")
    rendered = f.render()
    assert f.path.endswith("bad_raise.py") and f.line > 0
    assert "PSL401" in rendered and "[raw-raise]" in rendered
    assert "hint:" in rendered  # the fix hint is part of the contract


# ---------------------------------------------------------------------------
# suppression machinery: inline allow() + committed baseline
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_line_shift_immunity(tmp_path):
    # A baselined finding stays suppressed even after unrelated edits
    # shift its line number (keys are content-based, not line-based).
    src = tmp_path / "legacy.py"
    src.write_text("def f():\n    raise RuntimeError('legacy debt')\n")
    corpus = load_corpus([src])
    findings = run_checkers(corpus)
    assert findings
    bl = tmp_path / "baseline.txt"
    write_baseline(bl, corpus, findings)
    active, suppressed = lint_paths([src], baseline_path=bl)
    assert not active and suppressed

    src.write_text("# a new comment shifting every line\n\n"
                   "def f():\n    raise RuntimeError('legacy debt')\n")
    active, suppressed = lint_paths([src], baseline_path=bl)
    assert not active and suppressed

    # ...but a NEW finding is not hidden by the old baseline.
    src.write_text(src.read_text()
                   + "\ndef g():\n    raise RuntimeError('fresh')\n")
    active, _ = lint_paths([src], baseline_path=bl)
    assert len(active) == 1 and "fresh" in Path(src).read_text()


def test_baseline_keys_survive_relative_vs_absolute_invocation(tmp_path):
    # The documented flow writes the baseline via the CLI with a
    # repo-relative path; tier-1 lints the absolute path.  Keys must be
    # invocation-independent or the first baselined finding desyncs the
    # two gates.
    bl = tmp_path / "bl.txt"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint",
         "tests/fixtures/pslint/bad_raise.py",
         "--baseline", str(bl), "--write-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert read_baseline(bl)
    active, suppressed = lint_paths([FIXTURES / "bad_raise.py"],
                                    baseline_path=bl)
    assert not active and suppressed


def test_committed_baseline_is_empty():
    # The zero-noise contract: the default run is clean because the CODE
    # is clean, not because debt accumulated in the baseline.  A finding
    # may only land here with explicit review sign-off.
    assert read_baseline(BASELINE) == set()


def test_allow_matches_rule_name_and_checker_id(tmp_path):
    for token in ("raw-raise", "PSL401"):
        src = tmp_path / f"t_{token.replace('-', '_')}.py"
        src.write_text("def f():\n"
                       f"    raise RuntimeError('x')  # pslint: allow({token})\n")
        active, suppressed = lint_paths([src], baseline_path=None)
        assert not active and len(suppressed) == 1, token


# ---------------------------------------------------------------------------
# CLI contract (make lint / standalone CI use)
# ---------------------------------------------------------------------------

def test_cli_exits_zero_on_clean_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint", "pytorch_ps_mpi_tpu"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_exits_nonzero_on_findings():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint",
         str(FIXTURES / "bad_raise.py"), "--no-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "PSL401" in proc.stdout and "hint:" in proc.stdout


def test_cli_rejects_missing_path():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint", "no/such/package"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2


def test_cli_rejects_unknown_format_and_flags():
    """Bad invocations must refuse LOUDLY with exit 2 (stderr names the
    offender), never lint a subset silently — for flags exactly like for
    unknown paths."""
    fixture = str(FIXTURES / "bad_raise.py")
    for argv in (["--format", "yaml", fixture],
                 ["--definitely-not-a-flag", fixture]):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.pslint", *argv],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2, argv
        assert ("invalid choice" in proc.stderr
                or "unrecognized arguments" in proc.stderr), proc.stderr
    # In-process callers get the same contract as the shell (main()
    # RETURNS 2 instead of leaking argparse's SystemExit).
    from tools.pslint.__main__ import main
    assert main(["--format", "yaml", fixture]) == 2


def test_cli_json_format_machine_readable():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint",
         str(FIXTURES / "bad_raise.py"), "--no-baseline",
         "--format", "json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1  # exit codes unchanged by the format
    doc = json.loads(proc.stdout)
    assert doc["summary"]["active"] == len(doc["findings"]) > 0
    for f in doc["findings"]:
        assert {"file", "line", "id", "rule", "message",
                "fix_hint"} <= set(f)
        assert f["file"].endswith("bad_raise.py") and f["line"] > 0
    assert any(f["id"] == "PSL401" for f in doc["findings"])


def test_lint_wall_clock_budget():
    """The satellite perf contract: a full `make lint` (CLI, cold
    process, all eight checkers incl. the exhaustive model run) stays
    under ~3 s — pslint must remain cheap enough to gate every PR.
    Best-of-3 so a transiently loaded box doesn't flake the gate; a
    genuinely slower CI host can widen the budget via
    PSLINT_LINT_BUDGET_S without losing the regression signal."""
    import os

    budget = float(os.environ.get("PSLINT_LINT_BUDGET_S", "3.0"))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "tools.pslint", "pytorch_ps_mpi_tpu"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        best = min(best, time.perf_counter() - t0)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        if best < budget:
            break  # already inside the budget — don't burn CI time
    assert best < budget, f"make lint took {best:.2f}s (budget ~{budget} s)"


def test_parse_cache_shares_modules_across_runs():
    """The parse-once contract: two lints of the same unchanged file in
    one process share the SourceModule (AST + token stream), they don't
    re-parse."""
    target = [REPO / "pytorch_ps_mpi_tpu" / "transport.py"]
    c1, c2 = load_corpus(target), load_corpus(target)
    assert c1[0] is c2[0]


# ---------------------------------------------------------------------------
# PSL5xx/6xx: tamper tests on the REAL modules — the checkers must catch
# a seeded regression in the actual tree, not just in fixtures
# ---------------------------------------------------------------------------

def _tamper_package(tmp_path, rel: str, old: str, new: str):
    """Copy the real package, apply one textual mutation, return
    (package dir, 1-based line of the mutation)."""
    pkg = tmp_path / "pkg"
    shutil.copytree(REPO / "pytorch_ps_mpi_tpu", pkg,
                    ignore=shutil.ignore_patterns("__pycache__"))
    target = pkg / rel
    text = target.read_text()
    assert text.count(old) == 1, f"tamper anchor drifted: {old!r}"
    target.write_text(text.replace(old, new))
    anchor = new.strip().splitlines()[0]
    line = next(i for i, ln in enumerate(
        target.read_text().splitlines(), 1) if anchor in ln)
    return pkg, line


def _active_ids(pkg) -> "set[tuple[str, int]]":
    active, _ = lint_paths([pkg], baseline_path=None)
    return {(f.checker, f.line) for f in active}


def test_tamper_lock_reorder_fires_psl501(tmp_path):
    # Invert the one established two-lock acquisition: the declared
    # lock-order(_rank_lock < _stats_lock) must convict the exact line.
    pkg, line = _tamper_package(
        tmp_path, "multihost_async.py",
        "with self._rank_lock, self._stats_lock:",
        "with self._stats_lock, self._rank_lock:")
    assert _active_ids(pkg) == {("PSL501", line)}


def test_tamper_control_through_gate_fires_psl602_and_deadlocks(tmp_path):
    # Route CONTROL frames through the credit gate: the model must find
    # the deadlock AND the exact line where control started gating.
    pkg, line = _tamper_package(
        tmp_path, "transport.py",
        "self._send_control(payload)\n        return True",
        "self.send_data(payload)\n        return True")
    found = _active_ids(pkg)
    assert ("PSL602", line) in found
    cls_line = next(i for i, ln in enumerate(
        (pkg / "transport.py").read_text().splitlines(), 1)
        if ln.startswith("class Session"))
    assert ("PSL601", cls_line) in found


def test_tamper_data_kind_bypassing_gate_fires_psl602(tmp_path):
    pkg, line = _tamper_package(
        tmp_path, "transport.py",
        'DATA_FRAME_KINDS = frozenset((b"GRAD", b"AGGR", b"REPL"))',
        'DATA_FRAME_KINDS = frozenset((b"AGGR", b"REPL"))')
    assert _active_ids(pkg) == {("PSL602", line)}


def test_tamper_shed_newest_first_fires_psl604(tmp_path):
    # The overflow shed lives in `_shed_overflow` (shared by the plain
    # and segmented data sends since v9) — one popleft, one tamper.
    pkg, line = _tamper_package(
        tmp_path, "transport.py",
        "            self._pending.popleft()\n            if self._sentries:",
        "            self._pending.pop()\n            if self._sentries:")
    assert _active_ids(pkg) == {("PSL604", line)}


def test_tamper_repl_codec_byte_dropped_fires_psl304(tmp_path):
    # Strip the v12 codec-id byte from the REAL replication encoder:
    # the standby's REPL decode branch still unpacks it, so the drift
    # checker must convict the encode site (a reader decoding the
    # payload's first byte as a codec id is silent corruption).
    pkg, line = _tamper_package(
        tmp_path, "multihost_async.py",
        'sent = self._repl_session.send_data(\n'
        '                b"REPL" + _U64.pack(step)\n'
        '                + _U8.pack(self._wire_codec_id) + blob, '
        'deadline=dl)',
        'sent = self._repl_session.send_data(\n'
        '                b"REPL" + _U64.pack(step) + blob, deadline=dl)')
    assert ("PSL304", line) in _active_ids(pkg)


def test_tamper_snapshot_lock_stripped_fires_psl801_races(tmp_path):
    # Strip the copy-under-lock from the REAL RequestLatency.snapshot:
    # the heartbeat thread keeps appending under `_win_lock` while the
    # snapshot now iterates the deque lock-free — the lockset pass must
    # convict exactly the torn iteration line (PR 7's actual bug class).
    pkg, line = _tamper_package(
        tmp_path, "utils/timing.py",
        "        with self._win_lock:\n"
        "            data = list(self._win)\n"
        "            ema, n = self.ema, self.n\n",
        "        data = list(self._win)\n"
        "        ema, n = self.ema, self.n\n")
    assert _active_ids(pkg) == {("PSL801", line)}


def test_tamper_flood_bump_lock_stripped_fires_psl802_races(tmp_path):
    # Strip `_overload_lock` from the worker flood-injector's counter
    # bump: `fault_stats` is declared single-writer(serve-loop), so an
    # unlocked += from the injector thread is a lost-update race the
    # single-writer contract must convict at exactly the bump line.
    pkg, line = _tamper_package(
        tmp_path, "async_ps.py",
        "                            with self._overload_lock:\n"
        "                                self.fault_stats[key] += 1\n",
        "                            self.fault_stats[key] += 1\n")
    assert _active_ids(pkg) == {("PSL802", line)}


def test_blocking_allowed_is_scoped_to_the_declaring_class(tmp_path):
    # Session's blocking-allowed `_lock` must not exempt an UNRELATED
    # class's same-named lock from PSL502 — the exemption rides the
    # declaring hierarchy, not the program-global lock name.
    src = tmp_path / "scoped.py"
    src.write_text(
        "import threading\n\n\n"
        "class SendSide:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()  # pslint: blocking-allowed\n"
        "        self.sock = None\n\n"
        "    def send(self, b):\n"
        "        with self._lock:\n"
        "            self.sock.sendall(b)  # ok: the send lock's job\n\n\n"
        "class Unrelated:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.sock = None\n\n"
        "    def serve(self):\n"
        "        with self._lock:\n"
        "            self.sock.sendall(b'x')\n")
    active, _ = lint_paths([src], baseline_path=None)
    hits = [(f.checker, "Unrelated" in f.message) for f in active]
    assert hits == [("PSL502", True)], [f.render() for f in active]


def test_blocking_named_method_reports_once(tmp_path):
    # `self.recv()` under a lock matches both the blocking-name
    # heuristic and the resolved call edge into a blocking method —
    # exactly ONE PSL502 must land on the line, not two wordings.
    src = tmp_path / "named.py"
    src.write_text(
        "import threading\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._m = threading.Lock()\n"
        "        self.sock = None\n\n"
        "    def recv(self):\n"
        "        return self.sock.recv(4)\n\n"
        "    def caller(self):\n"
        "        with self._m:\n"
        "            return self.recv()\n")
    active, _ = lint_paths([src], baseline_path=None)
    hits = [f for f in active if f.checker == "PSL502"]
    assert len(hits) == 1, [f.render() for f in active]


def test_deferred_closure_locks_do_not_leak_to_call_sites(tmp_path):
    # Defining a thread-body closure acquires nothing: the locks ITS
    # body takes must not count as acquired at `self.start()` call
    # sites, or a declared opposite order fabricates a PSL501 cycle.
    src = tmp_path / "closure.py"
    src.write_text(
        "import threading\n\n"
        "# pslint: lock-order(_b < _a)\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n\n"
        "    def start(self):\n"
        "        def body():\n"
        "            with self._b:\n"
        "                pass\n"
        "        threading.Thread(target=body, daemon=True).start()\n\n"
        "    def caller(self):\n"
        "        with self._a:\n"
        "            self.start()\n")
    active, _ = lint_paths([src], baseline_path=None)
    assert not active, [f.render() for f in active]


def test_tamper_park_without_copy_fires_psl701(tmp_path):
    # Remove the copy-on-park materialization: Session.send_data parks
    # the CALLER's buffer again (the pre-ISSUE-12 ownership hazard,
    # through the `parked = payload` ALIAS — provenance tracking, not
    # name spelling) and the checker must convict the exact park line.
    pkg, _ = _tamper_package(
        tmp_path, "transport.py",
        "parked = bytes(payload)",
        "parked = payload")
    line = next(i for i, ln in enumerate(
        (pkg / "transport.py").read_text().splitlines(), 1)
        if "self._pending.append(parked)" in ln)
    assert _active_ids(pkg) == {("PSL701", line)}


def test_tamper_segment_park_without_copy_fires_psl701(tmp_path):
    # The v9 scatter-gather park: remove the per-segment copy-on-park
    # in Session.send_data_segments (the parked iovec then aliases
    # every caller-owned leaf view) — the checker must convict the
    # exact park line through the `parked = segments` alias.
    pkg, _ = _tamper_package(
        tmp_path, "transport.py",
        "parked = [bytes(s) for s in segments]",
        "parked = segments")
    lines = (pkg / "transport.py").read_text().splitlines()
    park = [i for i, ln in enumerate(lines, 1)
            if "self._pending.append(parked)" in ln]
    # send_data's park + send_data_segments' + park_data_parts' (v11).
    assert len(park) == 3
    assert _active_ids(pkg) == {("PSL701", park[1])}


def test_tamper_stripped_ownership_annotation_fires_psl702(tmp_path):
    # Strip the serializer's declared ownership transfer: the encode
    # arena's escaping view loses its contract and PSL702 must convict
    # the escape site (the `.data` return), not the def line.
    pkg, _ = _tamper_package(
        tmp_path, "native/serializer.py",
        "# pslint: transfers-ownership\ndef _encode_frames",
        "def _encode_frames")
    line = next(i for i, ln in enumerate(
        (pkg / "native" / "serializer.py").read_text().splitlines(), 1)
        if "out[:total].data" in ln)
    assert _active_ids(pkg) == {("PSL702", line)}


def test_buffer_checker_value_flow_through_corpus_functions(tmp_path):
    # The CorpusIndex value-flow half: a helper annotated
    # transfers-ownership makes its CALLERS owners of what they got —
    # `v = make_arena_view()` then `return v` is clean; the same flow
    # through an UNannotated view-returning helper convicts the helper
    # itself (once), never the caller twice.
    src = tmp_path / "flow.py"
    src.write_text(
        "# The view is the sole reference to the arena.\n"
        "# pslint: transfers-ownership\n"
        "def make_owned():\n"
        "    arena = bytearray(64)\n"
        "    return memoryview(arena)\n\n\n"
        "def leaky():\n"
        "    arena = bytearray(64)\n"
        "    return memoryview(arena)\n\n\n"
        "def caller():\n"
        "    v = make_owned()\n"
        "    return v\n")
    active, _ = lint_paths([src], baseline_path=None)
    assert [(f.checker, "leaky" in f.message) for f in active] \
        == [("PSL702", True)], [f.render() for f in active]


def test_buffer_checker_nested_def_loops_report_once(tmp_path):
    # A recv-under-live-view loop inside a NESTED def belongs to the
    # nested scope only — the enclosing function's pass must not
    # double-report it with the wrong attribution.
    src = tmp_path / "nested.py"
    src.write_text(
        "def outer(sock, n, out):\n"
        "    def reader():\n"
        "        buf = bytearray(n)\n"
        "        while True:\n"
        "            sock.recv_into(buf)\n"
        "            out.append(memoryview(buf))\n"
        "    return reader\n")
    active, _ = lint_paths([src], baseline_path=None)
    hits = [f for f in active if f.checker == "PSL703"]
    assert len(hits) == 1 and "reader" in hits[0].message, \
        [f.render() for f in active]


def test_buffer_checker_rebind_clears_handoff_state(tmp_path):
    # The common loop idiom — hand off, then REBIND to a fresh buffer —
    # is not a mutation of the handed-off frame.
    src = tmp_path / "rebind.py"
    src.write_text(
        "def pump(sock, n):\n"
        "    buf = bytearray(n)\n"
        "    sock.sendall(buf)\n"
        "    buf = bytearray(n)\n"
        "    buf[0] = 1\n"
        "    return buf\n")
    active, _ = lint_paths([src], baseline_path=None)
    assert not active, [f.render() for f in active]


# ---------------------------------------------------------------------------
# --changed incremental mode (make lint-fast)
# ---------------------------------------------------------------------------

def _git(cwd, *args):
    proc = subprocess.run(
        ["git", "-c", "user.name=t", "-c", "user.email=t@t", *args],
        cwd=cwd, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    return proc


def test_changed_mode_gates_only_dirty_files(tmp_path):
    repo = tmp_path / "repo"
    repo.mkdir()
    (repo / "committed.py").write_text(
        "def f():\n    raise RuntimeError('legacy')\n")
    (repo / "fresh.py").write_text(
        "def g():\n    raise RuntimeError('fresh')\n")
    _git(repo, "init", "-q")
    _git(repo, "add", ".")
    _git(repo, "commit", "-qm", "seed")
    # Clean tree: --changed skips the lint entirely and exits 0 even
    # though a full run would find both raw raises.
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint", ".", "--no-baseline",
         "--changed"], cwd=repo, capture_output=True, text=True,
        timeout=120, env={**__import__("os").environ,
                          "PYTHONPATH": str(REPO)})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no .py files changed" in proc.stdout
    # The early exit keeps the --format json contract (machine
    # consumers must always get parseable output).
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint", ".", "--no-baseline",
         "--changed", "--format", "json"], cwd=repo, capture_output=True,
        text=True, timeout=120, env={**__import__("os").environ,
                                     "PYTHONPATH": str(REPO)})
    assert proc.returncode == 0
    assert json.loads(proc.stdout)["summary"]["active"] == 0
    # Dirty one file: only ITS finding gates (the committed file's debt
    # is the full run's business, not the edit loop's).
    (repo / "fresh.py").write_text(
        "def g():\n    raise RuntimeError('fresher')\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint", ".", "--no-baseline",
         "--changed"], cwd=repo, capture_output=True, text=True,
        timeout=120, env={**__import__("os").environ,
                          "PYTHONPATH": str(REPO)})
    assert proc.returncode == 1
    assert "fresh.py" in proc.stdout
    assert "committed.py" not in proc.stdout


def test_changed_mode_falls_back_to_full_run_outside_a_repo(tmp_path):
    import os as _os

    plain = tmp_path / "plain"
    plain.mkdir()
    (plain / "mod.py").write_text(
        "def f():\n    raise RuntimeError('x')\n")
    env = {**_os.environ, "PYTHONPATH": str(REPO),
           # A git dir inherited from a parent of tmp_path would turn
           # the fallback test into a dirty-files test.
           "GIT_CEILING_DIRECTORIES": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, "-m", "tools.pslint", "mod.py", "--no-baseline",
         "--changed"], cwd=plain, capture_output=True, text=True,
        timeout=120, env=env)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "PSL401" in proc.stdout


def test_new_checker_ids_roundtrip_allow_and_baseline(tmp_path):
    # allow() by checker id for the new families…
    src = tmp_path / "abba.py"
    src.write_text(
        "import threading\n\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b:  # pslint: allow(PSL501): demo\n"
        "                pass\n\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n")
    active, suppressed = lint_paths([src], baseline_path=None)
    assert {f.checker for f in active} == {"PSL501"}
    assert len(active) == 1 and len(suppressed) == 1
    # …and the committed-baseline flow round-trips PSL5xx/PSL6xx keys.
    paths = [FIXTURES / "bad_deadlock.py",
             FIXTURES / "bad_protocol_model.py"]
    corpus = load_corpus(paths)
    findings = run_checkers(corpus)
    assert {f.checker[:4] for f in findings} == {"PSL5", "PSL6"}
    bl = tmp_path / "bl.txt"
    write_baseline(bl, corpus, findings)
    active, suppressed = lint_paths(paths, baseline_path=bl)
    assert not active and suppressed


# ---------------------------------------------------------------------------
# the credit-gate model itself: exhaustive verification + mutations
# ---------------------------------------------------------------------------

def test_gate_model_verifies_correct_rules():
    from tools.pslint.model import GateRules, explore

    report = explore(GateRules())
    assert report.ok(), vars(report)
    # Exhaustive means a real state space, not a handful of happy paths
    # — and the shed path must be REACHABLE at this configuration.
    assert report.states > 500


def test_gate_model_flags_each_seeded_mutation():
    from tools.pslint.model import GateRules, explore

    gated = explore(GateRules(control_gated=True))
    assert gated.deadlock and gated.control_blocked
    assert explore(GateRules(replenish_flushes=False)).undrained
    assert explore(GateRules(shed_oldest=False)).shed_violations
    assert explore(GateRules(flush_fifo=False)).flush_violations
    # DATA bypassing the gate is a STATIC violation (PSL602): the model
    # itself sees no stall at all — document that division of labor.
    assert explore(GateRules(data_gated=False)).ok()


def test_role_automata_extracts_real_protocol_roles():
    from tools.pslint.protocol import role_automata

    corpus = load_corpus([REPO / "pytorch_ps_mpi_tpu"
                          / "multihost_async.py"])
    auto = role_automata(corpus)
    assert b"GRAD" in auto["AsyncPSWorker"]["sends"]
    assert b"GRAD" in auto["AsyncPSServer"]["receives"]
    assert b"REPL" in auto["AsyncPSServer"]["sends"]  # primary replicates


def test_replenish_never_called_fires_psl603(tmp_path):
    # A program whose data-sending role never adopts a credit replenish
    # starves permanently at the first stall — cross-module liveness.
    src = tmp_path / "mini.py"
    src.write_text(
        "from collections import deque\n\n\n"
        "class MiniSession:\n"
        "    def __init__(self):\n"
        "        self._credits = 1\n"
        "        self._pending = deque()\n"
        "        self.max_pending = 2\n"
        "        self._sock = None\n\n"
        "    def send_data(self, payload):\n"
        "        if self._credits > 0:\n"
        "            self._credits -= 1\n"
        "            self._sock.sendall(payload)\n"
        "            return True\n"
        "        self._pending.append(payload)\n"
        "        return False\n\n"
        "    def replenish(self, credits):\n"
        "        self._credits = int(credits)\n"
        "        while self._pending and self._credits > 0:\n"
        "            self._credits -= 1\n"
        "            self._sock.sendall(self._pending.popleft())\n\n\n"
        "def push(sess, blob):\n"
        "    sess.send_data(b\"GRAD\" + blob)\n")
    active, _ = lint_paths([src], baseline_path=None)
    assert any(f.checker == "PSL603" for f in active), \
        [f.render() for f in active]


# ---------------------------------------------------------------------------
# 3. runtime regression: snapshot key parity across deployments
# ---------------------------------------------------------------------------

def _tiny_params():
    import jax.numpy as jnp
    return [("w", jnp.zeros((2,), jnp.float32))]


def test_fault_snapshot_key_parity_and_render_coverage():
    """Belt-and-suspenders for drift checker PSL302 at runtime: the
    server's fault snapshot must be a superset of the in-process base
    snapshot (a field added to `_base_fault_snapshot` must reach BOTH
    deployments' histories), and every integer counter either deployment
    initializes must render via `format_fault_stats` (a bumped-but-
    invisible counter is exactly the PR 4 drift incident)."""
    from pytorch_ps_mpi_tpu.async_ps import AsyncPS
    from pytorch_ps_mpi_tpu.multihost_async import AsyncPSServer
    from pytorch_ps_mpi_tpu.utils.timing import format_fault_stats

    inproc = AsyncPS(_tiny_params(), quota=1)
    server = AsyncPSServer(_tiny_params(), quota=1, port=0)
    try:
        base_keys = set(inproc._base_fault_snapshot())
        server_keys = set(server._fault_stats_snapshot())
        assert base_keys <= server_keys, (
            "base snapshot fields missing from the server snapshot: "
            f"{sorted(base_keys - server_keys)}")
        assert set(inproc.fault_stats) <= set(server.fault_stats)
        for stats in (inproc.fault_stats, server.fault_stats):
            for key, value in stats.items():
                if isinstance(value, int):
                    assert format_fault_stats({key: 1}) != "clean", (
                        f"counter {key!r} is invisible to "
                        f"format_fault_stats")
    finally:
        server.close()


# ---------------------------------------------------------------------------
# 4. runtime race sanitizer — the dynamic complement of PSL8xx
# ---------------------------------------------------------------------------

def test_race_sanitizer_trips_off_lock_helper_races():
    """A `# pslint: holds(_lock)` helper called WITHOUT the session
    lock must raise the typed RaceDetectedError (not a bare assert) and
    count the trip — the caller-side obligation the static pass can
    only document, convicted live."""
    from pytorch_ps_mpi_tpu.errors import RaceDetectedError
    from pytorch_ps_mpi_tpu.transport import Session

    sess = Session(None, race_sanitizer=True)
    with pytest.raises(RaceDetectedError, match="_gate_open"):
        sess._gate_open()
    assert sess.stats["race_trips"] == 1
    assert sess.stats["race_checks"] == 1
    with sess._lock:
        assert sess._gate_open()  # lock held: the same call is legal
    assert sess.stats["race_trips"] == 1  # no new trip
    assert sess.stats["race_checks"] == 2


def test_race_sanitizer_sees_through_other_threads_races():
    """Holding the lock on ANOTHER thread must not satisfy this
    thread's obligation — ownership is per-thread, not per-lock."""
    import threading

    from pytorch_ps_mpi_tpu.errors import RaceDetectedError
    from pytorch_ps_mpi_tpu.transport import Session

    sess = Session(None, race_sanitizer=True)
    sess._lock.acquire()
    try:
        outcome = {}

        def intruder():
            try:
                sess._consume_gate()
                outcome["r"] = "silent"
            except RaceDetectedError:
                outcome["r"] = "tripped"

        t = threading.Thread(target=intruder)
        t.start()
        t.join(timeout=30)
    finally:
        sess._lock.release()
    assert outcome["r"] == "tripped"
    assert sess.stats["race_trips"] == 1


def test_race_sanitizer_disabled_by_flag_races():
    """`race_sanitizer=False` must beat the suite-wide
    PS_RACE_SANITIZER=1 env (the kwarg is the per-session override):
    plain Lock, zero probes, zero overhead on the hot path."""
    from pytorch_ps_mpi_tpu.transport import Session

    sess = Session(None, race_sanitizer=False)
    assert sess._gate_open()  # no lock held, no sanitizer — no raise
    assert sess.stats["race_checks"] == 0
    assert sess.stats["race_trips"] == 0
    # The lock stays a plain threading.Lock — no wrapper overhead.
    assert type(sess._lock).__name__ != "_TrackedLock"
