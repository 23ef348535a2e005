"""The documents and the sources name only files that exist.

Every path a document or a source file names, when it starts with one of
the repository's top-level directories or is a bare ``*.py`` / ``*.md`` /
``*.json`` name in backticks, has to be there: a glob or a
``<placeholder>`` has to match something, and ``:line`` and ``::test``
suffixes are not part of the path.  `ROADMAP.md` is left out (it keeps
struck history), `CHANGES.md` and the provenance documents too (they
record what was); ``/root/reference/...`` is the system this one was
modelled on and is not in this repository.
"""

import fnmatch
import functools
import glob
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("pytorch_ps_mpi_tpu", "perfbench", "tests", "tools", "benchmarks")

# A path under a top-level directory, wherever it stands (not the tail of a
# longer path or of a dotted module name).
_UNDER_DIR = re.compile(
    r"(?<![\w/.\-])(?:%s)/[\w./*<>{},\-]*" % "|".join(TOP_DIRS))
# A bare file name in backticks, with an optional `:line` or `::test`.
_BARE = re.compile(
    r"(?<!`)`([\w.\-*<>]+\.(?:py|md|json))(?:::?[\w\[\],:\-]*)?`(?!`)")

# Bare names that are somebody else's files.
NOT_OURS = {
    "config.json": "the published configuration on the model's hub page",
    "ckpt.fleet.json": "the manifest a fleet writes beside its checkpoints",
    "mpi_comms.py": "/root/reference",
    "serialization.py": "/root/reference",
    "test_mpi.py": "/root/reference",
}


@functools.lru_cache(maxsize=None)
def _tracked():
    """The files of the checkout, as git lists them or as they lie."""
    try:
        out = subprocess.run(["git", "ls-files", "-co", "--exclude-standard"],
                             cwd=ROOT, capture_output=True, text=True,
                             check=True).stdout.split("\n")
        files = [f for f in out if f and os.path.exists(os.path.join(ROOT, f))]
    except (OSError, subprocess.CalledProcessError):
        files = []
    if not files:   # a copy of the tree that is not a git checkout
        for d, dirs, names in os.walk(ROOT):
            dirs[:] = [x for x in dirs if not x.startswith(".")
                       and x not in ("__pycache__", "chiprun_out")]
            files += [os.path.relpath(os.path.join(d, n), ROOT)
                      for n in names]
    return files


def _sources():
    return sorted(
        f for f in _tracked() if f.endswith(".py") and (
            f.startswith(("pytorch_ps_mpi_tpu/", "tools/"))
            or "/" not in f))


DOCUMENTS = {
    "README.md": lambda: ["README.md"],
    "PERF.md": lambda: ["PERF.md"],
    "Makefile": lambda: ["Makefile"],
    "SKILL.md": lambda: [".claude/skills/verify/SKILL.md"],
    "sources": _sources,
}


def _pattern(path):
    """The path as a glob: placeholders and brace lists match anything."""
    path = re.sub(r"<[^<>]*>", "*", path)
    path = re.sub(r"\{[^{}]*\}", "*", path)
    return path.rstrip(".,:-")


def named_paths(text):
    """(what the text says, the glob it has to satisfy), for each path."""
    for m in _UNDER_DIR.finditer(text):
        yield m.group(0), _pattern(m.group(0))
    for m in _BARE.finditer(text):
        yield m.group(1), _pattern(m.group(1))


def missing(text, basenames):
    out = []
    for said, pattern in named_paths(text):
        if "/" in pattern:
            if not glob.glob(os.path.join(ROOT, pattern.rstrip("/"))):
                out.append(said)
        elif pattern not in NOT_OURS and not any(
                fnmatch.fnmatchcase(b, pattern) for b in basenames):
            # A bare name stands for a file at the root or, inside a
            # package, for the module of that name.
            out.append(said)
    return out


@pytest.mark.parametrize("document", sorted(DOCUMENTS))
def test_every_path_named_exists(document):
    basenames = {os.path.basename(f) for f in _tracked()}
    bad = {}
    for rel in DOCUMENTS[document]():
        with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
            gone = missing(f.read(), basenames)
        if gone:
            bad[rel] = sorted(set(gone))
    assert not bad, f"named but not in the repository: {bad}"


def test_the_reader_finds_what_is_missing():
    text = ("see `gone.py`, `" "benchmarks" "/GONE.json` and "
            "tests/test_docs.py::test_x, `ps.py:24`, perfbench/<name>.json, "
            "`tools/pslint/*.py`, /root/reference/tests/none.py, "
            "`NOPE_RECORD.json`")
    assert missing(text, {"ps.py", "test_docs.py"}) == [
        "benchmarks" "/GONE.json", "gone.py", "NOPE_RECORD.json"]
