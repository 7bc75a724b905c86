"""The documents that tell someone what to run name files that exist, and
the README's knob table lists the environment variables the program reads.

A PR that deletes a file and leaves its instructions standing fails here.
The records (``PERF.md``, ``ROADMAP.md``, ``CHANGES.md``, the
``docs/PERF_PR*_RECORD.md`` archives) name what is gone on purpose and are
not read; ``docs/API.md`` is generated.
"""
import functools
import glob
import os
import re

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _documents():
    docs = sorted(
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
        if os.path.basename(p) != "API.md"
        and not re.fullmatch(r"PERF_PR\d+_RECORD\.md", os.path.basename(p)))
    return (["README.md"] + docs
            + ["Makefile", ".claude/skills/verify/SKILL.md",
               "dockerfile.cpu", "dockerfile.cpu.test", "dockerfile.tpu"])


# Names a document uses that are not this repo's files, each with its reason.
NOT_OURS = {
    "docs/performance.rst": "the reference's (Bluefog's) own document",
    "examples/pytorch_optimization.py": "the reference's example",
    "basics.py": "the reference's module (README and PARITY map it to ours)",
    "mpi_ops.py": "the reference's module",
    "utility.py": "the reference's module",
    "setup.py": "the reference's build script",
    "docs/measured/": "created by the first banked autotune trial",
    "train.py": "the user's own script in a launcher example",
    "my_server.py": "the user's own script in a launcher example",
}

_UNDER_A_DIR = re.compile(
    r"(?<![\w/.-])((?:tools|examples|bluefog_tpu|perfbench|tests|docs)"
    r"/[\w./-]*\w/?)")
# a bare name: a module or document by its file name alone, or one of the
# root's records (upper-case stems: BENCHMARK.json).  A lower-case bare
# ``.json`` is what a command writes (``--out report.json``), not a file of
# the tree.
_BARE = re.compile(
    r"(?<![\w/.<>-])([A-Za-z_][\w.-]*\.(?:py|md)|[A-Z][A-Z_]*\.json)"
    r"(?![\w/])")
_NOT_THE_TREE = {"__pycache__", "chiprun_out", "chipcheck", "perfbench_out"}


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d not in _NOT_THE_TREE and not d.startswith(".")]
        names.update(files)
    return frozenset(names)


def named_paths(text):
    """``(under, bare)``: the paths ``text`` names under one of the repo's
    directories, and the ``.py`` / ``.md`` / record names it gives with no
    directory.  A pattern (``test_moe*.py``) or a placeholder (``<cell>``)
    is no path; nor is the reference's ``pytorch_*.py``."""
    under = set()
    for m in _UNDER_A_DIR.finditer(text):
        if text[m.end():m.end() + 1] not in ("*", "<", "{", "$"):
            under.add(m.group(1))
    bare = {m.group(1) for m in _BARE.finditer(text)
            if not m.group(1).startswith("pytorch_")}
    return under, bare


def missing_paths(text):
    under, bare = named_paths(text)
    missing = {p for p in under
               if not os.path.exists(os.path.join(REPO, p))}
    missing |= bare - _basenames()
    return sorted(missing - set(NOT_OURS))


@pytest.mark.parametrize("document", _documents())
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document)) as f:
        missing = missing_paths(f.read())
    assert not missing, f"{document} names files that do not exist: {missing}"


def test_a_path_that_does_not_exist_is_caught():
    text = ("run `python tools/no_such_tool.py --out report.json`, then "
            "no_such_script.py and NO_SUCH_RECORD.json (see "
            "tests/test_docs_paths.py::x, conftest.py and "
            "docs/performance.rst; /tmp/out.json and tests/test_moe*.py "
            "are no paths of ours).")
    assert missing_paths(text) == ["NO_SUCH_RECORD.json",
                                   "no_such_script.py",
                                   "tools/no_such_tool.py"]


# a whole name: ``BLUEFOG_MOE_*`` in prose names a family, not a knob
_KNOB = re.compile(r"BLUEFOG_[A-Z0-9_]*[A-Z0-9](?![A-Z0-9_])")


def _knobs_in_the_table():
    names = set()
    with open(os.path.join(REPO, "README.md")) as f:
        for line in f:
            if line.startswith("| `"):
                names.update(_KNOB.findall(line.split("|")[1]))
    return names


def _knobs_read():
    files = [os.path.join(REPO, "chip_smoke.py")]
    for top in ("bluefog_tpu", "tools"):
        for root, _, names in os.walk(os.path.join(REPO, top)):
            files += [os.path.join(root, n) for n in names
                      if n.endswith(".py")]
    names = set()
    for path in files:
        with open(path) as f:
            names.update(_KNOB.findall(f.read()))
    return names


def test_every_knob_the_readme_lists_is_read():
    assert _knobs_in_the_table() - _knobs_read() == set()


def test_every_knob_read_is_in_the_readme():
    assert _knobs_read() - _knobs_in_the_table() == set()
