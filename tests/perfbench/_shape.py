"""What the benchmark's tests hold a manifest to, stated once as rules of
ANY manifest: the one in the repo, and the same one with a configuration
of a new family, a cell and a metric appended (a later PR may append; it
may not edit a file under the benchmark's ``paths``, these tests among
them).  Nothing here reads the repo's BENCHMARK.json by itself: every
function takes a loaded manifest and the root its files lie under.

A rule returns its complaints, an empty list where the manifest is fine.
``RULES`` are those of the manifest as a whole.  A test file written for
one cell states what its PR put there with :func:`written_for` (the cell
IN its metrics' lists, the metrics in their relative order: never "the
list is exactly this") in a function ``manifest_rule(man, root)``, which
``test_perfbench_manifest.py`` finds by that name in every file beside it
and runs on the appended manifest too."""
import functools
import importlib
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from perfbench.harness import manifest  # noqa: E402

# Only a `benchmark` PR may change an end-to-end metric's name, order or
# bound, so these are literals, here and nowhere else.  A later PR's own
# end-to-end metric comes behind them.
END_TO_END = (("train_items_per_s_per_chip", 0.01),
              ("serve_tok_per_s", 0.08), ("ttft_p50_s", 0.05),
              ("token_gap_p90_s", 0.04), ("setup_s", 0.1))
PATHS = ["perfbench", "tests/perfbench"]
COMMAND = ["python3", "perfbench/run.py"]
RUN_SECONDS = 30
MAX_BYTES, MAX_CELLS, MAX_WHY = 64 * 1024, 24, 200


def cells_of(man, metric):
    """The cells that report ``metric``, in the manifest's order: those
    its ``workloads`` name, or every cell where it has no list."""
    entry, = [m for m in man["end_to_end"] + man["per_layer"]
              if m["name"] == metric]
    listed = entry.get("workloads")
    return [w["name"] for w in man["workloads"]
            if listed is None or w["name"] in listed]


@functools.lru_cache(maxsize=None)
def _family(name, root):
    return manifest.load_module("families", name, root)


def family_of(man, cell, root=ROOT):
    """The family adapter (perfbench/families/<family>.py under ``root``)
    of the cell's configuration."""
    cfg = manifest.resolve_cell(man, cell, root)["config"]
    return _family(cfg["family"], str(root))


_FAMILY_VAR = re.compile(
    r"(\w+)\s*=\s*manifest\.load_module\(\s*[\"']families[\"']")


def family_hooks(metric, root=ROOT):
    """The names a metric's reader asks the run's family adapter for: of
    whatever variable it binds to ``manifest.load_module("families", ...)``
    every attribute it takes or tests with ``hasattr``.  Read off the
    reader's source, so a reader a later PR brings is covered as it
    stands."""
    path = os.path.join(root, "perfbench", "metrics", metric + ".py")
    with open(path) as f:
        src = f.read()
    hooks = set()
    for var in set(_FAMILY_VAR.findall(src)):
        hooks |= set(re.findall(r"\b%s\.(\w+)" % re.escape(var), src))
        hooks |= set(re.findall(
            r"hasattr\(\s*%s\s*,\s*[\"'](\w+)[\"']" % re.escape(var), src))
    return sorted(hooks)


def own_check(man, root=ROOT):
    return manifest.check(man, root)


def envelope(man, root=ROOT):
    bad = []
    for key, want in (("paths", PATHS), ("command", COMMAND),
                      ("run_seconds", RUN_SECONDS)):
        if man[key] != want:
            bad.append(f"{key} {man[key]!r} != {want!r}")
    size = os.path.getsize(os.path.join(root, "BENCHMARK.json"))
    if size >= MAX_BYTES:
        bad.append(f"BENCHMARK.json holds {size} bytes")
    for entry in man["configs"] + man["workloads"]:
        if not 1 <= len(entry["why"]) <= MAX_WHY:
            bad.append(f"{entry['name']}: why of {len(entry['why'])}")
    return bad


def end_to_end(man, root=ROOT):
    got = tuple((m["name"], m["bound"])
                for m in man["end_to_end"][:len(END_TO_END)])
    return [] if got == END_TO_END else [
        f"the first end-to-end metrics are {got}, not {END_TO_END}"]


def cell_counts(man, root=ROOT):
    """At most 24 cells (of them ``max(1, cells // 4)`` on four chips:
    ``manifest.check`` has that one)."""
    n = len(man["workloads"])
    return [f"{n} cells"] if n > MAX_CELLS else []


def moves_inside(man, root=ROOT):
    """A per-layer metric is listed only where the end-to-end metric it
    should move is reported."""
    bad = []
    for m in man["per_layer"]:
        out = set(cells_of(man, m["name"])) - set(cells_of(man, m["moves"]))
        if out:
            bad.append(f"{m['name']} lists {sorted(out)}, where "
                       f"{m['moves']} is not reported")
    return bad


def hooks_held(man, root=ROOT):
    """A metric whose reader asks the family for a hook lists only cells
    whose family has it: under a reader that would find nothing to read,
    the listed cell is the fault."""
    bad = []
    for m in man["end_to_end"] + man["per_layer"]:
        hooks = family_hooks(m["name"], root)
        for cell in cells_of(man, m["name"]) if hooks else ():
            family = family_of(man, cell, root)
            lacks = [h for h in hooks if not hasattr(family, h)]
            if lacks:
                bad.append(f"{m['name']} lists {cell}, whose family "
                           f"{family.__name__} has no {lacks}")
    return bad


RULES = (own_check, envelope, end_to_end, cell_counts, moves_inside,
         hooks_held)


def complaints(man, root=ROOT):
    """Every rule's complaints, each behind its rule's name."""
    return [f"{rule.__name__}: {c}" for rule in RULES
            for c in rule(man, root)]


def written_for(man, cell, *, config, traffic, chips, metrics=()):
    """What a PR that wrote ``cell`` left in the manifest, however much
    has been appended since: the cell with its configuration, traffic and
    chips, the cell IN each of ``metrics``' lists, and ``metrics`` in the
    manifest in the relative order given."""
    bad = []
    entry = [w for w in man["workloads"] if w["name"] == cell]
    want = {"config": config, "traffic": traffic, "chips": chips}
    if len(entry) != 1:
        return [f"{len(entry)} cells named {cell}"]
    got = {k: entry[0][k] for k in want}
    if got != want:
        bad.append(f"{cell} is {got}, not {want}")
    if config not in [c["name"] for c in man["configs"]]:
        bad.append(f"no configuration {config}")
    names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    for name in metrics:
        if name not in names:
            bad.append(f"no metric {name}")
        elif cell not in cells_of(man, name):
            bad.append(f"{name} does not list {cell}")
    if not bad and sorted(metrics, key=names.index) != list(metrics):
        bad.append(f"{list(metrics)} stand in another order in the manifest")
    return bad


def first_cells(man, root=ROOT):
    """``{(family, kind): the first cell of that configuration's family
    and traffic's kind}`` over the manifest's cells: what a test runs once
    a pair, a later PR's family among them without an edit."""
    first = {}
    for w in man["workloads"]:
        c = manifest.resolve_cell(man, w["name"], root)
        first.setdefault((c["config"]["family"], c["traffic"]["kind"]),
                         w["name"])
    return first


def holding(man, hook, root=ROOT):
    """The cells whose family adapter has ``hook``, in the manifest's
    order."""
    return [w["name"] for w in man["workloads"]
            if hasattr(family_of(man, w["name"], root), hook)]


def rules_of_the_files():
    """``{file name: its manifest_rule}`` of every test file beside this
    one that states one, found by the name: a later PR's file is among
    them without an edit here."""
    found = {}
    for name in sorted(os.listdir(HERE)):
        if re.match(r"test_\w+\.py$", name):
            rule = getattr(importlib.import_module(name[:-3]),
                           "manifest_rule", None)
            if rule is not None:
                found[name] = rule
    return found
