"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by the NAME the manifest gives and never listed
in code:

    configuration <c>   perfbench/configs/<c>.json   ("file" in the manifest)
    traffic mix <t>     perfbench/traffic/<t>.json
    metric <m>          perfbench/metrics/<m>.py      (its reader)
    kind of run <k>     perfbench/runners/<k>.py      ("kind" in the traffic file)
    model family <f>    perfbench/families/<f>.py     ("family" in the config file)
                        perfbench/reference/<f>.py    (its plain reference)

A cell ``<config>.<traffic>`` resolves to the first two; the traffic file's
``kind`` picks the runner and the configuration's ``family`` the adapter
that knows how to build that family through the program's entry points.
"""
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load(path=MANIFEST):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def load_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(
        f"no {what} named {name!r}; known: {[e['name'] for e in entries]}")


def resolve_cell(manifest, workload, root=ROOT):
    """Cell name -> dict(cell, config entry, config file contents, traffic
    contents).  ``tiny`` groups stay unmerged; :func:`sized` applies them."""
    cell = _by_name(manifest["workloads"], workload, "workload")
    centry = _by_name(manifest["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(root, centry["file"]))
    traffic = load_json(os.path.join(root, "perfbench", "traffic",
                                     cell["traffic"] + ".json"))
    for key, doc, where in (("family", config, centry["file"]),
                            ("kind", traffic, cell["traffic"])):
        if not NAME_RE.match(str(doc.get(key, ""))):
            raise ManifestError(f"{where}: missing or malformed {key!r}")
    return {"cell": cell, "config_entry": centry, "config": config,
            "traffic": traffic}


def sized(doc, rehearsal):
    """The sizes a run uses: the file's own, or with its ``tiny`` group laid
    over them for the CPU rehearsal (tests only; never a device number)."""
    out = {k: v for k, v in doc.items() if k != "tiny"}
    if rehearsal:
        out.update(doc.get("tiny", {}))
    return out


def metrics_for(manifest, workload, group):
    """The manifest's metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_module(kind_dir, name, root=ROOT):
    """perfbench/<kind_dir>/<name>.py as a module (names may hold dots and
    dashes, so this goes by path, not by import)."""
    if not NAME_RE.match(name):
        raise ManifestError(f"malformed name {name!r}")
    path = os.path.join(root, "perfbench", kind_dir, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"no file perfbench/{kind_dir}/{name}.py")
    modname = "perfbench_%s_%s" % (kind_dir, re.sub(r"[^A-Za-z0-9_]", "_", name))
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(manifest, workload, group, run, root=ROOT):
    """Run every reader of the cell's ``group`` metrics over the run record.
    A reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in metrics_for(manifest, workload, group):
        value = load_module("metrics", m["name"], root).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check(manifest, root=ROOT):
    """The driver's rules that can be checked from the files alone.
    Returns a list of complaints (empty = fine)."""
    bad = []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            bad.append(f"{what}: bad name {n!r}")

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != want:
        bad.append(f"keys {sorted(manifest)} != {sorted(want)}")
        return bad
    paths = manifest["paths"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    seen = set()
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            name_ok(m.get("name"), group)
            if m["name"] in seen:
                bad.append(f"metric {m['name']} twice")
            seen.add(m["name"])
            if not UNIT_RE.match(m.get("unit", "")):
                bad.append(f"{m['name']}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                bad.append(f"{m['name']}: better={m.get('better')!r}")
            if m.get("source") not in SOURCES:
                bad.append(f"{m['name']}: source={m.get('source')!r}")
            allowed = {"name", "unit", "better", "source", "workloads"} | (
                {"bound"} if group == "end_to_end" else {"layer", "moves"})
            if set(m) - allowed:
                bad.append(f"{m['name']}: extra keys {set(m) - allowed}")
            if group == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"{m['name']}: end-to-end source {m['source']}")
                if not 0.01 <= m.get("bound", 0) <= 0.1:
                    bad.append(f"{m['name']}: bound {m.get('bound')}")
            else:
                if m.get("moves") not in e2e:
                    bad.append(f"{m['name']}: moves {m.get('moves')!r}")
                if not m.get("layer") or "\n" in m["layer"] or len(m["layer"]) > 200:
                    bad.append(f"{m['name']}: layer")
            if not os.path.isfile(os.path.join(
                    root, "perfbench", "metrics", m["name"] + ".py")):
                bad.append(f"{m['name']}: no reader file")
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    cfg_names, files = set(), set()
    for c in manifest["configs"]:
        name_ok(c.get("name"), "config")
        cfg_names.add(c["name"])
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']}: keys {sorted(c)}")
        f = c.get("file", "")
        if not any(f.startswith(p.rstrip("/") + "/") for p in paths):
            bad.append(f"config {c['name']}: file {f} outside paths")
        if f in files or not os.path.isfile(os.path.join(root, f)):
            bad.append(f"config {c['name']}: file {f} missing or shared")
        files.add(f)
        for k in c.get("reduced", []):
            name_ok(k, f"config {c['name']} reduced")
    cells, pairs, used = set(), set(), set()
    for w in manifest["workloads"]:
        name_ok(w.get("name"), "workload")
        name_ok(w.get("traffic"), "traffic")
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w['name']}: keys {sorted(w)}")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']} twice")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: config {w['config']}")
        if w.get("chips") not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w.get('chips')}")
        if not 1 <= len(w.get("why", "")) <= 200 or "\n" in w["why"]:
            bad.append(f"workload {w['name']}: why")
        try:
            cell = resolve_cell(manifest, w["name"], root)
            for kd, key, doc in (("runners", "kind", cell["traffic"]),
                                 ("families", "family", cell["config"]),
                                 ("reference", "family", cell["config"])):
                if not os.path.isfile(os.path.join(
                        root, "perfbench", kd, doc[key] + ".py")):
                    bad.append(f"workload {w['name']}: no perfbench/{kd}/"
                               f"{doc[key]}.py")
        except ManifestError as e:
            bad.append(f"workload {w['name']}: {e}")
        for group in ("end_to_end", "per_layer"):
            if not metrics_for(manifest, w["name"], group):
                bad.append(f"workload {w['name']}: no {group} metric")
    if cfg_names - used:
        bad.append(f"configs used by no cell: {cfg_names - used}")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(manifest['workloads'])}")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: unknown workload {w}")
    if not 1 <= manifest["run_seconds"] <= 51:
        bad.append(f"run_seconds {manifest['run_seconds']}")
    return bad
