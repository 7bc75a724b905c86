"""Tests for tools/trace_analyze.py (compute/comm/exposed-comm split).
The trace fixture is hand-written Chrome-trace JSON: deterministic
intervals whose overlap arithmetic is checkable by hand, no profiler
dependency."""
import gzip
import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trace_doc():
    """Device track: compute [0,100)+[150,250)ms, comm [80,130)+[200,220)ms.
    comm total 70ms; exposed = [100,130) = 30ms; busy = [0,130)+[150,250);
    wall 250ms; idle = [130,150) = 20ms.  (Trace units are microseconds.)"""
    ms = 1000.0
    ev = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "python host"}},
        # device events
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1",
         "ts": 0, "dur": 100 * ms},
        {"ph": "X", "pid": 1, "tid": 2, "name": "collective-permute.3",
         "ts": 80 * ms, "dur": 50 * ms},
        {"ph": "X", "pid": 1, "tid": 1, "name": "dot.7",
         "ts": 150 * ms, "dur": 100 * ms},
        {"ph": "X", "pid": 1, "tid": 2, "name": "all-reduce.9",
         "ts": 200 * ms, "dur": 20 * ms},
        # host noise that must be ignored (device pids exist)
        {"ph": "X", "pid": 2, "tid": 1, "name": "python busywork",
         "ts": 0, "dur": 500 * ms},
    ]
    return {"traceEvents": ev}


def test_trace_analyze_overlap_arithmetic(tmp_path):
    ta = _load("trace_analyze")
    doc = ta.analyze(_trace_doc()["traceEvents"])
    assert doc["ok"] is True
    assert doc["n_events"] == 4                 # host track excluded
    assert abs(doc["wall_ms"] - 250.0) < 1e-6
    assert abs(doc["compute_ms"] - 200.0) < 1e-6
    assert abs(doc["comm_ms"] - 70.0) < 1e-6
    assert abs(doc["comm_exposed_ms"] - 30.0) < 1e-6
    assert abs(doc["overlap_fraction"] - (1 - 30.0 / 70.0)) < 1e-3
    assert abs(doc["idle_ms"] - 20.0) < 1e-6


def test_trace_analyze_cli_on_gzipped_dir(tmp_path):
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump(_trace_doc(), f)
    out = tmp_path / "split.json"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_analyze.py"),
         str(tmp_path), "--out", str(out)],
        capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    doc = json.load(open(out))
    assert doc["ok"] and doc["comm_exposed_ms"] == 30.0


def test_trace_analyze_fallback_busiest_track():
    ta = _load("trace_analyze")
    # no process_name metadata at all -> the busiest pid wins (here the
    # 500 ms host-noise track, proving the fallback keys on duration)
    ev = [e for e in _trace_doc()["traceEvents"] if e["ph"] == "X"]
    doc = ta.analyze(ev)
    assert doc["ok"] and doc["n_events"] == 1
    # with the noise gone, the remaining single-pid trace analyzes fully
    doc = ta.analyze([e for e in ev if e["pid"] == 1])
    assert doc["ok"] and doc["n_events"] == 4


def test_comm_re_classification():
    """Pin the comm-vs-compute classifier: every modern collective spelling
    (ragged-all-to-all, fusion-wrapped async -start/-done forms, bare
    send/recv) is comm; fusions, copies, and convolutions are compute —
    copy-start/copy-done especially must NOT ride the '-start' suffix into
    the comm bucket."""
    ta = _load("trace_analyze")
    comm = [
        "ragged-all-to-all.1", "all-reduce-start.2", "all-reduce-done.2",
        "loop_fusion.collective-permute-start.5", "AllToAll.9",
        "all_gather.4", "reduce_scatter.1", "collective-broadcast.2",
        "send.3", "recv-done.4", "ppermute",
    ]
    compute = [
        "fusion.42", "copy-start.1", "copy-done.1",
        "dynamic-update-slice.7", "convolution.2", "dot.11",
    ]
    for name in comm:
        assert ta.COMM_RE.search(name), f"should be comm: {name}"
    for name in compute:
        assert not ta.COMM_RE.search(name), f"should be compute: {name}"


def test_obs_trace_fixture_arithmetic():
    """The committed obs-smoke fixture (Makefile `obs-smoke` runs the CLI
    on the same file): compute [0,100)+[150,250), comm
    [80,140)+[200,220), exposed [100,140), idle [140,150)."""
    ta = _load("trace_analyze")
    doc = json.load(open(
        os.path.join(REPO, "tests", "fixtures", "obs_trace.trace.json")))
    out = ta.analyze(doc["traceEvents"])
    assert out["ok"] and out["n_events"] == 8
    assert abs(out["wall_ms"] - 250.0) < 1e-6
    assert abs(out["compute_ms"] - 200.0) < 1e-6
    assert abs(out["comm_ms"] - 80.0) < 1e-6
    assert abs(out["comm_exposed_ms"] - 40.0) < 1e-6
    assert abs(out["overlap_fraction"] - 0.5) < 1e-3
    assert abs(out["idle_ms"] - 10.0) < 1e-6


def test_canonical_op_strips_instance_suffixes():
    ta = _load("trace_analyze")
    assert ta.canonical_op("collective-permute-start.5") == \
        "collective-permute-start"
    assert ta.canonical_op("all-reduce.2.1") == "all-reduce"
    assert ta.canonical_op("fusion") == "fusion"
    assert ta.canonical_op("") == ""


def test_overlap_trace_fixture_per_op_attribution():
    """The committed overlapped-step fixture (`make overlap-smoke` runs the
    CLI on the same file): compute [0,140)+[150,200), comm
    [10,50)+[120,160)+[200,220).  Aggregate: comm 100ms, exposed
    [140,150)+[200,220) = 30ms, overlap 0.70, wall 220ms, idle 0.
    Per-op: the two permute-starts canonicalize to one row (80ms total,
    10ms exposed); the trailing permute-done is fully exposed (20ms) and
    must rank first."""
    ta = _load("trace_analyze")
    doc = json.load(open(
        os.path.join(REPO, "tests", "fixtures", "overlap_trace.trace.json")))
    out = ta.analyze(doc["traceEvents"])
    assert out["ok"] and out["n_events"] == 6       # host track excluded
    assert abs(out["wall_ms"] - 220.0) < 1e-6
    assert abs(out["compute_ms"] - 190.0) < 1e-6
    assert abs(out["comm_ms"] - 100.0) < 1e-6
    assert abs(out["comm_exposed_ms"] - 30.0) < 1e-6
    assert abs(out["overlap_fraction"] - 0.70) < 1e-3
    assert abs(out["idle_ms"] - 0.0) < 1e-6
    rows = out["top_exposed_comm_ops"]
    assert [r["name"] for r in rows] == [
        "collective-permute-done", "collective-permute-start"]
    assert rows[0]["count"] == 1
    assert abs(rows[0]["total_ms"] - 20.0) < 1e-6
    assert abs(rows[0]["exposed_ms"] - 20.0) < 1e-6
    assert rows[1]["count"] == 2
    assert abs(rows[1]["total_ms"] - 80.0) < 1e-6
    assert abs(rows[1]["exposed_ms"] - 10.0) < 1e-6


def test_top_exposed_comm_ops_on_obs_fixture():
    """Per-op attribution over the obs fixture, hand-checked: the ragged
    all-to-all owns 30 of the 40 exposed ms, the fusion-wrapped permute
    owns 20 (their [120,130) overlap is attributed to BOTH — per-op rows
    may double-count time that two comm ops expose simultaneously, so the
    rows bound the aggregate from above), the async all-reduce halves are
    fully hidden and tie-break by name."""
    ta = _load("trace_analyze")
    doc = json.load(open(
        os.path.join(REPO, "tests", "fixtures", "obs_trace.trace.json")))
    out = ta.analyze(doc["traceEvents"])
    rows = out["top_exposed_comm_ops"]
    assert [r["name"] for r in rows] == [
        "ragged-all-to-all", "loop_fusion.collective-permute-start",
        "all-reduce-done", "all-reduce-start"]
    assert [r["exposed_ms"] for r in rows] == [30.0, 20.0, 0.0, 0.0]
    assert sum(r["exposed_ms"] for r in rows) >= out["comm_exposed_ms"]
