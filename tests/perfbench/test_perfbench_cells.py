"""Every cell's command end to end on the CPU at the files' ``tiny`` sizes
(``--rehearse``), and its refusal to measure without a TPU.  A rehearsal
prints no metric value: a CPU number never stands under a device metric's
name."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import manifest  # noqa: E402

MAN = manifest.load()
CELLS = {w["name"]: w for w in MAN["workloads"]}


def run_cell(cell, *extra, trace=0, seconds=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
    return subprocess.run(
        [sys.executable] + MAN["command"][1:] + [
            "--workload", cell, "--seed", "5", "--seconds", str(seconds),
            "--trace", str(trace), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", list(CELLS))
def test_cell_refuses_to_measure_without_a_tpu(cell):
    p = run_cell(cell)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


@pytest.mark.parametrize("cell,trace", [(c, 0) for c in CELLS] + [
    ("pythia-410m.serve-closed32", 1), ("pythia-410m.train-seq2048", 1)])
def test_cell_rehearses_end_to_end_on_cpu(cell, trace):
    p = run_cell(cell, "--rehearse", trace=trace)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == CELLS[cell]["chips"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in manifest.metrics_for(MAN, cell, group)}
    assert set(line["would_report"]) <= names
    if not trace:
        assert set(line["would_report"]) == names
    # the distribution of the readings is printed before the last line,
    # never under a device metric's name, and the series is kept in a file
    said = [l for l in lines[:-1] if l.startswith("[perfbench] series ")]
    assert said and all("cpu_rehearsal." in l for l in said[:-1])
    kept = os.path.join(ROOT, "perfbench_out", "series",
                        f"{cell}.seed5.trace{trace}.json")
    with open(kept) as f:
        doc = json.load(f)
    assert doc["rehearsal"] is True
    for s in doc["series"].values():
        assert s["summary"]["n"] == len(s["readings"]) > 0


def test_unknown_workload_is_refused():
    p = run_cell("resnet50.no-such-traffic")
    assert p.returncode != 0 and "no workload named" in p.stderr
    assert p.stdout.strip() == ""
