"""Every cell's command end to end on the CPU at the files' ``tiny`` sizes
(``--rehearse``), and its refusal to measure without a TPU.  A rehearsal
prints no metric value: a CPU number never stands under a device metric's
name."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import _shape  # noqa: E402
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


def test_a_traced_rehearsal_is_made_for_every_family_and_kind():
    traced = list(_shape.first_cells(MAN).values())
    assert {"resnet50.train-b256", "pythia-410m.train-seq2048",
            "pythia-410m.serve-closed32", "a.x-k1.serve-closed128-p2048",
            "k-exaone.serve-closed48-p8192"} <= set(traced)
    # a cell of a family and kind that has one already adds none
    assert "pythia-410m.gossip4-seq2048" not in traced
    more = {**MAN, "workloads": MAN["workloads"] + [dict(
        MAN["workloads"][-1], name="k-exaone.another")]}
    assert list(_shape.first_cells(more).values()) == traced


@pytest.mark.parametrize("cell,trace", [(c, 0) for c in CELLS] + [
    (c, 1) for c in _shape.first_cells(MAN).values()])
def test_cell_rehearses_end_to_end_on_cpu(cell, trace, tmp_path):
    p = run_cell(cell, "--rehearse", "--out-dir", str(tmp_path), trace=trace)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["correct"] is True, p.stdout[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == CELLS[cell]["chips"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # each number `correct` rests on beside its limit: the line's last key
    # and the last lines of standard error
    assert list(line)[-1] == "checks" and len(line["checks"]) >= 3
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    said = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert [l.split()[2] for l in said] == list(line["checks"])
    assert all(l.startswith("perfbench: check ") for l in said)
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in manifest.metrics_for(MAN, cell, group)}
    assert line["would_report"] and set(line["would_report"]) <= names
    if not trace:
        assert set(line["would_report"]) == names
    # the distribution of the readings is printed before the last line,
    # never under a device metric's name, and the series is kept in a file
    said = [l for l in lines[:-1] if l.startswith("[perfbench] series ")]
    assert said and all("cpu_rehearsal." in l for l in said[:-1])
    kept = tmp_path / "series" / f"{cell}.seed5.trace{trace}.json"
    with open(kept) as f:
        doc = json.load(f)
    assert doc["rehearsal"] is True
    for s in doc["series"].values():
        assert s["summary"]["n"] == len(s["readings"]) > 0


def test_unknown_workload_is_refused():
    p = run_cell("resnet50.no-such-traffic")
    assert p.returncode != 0 and "no workload named" in p.stderr
    assert p.stdout.strip() == ""


def test_compared_gathers_every_number_beside_its_limit():
    from perfbench.runners import _common
    ref = {"ok": True, "compared": {"loss_gap": [2e-6, 1e-3]}}
    structure = {"ok": True, "compared": {"cross_chip_all_reduces": [0, 0]},
                 "mixing": {"ok": True,
                            "compared": {"mixing_max_abs_err": [2e-7, 1e-5]}},
                 "collective_counts": {"collective-permute": 2}}
    got = _common.compared(ref, structure, compiles_in_window=(0, 0))
    assert got == {
        "loss_gap": {"value": 2e-6, "limit": 1e-3},
        "cross_chip_all_reduces": {"value": 0.0, "limit": 0.0},
        "mixing_max_abs_err": {"value": 2e-7, "limit": 1e-5},
        "compiles_in_window": {"value": 0.0, "limit": 0.0}}
    assert _common.compared({"ok": True}) == {}


@pytest.mark.parametrize("got,want,ok", [(2.5, 2.5004, True),
                                         (2.5, 2.51, False),
                                         (float("nan"), 2.5, False)])
def test_a_loss_is_compared_as_a_share_of_the_references(got, want, ok):
    from perfbench.families import _checks
    rep = _checks.loss_agrees(got, want, 1e-3)
    assert rep["ok"] is ok
    gap, limit = rep["compared"]["loss_gap"]
    assert limit == 1e-3 and (gap <= limit) is ok
    from perfbench.runners import _common
    value = _common.compared(rep)["loss_gap"]["value"]
    assert value is None if got != got else value == pytest.approx(gap)


BROKEN_DECODE = """
import sys
sys.path.insert(0, {root!r})
from bluefog_tpu.serve import ServeEngine
sound = ServeEngine.decode


def altered(self, *args, **kwargs):
    # every decoded token is another one than the program chose
    return (sound(self, *args, **kwargs) + 1) % self.cfg.vocab


ServeEngine.decode = altered
from perfbench import run
sys.exit(run.main(sys.argv[1:]))
"""


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path):
    """The rest of a run driven with the timed path broken underneath: the
    serving cell's decode call returns wrong tokens, the run still ends
    with a result line, and ``correct`` is false on the decoded tokens'
    reference logits (the other numbers stay inside their limits)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", BROKEN_DECODE.format(root=ROOT), "--workload",
         "pythia-410m.serve-closed32", "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearse", "--out-dir", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    over = {name for name, c in line["checks"].items()
            if c["value"] > c["limit"]}
    assert over == {"decode_logit_gap_share"}
    assert "check decode_logit_gap_share" in p.stderr

