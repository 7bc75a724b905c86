"""BENCHMARK.json against the driver's rules that files alone can show, and
the harness driven by data: a new cell, configuration and metric are new
files and appended entries, nothing else."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import manifest  # noqa: E402

MAN = manifest.load()
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]


def test_manifest_passes_its_own_check():
    assert manifest.check(MAN) == []


def test_manifest_is_the_issues_shape():
    assert CELLS == ["resnet50.train-b256", "pythia-410m.train-seq2048",
                     "pythia-410m.gossip4-seq2048",
                     "pythia-410m.serve-closed32"]
    assert [c["name"] for c in MAN["configs"]] == ["resnet50", "pythia-410m"]
    assert [m["name"] for m in MAN["end_to_end"]] == [
        "train_items_per_s_per_chip", "serve_tok_per_s", "ttft_p50_s",
        "token_gap_p90_s", "setup_s"]
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) == 1
    assert MAN["paths"] == ["perfbench", "tests/perfbench"]
    assert os.path.getsize(manifest.MANIFEST) < 64 * 1024


@pytest.mark.parametrize("name", CELLS + METRICS
                         + [c["name"] for c in MAN["configs"]]
                         + [w["traffic"] for w in MAN["workloads"]])
def test_names_use_only_the_allowed_characters(name):
    assert manifest.NAME_RE.match(name)


@pytest.mark.parametrize("metric", MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_unit_source_and_reader(metric):
    assert manifest.UNIT_RE.match(metric["unit"])
    assert metric["source"] in manifest.SOURCES
    assert metric["better"] in ("lower", "higher")
    mod = manifest.load_module("metrics", metric["name"])
    assert callable(mod.read) and mod.__doc__


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files_by_name(cell):
    c = manifest.resolve_cell(MAN, cell)
    assert c["config"]["family"] and c["traffic"]["kind"]
    for kind_dir, key, doc in (("runners", "kind", c["traffic"]),
                               ("families", "family", c["config"]),
                               ("reference", "family", c["config"])):
        assert os.path.isfile(os.path.join(
            ROOT, "perfbench", kind_dir, doc[key] + ".py"))
    assert manifest.metrics_for(MAN, cell, "end_to_end")
    assert manifest.metrics_for(MAN, cell, "per_layer")
    names = {m["name"] for m in manifest.metrics_for(MAN, cell, "end_to_end")}
    assert "setup_s" in names and len(names) >= 2
    e2e_here = names
    for m in manifest.metrics_for(MAN, cell, "per_layer"):
        assert m["moves"] in e2e_here     # reported only where what it moves is


def test_pythia_file_holds_the_sources_sizes():
    cfg = manifest.load_json(os.path.join(
        ROOT, "perfbench/configs/pythia-410m.json"))
    assert (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
        1024, 24, 16, 4096, 50304, 2048)
    assert cfg["departures"] and cfg["assumed"]
    fam = manifest.load_module("families", "composed_lm")
    assert fam.n_params(cfg) == 24 * 12 * 1024 * 1024 + 2 * 50304 * 1024
    assert fam.flops_per_item(cfg, {"seq_len": 2048}) == pytest.approx(
        6 * 405012480 + 6 * 24 * 1024 * 2048)


def test_check_catches_a_broken_manifest():
    bad = json.loads(json.dumps(MAN))
    bad["workloads"][0]["chips"] = 2
    bad["end_to_end"][0]["unit"] = "items per second"
    bad["per_layer"][0]["moves"] = "nothing"
    bad["workloads"][1]["chips"] = 4            # a second four-chip cell
    complaints = "\n".join(manifest.check(bad))
    for needle in ("chips 2", "bad unit", "moves", "four-chip"):
        assert needle in complaints


def test_a_new_cell_config_and_metric_are_only_new_files(tmp_path):
    """In a temporary copy: add a configuration, a traffic mix and a metric
    as files, append their entries, and run the new cell.  No file that
    was there is edited."""
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "perfbench").rglob("*")
              if p.is_file()}
    pb = tmp_path / "perfbench"
    cfg = json.loads((pb / "configs/pythia-410m.json").read_text())
    cfg["num_hidden_layers"] = 2
    (pb / "configs/dummy-lm.json").write_text(json.dumps(cfg))
    tr = json.loads((pb / "traffic/train-seq2048.json").read_text())
    tr["tiny"]["calls_per_block"] = 3
    (pb / "traffic/train-dummy.json").write_text(json.dumps(tr))
    (pb / "metrics/dummy.blocks.py").write_text(
        '"""Blocks read in the window."""\n\n\ndef read(run):\n'
        '    return len(run["readings"]["train_items_per_s_per_chip"])\n')
    man = json.loads(json.dumps(MAN))
    man["configs"].append({"name": "dummy-lm", "source": "none",
                           "file": "perfbench/configs/dummy-lm.json",
                           "reduced": ["num_hidden_layers"], "why": "test"})
    man["workloads"].append({"name": "dummy-lm.train-dummy",
                             "config": "dummy-lm", "traffic": "train-dummy",
                             "chips": 1, "why": "test"})
    man["end_to_end"][0]["workloads"].append("dummy-lm.train-dummy")
    man["end_to_end"].insert(0, {
        "name": "dummy.blocks", "unit": "count", "better": "higher",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["dummy-lm.train-dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    assert manifest.check(man, str(tmp_path)) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "dummy-lm.train-dummy", "--seed", "3", "--seconds", "1", "--trace",
         "0", "--rehearse"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    assert line["would_report"] == ["dummy.blocks", "setup_s",
                                    "train_items_per_s_per_chip"]
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


def test_an_open_loop_serving_cell_is_a_data_file(tmp_path):
    """PERF.md's open-loop and shared-prefix cells need no code: one
    traffic file for the general generator, appended entries, and it runs."""
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    pb = tmp_path / "perfbench"
    tr = json.loads((pb / "traffic/serve-closed32.json").read_text())
    for key in ("length_order", "ramp_output_tokens", "clients"):
        tr.pop(key)
        tr["tiny"].pop(key, None)
    tr.update(loop="open", rate_per_s=2.0)
    tr["tiny"].update(rate_per_s=150.0, ramp_seconds=0.3,
                      shared_prefix_tokens=2)
    (pb / "traffic/serve-open-test.json").write_text(json.dumps(tr))
    man = json.loads(json.dumps(MAN))
    cell = "pythia-410m.serve-open-test"
    man["workloads"].append({"name": cell, "config": "pythia-410m",
                             "traffic": "serve-open-test", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "pythia-410m.serve-closed32" in m.get("workloads", []):
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    assert manifest.check(man, str(tmp_path)) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed",
         "2", "--seconds", "1", "--trace", "0", "--rehearse"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] > 20
    assert line["would_report"] == ["serve_tok_per_s", "setup_s",
                                    "token_gap_p90_s", "ttft_p50_s"]
