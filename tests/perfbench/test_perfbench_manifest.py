"""BENCHMARK.json against the driver's rules that files alone can show, and
the harness driven by data: a new cell, configuration and metric are new
files and appended entries, nothing else."""
import json
import os
import shutil
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
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
# the cells the benchmark began with (PR 32), each with what it reports
# end to end; later cells are their own test files' to state
TRAINED = ("train_items_per_s_per_chip", "setup_s")
FIRST_CELLS = {
    "resnet50.train-b256": ("resnet50", "train-b256", 1, TRAINED),
    "pythia-410m.train-seq2048": ("pythia-410m", "train-seq2048", 1, TRAINED),
    "pythia-410m.gossip4-seq2048": ("pythia-410m", "gossip4-seq2048", 4,
                                    TRAINED),
    "pythia-410m.serve-closed32": (
        "pythia-410m", "serve-closed32", 1,
        ("serve_tok_per_s", "ttft_p50_s", "token_gap_p90_s", "setup_s")),
}


def manifest_rule(man, root=ROOT):
    """The first four cells and two configurations, first and in their
    order, whatever has been appended behind them."""
    bad = []
    if [w["name"] for w in man["workloads"]][:4] != list(FIRST_CELLS):
        bad.append("the first four cells are not the first four")
    if [c["name"] for c in man["configs"]][:2] != ["resnet50", "pythia-410m"]:
        bad.append("the first two configurations are not the first two")
    for cell, (config, traffic, chips, metrics) in FIRST_CELLS.items():
        bad += _shape.written_for(man, cell, config=config, traffic=traffic,
                                  chips=chips, metrics=metrics)
    return bad


@pytest.mark.parametrize("rule", _shape.RULES + (manifest_rule,),
                         ids=lambda r: r.__name__)
def test_the_manifest_keeps_the_rule(rule):
    assert rule(MAN, ROOT) == []


def broken(**edits):
    """The manifest with one thing wrong, by the rule that has to see it."""
    man = json.loads(json.dumps(MAN))
    by_name = {m["name"]: m for m in man["end_to_end"] + man["per_layer"]}
    if "list" in edits:
        metric, cell = edits["list"]
        by_name[metric]["workloads"].append(cell)
    if "bound" in edits:
        by_name[edits["bound"]]["bound"] = 0.09
    if "swap" in edits:
        a, b = (man["end_to_end"].index(by_name[n]) for n in edits["swap"])
        man["end_to_end"][a], man["end_to_end"][b] = \
            man["end_to_end"][b], man["end_to_end"][a]
    if "four_chips" in edits:
        [w for w in man["workloads"]
         if w["name"] == edits["four_chips"]][0]["chips"] = 4
    if "cells" in edits:
        man["workloads"] += [dict(man["workloads"][0], name=f"more.{i}")
                             for i in range(edits["cells"] - len(CELLS))]
    if "seconds" in edits:
        man["run_seconds"] = edits["seconds"]
    if "why" in edits:
        man["workloads"][0]["why"] = "w" * edits["why"]
    return man


@pytest.mark.parametrize("edits,rule,needle", [
    # a cell under a reader that would find nothing to read for its family
    (dict(list=("engine.decode_hbm_roofline_share",
                "pythia-410m.serve-closed32")), "hooks_held",
     "has no ['decode_floor_bytes']"),
    (dict(list=("moe.tokens_per_held_expert", "pythia-410m.serve-closed32")),
     "hooks_held", "held_experts"),
    (dict(list=("attn.decode_positions_read_per_lane",
                "a.x-k1.serve-closed128-p2048")), "hooks_held", "layers_of"),
    # a per-layer metric where what it should move is not reported
    (dict(list=("engine.decode_collect_s_p50",
                "a.x-k1.serve-closed128-p2048")), "moves_inside",
     "token_gap_p90_s is not reported"),
    (dict(list=("device.mfu", "pythia-410m.serve-closed32")),
     "moves_inside", "train_items_per_s_per_chip is not reported"),
    (dict(bound="serve_tok_per_s"), "end_to_end", "0.09"),
    (dict(swap=("ttft_p50_s", "token_gap_p90_s")), "end_to_end", "not"),
    (dict(four_chips="resnet50.train-b256"), "own_check", "2 four-chip"),
    (dict(cells=25), "cell_counts", "25 cells"),
    (dict(seconds=10), "envelope", "run_seconds"),
    (dict(why=201), "envelope", "why of 201"),
], ids=["dense_under_the_latent_floor", "dense_under_held_experts",
        "latent_under_layers_of", "collect_where_no_gap_is_reported",
        "mfu_on_a_serving_cell", "a_bound_changed", "two_metrics_swapped",
        "a_second_four_chip_cell", "a_twenty_fifth_cell",
        "run_seconds", "a_long_why"])
def test_a_rule_sees_what_it_guards(edits, rule, needle):
    said = _shape.complaints(broken(**edits))
    assert any(c.startswith(rule + ":") and needle in c for c in said), said


def test_what_a_pr_wrote_is_held_as_written_and_no_tighter():
    """``written_for``: the cell IN its metrics' lists and the metrics in
    their relative order; a second cell in a list, or another metric
    between two of them, is nobody's fault."""
    cell, (config, traffic, chips, metrics) = list(FIRST_CELLS.items())[3]
    rule = lambda man, **kw: _shape.written_for(man, cell, **{
        "config": config, "traffic": traffic, "chips": chips,
        "metrics": metrics, **kw})
    assert rule(MAN) == []
    man = json.loads(json.dumps(MAN))
    man["end_to_end"].insert(2, dict(man["end_to_end"][1], name="another"))
    man["end_to_end"][2]["workloads"].append("a.further-cell")
    assert rule(man) == []
    assert "another order" in rule(MAN, metrics=metrics[::-1])[0]
    assert "not" in rule(MAN, chips=4)[0]
    assert rule(MAN, metrics=("no.such_metric",)) == ["no metric "
                                                     "no.such_metric"]
    man["end_to_end"][1]["workloads"].remove(cell)
    assert rule(man) == [f"serve_tok_per_s does not list {cell}"]
    man["workloads"] = [w for w in man["workloads"] if w["name"] != cell]
    assert rule(man) == [f"0 cells named {cell}"]


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
    man["end_to_end"].append({
        "name": "dummy.blocks", "unit": "count", "better": "higher",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["dummy-lm.train-dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    assert _shape.complaints(man, str(tmp_path)) == []
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


LIKE = "a.x-k1.serve-closed128-p2048"
PROBE_READER = '''"""Routed experts the run's chip holds, by its family's word."""
from perfbench.harness import manifest


def read(run):
    family = manifest.load_module("families", run["config"]["family"])
    if not hasattr(family, "held_experts"):
        return None
    return family.held_experts(run["config"])
'''


def append_a_family_a_cell_and_a_metric(root, man, tag="probe"):
    """Under ``root`` (a copy of the repo's ``perfbench``): a configuration
    of a family of its own (the latent family's functions under another
    name, with its own reference), a serving cell listed under every
    metric ``LIKE`` lists, and a per-layer metric whose reader asks the
    family for a hook.  New files only; returns the appended manifest,
    the cell's name and the metric's."""
    pb = root / "perfbench"
    config, family, traffic = f"{tag}-moe", f"{tag}_moe", f"serve-closed-{tag}"
    cell, metric = f"{config}.{traffic}", f"{tag}.held_experts"
    cfg = json.loads((pb / "configs/a.x-k1.json").read_text())
    cfg["family"] = family
    (pb / f"configs/{config}.json").write_text(json.dumps(cfg))
    for kind in ("families", "reference"):
        (pb / kind / f"{family}.py").write_text(
            f'"""The latent family under a name of its own."""\n'
            f"from perfbench.{kind}.latent_moe import *  # noqa: F401,F403\n")
    shutil.copy(pb / "traffic/serve-closed128-p2048.json",
                pb / f"traffic/{traffic}.json")
    (pb / f"metrics/{metric}.py").write_text(PROBE_READER)
    man = json.loads(json.dumps(man))
    man["configs"].append({
        "name": config, "source": "none",
        "file": f"perfbench/configs/{config}.json",
        "reduced": ["num_hidden_layers"], "why": "a family nobody has seen"})
    man["workloads"].append({"name": cell, "config": config,
                             "traffic": traffic, "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(cell)
    man["per_layer"].append({
        "name": metric, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_tok_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return man, cell, metric


def test_an_appended_family_cell_and_metric_break_no_rule_of_any_file(
        tmp_path):
    """The twin of the test above for what the next PR appends: a
    configuration of a NEW family, a serving cell under every metric a
    held-experts cell lists, a per-layer metric that asks the family for a
    hook.  Every rule of a manifest and every test file's own
    cell-and-metric rule hold for the appended manifest as they hold for
    the repo's, the new cell rehearses with its readers, and no file that
    was there is edited."""
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "perfbench").rglob("*")
              if p.is_file()}
    man, cell, metric = append_a_family_a_cell_and_a_metric(tmp_path, MAN)
    root = str(tmp_path)
    assert _shape.complaints(man, root) == []
    rules = _shape.rules_of_the_files()
    # this file's, and one for each cell a model_config PR wrote, and the
    # scope metrics'
    assert {"test_perfbench_manifest.py", "test_perfbench_latent_moe.py",
            "test_perfbench_hybrid_moe.py",
            "test_perfbench_scopes.py"} <= set(rules)
    for name, rule in rules.items():
        assert rule(MAN, ROOT) == [], name
        assert rule(man, root) == [], name
    # the rules see the new entries: they are not vacuous on them
    assert cell in _shape.cells_of(man, "serve_tok_per_s")
    assert cell in _shape.holding(man, "held_experts", root)
    assert _shape.family_hooks(metric, root) == ["held_experts"]
    late = json.loads(json.dumps(man))
    late["per_layer"][-1]["workloads"].append("pythia-410m.serve-closed32")
    assert any(f"{metric} lists pythia-410m.serve-closed32" in c
               for c in _shape.complaints(late, root))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed",
         "3", "--seconds", "1", "--trace", "1", "--rehearse", "--out-dir",
         str(tmp_path / "out")], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    assert {metric, "moe.tokens_per_held_expert",
            "moe.pad_share"} <= set(line["would_report"])
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
