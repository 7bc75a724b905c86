"""``train_step.readout_device_s_per_step`` (PR 51): where its entry stands
in a manifest of any size, and its reader on a hand-made trace of a train
step: everything under the step program's ``readout`` scope, forward and
backward, a step; nothing, and no error, where there is nothing to read
(off the TPU, a program without a scope table, a cell that trains no
``train_step`` program)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import _shape  # noqa: E402
from perfbench.harness import manifest, scopes  # noqa: E402

NAME = "train_step.readout_device_s_per_step"
MOVES = "train_items_per_s_per_chip"
FAMILY = "composed_lm"
MAN = manifest.load()


def lm_training_cells(man, root=ROOT):
    """The cells that train the composed LM: they report the training
    rate and their configuration's family is ``composed_lm`` (the one step
    whose loss is a read-out over a vocabulary)."""
    return [c for c in _shape.cells_of(man, MOVES)
            if manifest.resolve_cell(man, c, root)["config"]["family"]
            == FAMILY]


def manifest_rule(man, root=ROOT):
    """PR 51's metric: a device-trace reading of the kernels' layer that
    moves the training rate, listed for the cells that train the composed
    LM, all of them and no other, behind the step's forward and backward
    readings whose pattern it follows."""
    entry = [m for m in man["per_layer"] if m["name"] == NAME]
    if len(entry) != 1:
        return [f"{len(entry)} metrics named {NAME}"]
    want = {"unit": "s", "better": "lower", "source": "device_trace",
            "layer": "kernels and device", "moves": MOVES}
    got = {k: entry[0].get(k) for k in want}
    bad = [] if got == want else [f"{NAME} is {got}, not {want}"]
    cells, listed = lm_training_cells(man, root), _shape.cells_of(man, NAME)
    if listed != cells:
        bad.append(f"{NAME} lists {listed}, not {cells}")
    names = [m["name"] for m in man["per_layer"]]
    order = ["train_step.forward_device_s_per_step",
             "train_step.backward_device_s_per_step", NAME]
    if sorted(order, key=names.index) != order:
        bad.append(f"{order} stand in another order in the manifest")
    return bad


def test_the_entry_stands_as_its_pr_wrote_it():
    assert manifest_rule(MAN) == []
    assert len(lm_training_cells(MAN)) >= 2
    # the rule sees a cell of another family in the list, and one left out
    per_layer = [dict(m, workloads=m["workloads"] + ["resnet50.train-b256"])
                 if m["name"] == NAME else m for m in MAN["per_layer"]]
    assert len(manifest_rule({**MAN, "per_layer": per_layer})) == 1
    per_layer = [dict(m, workloads=m["workloads"][:1])
                 if m["name"] == NAME else m for m in MAN["per_layer"]]
    assert len(manifest_rule({**MAN, "per_layer": per_layer})) == 1


TABLES = {"train_step": {"module": "jit_train_step", "ops": {
    "fusion.1": ("readout", "fwd"), "custom-call.1": ("readout", "fwd"),
    "custom-call.2": ("readout", "bwd"), "fusion.2": ("attn", "bwd"),
    "fusion.3": ("ADAPT", ""), "scatter.1": ("readout", "bwd")},
    "mixed": {}, "inherited": {}}}


def traced(steps=2):
    """One chip, ``steps`` calls of the step program: per step 30 + 110 ns
    of read-out forward, 250 + 40 backward, 500 of other work."""
    ops, modules = [], []
    for i in range(steps):
        t = 1000 + 2000 * i
        ops += [["fusion.1", t, 30], ["custom-call.1", t + 30, 110],
                ["fusion.2", t + 200, 400], ["custom-call.2", t + 600, 250],
                ["scatter.1", t + 850, 40], ["fusion.3", t + 900, 100]]
        modules.append(["jit_train_step(7)", t, 1000, {"run_id": i + 1}])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["pb:window", 0, 1000 + 2000 * steps, {}]]}]}]}


def test_the_reader_sums_the_readout_scope_in_both_directions_a_step():
    read = manifest.load_module("metrics", NAME).read
    run = {"device": {"platform": "tpu"}, "facts": {"traced_steps": 2},
           "device_scopes": scopes.Analysis(traced(2), TABLES)}
    assert read(run) == pytest.approx((30 + 110 + 250 + 40) * 1e-9)
    # a part of the whole step, and of its forward and backward readings
    whole = manifest.load_module(
        "metrics", "train_step.forward_device_s_per_step").read(run)
    assert whole == pytest.approx(140e-9)


@pytest.mark.parametrize("run", [
    {"device": {"platform": "cpu"}, "facts": {"traced_steps": 18},
     "workload": "pythia-410m.train-seq2048", "out_dir": "/nonexistent"},
    {"device": {"platform": "tpu"}, "facts": {"traced_steps": 18},
     "device_scopes": scopes.Analysis({"planes": []}, None)},
    {"device": {"platform": "tpu"}, "facts": {},
     "device_scopes": scopes.Analysis(traced(2), TABLES)},
    {"device": {"platform": "tpu"}, "facts": {"traced_steps": 2},
     "device_scopes": scopes.Analysis(traced(2), {
         "decode S=4": dict(TABLES["train_step"], module="jit_train_step")})},
], ids=["off the tpu", "no table", "no traced steps", "no train_step"])
def test_nothing_to_read_reads_none(run):
    assert manifest.load_module("metrics", NAME).read(run) is None


def test_a_step_with_no_readout_scope_reads_zero():
    """A parent whose step holds no instruction under the scope (none
    does: the scope is older than the reader) would read 0, not None."""
    tables = {"train_step": dict(TABLES["train_step"], ops={
        k: ("ffn", d) for k, (_, d) in TABLES["train_step"]["ops"].items()})}
    run = {"device": {"platform": "tpu"}, "facts": {"traced_steps": 2},
           "device_scopes": scopes.Analysis(traced(2), tables)}
    assert manifest.load_module("metrics", NAME).read(run) == 0.0
