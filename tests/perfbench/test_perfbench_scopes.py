"""Device time by the program's own scopes: the reader on a hand-made
trace (two programs that reuse an instruction name, two buckets of one jit
told apart by their call span), on the small trace recorded on the chip,
and the metrics that read it: which cells list them, and that a run
without a TPU gives none of them a number."""
import gzip
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
from perfbench.harness import manifest, program_spans, scopes  # noqa: E402

FIXTURE = os.path.join(ROOT, "perfbench", "fixtures", "scopes_small.json.gz")
MAN = manifest.load()
# which cells list each metric, by what a cell IS and not by its name:
# those that train, those that serve, those that serve held experts
TRAIN, SERVE, EXPERTS = "train", "serve", "experts"
NEW = {
    "train_step.forward_device_s_per_step": TRAIN,
    "train_step.backward_device_s_per_step": TRAIN,
    "train_step.optimizer_device_s_per_step": TRAIN,
    "gossip.communicate_device_s_per_step": TRAIN,
    "device.scoped_share": TRAIN,
    "engine.decode_attend_device_s_per_call": SERVE,
    "engine.decode_cache_write_device_s_per_call": SERVE,
    "engine.decode_ffn_device_s_per_call": SERVE,
    "engine.decode_readout_device_s_per_call": SERVE,
    "engine.prefill_attend_device_s_per_ktok": SERVE,
    "engine.prefill_ffn_device_s_per_ktok": SERVE,
    "moe.prefill_experts_device_s_per_ktok": EXPERTS,
    "device.serve_scoped_share": SERVE,
}


def cells_that(man, what, root=ROOT):
    """The cells of ``man`` that train (they report
    ``train_items_per_s_per_chip``), that serve (``serve_tok_per_s``), or
    that serve a family which holds experts (its adapter says how many:
    ``held_experts``)."""
    if what == TRAIN:
        return _shape.cells_of(man, "train_items_per_s_per_chip")
    serve = _shape.cells_of(man, "serve_tok_per_s")
    if what == SERVE:
        return serve
    return [c for c in _shape.holding(man, "held_experts", root)
            if c in serve]


def listing(man, name, root=ROOT):
    """Complaints about which cells list the scope metric ``name``: those
    its kind names, all of them and no other."""
    want = cells_that(man, NEW[name], root)
    got = _shape.cells_of(man, name)
    return [] if got == want else [f"{name} lists {got}, not {want}"]


def manifest_rule(man, root=ROOT):
    """PR 38's thirteen metrics: each listed for the cells of its kind,
    and in the manifest in the order their PR gave them."""
    bad = [c for name in NEW for c in listing(man, name, root)]
    names = [m["name"] for m in man["per_layer"]]
    if [n for n in names if n in NEW] != list(NEW):
        bad.append("the scope metrics stand in another order")
    return bad

TABLES = {
    "decode S=4": {"module": "jit__decode_body", "ops": {
        "fusion.1": ("cache.read", ""), "fusion.2": ("ffn", ""),
        "while.1": ("", ""), "copy.9": ("", "")},
        "mixed": {"fusion.2": ("attn.project", "ffn")}, "inherited": {}},
    "prefill Tpad=8": {"module": "jit__prefill_body", "ops": {
        "fusion.1": ("attn", ""), "fusion.2": ("moe.experts", "")},
        "mixed": {}, "inherited": {"fusion.2": "operands"}},
    "prefill Tpad=16": {"module": "jit__prefill_body", "ops": {
        "fusion.1": ("ffn", ""), "fusion.2": ("attn.project", "")},
        "mixed": {}, "inherited": {}},
}


def hand_made(run_ids=True, shift=0):
    """One chip: a decode call, a prefill of each bucket (both module
    events named alike), a program nobody registered.  Device stamps run
    ``shift`` ns ahead of the host's."""
    rid = (lambda n: {"run_id": n}) if run_ids else (lambda n: {})
    d = lambda t: t - shift
    ops = [["while.1", d(1000), 400], ["fusion.1", d(1000), 100],
           ["fusion.2", d(1150), 200], ["copy.9", d(1380), 20],
           ["fusion.1", d(2000), 300], ["fusion.2", d(2300), 100],
           ["fusion.1", d(3000), 500], ["fusion.2", d(3500), 50],
           ["fusion.77", d(3560), 10],
           ["fusion.1", d(4000), 40]]
    modules = [["jit__decode_body(11)", d(1000), 400, rid(1)],
               ["jit__prefill_body(22)", d(2000), 400, rid(2)],
               ["jit__prefill_body(33)", d(3000), 570, rid(3)],
               ["jit_other(44)", d(4000), 40, rid(4)]]
    host = [["pb:window", 0, 10000, {}],
            ["bf:engine.decode_call", 900, 600, {"S": 4}],
            ["bf:engine.prefill_call", 1900, 600,
             {"Tpad": 8, "tokens": 5}],
            ["bf:engine.prefill_call", 2900, 800,
             {"Tpad": 16, "tokens": 12}]]
    if run_ids:
        host += [["DoEnqueueProgram", 950, 5, {"run_id": 1}],
                 ["DoEnqueueProgram", 1950, 5, {"run_id": 2}],
                 ["DoEnqueueProgram", 2950, 5, {"run_id": 3}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


@pytest.mark.parametrize("run_ids,shift", [(True, 0), (False, 0),
                                           (False, 250), (True, 250)])
def test_an_op_is_looked_up_in_the_table_of_the_program_it_ran_in(run_ids,
                                                                  shift):
    """``fusion.1`` is the cache read of the decode program, the attention
    of one prefill bucket and the FFN of the other; the buckets share a
    module name and are told apart by their call span's ``Tpad``: through
    the enqueue event's ``run_id``, or by the time the module event
    starts, once moved onto the host's clock."""
    ana = scopes.Analysis(hand_made(run_ids, shift), TABLES, shift)
    ns = lambda x: pytest.approx(x * 1e-9)
    assert ana.by[("decode S=4", "cache.read", "")] == ns(100)
    assert ana.by[("decode S=4", "ffn", "")] == ns(200)
    # the loop's own time is what its body's ops leave of it
    assert ana.by[("decode S=4", "", "")] == ns(400 - 100 - 200 - 20 + 20)
    assert ana.by[("prefill Tpad=8", "attn", "")] == ns(300)
    assert ana.by[("prefill Tpad=8", "moe.experts", "")] == ns(100)
    assert ana.by[("prefill Tpad=16", "ffn", "")] == ns(500)
    assert ana.by[("prefill Tpad=16", "attn.project", "")] == ns(50)
    # an instruction the table does not know, a program nobody registered
    assert ana.no_row == {"prefill Tpad=16": ns(10)}
    assert ana.by[(None, "", "")] == ns(40)
    assert ana.calls == {"decode S=4": 1, "prefill Tpad=8": 1,
                         "prefill Tpad=16": 1, None: 1}
    assert ana.tokens == {"prefill Tpad=8": 5, "prefill Tpad=16": 12}
    assert ana.mixed_s == {"decode S=4": ns(200)}
    assert ana.inherited_s == {"prefill Tpad=8": ns(100)}
    # readings
    assert ana.per_call("decode ", scopes.ATTENTION) == ns(100)
    assert ana.per_call("decode ", ("cache.write",)) == 0.0
    assert ana.per_ktok("prefill ", scopes.FFN) == ns((100 + 500) / 0.017)
    assert ana.per_ktok("prefill ", ("moe.experts",)) == ns(100 / 0.017)
    assert ana.per_call("chunk ", scopes.FFN) is None
    total = 400 + 400 + 560 + 40
    assert ana.scoped_share() == pytest.approx(
        (100 + 200 + 300 + 100 + 500 + 50) / total)
    # each program's scopes and its rest sum to its op self time
    for key, own in (("decode S=4", 400), ("prefill Tpad=8", 400),
                     ("prefill Tpad=16", 560)):
        assert sum(t for (k, _, _), t in ana.by.items() if k == key) \
            == ns(own)
    text = "\n".join(ana.report())
    assert "prefill Tpad=16" in text and "fusion.77" in text
    assert "no table" in text


def test_time_outside_the_window_and_other_chips_are_averaged():
    doc = hand_made()
    doc["planes"][1]["lines"][0]["events"][0] = ["pb:window", 1100, 2000, {}]
    second = json.loads(json.dumps(doc["planes"][0]))
    second["name"] = "/device:TPU:1"
    doc["planes"].insert(1, second)
    ana = scopes.Analysis(doc, TABLES)
    assert ana.n_devices == 2
    # the decode program's first 100 ns lie before the window
    assert ("decode S=4", "cache.read", "") not in ana.by
    assert ana.by[("decode S=4", "ffn", "")] == pytest.approx(200e-9)
    assert ana.by[("prefill Tpad=16", "ffn", "")] == pytest.approx(100e-9)
    assert ana.calls["decode S=4"] == 1


def test_nothing_to_read_reads_none():
    for doc, tables in ((hand_made(), None), (hand_made(), {}),
                        ({"planes": []}, TABLES),
                        ({"planes": hand_made()["planes"][1:]}, TABLES)):
        ana = scopes.Analysis(doc, tables)
        assert ana.by == {} and ana.scoped_share() is None
        assert ana.per_call("decode ", scopes.FFN) is None
        assert ana.seconds("train_step", None, "fwd") is None
        assert ana.report() == [
            "device scopes: no device operation met a scope table"]


def test_the_recorded_chip_trace_splits_by_scope():
    """A tiny dense engine traced on a v5e
    (perfbench/tools/record_scopes_fixture.py): real event names, real
    tables.  Every module event finds its program, the two prefill
    buckets apart; every op event finds a row; the scopes of the engine's
    vocabulary all took time."""
    with gzip.open(FIXTURE, "rt") as f:
        doc = json.load(f)
    rec, tables = doc["recorded"], doc["tables"]
    assert rec["device_kind"].startswith("TPU v5")
    shift = program_spans.Analysis(doc).shift_ns
    assert shift == rec["shift_ns"] > 0
    ana = scopes.Analysis(doc, tables, shift)
    assert ana.calls == {"decode S=8": rec["decode_calls"],
                         **rec["prefill_calls"]}
    assert ana.tokens == rec["prefill_tokens"]
    assert ana.no_row == {} and (None, "", "") not in ana.by
    took = {(k.split()[0], s) for (k, s, _), t in ana.by.items() if t > 0}
    assert {("decode", s) for s in ("attn.project", "cache.read",
                                    "cache.write", "ffn", "readout")} <= took
    assert {("prefill", s) for s in ("attn.project", "attn", "cache.write",
                                     "ffn", "readout")} <= took
    # a program of 0.1 ms: the copies of its staged integers and the
    # loop's own time are a third of it (0.98 at a cell's sizes, PERF.md)
    assert 0.6 < ana.scoped_share() < 1.0
    # the same reading with the clock shift alone (no run ids): the call
    # spans last far longer than the shift's uncertainty
    for plane in doc["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if e[0] != scopes.ENQUEUE]
    again = scopes.Analysis(doc, tables, shift)
    assert again.calls == ana.calls and again.by == ana.by


@pytest.mark.parametrize("name", list(NEW))
def test_new_metrics_are_listed_for_their_cells_and_read_none_off_the_tpu(
        name):
    entry, = [m for m in MAN["per_layer"] if m["name"] == name]
    assert entry["source"] == "device_trace" and "bound" not in entry
    assert listing(MAN, name) == [] and cells_that(MAN, NEW[name])
    read = manifest.load_module("metrics", name).read
    run = {"device": {"platform": "cpu"}, "facts": {"traced_steps": 18},
           "workload": cells_that(MAN, NEW[name])[0],
           "out_dir": "/nonexistent"}
    assert read(run) is None
    # on the chip, with a program that has no registry or a trace that met
    # no table: nothing, and no error
    run = {"device": {"platform": "tpu"}, "facts": {"traced_steps": 18},
           "device_scopes": scopes.Analysis({"planes": []}, None)}
    assert read(run) is None


def test_new_metrics_keep_their_order_in_the_manifest():
    """In the order their PR gave them, wherever a later PR's metrics
    stand; and the rule sees two of them changing places."""
    assert manifest_rule(MAN) == []
    per_layer = list(MAN["per_layer"])
    a, b = (i for i, m in enumerate(per_layer) if m["name"] in list(NEW)[:2])
    per_layer[a], per_layer[b] = per_layer[b], per_layer[a]
    assert manifest_rule({**MAN, "per_layer": per_layer}) == [
        "the scope metrics stand in another order"]


def test_a_traced_cpu_rehearsal_reports_none_of_them(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
    p = subprocess.run(
        [sys.executable] + MAN["command"][1:] + [
            "--workload", "a.x-k1.serve-closed128-p2048", "--seed", "7",
            "--seconds", "1", "--trace", "1", "--rehearse", "--out-dir",
            str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["metrics"] == {} and not set(line["would_report"]) & set(NEW)
    assert "own scopes" not in p.stdout
