"""The program's bf: stage spans read back from a trace
(perfbench/harness/program_spans.py) on a small hand-made plain structure,
the per-layer metrics that read them, and both kinds of cell rehearsed with
``--trace 1``: every such metric finds something to read."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import manifest, program_spans  # noqa: E402
from test_perfbench_cells import run_cell  # noqa: E402

MAN = manifest.load()
NS = 1e-9
SERVE_METRICS = {
    "engine.decode_stage_in_s_p50", "engine.decode_dispatch_s_p50",
    "engine.decode_collect_s_p50", "engine.prefill_pad_share",
    "scheduler.step_host_s_p50", "scheduler.queue_wait_s_p50",
    "scheduler.decode_bucket_fill", "device.serve_idle_named_share"}
TRAIN_METRICS = {"train_step.wrapper_host_s_per_call"}


SKEW = 400      # the device's stamps run this many ns ahead of the host's


def hand_made(window=True, spans=True, runtime=True):
    """Three scheduler steps; the window cuts the first and the last.  On
    the host's clock the device is busy in [0, 1500), [3350, 3850), [5100,
    7900) and after the window, so it idles 6200 ns of the window's 10000;
    its events are stamped SKEW earlier.  The runtime's own events hold
    the skew between SKEW - 10 (the prefill's enqueue, 10 ns before its
    program starts) and SKEW + 10 (the decode program's completion)."""
    host = [
        ["bf:serve.step", 500, 2500, {}],
        ["bf:serve.step", 3000, 6000, {}],
        ["bf:serve.admit", 3100, 1000, {}],
        ["bf:serve.prefill", 3200, 800, {"prompt_len": 48, "waited_us": 40}],
        ["bf:engine.prefill_call", 3300, 600,
         {"Tpad": 64, "tokens": 48, "replica": 0}],
        ["bf:serve.pack", 4200, 300, {"lanes": 3, "S": 4}],
        ["bf:engine.decode_call", 4600, 3400, {"S": 4}],
        ["bf:engine.stage_in", 4600, 300, {}],
        ["bf:engine.dispatch", 4900, 100, {}],
        ["bf:engine.collect", 5000, 2990, {}],
        ["bf:serve.deliver", 8100, 800, {}],
        ["bf:serve.step", 9500, 2500, {}],
    ] if spans else [["pb:decode_call", 4600, 3400, {}]]
    if window:
        host.append(["pb:window", 1000, 10000, {}])
    programs = [(1, 0, 1500), (2, 3350, 500), (3, 5100, 2800),
                (4, 11000, 1000)]
    done = lambda run, at: ["CompleteCallbacks", at, 5,  # noqa: E731
                            {"run_id": run, "device_ordinal": 0}]
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host},
            {"name": "other-thread", "events": [
                ["bf:engine.decode_call", 4000, 100, {"S": 1}]]},
            {"name": "runtime", "events": [
                ["DoEnqueueProgram", 3340, 5, {}],
                ["DoEnqueueProgram", 5080, 5, {}],
                # outside every engine call: says nothing about the skew
                ["DoEnqueueProgram", 10000, 5, {}],
                done(1, 1700), done(2, 3880), done(3, 7910), done(4, 12300),
            ] if runtime else []}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 0 - SKEW, 1500], ["fusion.2", 3350 - SKEW, 500],
                ["while", 5100 - SKEW, 2800], ["fusion.3", 5200 - SKEW, 100],
                ["fusion.4", 11000 - SKEW, 1000]]},
            {"name": "XLA Modules", "events": [
                [f"jit_p{run}", at - SKEW, dur, {"run_id": run}]
                for run, at, dur in programs]},
            {"name": "Steps", "events": [["0", 0, 12000]]}]}]}


def test_nesting_durations_and_self_time():
    ana = program_spans.Analysis(hand_made())
    step, = ana.named("bf:serve.step")            # the one whole step
    assert [c.name for c in step.children] == [
        "bf:serve.admit", "bf:serve.pack", "bf:engine.decode_call",
        "bf:serve.deliver"]
    call, = ana.named("bf:engine.decode_call")    # the other thread's is not
    assert call.parent is step and call.attrs == {"S": 4}
    assert call.path == "serve.step/engine.decode_call"
    assert ana.named("bf:engine.stage_in", "bf:engine.decode_call")
    assert not ana.named("bf:engine.stage_in", "bf:engine.prefill_call")
    assert ana.median_s("bf:engine.collect", "bf:engine.decode_call") \
        == pytest.approx(2990 * NS)
    assert ana.uncovered_s("bf:engine.decode_call") == pytest.approx(10 * NS)
    assert step.self_time == 6000 - 1000 - 300 - 3400 - 800
    # the step outside the engine's calls: 6000 - 600 (prefill) - 3400
    assert ana.outside_s("bf:serve.step", "bf:engine.") \
        == pytest.approx(2000 * NS)


def test_idle_time_goes_to_the_innermost_span():
    ana = program_spans.Analysis(hand_made())
    assert ana.window_s == pytest.approx(10000 * NS)
    assert ana.idle_s == pytest.approx(6200 * NS)
    want = {
        "serve.step": 3500,          # two clipped steps + the whole one's own
        "serve.step/serve.admit": 200,
        "serve.step/serve.admit/serve.prefill": 200,
        "serve.step/serve.admit/serve.prefill/engine.prefill_call": 100,
        "serve.step/serve.pack": 300,
        "serve.step/engine.decode_call": 10,
        "serve.step/engine.decode_call/engine.stage_in": 300,
        "serve.step/engine.decode_call/engine.dispatch": 100,
        "serve.step/engine.decode_call/engine.collect": 190,
        "serve.step/serve.deliver": 800,
    }
    assert {k: round(v / NS) for k, v in ana.idle_by_path.items()} == want
    # 500 ns lie outside every span; leaves hold 4790 of the 6200
    assert sum(want.values()) == 6200 - 500
    assert ana.idle_named_share() == pytest.approx(4790 / 6200)
    text = "\n".join(ana.report())
    assert "(outside every bf: span)" in text and "ms/step" in text


def test_device_stamps_are_shifted_onto_the_hosts_clock():
    """The idle attribution above is right only because the skew between
    the two clocks was estimated from the trace and taken out."""
    ana = program_spans.Analysis(hand_made())
    assert ana.offset_ns == (SKEW - 10, SKEW + 10)
    assert "were shifted by" in "\n".join(ana.report())
    # a trace without the runtime's events cannot say: the totals are read
    # as stamped, and idle time is not split by span
    blind = program_spans.Analysis(hand_made(runtime=False))
    assert blind.offset_ns is None
    assert blind.idle_s == pytest.approx(6200 * NS)
    assert blind.idle_by_path != ana.idle_by_path
    text = "\n".join(blind.report())
    assert "not split by span" in text and "ms/step" not in text


def test_spans_are_clipped_to_the_window():
    ana = program_spans.Analysis(hand_made())
    cut = [s for s in ana.spans if not s.whole]
    assert [(s.start, s.end) for s in cut] == [(1000, 3000), (9500, 11000)]
    assert ana.attr_sum("bf:serve.pack", "lanes") == 3    # whole spans only
    # without the benchmark's window span: first to last stage span, the
    # device's work before and after it left out
    ana = program_spans.Analysis(hand_made(window=False))
    assert ana.window_s == pytest.approx(11500 * NS)
    assert len(ana.named("bf:serve.step")) == 3
    assert ana.idle_s == pytest.approx((11500 - 1000 - 500 - 2800 - 1000) * NS)


def read(metric, ana):
    return manifest.load_module("metrics", metric).read({"program_spans": ana})


def test_metrics_read_the_spans_and_their_counters():
    ana = program_spans.Analysis(hand_made())
    got = {m: read(m, ana) for m in SERVE_METRICS | TRAIN_METRICS}
    assert got == {
        "engine.decode_stage_in_s_p50": pytest.approx(300 * NS),
        "engine.decode_dispatch_s_p50": pytest.approx(100 * NS),
        "engine.decode_collect_s_p50": pytest.approx(2990 * NS),
        "engine.prefill_pad_share": pytest.approx(1 - 48 / 64),
        "scheduler.step_host_s_p50": pytest.approx(2000 * NS),
        "scheduler.queue_wait_s_p50": pytest.approx(40e-6),
        "scheduler.decode_bucket_fill": pytest.approx(3 / 4),
        "device.serve_idle_named_share": pytest.approx(4790 / 6200),
        "train_step.wrapper_host_s_per_call": None,    # no train span here
    }
    train = program_spans.Analysis({"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ["bf:train.train_step", 0, 1000, {"step": 1, "fused_k": 1}],
            ["bf:train.dispatch", 100, 600, {}],
            ["bf:train.train_step", 2000, 1400, {"step": 2, "fused_k": 1}],
            ["bf:train.dispatch", 2100, 800, {}]]}]}]})
    assert read("train_step.wrapper_host_s_per_call", train) \
        == pytest.approx(500 * NS)
    # no device plane (a CPU trace): no idle reading at all
    assert train.idle_s is None and train.idle_named_share() is None
    assert "no device in the trace" in train.report()[0]


def test_a_program_without_stage_spans_gives_nothing_and_does_not_raise():
    """The parent of the PR that added the spans is traced with these
    readers too: each returns None and the line leaves the metric out."""
    for doc in (hand_made(spans=False), {"planes": []}):
        ana = program_spans.Analysis(doc)
        assert ana.spans == [] and ana.report()
        for m in SERVE_METRICS | TRAIN_METRICS:
            assert read(m, ana) is None, m
    # and with no trace directory at all
    run = {"workload": "no-such-cell"}
    assert program_spans.of(run).spans == [] and "program_spans" in run


@pytest.mark.parametrize("cell,names", [
    ("pythia-410m.serve-closed32", SERVE_METRICS),
    ("pythia-410m.train-seq2048", TRAIN_METRICS)])
def test_traced_rehearsal_lists_every_new_metric(cell, names, tmp_path):
    assert names <= {m["name"] for m in
                     manifest.metrics_for(MAN, cell, "per_layer")}
    p = run_cell(cell, "--rehearse", "--out-dir", str(tmp_path), trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["metrics"] == {}
    # the CPU has no device plane, so nothing is read for an idle share
    assert names - {"device.serve_idle_named_share"} \
        == set(line["would_report"]) & names, line["would_report"]
    # what the spans said is printed before the last line, not in it
    assert any("program spans:" in l for l in lines[:-1])
    assert "program spans" not in lines[-1]
