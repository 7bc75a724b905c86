"""The program's stage ring read for the untraced window
(perfbench/harness/stage_ring.py): the grouping rule on hand-made records,
stalls injected into the tiny CPU scheduler named by stage, bucket and
seconds, and every serving cell that lists the four metrics rehearsed with
``--trace 1``: each reader finds something to read."""
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bluefog_tpu.utils import tracing  # noqa: E402
from perfbench.harness import manifest, stage_ring  # noqa: E402
from test_perfbench_cells import run_cell  # noqa: E402

MAN = manifest.load()
NEW = ("engine.decode_wait_s_p50", "engine.decode_read_back_s_p50",
       "scheduler.stage_excess_s_max", "scheduler.stall_s")
CELLS = [w for w in MAN["per_layer"] if w["name"] == NEW[0]][0]["workloads"]
STEP = "serve.step/engine.decode_call/"


def step(t, wait=0.004, dispatch=0.0005, deliver=0.001, S=32, prefill=None,
         cpu=0.002):
    """One scheduler step's records from ``t`` on, as the ring hands them
    out (in the order stages END in); returns (records, the step's end)."""
    out, at = [], t + 0.0001
    if prefill is not None:
        out += [("bf:engine.wait", None, at + 0.0002, at + 0.0002 + prefill,
                 4, None),
                ("bf:engine.collect", None, at + 0.0001,
                 at + 0.0003 + prefill, 3, None),
                ("bf:engine.prefill_call", 256, at, at + 0.0004 + prefill,
                 2, None),
                ("bf:serve.admit", None, at, at + 0.0005 + prefill, 1, None)]
        at += 0.0006 + prefill
    out.append(("bf:serve.pack", S, at, at + 0.0002, 1, None))
    call = at = at + 0.0003
    out.append(("bf:engine.stage_in", None, at, at + 0.0004, 2, None))
    at += 0.0004
    out.append(("bf:engine.dispatch", None, at, at + dispatch, 2, None))
    at += dispatch
    out += [("bf:engine.wait", None, at + 0.00001, at + 0.00001 + wait, 3,
             None),
            ("bf:engine.read_back", None, at + 0.00001 + wait,
             at + 0.00003 + wait, 3, None),
            ("bf:engine.collect", None, at, at + 0.00004 + wait, 2, None)]
    at += 0.00004 + wait
    out.append(("bf:engine.decode_call", S, call, at, 1, None))
    out.append(("bf:serve.deliver", None, at, at + deliver, 1, None))
    at += deliver
    out.append(("bf:serve.step", None, t, at + 0.0001, 0, cpu))
    return out, at + 0.0002


def window(*steps):
    """Records of scheduler steps laid end to end from t = 100."""
    records, t = [], 100.0
    for kw in steps:
        more, t = step(t, **kw)
        records += more
    return records, (100.0, t)


def test_a_calm_window_reads_medians_and_no_stall():
    records, win = window(*[{}] * 20)
    ana = stage_ring.Analysis(records, win)
    assert ana.decode_wait_s_p50 == pytest.approx(0.004)
    assert ana.decode_read_back_s_p50 == pytest.approx(0.00002)
    assert ana.stall_s == 0 and ana.stalls == [] and ana.lines == []
    assert 0 <= ana.excess_s_max < 1e-9
    # instances: leaves, and what a stage spent outside those beneath it
    assert set(p for p, _ in ana.groups) == {
        "serve.step/pack", STEP + "stage_in", STEP + "dispatch",
        STEP + "collect/wait", STEP + "collect/read_back",
        STEP + "collect/(self)", STEP + "(self)", "serve.step/deliver",
        "serve.step/(self)"}
    # the bucket is the nearest one up the path
    assert {b for p, b in ana.groups if p.startswith(STEP)} == {32}
    assert {b for p, b in ana.groups if not p.startswith(STEP)} == {
        32, None}                                   # pack's own S; none


def test_a_stalled_wait_names_its_path_bucket_seconds_and_the_idle_device():
    steps = [{}] * 30
    steps[10] = {"wait": 3.004, "cpu": 0.0021}      # the host heard late:
    steps[11] = {"wait": 0.00002}                   # n+1 was long finished
    steps[20] = {"wait": 1.504}                     # the device itself late:
    records, win = window(*steps)                   # the next wait as ever
    ana = stage_ring.Analysis(records, win, observed=True)
    assert [s.path for s in ana.stalls] == [STEP + "collect/wait"] * 2
    assert ana.stall_s == pytest.approx(4.5, abs=1e-6)
    assert ana.excess_s_max == pytest.approx(3.0, abs=1e-6)
    first, second = ana.lines
    assert first.startswith("stall at 0.066710 s: ")
    assert STEP + "collect/wait bucket 32 excess 3.000000 s over a median " \
        "of 0.004000 s (30 instances)" in first
    assert "thread CPU 0.002100 s across that serve.step of 3.0" in first
    assert "the observer's wakes came on time" in first
    assert "the next decode wait took 0.000020 s over a median of " \
        "0.004000 s: the device idled through the stall" in first
    assert "excess 1.500000 s" in second
    assert "the device was busy when the stall ended" in second
    # a stall in the dispatch: the SAME call's wait follows it and is
    # the witness
    steps = [{}] * 12
    steps[5] = {"dispatch": 2.0005, "wait": 0.00003}
    ana = stage_ring.Analysis(*window(*steps))
    line, = ana.lines
    assert STEP + "dispatch bucket 32 excess 2.000000 s" in line
    assert "the next decode wait took 0.000030 s" in line
    assert "the device idled through the stall" in line
    assert "observer" not in line                   # it was not running
    # where a step's prefill waits the call in flight out, the decode wait
    # is next to nothing at the median and bears no witness
    steps = [{"wait": 0.00003}] * 12
    steps[5] = {"wait": 0.13303}
    line, = stage_ring.Analysis(*window(*steps)).lines
    assert "the next decode wait took 0.000030 s over a median of " \
        "0.000030 s: a decode wait is next to nothing in this window " \
        "whatever the device did: no witness" in line


def test_a_stall_under_a_prefill_has_no_witness_and_a_pause_names_the_host():
    steps = [{"prefill": 0.16}] * 8
    steps[3] = {"prefill": 5.46}
    records, win = window(*steps)
    # the second observer, had it run: its wakes were 5.2 s late inside it
    records.append((stage_ring.PAUSE, None, 100.7, 105.9, 0, None))
    ana = stage_ring.Analysis(records, win, observed=True)
    line, = ana.lines
    assert "serve.step/admit/engine.prefill_call/collect/wait bucket 256 " \
        "excess 5.300000 s" in line
    assert "a prefill is synchronous: no later wait bears witness" in line
    assert "the observer's wakes were late by 5.200000 s inside it: the " \
        "process or the machine stood still" in line
    assert "next decode wait" not in line
    # the pause record is no stage: nothing nests beneath it
    assert all(n.name != stage_ring.PAUSE for n in ana.nodes)


def test_a_stall_is_over_a_tenth_of_a_second_and_over_three_medians():
    steps = [{"wait": 0.2}] * 10
    steps[4] = {"wait": 0.75}           # 0.55 s over, under three medians
    ana = stage_ring.Analysis(*window(*steps))
    assert ana.stalls == [] and ana.stall_s == 0
    assert ana.excess_s_max == pytest.approx(0.55)
    steps = [{}] * 10
    steps[4] = {"wait": 0.09}           # 22 medians, under a tenth of a second
    ana = stage_ring.Analysis(*window(*steps))
    assert ana.stalls == [] and ana.excess_s_max == pytest.approx(0.086)


def test_a_group_of_fewer_than_five_is_counted_and_not_judged():
    steps = [{}] * 12
    steps[2] = {"S": 8, "wait": 2.0}    # the one call at this bucket
    ana = stage_ring.Analysis(*window(*steps))
    assert ana.stalls == [] and ana.stall_s == 0
    # the six groups under a decode call and the pack's, at bucket 8
    assert ana.unjudged == 7
    assert ana.lines == ["stage ring: 7 groups of fewer than 5 instances "
                         "were not judged"]
    # with nothing to judge at all there is no reading, not a zero
    ana = stage_ring.Analysis(*window({}, {}))
    assert ana.stall_s is None and ana.excess_s_max is None
    assert ana.decode_wait_s_p50 == pytest.approx(0.004)


def test_an_overwritten_window_and_a_program_without_a_ring_read_nothing():
    records, win = window(*[{}] * 20)
    # the ring reaches back only to the window's fifth step
    ana = stage_ring.Analysis(records[50:], win, dropped=50)
    assert (ana.decode_wait_s_p50, ana.decode_read_back_s_p50,
            ana.excess_s_max, ana.stall_s) == (None,) * 4
    assert ana.lines == ["stage ring: 50 records were overwritten, some of "
                         "them this window's: no reading is made of it"]
    # overwritten before the window opened: nothing of the window is lost
    early = [("bf:serve.step", None, 90.0, 90.5, 0, 0.1)]
    ana = stage_ring.Analysis(early + records, win, dropped=7)
    assert ana.stall_s == 0 and ana.lines == []
    # the parent of the PR that added the ring: every reading None, no raise
    ana = stage_ring.Analysis(None, (0.0, 0.0))
    assert (ana.decode_wait_s_p50, ana.stall_s, ana.lines) == (None, None, [])
    run = {"facts": {}}
    assert stage_ring.of(run).stall_s is None and "stage_ring" in run
    for name in NEW:
        assert manifest.load_module("metrics", name).read(run) is None


# ---------------------------------------------------------------------------
# the tiny CPU scheduler, with stalls of a known length put into it
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def prog():
    cell = manifest.resolve_cell(MAN, "pythia-410m.serve-closed32")
    cfg = manifest.sized(cell["config"], True)
    traffic = manifest.sized(cell["traffic"], True)
    family = manifest.load_module("families", cfg["family"])
    p = family.build_serve(cfg, traffic, jax.devices("cpu")[:1], 11)
    p.warmup()
    return p


def serve(prog, steps, before=None):
    """``steps`` scheduler steps of a closed loop of four clients as one
    window; ``before(i, sched)`` runs ahead of step ``i``.  Returns the
    analysis of the window and the record of a run."""
    sched = prog.scheduler()
    rng = np.random.default_rng(3)

    def submit():
        sched.submit(rng.integers(0, prog.vocab, 6).tolist(),
                     max_new_tokens=8)
    for _ in range(4):
        submit()
    for _ in range(6):                                  # the ramp
        for _ in sched.step():
            submit()
    t_open = time.perf_counter()
    for i in range(steps):
        if before is not None:
            before(i, sched)
        for _ in sched.step():
            submit()
    run = {"facts": {"window": (t_open, time.perf_counter())}}
    sched.close()
    return stage_ring.of(run), run


@pytest.fixture
def armed(tmp_path):
    """The per-request ring armed, as ``BLUEFOG_TRACE`` arms it: the pause
    observer runs and an outermost stage carries the thread's CPU time."""
    tracing.reset()
    tracing.configure(str(tmp_path))
    yield
    tracing.reset()


def test_injected_stalls_are_named_by_stage_bucket_and_seconds(
        prog, armed, monkeypatch, capsys):
    due = {}
    real_jit, real_ready = prog.engine._decode_jit, jax.block_until_ready
    real_decode = prog.engine.decode

    class stalled:
        """``real``, a third of a second late on the call that is due."""

        def __init__(self, name, real):
            self.name, self.real = name, real

        def __call__(self, *args, **kwargs):
            if due.pop(self.name, False):
                time.sleep(0.3)
            return self.real(*args, **kwargs)

        def __getattr__(self, attr):            # a jitted function's own
            return getattr(self.real, attr)

    def decode(*args, **kwargs):
        # (a step's prefill calls wait too: the decode call's wait alone)
        if due.pop("decode", False):
            due["wait"] = True
        return real_decode(*args, **kwargs)

    monkeypatch.setattr(prog.engine, "_decode_jit",
                        stalled("dispatch", real_jit))
    monkeypatch.setattr(jax, "block_until_ready",
                        stalled("wait", real_ready))
    monkeypatch.setattr(prog.engine, "decode", decode)

    def before(i, sched):
        if i == 0:
            monkeypatch.setattr(sched, "_deliver",
                                stalled("deliver", sched._deliver))
        if i in (20, 40, 60):
            due[{20: "dispatch", 40: "decode", 60: "deliver"}[i]] = True

    ana, run = serve(prog, 80, before)
    assert not due                                      # each one was met
    S = prog.engine.scfg.batch_buckets[0]
    assert [(s.path, s.bucket) for s in ana.stalls] == [
        (STEP + "dispatch", S), (STEP + "collect/wait", S),
        ("serve.step/deliver", None)]
    assert ana.stall_s == pytest.approx(0.9, rel=0.1)
    assert all(s.excess == pytest.approx(0.3, rel=0.1) for s in ana.stalls)
    assert ana.excess_s_max == max(s.excess for s in ana.stalls)
    # one line a stall on standard error, each with the CPU time of the
    # thread across the step (it slept) and what the observer saw meanwhile
    said = [l for l in capsys.readouterr().err.splitlines()
            if l.startswith("perfbench: stall at ")]
    assert len(said) == 3
    for line, path in zip(said, (STEP + "dispatch", STEP + "collect/wait",
                                 "serve.step/deliver")):
        assert f": {path} bucket " in line and " excess 0.3" in line
        assert "thread CPU 0.0" in line
        assert "the observer's wakes came on time: the rest of the " \
            "process ran" in line
    assert "the next decode wait took" in said[0]
    assert "the next decode wait took" in said[1]
    assert "decode wait" not in said[2]                 # no call, no witness
    # the four readers hand out what the analysis holds, and print nothing
    # a second time
    read = {n: manifest.load_module("metrics", n).read(run) for n in NEW}
    assert read == {NEW[0]: ana.decode_wait_s_p50,
                    NEW[1]: ana.decode_read_back_s_p50,
                    NEW[2]: ana.excess_s_max, NEW[3]: ana.stall_s}
    assert all(v is not None and v > 0 for v in read.values())
    assert capsys.readouterr().err == ""


def test_a_calm_run_of_the_scheduler_reads_zero(prog):
    tracing.reset()                     # unarmed, as the driver's runs are
    for _ in range(2):          # (a loaded test machine may stall by itself)
        ana, _ = serve(prog, 60)
        if ana.stall_s == 0:
            break
    assert ana.stall_s == 0 and ana.stalls == []
    assert ana.excess_s_max is not None
    assert ana.decode_wait_s_p50 > 0 and ana.decode_read_back_s_p50 > 0
    # wait and read_back lie inside collect (i.node: the whole stage),
    # which holds the retrace check and the bookkeeping besides
    collect = np.median([i.node.dur for i in ana.groups[
        (STEP + "collect/(self)", prog.engine.scfg.batch_buckets[0])]])
    assert ana.decode_wait_s_p50 + ana.decode_read_back_s_p50 < collect


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_lists_the_four_names(cell, tmp_path):
    assert set(NEW) <= {m["name"] for m in
                        manifest.metrics_for(MAN, cell, "per_layer")}
    p = run_cell(cell, "--rehearse", "--out-dir", str(tmp_path), trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    assert set(NEW) <= set(line["would_report"]), line["would_report"]


def test_the_four_metrics_stand_as_appended():
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    names = [m["name"] for m in MAN["per_layer"]]
    assert sorted(NEW, key=names.index) == list(NEW)
    serving = [w["name"] for w in MAN["workloads"]
               if "serve" in w["traffic"]]
    for n in NEW:
        m = by_name[n]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "s", "lower", "program_span", "serve_tok_per_s")
        assert m["layer"] == ("serving engine" if n.startswith("engine.")
                              else "scheduler")
        # every serving cell but the one an accepted rule keeps out of any
        # list appended behind PR 41's metrics (PERF.md, open questions)
        assert set(m["workloads"]) == set(serving) - {
            "a.x-k1.serve-closed128-p2048"}
