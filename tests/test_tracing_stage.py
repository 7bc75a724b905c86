"""``tracing.stage``: the program's stage spans, in any profiler's trace
(``bf:<cat>.<name>``, entry attributes as stats), always in the stage ring
(name, bucket, start, end, depth; no object a record) and, armed, in the
per-request ring under the names the hand-gated blocks it replaced wrote."""
import gc
import glob
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from bluefog_tpu.optimizers import _InstrumentedStep
from bluefog_tpu.utils import flight as bfflight
from bluefog_tpu.utils import metrics as bfm
from bluefog_tpu.utils import tracing as bftrace


@pytest.fixture(autouse=True)
def _clean():
    bfm.reset_metrics()
    bftrace.reset()
    bfflight.reset()
    yield
    bftrace.reset()
    bfm.reset_metrics()
    bfflight.reset()


def bf_events(trace_dir, prefix="bf:"):
    """[(name, start_ns, end_ns, stats)] of the trace's bf: events (or
    those of another ``prefix``, or of a tuple of them), in time order,
    outermost first."""
    path = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats)) for ev in line.events
                    if ev.name.startswith(prefix)]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def inside(events, name, outer):
    """Events called ``name`` that lie within some event called ``outer``."""
    return [e for e in events if e[0] == name and any(
        o[0] == outer and o[1] <= e[1] and e[2] <= o[2] for o in events)]


def test_stage_off_path_cost_pin():
    """Unarmed and with no profiler session a stage is one annotation
    object: about a microsecond, pinned under a generous ceiling (fourteen
    a scheduler step of 110 ms must stay far under 1 %)."""
    n = 100_000
    t0 = time.perf_counter()
    for i in range(n):
        with bftrace.stage("engine-r0-1", "decode_call", cat="engine", S=32):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 20e-6, f"unarmed stage {per_span * 1e6:.2f}us/span"
    assert bftrace.spans() == []


def test_armed_stage_writes_the_ring_record_add_span_wrote(tmp_path):
    bftrace.configure(str(tmp_path))
    t0 = time.monotonic()
    with bftrace.stage("engine-r0-1", "spec_round", cat="engine",
                       parent=3, S=4, k=2) as st:
        st.attrs.update(drafted=8, accepted=5)     # known at the end only
    t1 = time.monotonic()
    rec, = bftrace.spans()
    assert t0 <= rec.pop("t0") <= rec.pop("t1") <= t1
    assert rec == {"kind": "span", "seq": 1, "span": 1,
                   "trace": "engine-r0-1", "name": "spec_round",
                   "cat": "engine", "parent": 3, "S": 4, "k": 2,
                   "drafted": 8, "accepted": 5}
    # disarmed between entry and exit: nothing half-recorded
    with bftrace.stage("t", "x", cat="serve"):
        bftrace.configure(None)
    assert len(bftrace.spans()) == 1


def test_stage_is_a_shell_where_jax_was_never_imported(monkeypatch, tmp_path):
    monkeypatch.setattr(bftrace, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", None)
    with bftrace.stage("t", "x", cat="train") as st:
        assert not st._ann
    bftrace.configure(str(tmp_path))               # the ring still works
    with bftrace.stage("t", "x", cat="train", step=1):
        pass
    assert [s["name"] for s in bftrace.spans()] == ["x"]
    # a shell that records: a launcher child's stages reach the stage ring
    assert [r.name for r in bftrace.stage_records()] == ["bf:train.x"] * 2


def nested_step():
    with bftrace.stage("s", "step", cat="serve"):
        with bftrace.stage("s", "pack", cat="serve", lanes=3, S=4):
            pass
        with bftrace.stage("e", "prefill_call", cat="engine", Tpad=64,
                           tokens=48):
            pass
        with bftrace.stage("e", "chunk_call", cat="engine", S=2, T=8):
            with bftrace.stage("e", "collect", cat="engine"):
                with bftrace.stage("e", "wait", cat="engine"):
                    time.sleep(0.002)


def test_unarmed_stages_land_in_the_stage_ring_with_depth_and_bucket():
    t0 = time.perf_counter()
    nested_step()
    t1 = time.perf_counter()
    recs = bftrace.stage_records()
    # in the order stages END in; the bucket is S, else Tpad, else T
    assert [(r.name, r.bucket, r.depth) for r in recs] == [
        ("bf:serve.pack", 4, 1), ("bf:engine.prefill_call", 64, 1),
        ("bf:engine.wait", None, 3), ("bf:engine.collect", None, 2),
        ("bf:engine.chunk_call", 2, 1), ("bf:serve.step", None, 0)]
    # on the clock of the runners' window, children inside their parents
    wait, collect, chunk, step = recs[2:]
    assert t0 <= step.t0 <= chunk.t0 <= collect.t0 <= wait.t0
    assert wait.t1 <= collect.t1 <= chunk.t1 <= step.t1 <= t1
    assert wait.t1 - wait.t0 >= 0.002
    # nothing was armed: the per-request ring stays what it was, and no
    # stage reads the thread's CPU clock (a system call)
    assert bftrace.spans() == [] and not bftrace.enabled()
    assert [r.cpu_s for r in recs] == [None] * 6
    assert bftrace.stage_dropped() == 0
    assert bftrace.stage_records(since=wait.t1) == recs[2:]
    assert bftrace.stage_records(since=t1) == []
    # armed, the same stages land in both rings, and the outermost stage
    # alone carries the thread's CPU time across it: it slept
    bftrace.configure("/nonexistent/never-written")
    nested_step()
    recs = bftrace.stage_records()[6:]
    assert [r.name for r in recs] == [r.name for r in bftrace.stage_records()[:6]]
    assert [r.cpu_s is not None for r in recs] == [False] * 5 + [True]
    step = recs[-1]
    assert 0 <= step.cpu_s < step.t1 - step.t0 - 0.0015
    assert [s["name"] for s in bftrace.spans()] == [
        "pack", "prefill_call", "wait", "collect", "chunk_call", "step"]


def test_the_stage_clock_is_the_runners_and_the_schedulers():
    """A record is stamped with ``perf_counter_ns``; the runners' window is
    ``perf_counter()`` and the scheduler's stamps ``monotonic()``: one
    clock on Linux, which the benchmark's reader relies on."""
    a = time.perf_counter_ns() / 1e9
    b, c = time.perf_counter(), time.monotonic()
    d = time.perf_counter_ns() / 1e9
    assert a <= b <= d + 1e-9 and abs(c - b) < 1e-3


def test_the_stage_ring_holds_no_object_a_record():
    """The runners freeze the collector's lists before the window so that
    no collection runs inside it: 100,000 stages leave the collector
    nothing to count, start no collection and keep no object."""
    for _ in range(3):
        nested_step()                   # names interned, caches warm
    gc.collect()
    before = [g["collections"] for g in gc.get_stats()]
    tracked, count = len(gc.get_objects()), gc.get_count()[0]
    for _ in range(50_000):
        with bftrace.stage("e", "decode_call", cat="engine", S=32):
            with bftrace.stage("e", "dispatch", cat="engine"):
                pass
    assert [g["collections"] for g in gc.get_stats()] == before
    assert abs(gc.get_count()[0] - count) < 50
    assert abs(len(gc.get_objects()) - tracked) < 50
    assert len(bftrace.stage_records()) == 100_000 + 18
    assert bftrace.STAGE_RING_BYTES == 27 * bftrace.STAGE_CAPACITY \
        and bftrace.STAGE_CAPACITY >= 262144


def test_the_stage_ring_overwrites_its_oldest_and_counts_them():
    with bftrace.stage("s", "first", cat="serve"):
        pass
    for _ in range(bftrace.STAGE_CAPACITY + 9):
        with bftrace.stage("e", "dispatch", cat="engine"):
            pass
    assert bftrace.stage_dropped() == 10
    recs = bftrace.stage_records()
    assert len(recs) == bftrace.STAGE_CAPACITY
    assert {r.name for r in recs} == {"bf:engine.dispatch"}
    assert all(a.t1 <= b.t0 for a, b in zip(recs[:100], recs[1:]))
    bftrace.reset()
    assert bftrace.stage_records() == [] and bftrace.stage_dropped() == 0


def pause_threads():
    return [t for t in threading.enumerate() if t.name == "bf-trace-pause"]


def test_the_pause_observer_lives_while_armed_and_records_a_late_wake(
        tmp_path, monkeypatch):
    assert pause_threads() == []
    real, jump = time.perf_counter_ns, [0]
    monkeypatch.setattr(bftrace, "_now_ns", lambda: real() + jump[0])
    bftrace.configure(str(tmp_path))
    watcher, = pause_threads()
    assert watcher.daemon
    bftrace.configure(str(tmp_path))                # armed twice: one thread
    assert pause_threads() == [watcher]
    time.sleep(0.05)                                # wakes on time: nothing
    assert bftrace.stage_records() == []
    t0 = bftrace._now_ns() / 1e9
    jump[0] += 700_000_000                          # the clock the observer
    deadline = time.time() + 5                      # reads ran 0.7 s on
    while not bftrace.stage_records() and time.time() < deadline:
        time.sleep(0.005)
    rec, = bftrace.stage_records()
    assert (rec.name, rec.bucket, rec.depth, rec.cpu_s) == (
        bftrace.PAUSE_NAME, None, 0, None)
    # from when the wake was due until it came
    assert t0 - 0.011 <= rec.t0 <= t0 + 0.05
    assert 0.65 < rec.t1 - rec.t0 < 0.75
    # flush writes the stage ring beside the request bundle
    with bftrace.stage("s", "step", cat="serve"):
        pass
    bftrace.flush()
    with open(bftrace.stages_path()) as f:
        doc = json.load(f)
    assert doc["schema"] == bftrace.STAGES_SCHEMA and doc["dropped"] == 0
    assert [doc["names"][r[0]] for r in doc["records"]] == [
        bftrace.PAUSE_NAME, "bf:serve.step"]
    assert doc["records"][0][2:4] == [rec.t0, rec.t1]
    bftrace.configure(None)                         # disarmed: it goes
    watcher.join(timeout=2)
    assert not watcher.is_alive() and pause_threads() == []


def test_train_wrapper_stages_its_call_and_its_dispatch(tmp_path,
                                                        monkeypatch):
    # building a producer reads no environment: arming is the caller's
    monkeypatch.setenv(bftrace.ENV_TRACE, str(tmp_path / "ring"))
    step = _InstrumentedStep(jax.jit(lambda p, s: (p + 1, s, p.sum())),
                             steps_per_call=2, donated=False)
    assert not bftrace.enabled()
    p = jnp.zeros((4,))
    p, s, _ = step(p, 0)                            # compiles outside the trace
    bftrace.configure(str(tmp_path / "ring"))
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        for _ in range(3):
            p, s, loss = step(p, s)
        jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    events = bf_events(tmp_path / "prof")
    steps = [e for e in events if e[0] == "bf:train.train_step"]
    assert [e[3] for e in steps] == [{"step": n, "fused_k": 2}
                                     for n in (2, 3, 4)]
    assert len(inside(events, "bf:train.dispatch",
                      "bf:train.train_step")) == 3
    ring = bftrace.spans()
    assert [(r["name"], r["cat"]) for r in ring] == [
        ("dispatch", "train"), ("train_step", "train")] * 3
    assert len({r["trace"] for r in ring}) == 1
    assert {k: ring[1][k] for k in ("step", "fused_k", "overlap")} == {
        "step": 2, "fused_k": 2, "overlap": False}
    # the whole wrapper, not the dispatch alone
    assert ring[1]["t0"] <= ring[0]["t0"] and ring[0]["t1"] <= ring[1]["t1"]
