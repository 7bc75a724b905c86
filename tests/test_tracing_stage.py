"""``tracing.stage``: the program's stage spans, always in the profiler's
trace (``bf:<cat>.<name>``, entry attributes as stats) and, armed, in the
ring under the names the hand-gated blocks it replaced wrote."""
import glob
import os
import sys
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
