"""The reduction from a profiler trace to busy time, idle gaps, exposed
collectives and top operations: on hand-made events, and on one small trace
recorded on the chip and kept with the benchmark."""
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import trace  # noqa: E402
from perfbench.harness.spans import Spans  # noqa: E402

FIXTURE = os.path.join(ROOT, "perfbench", "fixtures", "trace_small.json.gz")


def test_merge():
    merged, total = trace.merge([(0, 5), (3, 8), (10, 12)])
    assert merged == [[0, 8], [10, 12]] and total == 10


def test_time_goes_to_the_innermost_event():
    events = [["while.1", 0, 100], ["fusion.1", 10, 20], ["fusion.2", 40, 10],
              ["collective-permute-done.1", 60, 30], ["copy.3", 120, 5]]
    segs = trace.owned_segments(events)
    own = {}
    for n, s, e in segs:
        own[n] = own.get(n, 0) + e - s
    assert own == {"while.1": 40, "fusion.1": 20, "fusion.2": 10,
                   "collective-permute-done.1": 30, "copy.3": 5}
    assert [s for _, s, _ in segs] == sorted(s for _, s, _ in segs)


def synthetic():
    dev = lambda ops: {"lines": [{"name": "XLA Ops", "events": ops},
                                 {"name": "XLA Modules",
                                  "events": [["jit_step", 0, 1000]]}]}
    return {"planes": [
        {"name": "/device:TPU:0", **dev([
            ["fusion.1", 100, 300], ["collective-permute-done.2", 400, 100],
            ["fusion.3", 700, 200]])},
        {"name": "/device:TPU:1", **dev([
            ["fusion.1", 100, 300], ["collective-permute-done.2", 400, 300],
            ["fusion.3", 700, 200]])},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["pb:window", 0, 1000], ["pb:step_call", 0, 90],
            ["pb:wait_loss", 90, 900]]}]}]}


def test_reduce_on_hand_made_planes():
    r = trace.reduce(synthetic())
    assert r["n_devices"] == 2 and r["window_from"] == "pb:window"
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((600 + 800) / 2 * 1e-9)
    assert r["comm_exposed_s_worst"] == pytest.approx(300e-9)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(300e-9)
    assert ops["collective-permute-done.2"] == pytest.approx(200e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    # device 0 waits longest: 0-100 (mostly under step_call), 500-700 and
    # 900-1000 (under wait_loss); a gap goes whole to the span covering most
    assert gaps["wait_loss"] == pytest.approx(300e-9)
    assert gaps["step_call"] == pytest.approx(100e-9)
    longest = [g for g in r["breakdown"]["idle_gaps"]
               if g[0].startswith("longest:")]
    assert longest[0] == ["longest:wait_loss", pytest.approx(200e-9)]
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_reduce_without_device_operations_is_none():
    t = synthetic()
    t["planes"] = t["planes"][2:]
    assert trace.reduce(t) is None


def test_spans_record_wrap_and_window():
    sp = Spans()
    f = sp.wrap("decode_call", lambda x: x + 1)
    assert f(1) == 2 and f(2) == 3
    with sp.span("prefill_call"):
        pass
    assert len(sp.durations("decode_call")) == 2
    t_mid = sp.records[1][1]
    assert len(sp.durations("decode_call", t_open=t_mid)) == 1


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_reduce_on_the_recorded_chip_trace(recorded):
    """Three calls of a small program on a TPU v5 lite, the benchmark's
    spans around them (perfbench/tools/record_fixture.py)."""
    meta = recorded["recorded"]
    r = trace.reduce(recorded)
    assert r["n_devices"] == 1 and r["window_from"] == "pb:window"
    # the profiler's window span and the host clock around it agree
    assert r["window_s"] == pytest.approx(meta["host_window_s"], rel=0.05)
    assert 0 < r["busy_s"] < r["window_s"]
    # each call sleeps 2 ms on the host with the device idle
    assert r["window_s"] - r["busy_s"] >= meta["calls"] * 0.002
    assert r["comm_exposed_s_worst"] == 0.0
    ops = r["breakdown"]["device_ops"]
    assert 1 <= len(ops) <= 10 and all(t > 0 for _, t in ops)
    assert ops == sorted(ops, key=lambda x: -x[1])
    # self times add up to busy time: no instant is counted twice
    assert sum(r["self_time_s"].values()) == pytest.approx(r["busy_s"],
                                                           rel=1e-6)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert "host_pause" in gaps and gaps["host_pause"] >= 0.9 * meta[
        "calls"] * 0.002
    # names are the instruction's own, not its whole HLO text
    assert all(" " not in n and len(n) < 80 for n, _ in ops)
    assert "multiply_add_fusion.2_bf16_2048_2048" in r["self_time_s"]
    # the scan's `while` keeps only the time its four fusions leave it
    assert r["self_time_s"]["while_bf16_2048_2048"] < 0.01 * r[
        "self_time_s"]["multiply_add_fusion.2_bf16_2048_2048"]
    assert trace.idle_share(r) == pytest.approx(
        1 - r["busy_s"] / r["window_s"])


def test_short_name():
    long = ("%fusion.1504 = (f32[256]{0:T(256)}, f32[256,56,56,256]{3,0,2,1:"
            "T(8,128)}) fusion(f32[256]{0} %copy-done.499, bf16[9]{0} "
            "%collective-permute-done.1), kind=kOutput")
    assert trace.short_name(long) == "fusion.1504_f32_256_56_56_256"
    assert not trace.COMM_RE.search(trace.short_name(long))
    assert trace.short_name("%copy-done = bf16[8]{0} copy-done(%x)") == \
        "copy-done_bf16_8"
    assert trace.short_name("fusion.3") == "fusion.3"
    assert trace.short_name(trace.short_name(long)) == trace.short_name(long)
    assert trace.COMM_RE.search(trace.short_name(
        "%collective-permute-done.2 = f32[4]{0} collective-permute-done(%s)"))


MS = 1_000_000


def serving_steps(stamped_early_ns):
    """Two scheduler steps as the serving cell's trace has them: a prefill
    call of 7.9 ms, then a decode call of 16 ms.  The device runs from
    1 ms after each call starts; its events are STAMPED early."""
    host, dev, t = [["pb:window", 0, 60 * MS]], [], 1 * MS
    for _ in range(2):
        host.append(["pb:prefill_call", t, int(7.9 * MS)])
        dev.append(["fusion.1", t + 1 * MS - stamped_early_ns, 2 * MS])
        t += int(7.9 * MS)
        host.append(["pb:decode_call", t, 16 * MS])
        dev.append(["fusion.2", t + 1 * MS - stamped_early_ns, 13 * MS])
        t += 16 * MS
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": dev}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]}]}


@pytest.mark.parametrize("early_ms", [0.0, 1.3, 1.5, 1.7])
def test_idle_gaps_are_named_on_the_hosts_clock(early_ms):
    """On the host's clock the device idles 4.9 ms of a prefill call after
    its program (collect, seed_slot) and 1 ms at the head of each call; the
    decode call's idle time is its head and its 2 ms tail."""
    early = int(early_ms * MS)
    r = trace.reduce(serving_steps(early), device_shift_ns=early)
    gaps = dict(g for g in r["breakdown"]["idle_gaps"]
                if not g[0].startswith("longest:"))
    # prefill: 4.9 ms after the program + the decode call's 1 ms head (one
    # gap, named by the span that covers most of it), twice; and what the
    # stamped window holds before the first program (2 ms less the offset)
    assert gaps["prefill_call"] == pytest.approx(
        2 * 5.9e-3 + 2e-3 - early / 1e9)
    # decode: its 2 ms tail with the next prefill call's 1 ms head, and
    # the end of the window
    assert gaps["decode_call"] == pytest.approx(
        3e-3 + (60 - 46.8) * 1e-3 + early / 1e9)
    assert set(gaps) == {"prefill_call", "decode_call"}
    assert r["device_shift_s"] == pytest.approx(early / 1e9)
    # busy time and the window are taken as stamped
    assert r["busy_s"] == pytest.approx(30e-3)


def test_idle_gaps_named_as_stamped_go_to_the_neighbouring_span():
    """What the shift repairs: stamped 1.5 ms early and named unshifted,
    the 1 ms the device waits at the head of the first prefill call falls
    before every span."""
    early = int(1.5 * MS)
    shifted = dict(trace.reduce(serving_steps(early), device_shift_ns=early)[
        "breakdown"]["idle_gaps"][:3])
    stamped = dict(trace.reduce(serving_steps(early))[
        "breakdown"]["idle_gaps"][:3])
    assert "none" in stamped and "none" not in shifted
    assert stamped["prefill_call"] < shifted["prefill_call"]


def test_the_recorded_trace_names_its_gaps_after_the_shift(recorded):
    """The fixture's device events are stamped 1.04 ms before the host call
    that launched them (first program 44.998 ms, first ``pb:step_call``
    46.037 ms).  Shifted by that, each program starts inside its
    ``step_call`` and the pause after it still owns the long gaps."""
    shift = 46_036_677 - 44_998_420
    r = trace.reduce(recorded, device_shift_ns=shift)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "host_pause"
    assert gaps[0][1] >= 0.9 * recorded["recorded"]["calls"] * 0.002
    assert [n for n, _ in gaps if n.startswith("longest:")][:3] == [
        "longest:host_pause"] * 3
    # between two operations of one program the host is in `step_call`
    assert dict(gaps)["step_call"] == pytest.approx(7e-9)
    same = trace.reduce(recorded)
    assert (r["busy_s"], r["window_s"]) == (same["busy_s"], same["window_s"])
    assert "step_call" not in dict(same["breakdown"]["idle_gaps"])
