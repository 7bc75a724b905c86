"""Request-scoped tracing, the time-series store, and SLO tripwires.

Four layers under test:

* ``bluefog_tpu/utils/tracing.py`` — the span store: id minting, the
  bounded ring, JSONL bundles, env arming, and the hot-path cost pin
  (the flight-recorder discipline: one bool check disarmed);
* ``bluefog_tpu/utils/timeseries.py`` — bounded per-metric history:
  ring windows, exact percentiles, rates, the registry hook that feeds
  rings from live metrics, and point-clearing on ``reset_metrics``;
* ``bluefog_tpu/diagnostics.SLOEngine`` — multi-window burn rates over
  the store plus the four anomaly tripwires;
* ``tools/trace_report.py`` / ``tools/metrics_report.py`` /
  ``tools/postmortem.py`` — the operator-facing consumers, pinned
  against committed fixtures.

Plus the PR's acceptance drill: the 8-rank train→serve estate with
tracing armed — per-rank bundles merge into a critical-path table whose
per-request total equals the scheduler's own measured E2E latency, with
donation intact and the retrace sentinel at 0 (observability stays free).
"""
import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

from bluefog_tpu.diagnostics import DEFAULT_SLO_WINDOWS, SLOEngine
from bluefog_tpu.parallel import compose
from bluefog_tpu.serve import Scheduler, ServeConfig, ServeEngine
from bluefog_tpu.utils import flight as bfflight
from bluefog_tpu.utils import metrics as bfm
from bluefog_tpu.utils import timeseries as bfts
from bluefog_tpu.utils import tracing as bftrace

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(autouse=True)
def _clean():
    bfm.reset_metrics()
    bfts.reset()
    bftrace.reset()
    bfflight.reset()
    yield
    bftrace.reset()
    bfts.reset()
    bfm.reset_metrics()
    bfflight.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name.replace("/", "_") + "_mod", os.path.join(REPO, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# tracing.py: the span store
# ---------------------------------------------------------------------------

def test_disarmed_recorder_is_inert():
    assert not bftrace.enabled()
    assert bftrace.add_span("t", "x", 0.0, 1.0) == 0
    assert bftrace.mark("t", "m") == 0
    with bftrace.stage("t", "blk", cat="serve"):
        pass
    assert bftrace.spans() == [] and bftrace.dropped() == 0


def test_arm_record_flush_roundtrip(tmp_path):
    bftrace.configure(str(tmp_path))
    assert bftrace.enabled()
    t1, t2 = bftrace.new_trace("req"), bftrace.new_trace("req")
    assert t1 != t2 and t1.startswith("req-r")
    sid = bftrace.add_span(t1, "queue", 1.0, 2.0, cat="serve", replica=3)
    assert sid > 0
    bftrace.add_span(t1, "decode", 2.0, 2.5, cat="serve",
                     parent=sid, tokens=2)
    with bftrace.stage(t2, "prefill", cat="serve") as s:
        s.attrs["hit"] = True
    assert bftrace.spans()[-1]["span"] > sid
    path = bftrace.flush()
    assert path == bftrace.bundle_path()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    meta, spans = lines[0], lines[1:]
    assert meta["kind"] == "meta" and meta["schema"] == bftrace.SCHEMA
    assert {"rank", "mono", "wall", "n_spans", "dropped"} <= set(meta)
    assert meta["n_spans"] == len(spans) == 3
    by_name = {s["name"]: s for s in spans}
    assert by_name["queue"]["replica"] == 3
    assert by_name["decode"]["parent"] == sid
    assert by_name["prefill"]["hit"] is True
    # atomic write: no tmp file left behind
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_ring_bound_counts_drops(tmp_path):
    bftrace.configure(str(tmp_path), capacity=4)
    t = bftrace.new_trace()
    for i in range(10):
        bftrace.add_span(t, f"s{i}", float(i), float(i) + 0.5)
    assert len(bftrace.spans()) == 4
    assert bftrace.dropped() == 6
    assert [s["name"] for s in bftrace.spans()] == ["s6", "s7", "s8", "s9"]


def test_env_arming(tmp_path, monkeypatch):
    monkeypatch.delenv(bftrace.ENV_TRACE, raising=False)
    assert not bftrace.maybe_enable_from_env()
    monkeypatch.setenv(bftrace.ENV_TRACE, str(tmp_path))
    assert bftrace.maybe_enable_from_env() and bftrace.enabled()
    assert bftrace.bundle_path().startswith(str(tmp_path))


def test_hot_path_cost_pin(tmp_path):
    """The flight-recorder cost discipline: disarmed add_span is one bool
    check (sub-microsecond); armed it is one dict build + deque append.
    Bounds are ~10x slack over measured so CI noise cannot flake them."""
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        bftrace.add_span("t", "x", 0.0, 1.0)
    disarmed = (time.perf_counter() - t0) / n
    bftrace.configure(str(tmp_path))
    tr = bftrace.new_trace()
    t0 = time.perf_counter()
    for i in range(n):
        bftrace.add_span(tr, "x", 0.0, 1.0, cat="serve", call=i)
    armed = (time.perf_counter() - t0) / n
    assert disarmed < 5e-6, f"disarmed add_span {disarmed * 1e6:.2f}us/call"
    assert armed < 50e-6, f"armed add_span {armed * 1e6:.2f}us/call"


# ---------------------------------------------------------------------------
# timeseries.py: the bounded history store
# ---------------------------------------------------------------------------

def test_ring_window_and_stats():
    bfts.arm("m")
    for i in range(10):
        bfts.append("m", float(i), ts=float(i))
    assert bfts.latest("m") == 9.0
    assert bfts.mean("m") == pytest.approx(4.5)
    # window: ts >= now - window_s (inclusive cut)
    assert [v for _, v in bfts.history("m", window_s=3.0, now=9.0)] == \
        [6.0, 7.0, 8.0, 9.0]
    assert bfts.percentile("m", 0) == 0.0
    assert bfts.percentile("m", 100) == 9.0
    assert bfts.percentile("m", 50, window_s=3.0, now=9.0) == 8.0
    assert bfts.rate("m") == pytest.approx(1.0)     # +1 per 1s tick
    assert bfts.over_fraction("m", 6.5) == pytest.approx(0.3)
    assert bfts.percentile("empty", 50) is None
    assert bfts.over_fraction("empty", 1.0) is None


def test_ring_capacity_bound():
    r = bfts.arm("m", capacity=8)
    for i in range(100):
        bfts.append("m", float(i), ts=float(i))
    assert len(r.values()) == 8
    assert r.values()[0] == 92.0


def test_registry_metrics_feed_armed_rings():
    bfts.arm("h")
    h = bfm.histogram("h", "test", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    assert bfts.history("h") is not None
    assert [v for _, v in bfts.history("h")] == [0.05, 0.5, 2.0]  # raw values
    bfts.arm("g")
    bfm.gauge("g", "test").set(7.0)
    assert bfts.latest("g") == 7.0
    bfts.arm("c")
    c = bfm.counter("c", "test")
    c.inc(2.0)
    c.inc(3.0)
    assert [v for _, v in bfts.history("c")] == [2.0, 5.0]  # cumulative
    # an unarmed metric stays out of the store
    bfm.gauge("unarmed", "test").set(1.0)
    assert not bfts.armed("unarmed")


def test_reset_metrics_clears_points_keeps_arming():
    bfts.arm("g")
    bfm.gauge("g", "test").set(1.0)
    assert bfts.latest("g") == 1.0
    bfm.reset_metrics()
    assert bfts.armed("g")                 # arming survives
    assert bfts.latest("g") is None        # stale points do not
    bfm.gauge("g", "test").set(2.0)        # re-created metric re-attaches
    assert bfts.latest("g") == 2.0


# ---------------------------------------------------------------------------
# SLOEngine: burn rates + tripwires
# ---------------------------------------------------------------------------

_LAT = "bluefog_serve_token_latency_seconds"


class _StubSched:
    def __init__(self, pending=0, in_flight=0):
        self.pending, self.in_flight = pending, in_flight
        self.completed, self.failed = [], []


def test_burn_rate_math():
    eng = SLOEngine(p99_ms=100.0)
    assert dict(DEFAULT_SLO_WINDOWS) == {"5m": 300.0, "1h": 3600.0}
    now = 1000.0
    # 20 latency points, 2 over the 100 ms target -> bad fraction 0.1,
    # burn = 0.1 / 0.01 budget = 10.0
    for i in range(20):
        bfts.append(_LAT, 0.5 if i < 2 else 0.01, ts=now - 19 + i)
    burn = eng.burn_rates(now=now)
    assert burn[("5m", "p99")] == pytest.approx(10.0)
    assert burn[("1h", "p99")] == pytest.approx(10.0)
    assert burn[("5m", "ttft")] is None            # no TTFT events yet
    assert eng.breached()[("5m", "p99")] == pytest.approx(10.0)
    g = bfm.gauge("bluefog_slo_burn_rate")
    assert g.value(window="5m", slo="p99") == pytest.approx(10.0)


def test_availability_burn_from_scheduler_counts():
    eng = SLOEngine(availability=0.9)              # 10% error budget
    sched = _StubSched()
    sched.completed = [1, 2, 3]
    sched.failed = [4]                             # 25% bad / 0.1 budget
    out = eng.observe(sched, now=10.0)
    assert out["burn_rates"][("5m", "availability")] == pytest.approx(2.5)


def test_slo_fast_burn_tripwire_and_cooldown():
    eng = SLOEngine(p99_ms=100.0, burn_alert_threshold=10.0,
                    tripwire_cooldown=5)
    now = 100.0
    for i in range(10):                            # 50% bad -> burn 50
        bfts.append(_LAT, 0.5 if i % 2 else 0.01, ts=now - 9 + i)
    out = eng.observe(now=now)
    assert [f["kind"] for f in out["tripwires"]] == ["slo_fast_burn"]
    assert bfm.counter("bluefog_tripwire_total").value(
        kind="slo_fast_burn") == 1
    ev = [e for e in bfflight.events() if e["kind"] == "tripwire"]
    assert ev and ev[-1]["name"] == "slo_fast_burn"
    assert ev[-1]["slo"] == "p99" and ev[-1]["burn"] > 10.0
    # cooldown: the next observes stay quiet until it expires
    for _ in range(3):
        assert eng.observe(now=now)["tripwires"] == []
    for _ in range(2):
        eng.observe(now=now)
    assert bfm.counter("bluefog_tripwire_total").value(
        kind="slo_fast_burn") == 2


def test_step_time_regression_tripwire():
    eng = SLOEngine(step_baseline_n=5, step_time_factor=2.0)
    # banked baseline: first 5 observations ~0.1 s; recent mean 0.5 s
    for i in range(5):
        bfts.append("bluefog_step_time_s", 0.1, ts=float(i))
    for i in range(5):
        bfts.append("bluefog_step_time_s", 0.5, ts=5.0 + i)
    out = eng.observe(now=10.0)
    fired = [f for f in out["tripwires"]
             if f["kind"] == "step_time_regression"]
    assert fired and fired[0]["baseline_s"] == pytest.approx(0.1)
    assert fired[0]["factor"] == pytest.approx(5.0)


def test_step_regression_quiet_while_banking():
    eng = SLOEngine(step_baseline_n=5)
    for i in range(6):                  # < 2n points: baseline still banking
        bfts.append("bluefog_step_time_s", 0.1 * (i + 1), ts=float(i))
    assert eng.observe(now=6.0)["tripwires"] == []


def test_consensus_stall_tripwire():
    eng = SLOEngine(consensus_factor=2.0)
    for i, v in enumerate((1.0, 0.1, 1.5)):       # contracted then re-expanded
        bfts.append("bluefog_consensus_distance_max", v, ts=float(i))
    out = eng.observe(now=3.0)
    fired = [f for f in out["tripwires"] if f["kind"] == "consensus_stall"]
    assert fired and fired[0]["latest_distance"] == pytest.approx(1.5)
    # a contracting trace never fires
    bfm.reset_metrics()
    eng2 = SLOEngine()
    for i, v in enumerate((1.0, 0.5, 0.1)):
        bfts.append("bluefog_consensus_distance_max", v, ts=float(i))
    assert eng2.observe(now=3.0)["tripwires"] == []


def test_queue_growth_idle_tripwire():
    eng = SLOEngine(idle_steps=3)
    sched = _StubSched(pending=4, in_flight=0)
    assert eng.observe(sched)["tripwires"] == []
    assert eng.observe(sched)["tripwires"] == []
    out = eng.observe(sched)
    assert [f["kind"] for f in out["tripwires"]] == ["queue_growth_idle"]
    assert out["tripwires"][0]["pending"] == 4
    # any progress resets the streak
    eng2 = SLOEngine(idle_steps=2)
    busy = _StubSched(pending=4, in_flight=1)
    idle = _StubSched(pending=4, in_flight=0)
    eng2.observe(idle)
    eng2.observe(busy)
    assert eng2.observe(idle)["tripwires"] == []


def test_slo_env_defaults(monkeypatch):
    monkeypatch.setenv("BLUEFOG_SLO_P99_MS", "123")
    monkeypatch.setenv("BLUEFOG_SLO_TTFT_MS", "456")
    monkeypatch.setenv("BLUEFOG_SLO_AVAILABILITY", "0.95")
    eng = SLOEngine()
    assert eng.p99_s == pytest.approx(0.123)
    assert eng.ttft_s == pytest.approx(0.456)
    assert eng.availability == pytest.approx(0.95)
    with pytest.raises(ValueError):
        SLOEngine(availability=1.5)


# ---------------------------------------------------------------------------
# tools/trace_report.py vs the committed fixtures
# ---------------------------------------------------------------------------

def _fixture_bundles():
    return [os.path.join(FIXTURES, f"trace_rank{r}.trace.jsonl")
            for r in (0, 1)]


def test_trace_report_fixture_schema_and_breakdown():
    tr = _load_tool("tools/trace_report")
    doc, bundles = tr.report_from_files(_fixture_bundles())
    assert doc["ok"] and doc["schema"] == "bluefog-trace-report-1"
    assert doc["n_ranks"] == 2 and doc["ranks"] == [0, 1]
    assert doc["n_spans"] == 10 and doc["dropped"] == 2
    req = doc["requests"]["req-r0-1"]
    assert req["total_s"] == pytest.approx(0.08)
    assert req["queue_s"] == pytest.approx(0.01)
    assert req["prefill_s"] == pytest.approx(0.02)
    assert req["decode_s"] == pytest.approx(0.04)
    assert req["gap_s"] == pytest.approx(0.01)
    # the construction invariant: parts sum exactly to the E2E total
    assert req["queue_s"] + req["prefill_s"] + req["decode_s"] \
        + req["gap_s"] == pytest.approx(req["total_s"])
    assert req["n_decode_calls"] == 2 and req["prefix_hit"] is False
    assert req["tokens"] == 4 and req["replica"] == 0
    assert doc["critical_path"][0][0] == "req-r0-1"
    assert doc["train"] == {"steps": 2, "step_mean_s": 0.2, "probes": 1}
    # chrome trace: per-rank pids, metadata lanes, non-negative rel times
    ch = tr.chrome_trace(bundles)
    xs = [e for e in ch["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in ch["traceEvents"] if e["ph"] == "M"]
    assert {e["pid"] for e in xs} == {0, 1}
    assert any(e["name"] == "process_name" for e in ms)
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    # rank1 spans sit 0.5 s of wall clock after rank0's anchor
    r1 = min(e["ts"] for e in xs if e["pid"] == 1)
    assert r1 == pytest.approx(0.5e6, abs=1e3)


def test_trace_report_torn_line_and_bad_schema(tmp_path):
    tr = _load_tool("tools/trace_report")
    good = os.path.join(FIXTURES, "trace_rank0.trace.jsonl")
    torn = tmp_path / "torn.trace.jsonl"
    torn.write_text(open(good).read() + '{"kind": "span", "tru')
    doc, _ = tr.report_from_files([str(torn)])
    assert doc["ok"] and any("torn" in n for n in doc["notes"])
    bad = tmp_path / "bad.trace.jsonl"
    bad.write_text('{"kind": "meta", "schema": "nope", "mono": 0, "wall": 0}\n')
    with pytest.raises(ValueError):
        tr.load_bundle(str(bad))


def test_trace_report_gains_the_stage_rings_view(tmp_path):
    """``flush`` writes the stage ring beside the bundle and the report
    shows it by stage and bucket with the largest excesses and their
    paths, under the grouping rule the benchmark's reader keeps a copy of:
    the two copies agree on the same records."""
    tr = _load_tool("tools/trace_report")
    bftrace.configure(str(tmp_path))
    for i in range(12):
        with bftrace.stage("s", "step", cat="serve"):
            with bftrace.stage("s", "pack", cat="serve", lanes=2, S=4):
                pass
            with bftrace.stage("e", "decode_call", cat="engine", S=4):
                with bftrace.stage("e", "dispatch", cat="engine"):
                    pass
                with bftrace.stage("e", "collect", cat="engine"):
                    with bftrace.stage("e", "wait", cat="engine"):
                        time.sleep(0.25 if i == 7 else 0.001)
    records = [tuple(r) for r in bftrace.stage_records()]
    doc, _ = tr.report_from_files([bftrace.flush()])
    view = doc["stages"]["0"]
    assert view["n_records"] == len(records) == 72 and view["dropped"] == 0
    assert view["pauses"] == [] and view["unjudged_groups"] == 0
    wait = "serve.step/engine.decode_call/collect/wait"
    assert [g[:3] for g in view["groups"]][0] == [wait, 4, 12]
    assert {(g[0], g[1]) for g in view["groups"]} == {
        ("serve.step/pack", 4), (wait, 4), ("serve.step/(self)", None),
        ("serve.step/engine.decode_call/dispatch", 4),
        ("serve.step/engine.decode_call/collect/(self)", 4),
        ("serve.step/engine.decode_call/(self)", 4)}
    at, path, bucket, excess, median, n = view["largest_excesses"][0]
    assert (path, bucket, n) == (wait, 4, 12)
    assert 0.24 < excess < 0.35 and 0.001 <= median < 0.01
    assert abs(at - time.time()) < 60               # on the wall clock
    assert len(view["largest_excesses"]) == 10
    # the benchmark's copy of the rule, on the same records
    sys.path.insert(0, REPO)
    try:
        from perfbench.harness import stage_ring
    finally:
        sys.path.remove(REPO)
    ana = stage_ring.Analysis(records, (records[0][2] - 1, records[-1][3] + 1))
    assert {k: len(v) for k, v in ana.groups.items()} == {
        (g[0], g[1]): g[2] for g in view["groups"]}
    assert ana.excess_s_max == pytest.approx(excess, abs=1e-8)
    assert [s.path for s in ana.stalls] == [wait]
    # a window cuts the view as it cuts the spans
    late, _ = tr.report_from_files([bftrace.bundle_path()],
                                   since=time.time() + 60)
    assert late["stages"]["0"]["n_records"] == 0


# ---------------------------------------------------------------------------
# tools/metrics_report.py: histogram percentiles (vs committed fixtures)
# ---------------------------------------------------------------------------

def test_bucket_percentile_math():
    mr = _load_tool("tools/metrics_report")
    # 10 events: 5 in (0, 0.1], 4 in (0.1, 1.0], 1 overflow
    buckets = [[0.1, 5], [1.0, 4], ["+Inf", 1]]
    assert mr._bucket_percentile(buckets, 50) == pytest.approx(0.1)
    # p90 = 9th event: 4/4 through the (0.1, 1.0] bucket -> its far edge
    assert mr._bucket_percentile(buckets, 90) == pytest.approx(1.0)
    assert mr._bucket_percentile(buckets, 99) == pytest.approx(1.0)  # +Inf clamp
    assert mr._bucket_percentile([[0.1, 0], ["+Inf", 0]], 50) is None
    ps = mr._bucket_percentiles(buckets)
    assert set(ps) == {"p50", "p90", "p99"}


def test_metrics_report_percentiles_on_fixture():
    mr = _load_tool("tools/metrics_report")
    doc = mr.report_from_files(
        [os.path.join(FIXTURES, f"metrics_host{h}.metrics.jsonl")
         for h in (0, 1)])
    assert doc["ok"] and doc["n_hosts"] == 2
    hists = {n: m for n, m in doc["metrics"].items()
             if m.get("type") == "histogram" and m.get("buckets")}
    assert hists, "fixtures must carry at least one histogram"
    for name, h in hists.items():
        ps = h["percentiles"]
        assert set(ps) == {"p50", "p90", "p99"}, name
        vals = [ps["p50"], ps["p90"], ps["p99"]]
        assert all(v is not None for v in vals), name
        assert vals == sorted(vals), f"{name}: percentiles not monotone"
    st = doc["summary"]["step_time_s"]
    assert {"p50", "p90", "p99"} <= set(st)
    assert st["p50"] <= st["p99"]


# ---------------------------------------------------------------------------
# tools/postmortem.py: a dead replica's lost requests are NAMED
# ---------------------------------------------------------------------------

def test_postmortem_names_lost_requests():
    pm = _load_tool("tools/postmortem")
    bundles = {
        0: {
            "serve": {
                "dead_replicas": [1],
                "failed": [],
                "in_flight_traces": {
                    "0": [{"id": 3, "trace": "req-r0-4", "age_s": 0.25,
                           "queue_s": 0.01}],
                },
                "queued": [
                    {"id": 5, "trace": "req-r0-6", "age_s": 1.5},
                    {"id": 6, "trace": "req-r0-7", "age_s": 1.2},
                ],
            },
            "events": [
                {"kind": "serve", "name": "replica_killed", "replica": 1,
                 "requeued_requests": [5, 6]},
            ],
        },
    }
    notes = []
    out = pm._serve_block(bundles, notes)
    assert out["dead_replicas"] == [1]
    rows = out["lost_requests"]["1"]
    assert [r["id"] for r in rows] == [5, 6]
    assert rows[0]["trace"] == "req-r0-6"
    named = [n for n in notes if "went down holding" in n]
    assert named and "req 5 (trace req-r0-6, age 1.500s)" in named[0]
    assert "req 6 (trace req-r0-7" in named[0]


# ---------------------------------------------------------------------------
# The acceptance drill: traced 8-rank estate, breakdown == measured E2E
# ---------------------------------------------------------------------------

_CFG = dict(vocab=32, d_model=32, heads=4, layers=4, seq_len=32)


def _serve_estate(cpu_devices, seed=7):
    """2 training replicas (pp=2) on devices 0-3, 2 serving replicas
    (pp=2) on devices 4-7 — the test_serve estate shape."""
    import optax
    import bluefog_tpu.optimizers as bfopt

    cfg = compose.LMConfig(**_CFG)
    train_m = compose.compose_parallelism(2, 2, 1, 1,
                                          devices=cpu_devices[:4])
    serve_m = compose.compose_parallelism(2, 2, 1, 1,
                                          devices=cpu_devices[4:])
    grad_fn = compose.make_lm_grad_fn(cfg, train_m)
    step, strategy = compose.make_train_step(
        train_m, grad_fn, optax.sgd(0.05))
    train_params = compose.init_lm_params(cfg, train_m, seed=1)
    state = bfopt.init_distributed(strategy, train_params)
    toks = compose.make_lm_batch(cfg, train_m)
    train_params = compose.device_put(train_m, train_params)
    scfg = ServeConfig(batch_buckets=(1, 2, 4), prefill_buckets=(4, 8),
                       slots=4, max_len=32)
    eng = ServeEngine(serve_m, cfg,
                      compose.init_lm_params(cfg, serve_m, seed=seed), scfg)
    eng.warmup()
    return cfg, (step, state, train_params, toks), eng


# The three estate drills below compile the full train→serve estate each
# (~10 s apiece) — tier-1 keeps only the host-side battery above; the
# drills gate `make obs-trace-smoke`, which runs this file unfiltered.
@pytest.mark.slow
def test_traced_estate_breakdown_matches_measured_e2e(cpu_devices, tmp_path):
    """Tracing armed over the full train→serve estate: the merged report's
    per-request total IS the scheduler's measured E2E latency (same clock,
    same stamps — equal to the ms), parts sum to the total, train spans
    ride alongside, and the whole thing costs nothing the invariants can
    see: donation intact, zero retraces."""
    import jax

    cfg, (step, state, train_params, toks), eng = _serve_estate(cpu_devices)
    bftrace.configure(str(tmp_path))
    sched = Scheduler(eng)
    cache_probe = eng.cache["k"]

    rng = np.random.default_rng(0)
    reqs = [sched.submit(rng.integers(0, cfg.vocab,
                                      int(rng.integers(2, 9))).tolist(),
                         max_new_tokens=int(rng.integers(2, 6)))
            for _ in range(12)]
    train_done, guard = 0, 0
    while not sched.done:
        guard += 1
        assert guard < 500, "scheduler failed to drain"
        sched.step()
        if train_done < 3:
            train_params, state, loss = step(train_params, state, toks)
            jax.block_until_ready(loss)
            train_done += 1

    assert len(sched.completed) == 12
    bundle = bftrace.flush()
    tr = _load_tool("tools/trace_report")
    doc, _ = tr.report_from_files([bundle])
    assert doc["ok"] and doc["dropped"] == 0

    # every retired request has a row whose total equals the measured E2E
    for req in reqs:
        row = doc["requests"][req.trace_id]
        e2e = req.finished_at - req.submitted_at
        assert row["total_s"] == pytest.approx(e2e, abs=1e-3)
        assert row["queue_s"] + row["prefill_s"] + row["decode_s"] \
            + row["gap_s"] == pytest.approx(row["total_s"], abs=1e-6)
        assert row["n_decode_calls"] >= 1
        assert row["tokens"] == req.max_new_tokens
    # the critical path is the slowest request, and is one of ours
    slowest = max(reqs, key=lambda r: r.finished_at - r.submitted_at)
    assert doc["critical_path"][0][0] == slowest.trace_id
    # train + engine spans rode along in the same bundle
    assert doc["train"]["steps"] == 3
    cats = {s.get("cat") for s in bftrace.spans()}
    assert {"serve", "engine", "train"} <= cats

    # observability stayed free: donation intact, nothing retraced
    assert cache_probe.is_deleted()
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    sched.close()


@pytest.mark.slow
def test_flash_crowd_burn_crosses_threshold_and_trips(cpu_devices):
    """The acceptance's SLO leg: a flash-crowd burst against an
    impossible latency target drives the 5m p99 burn-rate gauge past the
    fast-burn threshold and records a tripwire flight event."""
    cfg, _, eng = _serve_estate(cpu_devices)
    sched = Scheduler(eng)
    slo = SLOEngine(p99_ms=0.001, burn_alert_threshold=10.0)
    sched.attach_slo(slo)

    rng = np.random.default_rng(1)
    for _ in range(16):                           # the crowd arrives at once
        sched.submit(rng.integers(0, cfg.vocab,
                                  int(rng.integers(2, 9))).tolist(),
                     max_new_tokens=3)
    guard = 0
    while not sched.done:
        guard += 1
        assert guard < 500
        sched.step()

    assert len(sched.completed) == 16
    burn = slo.last_burn[("5m", "p99")]
    assert burn is not None and burn > 10.0       # budget torched
    assert bfm.gauge("bluefog_slo_burn_rate").value(
        window="5m", slo="p99") == pytest.approx(burn)
    assert any(f["kind"] == "slo_fast_burn" for f in slo.fired)
    ev = [e for e in bfflight.events() if e["kind"] == "tripwire"]
    assert ev and ev[0]["name"] == "slo_fast_burn"
    assert bfm.counter("bluefog_tripwire_total").value(
        kind="slo_fast_burn") >= 1
    sched.close()


@pytest.mark.slow
def test_tracing_and_timeseries_overhead_invariants(cpu_devices, tmp_path):
    """Satellite pin: with tracing AND per-metric history both armed, a
    warmed serve loop still donates its carry and compiles nothing new —
    the whole observability stack rides outside the jit boundary."""
    cfg, _, eng = _serve_estate(cpu_devices)
    bftrace.configure(str(tmp_path))
    slo = SLOEngine()                  # arms the latency/TTFT/step rings
    sched = Scheduler(eng)
    sched.attach_slo(slo)
    cache_probe = eng.cache["k"]
    rng = np.random.default_rng(2)
    for _ in range(8):
        sched.submit(rng.integers(0, cfg.vocab,
                                  int(rng.integers(2, 9))).tolist(),
                     max_new_tokens=4)
    sched.drain()
    assert len(sched.completed) == 8
    assert cache_probe.is_deleted()
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    assert bfts.history(_LAT), "armed latency ring must have filled"
    assert len(bftrace.spans()) > 0
    sched.close()


def test_trace_report_since_last_window(tmp_path):
    tr = _load_tool("tools/trace_report")
    # window_bounds: later bound wins; non-positive --last rejected
    assert tr.window_bounds(since=50.0, last=10.0, now=100.0) == 90.0
    assert tr.window_bounds(since=95.0, last=10.0, now=100.0) == 95.0
    assert tr.window_bounds() is None
    with pytest.raises(ValueError):
        tr.window_bounds(last=-1)

    # anchor with wall == mono so span endpoints read as wall times
    lines = [json.dumps({"kind": "meta", "schema": "bluefog-trace-1",
                         "rank": 0, "mono": 0.0, "wall": 0.0}),
             json.dumps({"kind": "span", "seq": 0, "trace": "t", "span": 1,
                         "name": "train_step", "cat": "train",
                         "t0": 1.0, "t1": 5.0, "step": 1}),
             json.dumps({"kind": "span", "seq": 1, "trace": "t", "span": 2,
                         "name": "train_step", "cat": "train",
                         "t0": 8.0, "t1": 12.0, "step": 2})]
    p = tmp_path / "w.trace.jsonl"
    p.write_text("\n".join(lines) + "\n")

    doc, _ = tr.report_from_files([str(p)])
    assert doc["n_spans"] == 2 and "window" not in doc

    # the span that *ended* before the cut is dropped (and noted)...
    doc, _ = tr.report_from_files([str(p)], since=6.0)
    assert doc["n_spans"] == 1 and doc["train"]["steps"] == 1
    assert doc["window"] == {"since_ts": 6.0}
    assert any("dropped 1 span" in n for n in doc["notes"])

    # ...but a span still *running into* the window is kept: t1 inside
    doc, _ = tr.report_from_files([str(p)], since=4.0)
    assert doc["n_spans"] == 2
