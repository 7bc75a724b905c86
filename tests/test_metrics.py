"""Telemetry tests: registry primitives, exporters, the retrace sentinel,
consensus-health probes, and the multi-host report merger.

The centerpiece is the acceptance integration test: a short CPU training
loop with metrics enabled must leave a JSONL log and a Prometheus scrape
containing step-time, op-count/bytes, cache hit/miss, and a consensus-
distance series that is monotonically non-increasing on the static
doubly-stochastic Exp2(8) topology — and enabling ``metrics_every_k``
must cause ZERO additional compilations after warmup (retrace sentinel
stays 0, donation flags unchanged, the donated input really consumed).
"""
import importlib.util
import json
import os
import time
import types
import urllib.request

import jax
import jax.numpy as jnp
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import topology as tu
from bluefog_tpu.utils import metrics as bfm
from bluefog_tpu.utils import timeline as tl
from bluefog_tpu.utils import watchdog as wd

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURES = os.path.join(REPO, "tests", "fixtures")

N, D = 8, 16


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts from an empty registry and leaves no exporter
    running (the registry is process-global)."""
    bfm.reset_metrics()
    yield
    bfm.stop_metrics()
    bfm.stop_http_server()
    bfm.reset_metrics()


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------

def test_counter_labels_and_totals():
    c = bfm.counter("t_ops", "test ops")
    c.inc(op="put")
    c.inc(2.5, op="put")
    c.inc(op="get")
    assert c.value(op="put") == 3.5
    assert c.value(op="get") == 1.0
    assert c.value(op="missing") == 0.0
    assert c.total() == 4.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # same name returns the same object (a registry, not a constructor)
    assert bfm.counter("t_ops") is c


def test_gauge_and_ewma():
    g = bfm.gauge("t_g")
    assert g.value() is None
    g.set(3.0)
    g.set(1.5)
    assert g.value() == 1.5
    e = bfm.ewma("t_e", alpha=0.5)
    e.observe(1.0)
    assert e.value() == 1.0            # first observation seeds the average
    e.observe(3.0)
    assert abs(e.value() - 2.0) < 1e-9  # 0.5*3 + 0.5*1


def test_metric_type_conflict_raises():
    bfm.counter("t_conflict")
    with pytest.raises(TypeError):
        bfm.gauge("t_conflict")


def test_histogram_buckets_and_percentiles():
    h = bfm.histogram("t_h", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    d = h.dump()
    assert d["count"] == 4
    assert abs(d["sum"] - 5.555) < 1e-9
    assert d["buckets"][-1][0] == "+Inf"        # always closed at +Inf
    # per-bucket (non-cumulative) counts: one observation each
    assert [c for _, c in d["buckets"]] == [1, 1, 1, 1]
    assert h.percentile(0) == 0.005
    assert h.percentile(100) == 5.0
    assert bfm.histogram("t_empty").percentile(50) is None


def test_record_op_counts_and_bytes():
    x = jnp.ones((4, 4), jnp.float32)
    bfm.record_op("neighbor_allreduce", (x,))
    bfm.record_op("neighbor_allreduce", (x, x))
    bfm.record_op("barrier", ())
    ops = bfm.counter("bluefog_ops_total")
    assert ops.value(op="neighbor_allreduce") == 2
    assert ops.value(op="barrier") == 1
    assert bfm.counter("bluefog_op_bytes_total").value(
        op="neighbor_allreduce") == 3 * 64


def test_record_step_feeds_all_families():
    bfm.record_step(0.02, steps=4, donated=True, fused_k=4)
    assert bfm.counter("bluefog_train_steps_total").total() == 4
    assert bfm.get_metric("bluefog_step_time_s").dump()["count"] == 1
    assert bfm.gauge("bluefog_step_time_ewma_s").value() == 0.02
    assert bfm.gauge("bluefog_step_donated").value() == 1.0
    assert bfm.gauge("bluefog_step_fused_k").value() == 4.0


# ---------------------------------------------------------------------------
# Retrace sentinel
# ---------------------------------------------------------------------------

def test_retrace_sentinel_fires_only_after_steady_state():
    bfm.note_cache_event(False, key="warmup-compile")
    bfm.note_cache_event(True)
    assert bfm.counter("bluefog_compile_cache_misses_total").total() == 1
    assert bfm.counter("bluefog_compile_cache_hits_total").total() == 1
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0

    bfm.mark_steady_state(True)
    assert bfm.in_steady_state()
    bfm.note_cache_event(False, key="drifted-shape")
    bfm.note_cache_event(False, key="drifted-shape-2")
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 2
    # hits in steady state are fine
    bfm.note_cache_event(True)
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 2
    bfm.mark_steady_state(False)
    bfm.note_cache_event(False)
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 2


def test_metrics_every_k_validation():
    ok = types.SimpleNamespace(axes=("rank",))
    bfopt._check_metrics_every_k(None, ok)
    bfopt._check_metrics_every_k(3, ok)
    with pytest.raises(ValueError):
        bfopt._check_metrics_every_k(0, ok)
    with pytest.raises(ValueError):
        bfopt._check_metrics_every_k(
            1, types.SimpleNamespace(axes=("machine", "local")))


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_render_prometheus_cumulative_buckets_and_labels():
    bfm.counter("t_req", "requests").inc(2, op="put")
    h = bfm.histogram("t_lat", "latency", buckets=(0.001, 1.0))
    h.observe(0.0007)
    h.observe(2.0)
    body = bfm.render_prometheus()
    assert "# HELP t_req requests" in body
    assert "# TYPE t_req counter" in body
    assert 't_req{op="put"} 2.0' in body
    assert "# TYPE t_lat histogram" in body
    # buckets are CUMULATIVE in the exposition
    assert 't_lat_bucket{le="0.001"} 1' in body
    assert 't_lat_bucket{le="1.0"} 1' in body
    assert 't_lat_bucket{le="+Inf"} 2' in body
    assert "t_lat_sum 2.0007" in body
    assert "t_lat_count 2" in body


def test_http_server_scrapes_live_registry():
    port = bfm.start_http_server(0)
    assert port > 0
    bfm.counter("t_live").inc(7)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    assert "t_live 7.0" in body
    # registry is live, not snapshotted at server start
    bfm.counter("t_live").inc()
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    assert "t_live 8.0" in body
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/other", timeout=10)
    bfm.stop_http_server()


def test_jsonl_exporter_schema(tmp_path):
    prefix = str(tmp_path / "m")
    assert bfm.start_metrics(prefix)
    assert bfm.metrics_active()
    assert not bfm.start_metrics(prefix)      # second start is a no-op
    bfm.counter("t_c").inc()
    assert bfm.sample(step=1)
    bfm.counter("t_c").inc()
    out = bfm.stop_metrics()                   # writes one final sample
    assert out == prefix + ".metrics.jsonl"
    assert not bfm.metrics_active()
    assert not bfm.sample()                    # inactive -> no-op

    lines = [json.loads(l) for l in open(out)]
    assert len(lines) == 2
    for line in lines:
        assert {"ts", "host", "step", "metrics"} <= set(line)
    assert lines[0]["step"] == 1
    assert lines[0]["metrics"]["t_c"]["values"][""] == 1.0
    assert lines[1]["metrics"]["t_c"]["values"][""] == 2.0


def test_maybe_start_from_env(tmp_path, monkeypatch):
    prefix = str(tmp_path / "envm")
    monkeypatch.setenv("BLUEFOG_METRICS", prefix)
    monkeypatch.delenv("BLUEFOG_METRICS_PORT", raising=False)
    bfm.maybe_start_from_env()
    assert bfm.metrics_active()
    assert bfm.stop_metrics() == prefix + ".metrics.jsonl"


# ---------------------------------------------------------------------------
# Multi-host report merger (tools/metrics_report.py)
# ---------------------------------------------------------------------------

def _simulate_host(tmp_path, monkeypatch, host, n_steps):
    """One 'host' of a multi-host job: its own registry history and its
    own JSONL log, written through the real exporter."""
    bfm.reset_metrics()
    monkeypatch.setattr(bfm, "_host_id", lambda: host)
    prefix = str(tmp_path / f"host{host}")
    assert bfm.start_metrics(prefix)
    for i in range(n_steps):
        bfm.record_step(0.01 * (host + 1), steps=1, donated=True, fused_k=1)
        bfm.counter("bluefog_compile_cache_hits_total").inc()
        bfm.gauge("bluefog_consensus_distance_max").set(4.0 / (i + 1))
        bfm.sample(step=i + 1)
    return bfm.stop_metrics()


def test_metrics_report_merges_two_hosts(tmp_path, monkeypatch):
    """Acceptance: two simulated hosts' JSONL logs merge into one report —
    counters summed, histograms bucket-summed, gauges per-host."""
    p0 = _simulate_host(tmp_path, monkeypatch, host=0, n_steps=4)
    p1 = _simulate_host(tmp_path, monkeypatch, host=1, n_steps=3)
    mr = _load_tool("metrics_report")
    rep = mr.report_from_files([p0, p1])
    assert rep["ok"] and rep["n_hosts"] == 2
    assert rep["hosts"] == [0, 1]
    assert rep["n_samples"] == (4 + 1) + (3 + 1)    # + final stop samples
    steps = rep["metrics"]["bluefog_train_steps_total"]
    assert steps["values"][""] == 7.0                # summed across hosts
    hist = rep["metrics"]["bluefog_step_time_s"]
    assert hist["count"] == 7                        # bucket-wise merged
    g = rep["metrics"]["bluefog_consensus_distance_max"]
    assert set(g["per_host"]) == {"0", "1"}          # gauges stay per-host
    # final values: host0 4.0/4, host1 4.0/3 — max is host1's
    assert g["max"] == pytest.approx(4.0 / 3.0)
    series = rep["series"]["bluefog_consensus_distance_max"]
    assert {row[1] for row in series} == {0, 1}
    ts = [row[0] for row in series]
    assert ts == sorted(ts)
    assert rep["summary"]["cache"]["hits"] == 7.0
    assert rep["summary"]["cache"]["hit_ratio"] == 1.0


def test_metrics_report_on_committed_fixtures():
    """The committed two-host fixtures (also exercised by `make obs-smoke`)
    pin the JSONL schema: a rewrite of the exporter that breaks the report
    fails here."""
    mr = _load_tool("metrics_report")
    rep = mr.report_from_files([
        os.path.join(FIXTURES, "metrics_host0.metrics.jsonl"),
        os.path.join(FIXTURES, "metrics_host1.metrics.jsonl")])
    assert rep["ok"] and rep["n_hosts"] == 2 and rep["hosts"] == [0, 1]
    assert rep["n_samples"] == 10
    assert rep["summary"]["cache"]["hits"] == 19.0
    assert rep["metrics"]["bluefog_ops_total"]["values"][
        'op="neighbor_allreduce"'] == 24.0
    ewma = rep["series"]["bluefog_step_time_ewma_s"]
    assert len(ewma) >= 8 and ewma == sorted(ewma, key=lambda r: r[0])


def test_metrics_report_skips_torn_lines(tmp_path):
    log = tmp_path / "torn.metrics.jsonl"
    good = {"ts": 1.0, "host": 0, "step": 1,
            "metrics": {"c": {"type": "counter", "values": {"": 2.0}}}}
    log.write_text(json.dumps(good) + "\n" + '{"ts": 2.0, "host": 0, "st')
    mr = _load_tool("metrics_report")
    rep = mr.report_from_files([str(log)])
    assert rep["ok"] and rep["n_samples"] == 1
    assert rep["metrics"]["c"]["values"][""] == 2.0


# ---------------------------------------------------------------------------
# Satellite fixes: timeline open-span flush, watchdog stall telemetry
# ---------------------------------------------------------------------------

def test_stop_timeline_flushes_open_spans(tmp_path):
    """Spans still open at stop (a hang, an exception path) must land in
    the artifact as complete events up to the stop time, not vanish."""
    prefix = str(tmp_path / "fl")
    assert tl.start_timeline(prefix, with_device_trace=False)
    assert tl.timeline_start_activity("t1", "NEGOTIATE")
    assert tl.timeline_start_activity("t1", "COMMUNICATE")   # nested
    assert tl.timeline_start_activity("t2", "QUEUE")
    out = tl.stop_timeline()
    events = json.load(open(out))["traceEvents"]
    got = {(e["cat"], e["name"]) for e in events}
    assert {("t1", "NEGOTIATE"), ("t1", "COMMUNICATE"),
            ("t2", "QUEUE")} <= got, got
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
    # stop cleared the open-span table: a fresh session starts clean
    assert tl.start_timeline(str(tmp_path / "fl2"), with_device_trace=False)
    assert tl.stop_timeline().endswith("fl2.activities.json")


def test_watchdog_stall_increments_counter_and_records_span(
        tmp_path, monkeypatch):
    prefix = str(tmp_path / "wd")
    assert tl.start_timeline(prefix, with_device_trace=False)
    try:
        # a computation that "stalls" for several watchdog intervals
        monkeypatch.setattr(wd, "jax", types.SimpleNamespace(
            block_until_ready=lambda x: (time.sleep(0.3), x)[1]))
        assert wd.synchronize_with_watchdog(
            7, interval=0.05, name="stalltest") == 7
    finally:
        out = tl.stop_timeline()
    stalls = bfm.counter("bluefog_watchdog_stalls_total")
    assert stalls.value(name="stalltest") >= 1
    events = json.load(open(out))["traceEvents"]
    spans = [e for e in events
             if e["name"] == "STALL" and e["cat"] == "stalltest"]
    assert spans and all(e["ph"] == "X" and e["dur"] > 0 for e in spans)


def test_watchdog_happy_path_stays_silent():
    """A wait that completes at once counts no stall of ITS name.  (The
    process-wide total is not this test's to read: the watcher thread of
    an earlier wait, here the stall test's, whose sixth interval of 0.05 s
    ends as its 0.3 s computation does, can still be inside its loop body
    when the registry is reset, and count its stall after it.)"""
    stalls = bfm.counter("bluefog_watchdog_stalls_total")
    before = stalls.value(name="quick")
    assert wd.synchronize_with_watchdog(
        jnp.ones(()), interval=60.0, name="quick") is not None
    assert stalls.value(name="quick") == before == 0


def test_watchdog_timeout_escalates_to_error(monkeypatch):
    """timeout= turns the warn-forever watchdog into a failure detector:
    a hung computation raises TimeoutError naming the computation and the
    stall intervals elapsed, and counts a timeout metric."""
    monkeypatch.setattr(wd, "jax", types.SimpleNamespace(
        block_until_ready=lambda x: (time.sleep(10), x)[1]))
    with pytest.raises(TimeoutError, match=
                       r"slowstep did not complete within 0\.15 s"):
        wd.synchronize_with_watchdog(
            7, interval=0.04, name="slowstep", timeout=0.15)
    try:
        wd.synchronize_with_watchdog(
            7, interval=0.04, name="slowstep", timeout=0.15)
    except TimeoutError as e:
        assert "stall-warning interval" in str(e)
    assert bfm.counter("bluefog_watchdog_timeouts_total").value(
        name="slowstep") == 2


def test_watchdog_timeout_happy_path_unchanged():
    """A timeout that never fires changes nothing: the value comes back
    and no timeout metric appears."""
    out = wd.synchronize_with_watchdog(
        jnp.ones(()), interval=60.0, name="quick2", timeout=30.0)
    assert out is not None
    assert bfm.counter("bluefog_watchdog_timeouts_total").total() == 0


def test_watchdog_timeout_path_propagates_errors(monkeypatch):
    """An error raised by the blocking wait surfaces on the CALLER thread,
    not swallowed on the helper."""
    def boom(x):
        raise ValueError("dead backend")
    monkeypatch.setattr(wd, "jax", types.SimpleNamespace(
        block_until_ready=boom))
    with pytest.raises(ValueError, match="dead backend"):
        wd.synchronize_with_watchdog(7, name="errpath", timeout=5.0)


# ---------------------------------------------------------------------------
# The acceptance integration test: training loop under full telemetry
# ---------------------------------------------------------------------------

@pytest.fixture
def ctx(cpu_devices):
    bf.init(devices=cpu_devices)
    bf.set_topology(tu.ExponentialTwoGraph(N), is_weighted=True)
    yield
    bf.shutdown()


def grad_fn(params, batch):
    loss = jnp.mean((params["w"] - batch) ** 2)
    return loss, jax.grad(lambda p: jnp.mean((p["w"] - batch) ** 2))(params)


def test_training_loop_full_telemetry(ctx, tmp_path):
    bfm.reset_metrics()
    prefix = str(tmp_path / "train")
    assert bfm.start_metrics(prefix)
    port = bfm.start_http_server(0)

    # lr=0 pure gossip: params evolve ONLY by mixing, so the consensus
    # distance must contract monotonically on the static doubly-stochastic
    # Exp2(8) topology (the paper's convergence mechanism, isolated)
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.0), bfopt.neighbor_communicator(bf.static_schedule()))
    params = {"w": jnp.broadcast_to(
        jnp.arange(float(N))[:, None], (N, D)).astype(jnp.float32)}
    state = bfopt.init_distributed(strat, params)
    step = bfopt.make_train_step(grad_fn, strat, metrics_every_k=2)
    batch = jnp.zeros((N, D), jnp.float32)

    # eager ops (first compiles included) run BEFORE warmup completes, so
    # their cache misses cannot trip the steady-state sentinel
    x = bf.shard_distributed(batch + 1.0)
    bf.synchronize(bf.neighbor_allreduce(x))
    bf.synchronize(bf.allreduce(x))

    sizes = []
    w1 = None
    for i in range(6):
        params, state, loss = step(params, state, batch)
        jax.block_until_ready(loss)
        sizes.append(step._jit_cache_len())
        if i == 0:
            w1 = params["w"]          # first mesh-sharded (donatable) buffer
    # metrics_every_k left donation intact: once inputs carry the mesh
    # sharding (call 2 on), the pre-step buffer is consumed in place
    assert w1.is_deleted()

    # ZERO additional compilations after warmup: the jit cache stopped
    # growing at warmup (call 2) and the retrace sentinel never fired
    assert sizes[1] is not None and sizes[-1] == sizes[1], sizes
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    assert bfm.in_steady_state()
    assert bfm.gauge("bluefog_step_donated").value() == 1.0
    assert bfm.gauge("bluefog_step_fused_k").value() == 1.0

    # Prometheus scrape carries every required family
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    for needle in (
            "bluefog_step_time_s_bucket", "bluefog_step_time_ewma_s",
            'bluefog_ops_total{op="neighbor_allreduce"}',
            'bluefog_op_bytes_total{op="neighbor_allreduce"}',
            "bluefog_compile_cache_hits_total",
            "bluefog_compile_cache_misses_total",
            "bluefog_consensus_distance_max",
            "bluefog_train_steps_total"):
        assert needle in body, needle
    bfm.stop_http_server()

    out = bfm.stop_metrics()
    lines = [json.loads(l) for l in open(out)]
    assert len(lines) == 7            # one per step call + the stop sample
    for line in lines:
        assert {"ts", "host", "step", "metrics"} <= set(line)
    fams = set(lines[-1]["metrics"])
    assert {"bluefog_step_time_s", "bluefog_step_time_ewma_s",
            "bluefog_ops_total", "bluefog_op_bytes_total",
            "bluefog_compile_cache_hits_total",
            "bluefog_compile_cache_misses_total",
            "bluefog_consensus_distance_max",
            "bluefog_neighbor_disagreement_max"} <= fams, fams
    assert lines[-1]["metrics"]["bluefog_step_time_s"]["count"] == 6

    # the consensus-distance series contracts monotonically
    dist = [line["metrics"]["bluefog_consensus_distance_max"]["values"][""]
            for line in lines
            if "bluefog_consensus_distance_max" in line["metrics"]]
    assert len(dist) >= 3, dist
    assert all(b <= a + 1e-6 for a, b in zip(dist, dist[1:])), dist
    assert dist[-1] < 0.5 * dist[0], dist        # it genuinely contracted

    # the artifact summary block is complete
    ms = bfm.metrics_summary()
    assert ms["step_time_s"]["count"] == 6
    assert ms["step_time_s"]["p50"] is not None
    assert ms["comm_bytes_total"] > 0
    assert ms["cache"]["hits"] > 0 and ms["cache"]["misses"] > 0
    assert ms["retrace_after_warmup"] == 0
    assert ms["consensus"]["consensus_distance_max"] == dist[-1]


def test_diagnose_consensus_direct(ctx):
    """diagnose_consensus as a user API: per-rank arrays, gauges
    published, and exact zero once ranks agree."""
    from bluefog_tpu import diagnostics as bfdiag

    params = {"w": jnp.broadcast_to(
        jnp.arange(float(N))[:, None], (N, D)).astype(jnp.float32)}
    out = bfdiag.diagnose_consensus(params)
    assert out["consensus_distance"].shape == (N,)
    assert out["neighbor_disagreement"].shape == (N,)
    assert out["consensus_distance_max"] > 0
    assert out["neighbor_disagreement_max"] > 0
    assert bfm.gauge("bluefog_consensus_distance_max").value() == pytest.approx(
        out["consensus_distance_max"])

    same = {"w": jnp.ones((N, D), jnp.float32)}
    out = bfdiag.diagnose_consensus(same)
    assert out["consensus_distance_max"] == pytest.approx(0.0, abs=1e-5)
    assert out["neighbor_disagreement_max"] == pytest.approx(0.0, abs=1e-5)
    # record=False leaves the gauges untouched
    before = bfm.gauge("bluefog_consensus_distance_max").value()
    bfdiag.diagnose_consensus(params, record=False)
    assert bfm.gauge("bluefog_consensus_distance_max").value() == before
