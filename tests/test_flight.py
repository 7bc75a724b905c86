"""Flight recorder, cross-rank postmortems, and live straggler detection.

Covers the black-box contract end to end: the lock-free ring buffer, the
self-describing dump bundle, dump-on-failure plumbing (watchdog timeout,
excepthook, launcher SIGTERM teardown), ``tools/postmortem.py``'s verdict
on per-rank bundles (committed fixtures + torn bundles + a real chaos-kill
job), the chaos-fed live straggler detector, and the zero-overhead pin
(recording on: zero retraces after warmup, donation intact).
"""
import importlib
import importlib.util
import json
import os
import sys
import time
import types

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import diagnostics as bfdiag
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import topology as tu
from bluefog_tpu.utils import chaos
from bluefog_tpu.utils import flight
from bluefog_tpu.utils import metrics as bfm
from bluefog_tpu.utils import watchdog as wd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")
N, D = 8, 16


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


postmortem = _load_tool("postmortem")
metrics_report = _load_tool("metrics_report")


@pytest.fixture(autouse=True)
def _clean():
    flight.reset()
    bfm.reset_metrics()
    chaos.uninstall()
    yield
    chaos.uninstall()
    flight.reset()
    bfm.stop_metrics()
    bfm.reset_metrics()


@pytest.fixture
def ctx(cpu_devices):
    bf.init(devices=cpu_devices)
    bf.set_topology(tu.ExponentialTwoGraph(N), is_weighted=True)
    yield
    bf.shutdown()


# ---------------------------------------------------------------------------
# Ring buffer
# ---------------------------------------------------------------------------

def test_ring_overflow_keeps_newest_and_counts_dropped(tmp_path):
    flight.configure(8)
    for i in range(20):
        flight.record("op", name="x", step=i)
    evs = flight.events()
    assert len(evs) == 8
    assert [e["step"] for e in evs] == list(range(12, 20))   # oldest first
    assert [e["seq"] for e in evs] == list(range(13, 21))    # seq monotone
    bundle = json.load(open(flight.dump(str(tmp_path / "b.json"))))
    assert bundle["dropped"] == 12 and bundle["n_events"] == 8


def test_capacity_zero_disables_recording():
    flight.configure(0)
    flight.record("op", name="x")
    flight.record_op("neighbor_allreduce")
    assert flight.events() == [] and flight.last_event() is None
    assert flight.last_event_description() is None
    flight.configure(4)                          # re-enable mid-run
    flight.record("op", name="y")
    assert len(flight.events()) == 1
    with pytest.raises(ValueError):
        flight.configure(-1)


def test_last_event_description_formats():
    flight.record_op("neighbor_allreduce")
    flight.record_op("neighbor_allreduce")
    desc = flight.last_event_description(now=flight.last_event()["ts"] + 12.3)
    assert desc == "neighbor_allreduce call 2, 12.3s ago"
    flight.record("step_begin", name="train_step", step=5)
    assert flight.last_event_description().startswith("train_step step 5,")


# ---------------------------------------------------------------------------
# Bundles + dump-on-failure
# ---------------------------------------------------------------------------

def test_note_failure_autodumps_with_reason_history(tmp_path):
    flight.set_dump_dir(str(tmp_path))
    flight.record("step_begin", name="train_step", step=3)
    path = flight.note_failure("nonfinite", detail="ranks (4,) failed",
                               step=3)
    assert path == str(tmp_path / "flight_rank0.json")
    bundle = json.load(open(path))
    assert bundle["schema"] == flight.SCHEMA
    for key in ("rank", "pid", "ts", "reason", "reasons", "capacity",
                "n_events", "dropped", "events", "topology", "open_spans",
                "metrics"):
        assert key in bundle, key
    fail = [e for e in bundle["events"] if e["kind"] == "failure"]
    assert fail and fail[0]["name"] == "nonfinite" and fail[0]["step"] == 3
    # second dump overwrites the file but keeps the dump history
    flight.dump(reason="manual")
    bundle2 = json.load(open(path))
    assert bundle2["reasons"] == ["nonfinite", "manual"]
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]  # atomic


def test_dump_carries_topology_and_metrics_blocks(ctx, tmp_path):
    x = bf.shard_distributed(jnp.ones((N, D), jnp.float32))
    bf.synchronize(bf.neighbor_allreduce(x))
    bundle = json.load(open(flight.dump(str(tmp_path / "b.json"))))
    topo = bundle["topology"]
    assert topo["size"] == N and not topo["healed"]
    assert len(topo["in_neighbors"]) == N
    assert bundle["metrics"] is not None
    ops = [e for e in bundle["events"] if e["kind"] == "op"]
    assert ops and ops[-1]["name"] == "neighbor_allreduce"


def test_maybe_enable_from_env_arms_capacity_and_handlers(
        monkeypatch, tmp_path):
    monkeypatch.setenv(flight.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(flight.ENV_EVENTS, "16")
    prev_hook = sys.excepthook
    assert flight.maybe_enable_from_env()
    assert flight.enabled() and flight.capacity() == 16
    assert sys.excepthook is not prev_hook       # excepthook chained
    flight.reset()
    assert sys.excepthook is prev_hook           # reset restores
    assert not flight.enabled()
    monkeypatch.delenv(flight.ENV_DIR)
    assert not flight.maybe_enable_from_env()    # no dir: stays disarmed


def test_watchdog_timeout_names_last_flight_event(monkeypatch, tmp_path):
    flight.set_dump_dir(str(tmp_path))
    flight.record_op("neighbor_allreduce")
    monkeypatch.setattr(wd, "jax", types.SimpleNamespace(
        block_until_ready=lambda x: (time.sleep(10), x)[1]))
    with pytest.raises(TimeoutError,
                       match=r"last event: neighbor_allreduce call 1"):
        wd.synchronize_with_watchdog(7, interval=0.04, name="slowstep",
                                     timeout=0.15)
    # the timeout flushed the black box before raising
    bundle = json.load(open(tmp_path / "flight_rank0.json"))
    assert bundle["reason"] == "watchdog_timeout"
    fail = [e for e in bundle["events"] if e["kind"] == "failure"]
    assert fail and fail[-1]["name"] == "watchdog_timeout"


# ---------------------------------------------------------------------------
# Postmortem tool
# ---------------------------------------------------------------------------

def test_postmortem_on_committed_fixtures_names_killed_rank():
    """The committed two-rank bundles (rank 1 chaos-killed at step 30,
    rank 0 torn down by SIGTERM): the verdict blames rank 1, not the
    survivor whose dump reason is also failure-ish.  Mirrors
    ``make postmortem-smoke``."""
    doc = postmortem.report_from_files([
        os.path.join(FIXTURES, "flight_rank0.json"),
        os.path.join(FIXTURES, "flight_rank1.json")])
    assert doc["ok"] and doc["schema"] == postmortem.SCHEMA
    for key in ("n_bundles", "ranks", "torn", "verdict", "per_rank",
                "step_time", "consensus", "topology"):
        assert key in doc, key
    assert doc["ranks"] == [0, 1] and doc["torn"] == []
    v = doc["verdict"]
    assert v["first_failed_rank"] == 1
    assert v["failure_step"] == 30
    assert v["failure_kind"] == "kill"
    assert doc["per_rank"]["0"]["reasons"] == ["sigterm"]
    assert doc["consensus"] == [[28, 0.021]]
    assert doc["topology"]["size"] == 2
    assert [0, 1] in doc["topology"]["edges_at_failure"]


def test_postmortem_tolerates_torn_bundle(tmp_path, capsys):
    good = os.path.join(FIXTURES, "flight_rank1.json")
    torn = tmp_path / "flight_rank0.json"
    torn.write_text(open(good).read()[:100])     # killed mid-write
    doc = postmortem.report_from_files([str(torn), good])
    assert doc["ok"] and doc["n_bundles"] == 1
    assert doc["torn"] == [str(torn)]
    assert any("torn bundle" in n for n in doc["notes"])
    assert "torn bundle" in capsys.readouterr().err
    assert doc["verdict"]["first_failed_rank"] == 1
    # every bundle torn: report degrades to ok=False, still no traceback
    doc2 = postmortem.report_from_files([str(torn)])
    assert not doc2["ok"] and doc2["torn"] == [str(torn)]


def test_postmortem_stall_verdict_without_failure_events():
    def mk(rank, last_step):
        return {"schema": postmortem.SCHEMA, "rank": rank, "ts": 1.0,
                "reasons": ["exit"], "events": [
                    {"seq": s, "ts": float(s), "kind": "step_end",
                     "name": "train_step", "step": s, "dur_s": 0.1}
                    for s in range(1, last_step + 1)]}
    doc = postmortem.analyze({0: mk(0, 40), 1: mk(1, 12), 2: mk(2, 41)})
    v = doc["verdict"]
    assert v["failure_kind"] == "stalled"
    assert v["first_failed_rank"] == 1 and v["failure_step"] == 12


def test_metrics_report_warns_on_torn_jsonl_line(tmp_path, capsys):
    src = os.path.join(FIXTURES, "metrics_host0.metrics.jsonl")
    torn = tmp_path / "host0.metrics.jsonl"
    torn.write_text(open(src).read() + '{"ts": 12, "metrics": {"trunc')
    doc = metrics_report.report_from_files([str(torn)])
    assert doc["ok"] and doc["n_hosts"] == 1
    assert any("torn JSONL line" in n for n in doc["notes"])
    err = capsys.readouterr().err
    assert "torn JSONL line" in err and str(torn) in err


# ---------------------------------------------------------------------------
# Live straggler detection
# ---------------------------------------------------------------------------

def test_detect_stragglers_unit():
    t = np.full(8, 0.1)
    assert bfdiag.detect_stragglers(t) == ()
    t[3] = 0.5
    assert bfdiag.detect_stragglers(t) == (3,)
    t[5] = 0.9                                   # slowest first
    assert bfdiag.detect_stragglers(t) == (5, 3)
    assert bfdiag.detect_stragglers(t, dead_ranks=(5,)) == (3,)
    # a global slowdown is not a straggler: median moves with the fleet
    assert bfdiag.detect_stragglers(np.full(8, 2.0)) == ()
    # min_skew_s filters microsecond noise on a fast step
    t = np.full(8, 1e-4)
    t[2] = 3e-4
    assert bfdiag.detect_stragglers(t, min_skew_s=0.01) == ()


def _lr0_step(metrics_every_k=None):
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.0), bfopt.neighbor_communicator(bf.static_schedule()))
    params = {"w": jnp.broadcast_to(
        jnp.arange(float(N))[:, None], (N, D)).astype(jnp.float32)}
    state = bfopt.init_distributed(strat, params)
    step = bfopt.make_train_step(
        lambda p, b: (jnp.mean((p["w"] - b) ** 2),
                      jax.grad(lambda q: jnp.mean((q["w"] - b) ** 2))(p)),
        strat, metrics_every_k=metrics_every_k)
    return step, params, state, jnp.zeros((N, D), jnp.float32)


@pytest.mark.parametrize("rank,onset", [(3, 1), (5, 2)])
def test_chaos_throttle_shows_up_as_live_straggler(ctx, tmp_path, rank,
                                                   onset):
    """Acceptance: throttle one rank from step ``onset``, run a probed
    loop — the detector names that rank through the piggybacked probe, the
    gauges agree, and a postmortem over this process's bundle agrees
    again: the right rank AND, by the bundle's chaos events, the right
    onset step."""
    chaos.install(f"throttle:from={onset},until=99,t=0.05,rank={rank}")
    step, params, state, batch = _lr0_step(metrics_every_k=2)
    for _ in range(6):
        params, state, loss = step(params, state, batch)
        jax.block_until_ready(loss)

    t = bfdiag.last_step_times()
    assert t is not None and t.shape == (N,)
    assert t[rank] > 2.0 * np.median(np.delete(t, rank))
    assert bfdiag.detect_stragglers() == (rank,)
    assert bfm.gauge("bluefog_straggler_rank").value() == float(rank)
    assert bfm.gauge("bluefog_step_time_skew").value() >= 0.05 * 0.9
    probes = [e for e in flight.events() if e["kind"] == "consensus"]
    assert probes and probes[-1]["stragglers"] == [rank]
    assert len(probes[-1]["step_times"]) == N

    # the postmortem's single-bundle fallback reads the same probe table
    bundle = flight.dump(str(tmp_path / "flight_rank0.json"))
    doc = postmortem.report_from_files([bundle])
    assert doc["ok"] is True
    assert doc["step_time"]["straggler_rank"] == rank
    assert doc["step_time"]["skew_s"] >= 0.05 * 0.9
    assert doc["consensus"]                     # trajectory present
    injected = [e for e in json.load(open(bundle))["events"]
                if e.get("kind") == "chaos"]
    assert injected and min(e["step"] for e in injected) == onset
    assert all(e["rank"] == rank for e in injected)


def test_postmortem_blames_committed_fixture():
    """Deterministic (no subprocess): the committed straggler bundle is
    blamed with rank 5, onset step 2 — schema drift in either the flight
    recorder or the postmortem tool breaks this first."""
    fixture = os.path.join(FIXTURES, "flight_straggler.json")
    rep = postmortem.report_from_files([fixture])
    assert rep["schema"] == "bluefog-flight-1"
    st = rep["step_time"]
    assert st["straggler_rank"] == 5
    assert st["skew_s"] == pytest.approx(0.05, rel=0.25)
    bundle = json.load(open(fixture))
    chaos_events = [e for e in bundle["events"] if e.get("kind") == "chaos"]
    assert min(e["step"] for e in chaos_events) == 2
    assert {e["rank"] for e in chaos_events} == {5}
    # the in-bundle consensus probe saw the same skew the report blames
    cons = [e for e in bundle["events"] if e.get("kind") == "consensus"]
    assert cons[-1]["stragglers"] == [5]


def test_probe_without_step_times_unchanged(ctx):
    """Callers that never pass step_times keep the old program and the old
    output keys — the piggyback is additive."""
    x = bf.shard_distributed(jnp.ones((N, D), jnp.float32))
    out = bfdiag.diagnose_consensus({"w": x}, record=False)
    assert "step_time_skew_s" not in out and "straggler_ranks" not in out


# ---------------------------------------------------------------------------
# The no-overhead pin: recording on, zero retraces, donation intact
# ---------------------------------------------------------------------------

def test_recorder_on_keeps_zero_retraces_and_donation(ctx, tmp_path):
    """The PR's cost-discipline acceptance: with the recorder enabled (and
    a dump dir armed) a warmed probed loop still compiles nothing after
    warmup and still donates its buffers — the black box rides along for
    free."""
    flight.set_dump_dir(str(tmp_path))
    assert flight.capacity() > 0
    step, params, state, batch = _lr0_step(metrics_every_k=2)
    sizes, w1 = [], None
    for i in range(6):
        params, state, loss = step(params, state, batch)
        jax.block_until_ready(loss)
        sizes.append(step._jit_cache_len())
        if i == 0:
            w1 = params["w"]
    assert w1.is_deleted()                       # donation intact
    assert sizes[1] is not None and sizes[-1] == sizes[1], sizes
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    kinds = {e["kind"] for e in flight.events()}
    assert {"step_begin", "step_end", "consensus"} <= kinds
    ends = [e for e in flight.events() if e["kind"] == "step_end"]
    assert ends[-1]["step"] == 6 and "fused_k" in ends[-1]
    assert ends[-1]["donated"] is True


# ---------------------------------------------------------------------------
# End-to-end: chaos kill under the launcher -> bundles -> postmortem
# ---------------------------------------------------------------------------

_CHILD = """\
import importlib, os, sys, time, types

# jax-free bootstrap: load bluefog_tpu/utils as a standalone package so the
# child pays no jax import (the recorder's cost contract for launcher
# children)
pkg = types.ModuleType("bfu")
pkg.__path__ = [sys.argv[1]]
sys.modules["bfu"] = pkg
flight = importlib.import_module("bfu.flight")
chaos = importlib.import_module("bfu.chaos")

assert "jax" not in sys.modules
assert flight.maybe_enable_from_env()
assert chaos.maybe_install_from_env()
for step in range(1, 101):
    flight.record("step_begin", name="train_step", step=step)
    chaos.on_train_step(step)           # rank 3 dies here at step 30
    flight.record("step_end", name="train_step", step=step, dur_s=0.01)
    time.sleep(0.01)
"""


def test_launcher_collects_bundles_and_postmortem_blames_dead_rank(
        tmp_path, capsys):
    """Acceptance (e2e): BLUEFOG_CHAOS kills rank 3 at step 30 of an 8-rank
    launcher job; every rank's bundle lands in --flight-dir (the victim via
    excepthook, the survivors via the teardown SIGTERM), the launcher names
    the collection, and the postmortem identifies rank 3 / step 30."""
    from bluefog_tpu.run import launcher

    utils_dir = os.path.join(REPO, "bluefog_tpu", "utils")
    fdir = tmp_path / "flight"
    script = tmp_path / "loop.py"
    script.write_text(_CHILD)
    code = launcher.main(
        ["-np", "8", "--flight-dir", str(fdir),
         "-x", "BLUEFOG_CHAOS=kill:step=30,rank=3",
         "--", sys.executable, str(script), utils_dir])
    assert code != 0
    err = capsys.readouterr().err
    assert "rank 3 exited with code 1" in err
    assert f"collected 8 flight bundle(s) in {fdir}" in err
    assert "postmortem: python tools/postmortem.py --dir" in err

    bundles = sorted(os.listdir(fdir))
    assert bundles == [f"flight_rank{r}.json" for r in range(8)]
    doc = postmortem.report_from_files(
        [str(fdir / b) for b in bundles])
    assert doc["ok"] and doc["n_bundles"] == 8 and doc["torn"] == []
    v = doc["verdict"]
    assert v["first_failed_rank"] == 3
    assert v["failure_step"] == 30
    assert v["failure_kind"] in ("kill", "exception")
    # the victim recorded its death; survivors dumped on SIGTERM and ran on
    assert "exception" in doc["per_rank"]["3"]["reasons"]
    assert doc["per_rank"]["3"]["last_step"] == 30
    for r in (0, 1, 2, 4, 5, 6, 7):
        pr = doc["per_rank"][str(r)]
        assert "sigterm" in pr["reasons"]
        assert pr["last_step"] >= 30


# ---------------------------------------------------------------------------
# Timeseries tails in bundles, postmortem trajectories, report windows
# ---------------------------------------------------------------------------

def test_dump_embeds_timeseries_tails_and_postmortem_plots(tmp_path):
    from bluefog_tpu.utils import timeseries as ts
    ts.arm("bluefog_step_time_ewma_s", capacity=512)
    try:
        # flat 0.1s step time with a 2x ramp over the last 10 points —
        # exactly the shape a postmortem should surface at a glance
        for i in range(300):
            ts.append("bluefog_step_time_ewma_s",
                      0.2 if i >= 290 else 0.1, ts=float(i))
        bundle = json.load(open(flight.dump(str(tmp_path / "b.json"),
                                            reason="probe")))
        blk = bundle["timeseries"]
        assert {"mono", "wall"} <= set(blk["anchor"])
        pts = blk["series"]["bluefog_step_time_ewma_s"]
        assert len(pts) == flight._TS_TAIL       # ring tail, bounded
        assert pts[-1][1] == pytest.approx(0.2)

        # postmortem turns the embedded tails into per-rank trajectories
        doc = postmortem.analyze({0: bundle})
        traj = doc["timeseries"]["bluefog_step_time_ewma_s"]["0"]
        assert traj["n"] == len(pts)
        assert traj["last"] == pytest.approx(0.2)
        assert traj["median"] == pytest.approx(0.1)
        assert traj["last_over_median"] == pytest.approx(2.0)
        assert traj["spark"] and len(traj["spark"]) <= 64
        assert len(traj["points"]) <= 64
        # points are re-anchored to wall clock via the bundle anchor
        off = blk["anchor"]["wall"] - blk["anchor"]["mono"]
        assert traj["points"][-1][0] == pytest.approx(
            pts[-1][0] + off, abs=1e-3)
    finally:
        ts.reset()
    # bundles without the block (older dumps) stay readable: no key
    doc2 = postmortem.analyze({0: {k: v for k, v in bundle.items()
                                   if k != "timeseries"}})
    assert "timeseries" not in doc2


def test_metrics_report_since_last_window(tmp_path):
    # window_bounds: later bound wins when --since and --last combine
    assert metrics_report.window_bounds(since=50.0, last=10.0,
                                        now=100.0) == 90.0
    assert metrics_report.window_bounds(since=95.0, last=10.0,
                                        now=100.0) == 95.0
    assert metrics_report.window_bounds() is None
    with pytest.raises(ValueError):
        metrics_report.window_bounds(last=0)

    def line(ts, ewma):
        m = {"bluefog_step_time_ewma_s":
                 {"type": "gauge", "help": "h", "values": {"": ewma}}}
        doc = {"host": 0, "metrics": m}
        if ts is not None:
            doc["ts"] = ts
        return json.dumps(doc)

    log = tmp_path / "h0.metrics.jsonl"
    log.write_text("\n".join([line(None, 0.3),     # ts-less: kept + noted
                              line(100.0, 0.2),
                              line(200.0, 0.1)]) + "\n")
    full = metrics_report.report_from_files([str(log)])
    assert "window" not in full and full["n_samples"] == 3
    assert len(full["series"]["bluefog_step_time_ewma_s"]) == 3

    doc = metrics_report.report_from_files([str(log)], since=150.0)
    assert doc["window"] == {"since_ts": 150.0}
    assert doc["n_samples"] == 2                   # ts-less survivor + 200.0
    assert len(doc["series"]["bluefog_step_time_ewma_s"]) == 2
    assert any("without a ts kept" in n for n in doc["notes"])
