"""Preemptible fleets: the spot-preemption story end to end.

The preempt battery: the chaos ``preempt`` fault kind (zone blocks, grace
window, re-grant delay, the 143 exit-code convention), the
``bluefog-preempt-trace-1`` grammar (generators + launcher loader), the
launcher's notice → grace → kill → re-grant replay with graceful drain
(flight + trace bundles flush inside the grace window, any exit code is a
clean retirement), the warm executable pool's compile-counter invariant
(regrow to a previously-seen world shape costs zero fresh compiles), the
``DeserializeLoadedExecutable`` probe gate, the pace-adaptive staleness
controller, serve-side replica preemption, and the postmortem ``preempted``
blame.
"""
import importlib.util
import json
import os
import pathlib
import signal
import subprocess
import sys
import time

import numpy as np
import jax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import resilience as rz
from bluefog_tpu.parallel import context as bfctx
from bluefog_tpu.parallel import exec_cache as bfexec
from bluefog_tpu.run import launcher
from bluefog_tpu.utils import chaos
from bluefog_tpu.utils import flight
from bluefog_tpu.utils import metrics as bfm

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean():
    bfm.reset_metrics()
    chaos.uninstall()
    rz.reset()
    flight.reset()
    bfexec.clear()
    yield
    chaos.uninstall()
    rz.reset()
    flight.reset()
    bfexec.clear()
    bfm.stop_metrics()
    bfm.reset_metrics()


@pytest.fixture
def world4(cpu_devices):
    bf.init(devices=cpu_devices[:4])
    yield bf.get_context()
    bf.shutdown()


# ---------------------------------------------------------------------------
# chaos: the preempt fault kind
# ---------------------------------------------------------------------------

def test_preempt_parse_zone_grace_regrant():
    plan = chaos.ChaosPlan.parse(
        "zones=4;preempt:step=3,zone=1,grace=2,regrant=5.5")
    assert plan.zones == 4
    f = plan.faults[0]
    assert f.kind == "preempt" and f.step == 3
    assert f.zone == 1 and f.rank is None
    assert f.grace == pytest.approx(2.0)
    assert f.regrant == pytest.approx(5.5)


def test_preempt_parse_rejects_bad_specs():
    with pytest.raises(ValueError):        # rank XOR zone, not both
        chaos.ChaosPlan.parse("preempt:step=1,rank=0,zone=0")
    with pytest.raises(ValueError):        # needs a victim
        chaos.ChaosPlan.parse("preempt:step=1")
    with pytest.raises(ValueError):        # zone out of the plan's range
        chaos.ChaosPlan.parse("zones=2;preempt:step=1,zone=2")
    with pytest.raises(ValueError):        # grace must be >= 0
        chaos.ChaosPlan.parse("preempt:step=1,rank=0,grace=-1")
    with pytest.raises(ValueError):        # preempt is step/time-matched only
        chaos.ChaosPlan.parse("preempt:step=1,rank=0,op=neighbor_allreduce")
    with pytest.raises(ValueError):        # zone= is preempt vocabulary
        chaos.ChaosPlan.parse("kill:step=1,zone=0")


def test_zone_victims_contiguous_blocks():
    assert chaos.zone_victims(0, 8, 4) == (0, 1)
    assert chaos.zone_victims(3, 8, 4) == (6, 7)
    # uneven split: every rank is in exactly one zone
    blocks = [chaos.zone_victims(z, 5, 2) for z in range(2)]
    assert blocks == [(0, 1), (2, 3, 4)]
    with pytest.raises(ValueError):
        chaos.zone_victims(2, 8, 2)


def test_preempt_fires_with_notice_and_spot_exit_code(world4):
    flight.configure(1024)
    chaos.install("zones=2;preempt:step=5,zone=1,grace=1.5,regrant=4")
    with pytest.raises(chaos.RankPreempted) as ei:
        for step in range(1, 8):
            chaos.on_train_step(step)
    e = ei.value
    assert e.ranks == (2, 3)               # zone 1 of 2 in a 4-rank world
    assert e.zone == 1 and e.step == 5
    assert e.grace == pytest.approx(1.5)
    assert e.regrant == pytest.approx(4.0)
    assert e.code == chaos.DEFAULT_PREEMPT_CODE == 143   # 128 + SIGTERM
    # advance notice lands in the flight ring before the fault event
    kinds = [ev["kind"] for ev in flight.events()
             if ev["kind"] in ("preempt_notice", "chaos")]
    assert kinds == ["preempt_notice", "chaos"]
    ev = [x for x in flight.events() if x["kind"] == "chaos"][0]
    assert ev["name"].startswith("preempt")
    assert ev["victims"] == [2, 3] and ev["zone"] == 1
    assert int(bfm.counter("bluefog_faults_injected_total").total()) == 1


def test_preempt_rank_variant_and_custom_code():
    chaos.install("preempt:step=1,rank=2,code=99")
    with pytest.raises(chaos.RankPreempted) as ei:
        chaos.on_train_step(1)
    assert ei.value.ranks == (2,) and ei.value.code == 99


def test_preempt_multiprocess_gating(monkeypatch):
    """In a launcher-spawned job only the victim processes enact the
    reclaim — a rank outside the zone block sails through the step."""
    monkeypatch.setenv("BLUEFOG_NUM_PROCESSES", "4")
    monkeypatch.setenv("BLUEFOG_PROCESS_ID", "3")
    chaos.install("zones=2;preempt:step=1,zone=0")
    chaos.on_train_step(1)                 # rank 3 is not in zone 0: spared
    chaos.uninstall()
    monkeypatch.setenv("BLUEFOG_PROCESS_ID", "1")
    chaos.install("zones=2;preempt:step=1,zone=0")
    with pytest.raises(chaos.RankPreempted):
        chaos.on_train_step(1)


# ---------------------------------------------------------------------------
# the trace grammar: generators + launcher loader
# ---------------------------------------------------------------------------

def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_mod", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_generators_deterministic_and_sorted(tmp_path):
    pt = _load_tool("preempt_trace")
    out = tmp_path / "t.json"
    for pattern in ("diurnal", "mass", "slow-regrant"):
        argv = ["--pattern", pattern, "--world", "8", "--zones", "4",
                "--duration", "20", "--seed", "7", "--out", str(out)]
        assert pt.main(argv) == 0
        doc = json.loads(out.read_text())
        assert doc["schema"] == "bluefog-preempt-trace-1"
        assert doc["pattern"] == pattern
        ts = [e["t"] for e in doc["events"]]
        assert ts == sorted(ts) and doc["events"]
        assert all(0 <= e["zone"] < 4 for e in doc["events"])
        assert pt.main(argv) == 0          # seeded: byte-stable
        assert json.loads(out.read_text()) == doc


def test_trace_mass_fraction_and_slow_regrant_semantics(tmp_path):
    pt = _load_tool("preempt_trace")
    out = tmp_path / "t.json"
    pt.main(["--pattern", "mass", "--world", "8", "--zones", "4",
             "--fraction", "0.75", "--out", str(out)])
    doc = json.loads(out.read_text())
    assert len(doc["events"]) == 3         # round(4 * 0.75)
    pt.main(["--pattern", "slow-regrant", "--world", "8", "--zones", "4",
             "--regrant", "5", "--slow-factor", "6", "--out", str(out)])
    doc = json.loads(out.read_text())
    assert all(e["regrant"] == pytest.approx(30.0) for e in doc["events"])
    with pytest.raises(SystemExit):        # zones must divide into the world
        pt.main(["--pattern", "mass", "--world", "2", "--zones", "4"])


def test_load_preempt_trace_normalizes_and_validates(tmp_path):
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({
        "schema": "bluefog-preempt-trace-1", "zones": 2, "world": 4,
        "grace": 9.0,
        "events": [{"t": 5.0, "zone": 1, "regrant": 2},
                   {"t": 1.0, "victims": [0], "grace": 0.5}]}))
    trace = launcher._load_preempt_trace(str(path))
    assert trace["zones"] == 2 and trace["world"] == 4
    assert [e["t"] for e in trace["events"]] == [1.0, 5.0]   # re-sorted
    assert trace["events"][0]["victims"] == [0]
    assert trace["events"][0]["grace"] == pytest.approx(0.5)
    assert trace["events"][1]["grace"] == pytest.approx(9.0)  # doc default
    path.write_text(json.dumps({"schema": "nope", "events": []}))
    with pytest.raises(SystemExit, match="schema"):
        launcher._load_preempt_trace(str(path))
    path.write_text(json.dumps({
        "schema": "bluefog-preempt-trace-1",
        "events": [{"t": 1.0}]}))
    with pytest.raises(SystemExit, match="neither victims nor a zone"):
        launcher._load_preempt_trace(str(path))


def test_preempt_trace_flag_requires_np(tmp_path):
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({
        "schema": "bluefog-preempt-trace-1",
        "events": [{"t": 0.1, "victims": [0]}]}))
    with pytest.raises(SystemExit, match="requires -np"):
        launcher.main(["--preempt-trace", str(path), "--",
                       sys.executable, "-c", "pass"])


# ---------------------------------------------------------------------------
# launcher replay: notice -> grace drain -> kill -> re-grant
# ---------------------------------------------------------------------------

def test_preempt_sigterm_grace_drain_and_regrant(tmp_path, capsys):
    """The graceful path: the victim gets the SIGTERM advance notice, has
    the whole grace window to drain (its exit — any code — counts as a
    clean retirement, the PR 8 rule), and the reclaimed capacity returns
    as a fresh-identity join."""
    drain_marker = tmp_path / "drain"
    join_marker = tmp_path / "join"
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({
        "schema": "bluefog-preempt-trace-1", "zones": 2, "world": 2,
        "events": [{"t": 0.3, "zone": 1, "grace": 30, "regrant": 0.1}]}))
    prog = (
        "import os, signal, sys, time\n"
        "if os.environ.get('BLUEFOG_JOIN_COUNT'):\n"
        "    open(%r, 'w').write('JOIN_COUNT=%%s NUM=%%s' %% (\n"
        "        os.environ['BLUEFOG_JOIN_COUNT'],\n"
        "        os.environ['BLUEFOG_NUM_PROCESSES']))\n"
        "    sys.exit(0)\n"
        "def drain(signum, frame):\n"
        "    open(%r, 'w').write(\n"
        "        'grace=%%s' %% os.environ.get('BLUEFOG_PREEMPT_GRACE'))\n"
        "    sys.exit(7)\n"                # a non-zero drain exit is CLEAN
        "if os.environ['BLUEFOG_PROCESS_ID'] == '1':\n"
        "    signal.signal(signal.SIGTERM, drain)\n"
        "    time.sleep(600)\n"
        "for _ in range(1200):\n"
        "    if os.path.exists(%r): sys.exit(0)\n"
        "    time.sleep(0.05)\n"
        "sys.exit(1)\n" % (str(join_marker), str(drain_marker),
                           str(join_marker)))
    t0 = time.perf_counter()
    code = launcher.main(
        ["-np", "2", "--preempt-trace", str(trace), "--preempt-grace", "30",
         "--", sys.executable, "-c", prog])
    assert code == 0
    assert time.perf_counter() - t0 < 120
    err = capsys.readouterr().err
    assert "preempt: zone 1 reclaiming rank(s) [1]" in err
    assert "rank 1 preempted (exit code 7)" in err
    assert "preempt re-grant: starting rank 2 (fresh identity, join 1)" in err
    assert "grace expired" not in err       # the victim drained voluntarily
    # the drain ran inside the grace window, with the window advertised
    assert drain_marker.read_text() == "grace=30.0"
    got = join_marker.read_text()
    assert "JOIN_COUNT=1" in got and "NUM=2" in got


def test_sigterm_advance_notice_flushes_flight_and_trace(tmp_path):
    """The spot-preemption drain itself: a SIGTERM to a rank with the
    flight recorder and trace ring armed dumps the flight bundle AND
    flushes the trace ring before the process dies — a follow-up SIGKILL
    would skip both atexit hooks."""
    flight_dir = tmp_path / "flight"
    trace_dir = tmp_path / "traces"
    ready = tmp_path / "ready"
    prog = (
        "import os, sys, time\n"
        "from bluefog_tpu.utils import flight, tracing\n"
        "flight.maybe_enable_from_env()\n"
        "tracing.maybe_enable_from_env()\n"
        "flight.record('train', name='step', step=1)\n"
        "tracing.add_span(tracing.new_trace(), 'step', 0.0, 0.001)\n"
        "open(%r, 'w').write('armed')\n"
        "time.sleep(600)\n" % str(ready))
    env = dict(os.environ, BLUEFOG_FLIGHT_DIR=str(flight_dir),
               BLUEFOG_TRACE=str(trace_dir), BLUEFOG_PROCESS_ID="1",
               JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", prog], cwd=REPO, env=env)
    try:
        # no race with the victim's start-up (a minute of imports under six
        # loaded workers): wait for as long as it lives, and it ends itself
        while not ready.exists() and p.poll() is None:
            time.sleep(0.05)
        assert ready.exists(), "victim died before it armed its handlers"
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=600) == -signal.SIGTERM    # a liveness bound
    finally:
        p.kill()
    # what the notice must leave, and in which order: ONE bundle, dumped by
    # the signal (a signal death skips atexit), the step before the signal
    bundles = list(flight_dir.glob("*.json"))
    assert len(bundles) == 1, "no flight bundle flushed on the advance notice"
    dumped = json.loads(bundles[0].read_text())
    assert dumped["reason"] == "sigterm"
    names = [e.get("name") for e in dumped["events"]]
    assert names.count("step") == 1 and names.count("SIGTERM") == 1
    assert names.index("step") < names.index("SIGTERM")
    traces = list(trace_dir.glob("*"))
    assert traces, "trace ring did not flush on SIGTERM"
    spans = [json.loads(line)
             for t in traces for line in t.read_text().splitlines() if line]
    assert any(s.get("name") == "step" for s in spans)


def test_preempt_stubborn_victim_killed_after_grace(tmp_path, capsys):
    """A victim that ignores the advance notice is SIGKILLed when the
    grace window expires — and the kill still counts as a clean
    retirement, not a job failure."""
    join_marker = tmp_path / "join"
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({
        "schema": "bluefog-preempt-trace-1",
        "events": [{"t": 0.2, "victims": [1], "grace": 0.4,
                    "regrant": 0.1}]}))
    prog = (
        "import os, signal, sys, time\n"
        "if os.environ.get('BLUEFOG_JOIN_COUNT'):\n"
        "    open(%r, 'w').write('joined')\n"
        "    sys.exit(0)\n"
        "if os.environ['BLUEFOG_PROCESS_ID'] == '1':\n"
        "    signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "    time.sleep(600)\n"
        "for _ in range(1200):\n"
        "    if os.path.exists(%r): sys.exit(0)\n"
        "    time.sleep(0.05)\n"
        "sys.exit(1)\n" % (str(join_marker), str(join_marker)))
    code = launcher.main(
        ["-np", "2", "--preempt-trace", str(trace),
         "--", sys.executable, "-c", prog])
    assert code == 0
    err = capsys.readouterr().err
    assert "preempt: grace expired, killing rank 1" in err
    assert "rank 1 preempted (exit code" in err
    assert join_marker.read_text() == "joined"


# ---------------------------------------------------------------------------
# warm executable pool: the compile-counter invariant
# ---------------------------------------------------------------------------

def _step(params):
    out = bf.neighbor_allreduce(params)
    jax.block_until_ready(out)
    return out


def test_warm_regrow_to_seen_shape_costs_zero_fresh_compiles(world4):
    rng = np.random.default_rng(0)
    w = jax.device_put(rng.standard_normal((4, 8)).astype(np.float32),
                       NamedSharding(world4.mesh, P("rank")))
    params = {"w": _step(w)}

    def cycle(p):
        """Preempt-shaped shrink to 2, step, re-grant regrow back to 4."""
        small, h = rz.regrow_world(2, p)
        h.commit()
        small["w"] = _step(small["w"])
        big, h2 = rz.regrow_world(4, small)
        h2.commit()
        big["w"] = _step(big["w"])
        return big

    # cycle 1 is cold: the 2-world step program and the joiner-pull
    # bootstrap programs compile once
    params = cycle(params)
    # cycle 2 replays a previously-seen transition end to end: the warm
    # pool re-seeds every program, so ZERO fresh compiles anywhere —
    # shrink, step, regrow, joiner pull, step
    misses0 = bfctx.program_cache_stats()["misses"]
    cycle(params)
    assert bfctx.program_cache_stats()["misses"] == misses0
    st = bfexec.stats()
    assert st["stashes"] >= 4 and st["restores"] >= 3
    assert st["entries_restored"] >= 1


def test_exec_cache_off_gate(monkeypatch, world4):
    monkeypatch.setenv(bfexec.ENV_VAR, "off")
    assert not bfexec.enabled()
    assert bfexec.stash() == 0
    assert bfexec.restore() == 0
    assert bfexec.pool_size() == 0
    monkeypatch.setenv(bfexec.ENV_VAR, "")
    assert bfexec.enabled()                # unset/empty: in-memory pool on


def test_world_key_buckets_by_shape(world4):
    k4 = bfexec.world_key()
    assert k4[0] == "bfexec-1" and k4[2] == 4
    bfctx.reinit(2)
    assert bfexec.world_key() != k4
    bfctx.reinit(4)
    assert bfexec.world_key() == k4        # same shape: same bucket


# ---------------------------------------------------------------------------
# pace-adaptive staleness: K learned from fleet pace signals
# ---------------------------------------------------------------------------

def test_staleness_controller_recommendation_math(world4):
    from bluefog_tpu.optimizers import AdaptiveStalenessController
    c = AdaptiveStalenessController(k_min=0, k_max=16, patience=1)
    assert c.recommend([]) is None
    assert c.recommend([1.0, 1.0, 1.0, 1.0]) == 0          # lockstep pace
    assert c.recommend([1.0, 1.0, 1.0, 3.5]) == 3          # ceil(3.5)-1
    assert c.recommend([1.0, 1.0, 1.0, 99.0]) == 16        # clamped
    # a dead rank's stale entry must not deepen the window
    d = AdaptiveStalenessController(patience=1, dead_ranks=(3,))
    assert d.recommend([1.0, 1.0, 1.0, 99.0]) == 0
    assert d.recommend([np.inf, 1.0, 1.0, 1.0]) == 0       # non-finite


def test_staleness_controller_patience_hysteresis(world4):
    from bluefog_tpu.optimizers import AdaptiveStalenessController
    flight.configure(1024)
    cur0 = bfctx.async_gossip_bound()       # the context's default bound
    assert cur0 == 4
    c = AdaptiveStalenessController(patience=2)
    slow = [1.0, 1.0, 1.0, 2.5]
    assert c.observe(slow) is None          # streak 1 of 2: held back
    assert c.observe(slow) == 2             # patience met: applied
    assert bfctx.async_gossip_bound() == 2 and c.applied == 2
    evs = [e for e in flight.events() if e.get("kind") == "async_bound"]
    assert evs and evs[0]["old"] == 4 and evs[0]["new"] == 2
    assert evs[0]["reason"] == "pace_adaptive"
    # a single noisy observation cannot thrash the compiled program
    even = [1.0, 1.0, 1.0, 1.0]
    assert c.observe(even) is None          # candidate 0, streak 1
    assert c.observe(slow) is None          # streak broken: back to 2 == cur
    assert bfctx.async_gossip_bound() == 2
    # pace recovers for good: K shrinks back toward lockstep
    assert c.observe(even) is None
    assert c.observe(even) == 0
    assert bfctx.async_gossip_bound() == 0


def test_staleness_controller_validation():
    from bluefog_tpu.optimizers import AdaptiveStalenessController
    with pytest.raises(ValueError):
        AdaptiveStalenessController(k_min=5, k_max=2)
    with pytest.raises(ValueError):
        AdaptiveStalenessController(patience=0)


# ---------------------------------------------------------------------------
# serve: replica preemption is a park-free drain, not a crash
# ---------------------------------------------------------------------------

def test_serve_preempt_replica_requeues_and_records():
    from bluefog_tpu.serve.scheduler import Scheduler

    class _Scfg:
        slots = 4
        prefix_pages = 2
        prefix_page_tokens = 4

    class _M:
        dp = 2

    class _Eng:
        m = _M()
        scfg = _Scfg()

    flight.configure(1024)
    sched = Scheduler(_Eng())
    try:
        lost = sched.preempt_replica(1, zone=3, grace=25.0)
        assert lost == []
        assert 1 not in sched.live_replicas()
        evs = [e for e in flight.events()
               if e.get("name") == "replica_preempt_notice"]
        assert evs and evs[0]["replica"] == 1
        assert evs[0]["zone"] == 3 and evs[0]["grace"] == pytest.approx(25.0)
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# postmortem: blamed as "preempted", not "killed"
# ---------------------------------------------------------------------------

def test_postmortem_blames_preempted_not_killed(tmp_path, world4):
    flight.configure(1024)
    chaos.install("zones=2;preempt:step=2,zone=1,grace=1,regrant=3")
    with pytest.raises(chaos.RankPreempted):
        for step in range(1, 4):
            chaos.on_train_step(step)
    chaos.uninstall()
    bundle = flight.dump(str(tmp_path / "flight_preempt.json"),
                         reason="preempt")
    pm = _load_tool("postmortem")
    report = pm.report_from_files([bundle])
    v = report["verdict"]
    assert v["failure_kind"] == "preempted"
    assert v["first_failed_rank"] in (2, 3)          # a zone-1 victim
    assert "spot preemption" in v["detail"]
    blk = report["preempt"]
    assert blk["victims"] == [2, 3] and blk["zones"] == [1]
    assert any('blamed as "preempted"' in n for n in report["notes"])


# ---------------------------------------------------------------------------
# the full goodput drill: trace -> bench -> gates (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_preempt_bench_end_to_end(tmp_path):
    """Generate a mass-preemption + slow-re-grant trace, replay it through
    preempt_bench, and hold the three gates: goodput floor, float64
    continuity, and the zero-fresh-compile warm regrowth invariant."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_")
           and k not in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_ENABLE_X64")}
    trace = tmp_path / "mass.json"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "preempt_trace.py"),
         "--pattern", "mass", "--world", "4", "--zones", "2",
         "--duration", "8", "--grace", "1", "--regrant", "3",
         "--out", str(trace)],
        cwd=REPO, capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "preempt_bench.py"),
         "--trace", str(trace), "--virtual-cpu", "4",
         "--flight-dir", str(tmp_path / "flight")],
        cwd=REPO, capture_output=True, text=True, timeout=420, env=env)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["schema"] == "bluefog-preempt-bench-1" and doc["ok"]
    assert doc["continuity_ok"] and doc["warm_fresh_compiles"] == 0
    assert doc["goodput_fraction"] >= doc["goodput_floor"]
    assert doc["victims_total"] >= 2
    # the bundle it dumped blames the reclaim as a preemption
    pm = _load_tool("postmortem")
    report = pm.report_from_files([doc["flight_bundle"]])
    assert report["verdict"]["failure_kind"] == "preempted"
