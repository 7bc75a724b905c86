"""Asynchronous window gossip: bounded-staleness straggler-immune training.

The contract pinned here (ISSUE: async tentpole acceptance):

* **Column-stochastic under any staleness** — the extended-state mixing
  matrices (value ⊕ mailbox, :func:`bluefog_tpu.ops.windows
  .async_mixing_matrices`) keep every column summing to 1 for seeded
  per-rank activity vectors, per tick and cumulatively.
* **Model == machine** — the compiled strategy's de-biased trajectory
  matches the host-side matrix model tick for tick under a heterogeneous
  pace table (the mailboxes really accumulate across skipped ticks).
* **K=0 is synchronous** — a float64 subprocess oracle: staleness bound 0
  is trajectory-identical (~1e-12) to combine-then-adapt on the same
  column-stochastic push schedule.
* **K>0 still converges** — consensus contracts monotonically with a
  straggler in the fleet, donation intact, zero post-warmup retraces.
* **Plannable** — ``async_window_gossip`` is enumerated, audited (dst
  weighting rejected with the constructor's reason), and a winning plan
  replays through ``Plan.build_strategy``.
* **Observable** — ``observe_async_staleness`` publishes the
  ``bluefog_async_staleness_steps`` / ``bluefog_async_forced_sync`` gauges
  from the step's carried depth (no collective, no compile).
* **Benchable** — ``tools/gossip_bench.py --async-frontier`` emits a
  versioned ``bluefog-gossip-async-1`` artifact in which async
  wall-clock-to-consensus strictly beats sync under a 10x straggler.
"""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import diagnostics as bfdiag
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import topology as tu
from bluefog_tpu.ops import windows as wops
from bluefog_tpu.utils import flight
from bluefog_tpu.utils import metrics as bfm

N, D = 8, 16
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    bfm.reset_metrics()
    flight.reset()
    yield
    flight.reset()
    bfm.stop_metrics()
    bfm.reset_metrics()


@pytest.fixture
def ctx(cpu_devices):
    bf.init(devices=cpu_devices)
    bf.set_topology(tu.ExponentialTwoGraph(N))
    yield
    bf.shutdown()


def _push_sched(topo=None):
    return bfopt.push_schedule(
        topo if topo is not None else tu.ExponentialTwoGraph(N), N)


def _zero_grad_fn(p, _):
    return jnp.zeros(()), jax.tree.map(jnp.zeros_like, p)


def _shard(tree):
    return jax.tree.map(bf.shard_distributed, tree)


def _consensus_max(params):
    return float(bf.consensus_distance(params).max())


# ---------------------------------------------------------------------------
# staleness-aware mixing: column-stochasticity property (host math only)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo_fn", [
    lambda: tu.ExponentialTwoGraph(N),
    lambda: tu.RingGraph(N, connect_style=0),
])
def test_async_mixing_columns_stochastic_under_seeded_staleness(topo_fn):
    """Every effective mixing column sums to 1 for ANY activity pattern —
    the invariant that keeps push-sum de-biasing exact under arbitrary
    per-rank staleness (mirrors the membership-invariant property sweep)."""
    sched = _push_sched(topo_fn())
    K = max(sched.max_in_degree, 1)
    m = N + N * K
    rng = np.random.RandomState(1234)
    cumulative = np.eye(m)
    for trial in range(40):
        active = rng.rand(N) < rng.uniform(0.15, 0.95)
        P, C = wops.async_mixing_matrices(sched, active)
        M = C @ P
        np.testing.assert_allclose(P.sum(axis=0), 1.0, atol=1e-12)
        np.testing.assert_allclose(C.sum(axis=0), 1.0, atol=1e-12)
        np.testing.assert_allclose(M.sum(axis=0), 1.0, atol=1e-12)
        cumulative = M @ cumulative
        # the product over the whole seeded staleness history stays
        # column-stochastic: mass is conserved, never minted
        np.testing.assert_allclose(cumulative.sum(axis=0), 1.0, atol=1e-10)
    # edge patterns: fully sync and fully stalled
    for active in (np.ones(N, bool), np.zeros(N, bool)):
        P, C = wops.async_mixing_matrices(sched, active)
        np.testing.assert_allclose((C @ P).sum(axis=0), 1.0, atol=1e-12)
    # a stalled tick is the identity on the extended state
    P, C = wops.async_mixing_matrices(sched, np.zeros(N, bool))
    np.testing.assert_allclose(C @ P, np.eye(m), atol=0)
    with pytest.raises(ValueError, match="active must have shape"):
        wops.async_mixing_matrices(sched, np.ones(3, bool))


def test_async_compiled_trajectory_matches_matrix_model(ctx):
    """The compiled strategy IS the matrix model: under a heterogeneous
    pace table (no forced syncs), the de-biased params equal the host-side
    extended-state product ``z = (ΠCP x) / (ΠCP p)`` every tick — skipped
    ticks really leave mail accumulating in the neighbor's slot."""
    sched = _push_sched()
    K = max(sched.max_in_degree, 1)
    pace = [1, 1, 2, 3, 1, 1, 1, 4]
    strat = bfopt.async_window_gossip(
        optax.sgd(0.0), sched, staleness_bound=50, pace=pace)
    step = bfopt.make_train_step(_zero_grad_fn, strat, donate=False)

    rng = np.random.RandomState(11)
    x0 = rng.randn(N, D).astype(np.float32)
    params = _shard({"w": jnp.asarray(x0)})
    state = _shard(bfopt.init_distributed(strat, params))
    batch = jnp.zeros((N, 1))

    m = N + N * K
    X = np.zeros((m, D))
    X[:N] = x0
    mass = np.zeros(m)
    mass[:N] = 1.0
    for tick in range(12):
        params, state, _ = step(params, state, batch)
        active = np.array([tick % pace[r] == 0 for r in range(N)])
        P, C = wops.async_mixing_matrices(sched, active)
        X = C @ P @ X
        mass = C @ P @ mass
        z_model = X[:N] / mass[:N, None]
        np.testing.assert_allclose(
            np.asarray(params["w"]), z_model, atol=2e-5,
            err_msg=f"tick {tick}, active={active}")
    # the straggler really skipped adapts: local_steps is per-pace
    local = np.asarray(state.comm_state.local_steps).reshape(-1)
    assert local[0] == 12 and local[7] == 3, local


# ---------------------------------------------------------------------------
# float64 oracle: K=0 == synchronous combine-then-adapt
# ---------------------------------------------------------------------------

_K0_ORACLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "1"
import json
import jax
import jax.numpy as jnp
import numpy as np
import optax
import bluefog_tpu as bf
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import topology as tu

N, D = 8, 16
bf.init(platform="cpu")
bf.set_topology(tu.ExponentialTwoGraph(N))
sched = bfopt.push_schedule(bf.load_topology(), N)
rng = np.random.RandomState(3)
params0 = {"w": jnp.asarray(rng.randn(N, D))}
target = jnp.asarray(rng.randn(D))


def grad_fn(p, _):
    loss_of = lambda q: jnp.mean((q["w"] - target) ** 2)
    return loss_of(p), jax.grad(loss_of)(p)


def run(strat):
    step = bfopt.make_train_step(grad_fn, strat, donate=False)
    params = jax.tree.map(jnp.copy, params0)
    state = bfopt.init_distributed(strat, params)
    batch = jnp.zeros((N, 1))
    traj = []
    for _ in range(12):
        params, state, loss = step(params, state, batch)
        traj.append(np.asarray(params["w"]))
    return traj


a = run(bfopt.async_window_gossip(optax.sgd(0.05), sched, staleness_bound=0))
b = run(bfopt.STRATEGIES["neighbor_cta"].build(
    optax.sgd(0.05), schedule=sched, wire=None, concurrent=None,
    delayed=False, num_steps_per_communication=1))
maxdiff = max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))
spread0 = float(np.max(np.abs(a[0] - a[0].mean(axis=0))))
spreadT = float(np.max(np.abs(a[-1] - a[-1].mean(axis=0))))
print(json.dumps({"maxdiff": maxdiff, "spread0": spread0,
                  "spreadT": spreadT}))
"""


def test_float64_oracle_k0_identical_to_synchronous_cta():
    """Staleness bound 0 statically folds the activity machinery away: the
    trajectory must equal synchronous combine-then-adapt on the same push
    schedule to float64 round-off, and consensus must contract."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, "-c", _K0_ORACLE],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=420, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["maxdiff"] < 1e-12, doc
    assert doc["spreadT"] < doc["spread0"], doc


# ---------------------------------------------------------------------------
# K>0: contraction with a straggler, donation, retrace sentinel
# ---------------------------------------------------------------------------

def test_async_consensus_contracts_with_straggler(ctx):
    """Pure gossip with rank 3 at one-third pace: consensus distance must
    contract monotonically on every pace-covering window, with donation
    intact and zero steady-state retraces."""
    sched = _push_sched()
    strat = bfopt.async_window_gossip(
        optax.sgd(0.0), sched, staleness_bound=4,
        pace=[1, 1, 1, 3, 1, 1, 1, 1])
    step = bfopt.make_train_step(_zero_grad_fn, strat, donate=True)

    rng = np.random.RandomState(5)
    params = _shard({"w": jnp.asarray(rng.randn(N, D).astype(np.float32))})
    state = _shard(bfopt.init_distributed(strat, params))
    batch = jnp.zeros((N, 1))

    old_w = params["w"]
    trace = [_consensus_max(params)]
    params, state, _ = step(params, state, batch)
    # donation: the consumed input buffer is really gone
    with pytest.raises(RuntimeError):
        np.asarray(old_w)
    trace.append(_consensus_max(params))
    params, state, _ = step(params, state, batch)
    trace.append(_consensus_max(params))
    steady = step._cache_size()
    for _ in range(15):
        params, state, _ = step(params, state, batch)
        trace.append(_consensus_max(params))
    assert step._cache_size() == steady, (
        "async gossip retraced in steady state")
    # monotone on pace-covering windows (every 3 ticks the straggler has
    # contributed at least once), and a real contraction overall
    window = trace[::3]
    assert all(b < a for a, b in zip(window, window[1:])), trace
    assert trace[-1] < 0.05 * trace[0], trace
    # the straggler's mail kept its weight: push-sum mass stays conserved
    p = np.asarray(state.comm_state.p).reshape(-1)
    p_mail = np.asarray(state.comm_state.p_recv).reshape(N, -1)
    np.testing.assert_allclose(p.sum() + p_mail.sum(), N, rtol=1e-5)


def test_async_forced_sync_fires_past_bound(ctx):
    """A straggler slower than the bound trips the fleet-wide sync-up flag,
    and the forced tick really lands the straggler's adapt."""
    sched = _push_sched()
    strat = bfopt.async_window_gossip(
        optax.sgd(0.0), sched, staleness_bound=2,
        pace=[1, 1, 1, 8, 1, 1, 1, 1])
    step = bfopt.make_train_step(_zero_grad_fn, strat, donate=False)
    params = _shard({"w": jnp.ones((N, D), jnp.float32)})
    state = _shard(bfopt.init_distributed(strat, params))
    batch = jnp.zeros((N, 1))
    forced_ticks = []
    for tick in range(8):
        params, state, _ = step(params, state, batch)
        if bool(np.asarray(state.comm_state.force).any()):
            forced_ticks.append(tick)
    assert forced_ticks, "bound 2 with a pace-8 straggler never forced"
    # depth never runs unboundedly ahead of the bound: the sync-up lands
    # one tick after the breach is observed
    depth = np.asarray(state.comm_state.depth).reshape(-1)
    assert depth.max() <= 2 + 2, depth
    local = np.asarray(state.comm_state.local_steps).reshape(-1)
    assert local[3] > 1, "forced sync-ups never woke the straggler"


# ---------------------------------------------------------------------------
# constructor contracts + context knob
# ---------------------------------------------------------------------------

def test_async_rejects_dst_weighted_schedule(ctx):
    from bluefog_tpu.autotune.candidates import schedule_for
    dst = schedule_for({"family": "exp2", "size": N}, "dst", N)
    strat = bfopt.async_window_gossip(optax.sgd(0.1), dst)
    with pytest.raises(ValueError, match="column-stochastic push"):
        strat.init({"w": jnp.zeros((D,))})
    assert bfopt.strategy_constraint_violation(
        "async_window_gossip", schedule=dst) is not None


def test_async_pace_and_bound_validation(ctx):
    sched = _push_sched()
    with pytest.raises(ValueError, match="staleness_bound must be >= 0"):
        bfopt.async_window_gossip(
            optax.sgd(0.1), sched, staleness_bound=-1).init(
                {"w": jnp.zeros((D,))})
    bad = bfopt.async_window_gossip(
        optax.sgd(0.0), sched, staleness_bound=1, pace=[1, 2])
    step = bfopt.make_train_step(_zero_grad_fn, bad, donate=False)
    params = _shard({"w": jnp.ones((N, D), jnp.float32)})
    state = _shard(bfopt.init_distributed(bad, params))
    with pytest.raises(ValueError, match="pace must be"):
        step(params, state, jnp.zeros((N, 1)))


def test_async_knob_resolution(ctx, monkeypatch):
    monkeypatch.delenv("BLUEFOG_ASYNC", raising=False)
    assert bf.async_gossip_bound() == 4          # library default
    monkeypatch.setenv("BLUEFOG_ASYNC", "7")
    assert bf.async_gossip_bound() == 7          # env overrides default
    bf.set_async_gossip(2)
    assert bf.async_gossip_bound() == 2          # knob overrides env
    bf.set_async_gossip(None)
    assert bf.async_gossip_bound() == 7
    with pytest.raises(ValueError):
        bf.set_async_gossip(-3)
    monkeypatch.setenv("BLUEFOG_ASYNC", "-1")
    with pytest.raises(ValueError):
        bf.async_gossip_bound()


# ---------------------------------------------------------------------------
# autotune: enumerable, audited, plannable, replayable
# ---------------------------------------------------------------------------

def test_async_autotune_enumerated_audited_and_replayable(ctx, tmp_path):
    from bluefog_tpu.autotune import autotune, enumerate_candidates
    exp2 = {"family": "exp2", "size": N}
    accepted, rejected = enumerate_candidates(
        N, algorithms=("async_window_gossip",), topologies=(exp2,),
        wires=(None,), fused_k=(1,), include_concurrent=False,
        include_delayed=False)
    assert [c.algorithm for c in accepted] == ["async_window_gossip"]
    assert accepted[0].weights == "push"
    assert [r["config"]["weights"] for r in rejected] == ["dst"]
    assert "column-stochastic push" in rejected[0]["reason"]

    plan = autotune(
        params={"w": jnp.zeros((64, 8), jnp.float32)},
        algorithms=("async_window_gossip", "neighbor_cta"),
        topologies=(exp2,), wires=(None,), fused_k=(1,),
        include_delayed=False, include_concurrent=False,
        opt_factory=lambda: optax.sgd(0.05),
        measured_dir=str(tmp_path), bank_trials=False)
    audit = plan.doc["audit"]
    assert audit["considered"] == len(audit["scored"]) + len(audit["rejected"])
    assert any(s["key"].startswith("async_window_gossip")
               for s in audit["scored"]), "async never scored"
    assert any(r["key"].startswith("async_window_gossip")
               and "weights=dst" in r["key"]
               and "column-stochastic push" in r["reason"]
               for r in audit["rejected"])

    # replay: the async candidate reconstructs through the registry and
    # trains (exactly what bench/serve do with a saved plan)
    replayed = next(c for c in accepted if c.weights == "push")
    from bluefog_tpu.autotune.plan import Plan, make_plan_doc
    doc = make_plan_doc(config=replayed.config(), objective="step_time",
                        n_chips=N, device_kind="cpu",
                        predicted={}, audit={"scored": [], "rejected": [],
                                             "considered": 0})
    strat = Plan(doc).build_strategy(optax.sgd(0.05))
    step = bfopt.make_train_step(_zero_grad_fn, strat, donate=False)
    params = _shard({"w": jnp.ones((N, D), jnp.float32)})
    state = _shard(bfopt.init_distributed(strat, params))
    params, state, _ = step(params, state, jnp.zeros((N, 1)))
    assert bool(np.isfinite(np.asarray(params["w"])).all())


# ---------------------------------------------------------------------------
# observability: the staleness-depth probe
# ---------------------------------------------------------------------------

def test_observe_async_staleness_publishes_gauges(ctx):
    sched = _push_sched()
    strat = bfopt.async_window_gossip(
        optax.sgd(0.0), sched, staleness_bound=6,
        pace=[1, 1, 1, 4, 1, 1, 1, 1])
    step = bfopt.make_train_step(_zero_grad_fn, strat, donate=False)
    params = _shard({"w": jnp.ones((N, D), jnp.float32)})
    state = _shard(bfopt.init_distributed(strat, params))
    # stop right before the straggler's pace-4 reactivation: the carried
    # depth peaks at tick 3 (last delivery was tick 0)
    for _ in range(4):
        params, state, _ = step(params, state, jnp.zeros((N, 1)))
    sample = bfdiag.observe_async_staleness(state)
    assert sample is not None
    assert sample["staleness_depth"].shape == (N,)
    assert sample["local_steps"].shape == (N,)
    assert sample["staleness_depth_max"] >= 2     # the pace-4 straggler
    assert sample["forced_sync_pending"] in (True, False)
    g = bfm.gauge("bluefog_async_staleness_steps")
    assert g.value() == float(sample["staleness_depth_max"])
    assert bfm.gauge("bluefog_async_forced_sync").value() in (0.0, 1.0)
    kinds = {e["kind"] for e in flight.events()}
    assert "async_staleness" in kinds
    # non-async states are a polite no-op, not a crash
    assert bfdiag.observe_async_staleness(object()) is None


def test_instrumented_step_samples_staleness(ctx):
    """metrics_every_k wires the probe into the step shim itself: training
    with an async strategy publishes the staleness gauge with no user
    code."""
    sched = _push_sched()
    strat = bfopt.async_window_gossip(
        optax.sgd(0.0), sched, staleness_bound=6,
        pace=[1, 1, 1, 4, 1, 1, 1, 1])
    step = bfopt.make_train_step(_zero_grad_fn, strat, donate=False,
                                 metrics_every_k=2)
    params = _shard({"w": jnp.ones((N, D), jnp.float32)})
    state = _shard(bfopt.init_distributed(strat, params))
    for _ in range(5):
        params, state, _ = step(params, state, jnp.zeros((N, 1)))
    assert bfm.gauge("bluefog_async_staleness_steps").value() is not None


# ---------------------------------------------------------------------------
# the async frontier bench artifact
# ---------------------------------------------------------------------------

def test_async_frontier_artifact_async_beats_sync(tmp_path):
    """The headline: one rank throttled 10x on Exp2(8), async reaches the
    consensus target at a smaller cost than synchronous, artifact schema
    versioned.  The cost is COUNTED, in unthrottled ticks under a fixed
    delay of ``factor - 1`` ticks: the sync arm pays the straggler's delay
    on every tick, the async arm only before a forced sync-up.  (The
    artifact's own ``wall_s`` / ``won`` race the host's clock under six
    loaded workers; the counts do not.)"""
    out = tmp_path / "async_frontier.json"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gossip_bench.py"),
         "--async-frontier", "--virtual-cpu", "--params", "2048",
         "--out", str(out)],
        cwd=REPO, capture_output=True, text=True, timeout=420, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(out.read_text())
    assert doc["schema"] == "bluefog-gossip-async-1"
    assert doc["n"] == N and doc["topology"] == "expo2(8)"
    assert doc["throttle"]["factor"] == 10
    for arm in ("sync", "async"):
        assert doc[arm]["reached_target"] is True, doc
        assert doc[arm]["ticks"] >= 1 and doc[arm]["wall_s"] > 0
    assert doc["async"]["staleness_max"] > doc["staleness_bound"] - 1
    factor = doc["throttle"]["factor"]
    sync_cost = doc["sync"]["ticks"] * factor
    async_cost = (doc["async"]["ticks"]
                  + doc["async"]["forced_syncs"] * (factor - 1))
    assert doc["async"]["forced_syncs"] < doc["sync"]["ticks"], doc
    assert async_cost < sync_cost, (async_cost, sync_cost, doc)
