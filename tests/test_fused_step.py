"""Step-path performance contract: donation, fusion, and the compile cache.

Pins the three tentpole properties of the training step path:

* buffer donation — the jitted step's optimized HLO aliases the
  params/opt-state inputs to outputs (``input_output_alias``), and the
  caller-visible effect is real: the pre-step buffers are consumed;
* fused multi-step execution — ``steps_per_call=k`` compiles to ONE
  executable (no retrace across calls), follows the SAME trajectory as k
  separate calls (including a rotating dynamic topology), in 1/k of the
  host round trips;
* the process-level program cache — repeated builds of the same
  (schedule, mesh, shape) program never re-lower.
"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import schedule as sch
from bluefog_tpu import topology as tu
from bluefog_tpu.parallel import context as bfctx
from bluefog_tpu.utils import metrics as bfm

N, D = 8, 6


def grad_fn(params, batch):
    A, b = batch

    def loss(w):
        r = A @ w["w"] - b
        return jnp.mean(r * r)

    l, g = jax.value_and_grad(loss)(params)
    return l, g


@pytest.fixture(autouse=True)
def ctx(cpu_devices):
    bf.init(devices=cpu_devices, nodes_per_machine=1)
    bf.set_topology(tu.ExponentialTwoGraph(N), is_weighted=True)
    yield
    bf.shutdown()


def _setup(seed=0):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.normal(size=(N, 20, D)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(N, 20)), jnp.float32)
    params = {"w": jnp.asarray(rng.normal(size=(N, D)), jnp.float32)}
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.05), bfopt.neighbor_communicator(bf.static_schedule()))
    state = bfopt.init_distributed(strat, params)
    return strat, params, state, (A, b)


def test_fused_step_hlo_aliases_donated_inputs():
    """AOT pin: the fused k-step body is ONE executable whose optimized
    HLO aliases the donated params/opt-state input buffers to outputs."""
    strat, params, state, batch = _setup()
    step = bfopt.make_train_step(grad_fn, strat, steps_per_call=3,
                                 reuse_batch=True, donate=True)
    hlo = step.lower(params, state, batch).compile().as_text()
    assert "input_output_alias" in hlo, (
        "donated params/opt-state must be aliased in the compiled module")
    # the donation contract is the public constant, not a guess
    assert bfopt.TRAIN_STEP_DONATE_ARGNUMS == (0, 1)


def test_undonated_step_has_no_aliases():
    strat, params, state, batch = _setup()
    step = bfopt.make_train_step(grad_fn, strat, donate=False)
    hlo = step.lower(params, state, batch).compile().as_text()
    assert "input_output_alias" not in hlo


def test_donated_buffers_are_consumed():
    """The caller-visible half of donation: once the inputs carry the mesh
    sharding (every call after the first — the first call's replicated
    host arrays are resharded, which copies), the pre-step param buffer
    is consumed by the call, not silently copied."""
    strat, params, state, batch = _setup()
    step = bfopt.make_train_step(grad_fn, strat, donate=True)
    params, state, _ = step(params, state, batch)    # reshard to the mesh
    old_w = params["w"]
    params2, state2, _ = step(params, state, batch)
    jax.block_until_ready(params2["w"])
    assert np.isfinite(np.asarray(params2["w"])).all()
    assert old_w.is_deleted(), "donated input must be consumed in place"
    with pytest.raises(RuntimeError):
        np.asarray(old_w)


def test_fused_step_no_retrace_across_calls():
    strat, params, state, batch = _setup()
    step = bfopt.make_train_step(grad_fn, strat, steps_per_call=4,
                                 reuse_batch=True, donate=True)
    # the first call resolves input shardings (replicated host arrays ->
    # mesh-sharded outputs), so steady state starts at call 2
    params, state, loss = step(params, state, batch)
    params, state, loss = step(params, state, batch)
    steady = step._cache_size()
    for _ in range(3):
        params, state, loss = step(params, state, batch)
    jax.block_until_ready(loss)
    assert step._cache_size() == steady, (
        "steady-state fused calls must reuse the compiled executable, "
        "not retrace")


def test_reuse_batch_requires_fusion():
    strat, *_ = _setup()
    with pytest.raises(ValueError, match="steps_per_call"):
        bfopt.make_train_step(grad_fn, strat, steps_per_call=1,
                              reuse_batch=True)


def _dynamic_strategy():
    topo = tu.ExponentialTwoGraph(N)
    scheds = sch.compile_dynamic_schedules(
        lambda r: tu.GetDynamicOnePeerSendRecvRanks(topo, r), N)
    return bfopt.adapt_with_combine(
        optax.sgd(0.05), bfopt.neighbor_communicator(schedules=scheds))


@pytest.mark.parametrize("dynamic", [False, True])
def test_fused_trajectory_matches_unfused(dynamic):
    """k fused steps == k separate calls, leaf for leaf — including a
    dynamic topology whose lax.switch rotates INSIDE the fused body (the
    step counter lives in the carried optimizer state)."""
    k = 4
    strat = _dynamic_strategy() if dynamic else _setup()[0]
    _, params, _, batch = _setup()
    state = bfopt.init_distributed(strat, params)

    one = bfopt.make_train_step(grad_fn, strat, donate=False)
    p1, s1 = params, state
    for _ in range(k):
        p1, s1, _ = one(p1, s1, batch)

    fused = bfopt.make_train_step(grad_fn, strat, steps_per_call=k,
                                  reuse_batch=True, donate=False)
    pk, sk, losses = fused(params, state, batch)
    assert losses.shape == (N, k)
    np.testing.assert_allclose(np.asarray(pk["w"]), np.asarray(p1["w"]),
                               rtol=1e-5, atol=1e-6)


def test_fused_amortizes_host_round_trips():
    """With the host in the loop (a sync after every call), k steps in
    one executable are ONE host round trip where the single-step program
    makes k.  Counted from the step wrapper's own registry (a call is one
    observation of ``bluefog_step_time_s``, a step one count of
    ``bluefog_train_steps_total``): both programs take the same number of
    optimizer steps to the same parameters, the fused one in 1/k of the
    dispatches.  What a round trip costs is a chip's to say
    (``train_step.wrapper_host_s_per_call`` in the benchmark)."""
    strat, *_ = _setup()
    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.normal(size=(N, 4, 2)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(N, 4)), jnp.float32)
    batch = (A, b)
    params = {"w": jnp.asarray(rng.normal(size=(N, 2)), jnp.float32)}
    state = bfopt.init_distributed(strat, params)
    k, reps = 16, 3

    def round_trips(step, calls):
        bfm.reset_metrics()
        p, s = params, state
        for _ in range(calls):
            p, s, loss = step(p, s, batch)
            jax.block_until_ready(loss)
        calls_seen = bfm.histogram("bluefog_step_time_s").dump()["count"]
        steps = bfm.counter("bluefog_train_steps_total").total()
        return calls_seen, steps, np.asarray(p["w"])

    one = bfopt.make_train_step(grad_fn, strat, donate=False)
    fused = bfopt.make_train_step(grad_fn, strat, steps_per_call=k,
                                  reuse_batch=True, donate=False)
    unfused_calls, unfused_steps, w_unfused = round_trips(one, reps * k)
    fused_calls, fused_steps, w_fused = round_trips(fused, reps)
    assert unfused_steps == fused_steps == reps * k
    assert (unfused_calls, fused_calls) == (reps * k, reps)
    np.testing.assert_allclose(w_fused, w_unfused, rtol=1e-5, atol=1e-6)


def test_program_cache_no_relower():
    """Two identical op invocations lower once; the shared process cache
    (parallel/context.py) serves the second."""
    bfctx.clear_program_cache()
    x = jnp.ones((N, 4), jnp.float32)
    before = bfctx.program_cache_stats()
    y1 = bf.neighbor_allreduce(x)
    y2 = bf.neighbor_allreduce(x)
    jax.block_until_ready((y1, y2))
    after = bfctx.program_cache_stats()
    assert after["misses"] == before["misses"] + 1
    assert after["hits"] >= before["hits"] + 1
    # donation is part of the key: a donating variant is a DIFFERENT program
    y3 = bf.neighbor_allreduce(jnp.ones((N, 4), jnp.float32), donate=True)
    jax.block_until_ready(y3)
    assert bfctx.program_cache_stats()["misses"] == before["misses"] + 2


def test_cached_lowering_returns_same_executable():
    calls = {"n": 0}

    def traced(x):
        calls["n"] += 1
        return x * 2.0

    f = jax.jit(traced)
    x = jnp.ones((4,), jnp.float32)
    c1 = bfctx.cached_lowering(("test-lower", 4), f, x)
    c2 = bfctx.cached_lowering(("test-lower", 4), f, x)
    assert c1 is c2
    assert calls["n"] == 1
    np.testing.assert_allclose(np.asarray(c1(x)), 2.0 * np.ones(4))
