"""PowerSGD low-rank gradient compression (beyond-reference DP lever).

Oracles: convergence to the closed-form optimum on a matrix least-squares
problem (error feedback makes the rank-r approximation error decay),
projection exactness at full rank, rank lock-step, small-leaf exactness,
and the wire-bytes cut in the compiled v5e schedule.
"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import topology as tu
from bluefog_tpu.utils.hlo_bytes import wire_stats

N, D, C = 8, 8, 16


@pytest.fixture(autouse=True)
def ctx(cpu_devices):
    bf.init(devices=cpu_devices)
    bf.set_topology(tu.ExponentialTwoGraph(N), is_weighted=True)
    yield
    bf.shutdown()


def _problem(seed=0):
    rng = np.random.default_rng(seed)
    W_star = rng.normal(size=(D, C))
    A = rng.normal(size=(N, 24, D))
    B = A @ W_star + 0.05 * rng.normal(size=(N, 24, C))
    AtA = sum(A[r].T @ A[r] for r in range(N))
    AtB = sum(A[r].T @ B[r] for r in range(N))
    return (jnp.asarray(A, jnp.float32), jnp.asarray(B, jnp.float32),
            np.linalg.solve(AtA, AtB))


def grad_fn(params, batch):
    A, B = batch
    return jax.value_and_grad(
        lambda p: jnp.mean((A @ p["W"] - p["b"] - B) ** 2))(params)


def _run(strategy, steps=400, chunk=50):
    A, B, W_opt = _problem()
    params = bfopt.replicate({"W": jnp.zeros((D, C), jnp.float32),
                              "b": jnp.zeros((C,), jnp.float32)})
    state = bfopt.init_distributed(strategy, params)
    step = bfopt.make_train_step(grad_fn, strategy, steps_per_call=chunk)
    batch = jax.tree.map(
        lambda x: jnp.broadcast_to(x[:, None], (N, chunk) + x.shape[1:]),
        (A, B))
    for _ in range(steps // chunk):
        params, state, loss = step(params, state, batch)
        jax.block_until_ready(loss)
    return params, W_opt


def test_powersgd_converges_with_error_feedback():
    """rank-2 compression of a [8, 16] gradient still drives every rank to
    the global optimum: the feedback loop turns the rank deficit into a
    decaying perturbation, not a bias.  The uncompressed bias leaf rides
    exactly."""
    strat = bfopt.powersgd_allreduce(
        optax.sgd(0.03, momentum=0.9), compression_rank=2,
        min_compress_size=64)
    params, W_opt = _run(strat)
    W = np.asarray(params["W"])
    for r in range(N):
        np.testing.assert_allclose(W[r], W_opt, atol=0.08)
    # synchronous strategy: all ranks bitwise in lock-step
    for r in range(1, N):
        np.testing.assert_array_equal(W[0], W[r])


def test_powersgd_full_rank_identical_grads_is_exact():
    """With rank >= min(m, k) and identical gradients on every rank, the
    power iteration projects M onto its own column space — the compressed
    allreduce returns the exact mean."""
    strat = bfopt.powersgd_allreduce(
        optax.sgd(1.0), compression_rank=D, min_compress_size=64)
    rng = np.random.default_rng(3)
    G = rng.normal(size=(D, C)).astype(np.float32)

    mesh = bf.mesh()

    def f(g):
        state = strat.init({"W": jnp.zeros((D, C), jnp.float32)})
        new_p, _ = strat.update({"W": g[0]}, state,
                                {"W": jnp.zeros((D, C), jnp.float32)})
        return new_p["W"][None]

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("rank"), out_specs=P("rank")))
    g_dist = jnp.broadcast_to(jnp.asarray(G), (N, D, C))
    out = np.asarray(fn(g_dist))
    # sgd(1.0): new params = -ghat; identical grads -> mean == G exactly
    for r in range(N):
        np.testing.assert_allclose(out[r], -G, rtol=1e-4, atol=1e-5)


def test_powersgd_rejects_bad_rank():
    with pytest.raises(ValueError, match="compression_rank"):
        bfopt.powersgd_allreduce(optax.sgd(0.1), compression_rank=0)


@pytest.mark.slow
def test_powersgd_wire_bytes_cut_on_v5e():
    """The compiled TPU schedule allreduces the rank-r factors, not the
    full matrix: payload ~ (m + k) * r * 4 bytes vs m * k * 4.

    slow: AOT-compiling the two v5e train steps dominates the fast tier
    (460 s of XLA compile on the CPU-only CI box)."""
    from jax.experimental import topologies

    try:
        td = topologies.get_topology_desc("v5e:2x4", platform="tpu")
    except Exception as e:
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    mesh = Mesh(np.array(td.devices), ("rank",))
    m, k, r = 1024, 512, 4
    strat = bfopt.powersgd_allreduce(
        optax.sgd(0.1), compression_rank=r)
    base = bfopt.gradient_allreduce(optax.sgd(0.1), fuse=False)

    def make(strategy):
        def f(g, e, q):
            state = bfopt.DecentralizedState(
                jnp.zeros((), jnp.int32),
                optax.sgd(0.1).init({"W": g[0]}),
                ((e[0],), (q[0],)) if strategy is strat else None)
            new_p, _ = strategy.update({"W": g[0]}, state, {"W": g[0]})
            return new_p["W"][None]

        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("rank"),) * 3,
            out_specs=P("rank")))

    sds = lambda shape: jax.ShapeDtypeStruct(
        (N,) + shape, jnp.float32, sharding=NamedSharding(mesh, P("rank")))
    txt = make(strat).lower(
        sds((m, k)), sds((m, k)), sds((k, r))).compile().as_text()
    _, bytes_c = wire_stats(txt)
    txt_b = make(base).lower(
        sds((m, k)), sds((m, k)), sds((k, r))).compile().as_text()
    _, bytes_b = wire_stats(txt_b)
    compressed = bytes_c.get("all-reduce", 0)
    full = bytes_b.get("all-reduce", 0)
    assert full >= m * k * 4                    # baseline moves the matrix
    assert compressed <= (m + k) * r * 4 * 2    # factors only (some slack)
    assert compressed * 8 < full                # >8x wire cut at r=4
