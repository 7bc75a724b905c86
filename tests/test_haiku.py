"""Framework-agnosticism: the strategies train a dm-haiku model unchanged.

The reference maintains a second full binding layer for TensorFlow
(SURVEY.md §2.3: custom ops, gradient registrations, DistributedOptimizer /
DistributedGradientTape).  Here the op/optimizer surface is pytree-generic,
so a second NN framework needs zero adapter code — this test is the parity
evidence: a haiku MLP trains to consensus with the same strategies the flax
models use.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import topology as tu
from bluefog_tpu.utils import utility

haiku = pytest.importorskip("haiku")

N = 8


@pytest.fixture(autouse=True)
def ctx(cpu_devices):
    bf.init(devices=cpu_devices, nodes_per_machine=1)
    bf.set_topology(tu.ExponentialTwoGraph(N), is_weighted=True)
    yield
    bf.shutdown()


def test_haiku_model_trains_with_gossip():
    def net_fn(x):
        return haiku.nets.MLP([16, 4])(x)

    net = haiku.without_apply_rng(haiku.transform(net_fn))
    params = net.init(jax.random.PRNGKey(0), jnp.ones((2, 8)))

    def grad_fn(p, batch):
        xb, yb = batch

        def loss_fn(q):
            return jnp.mean((net.apply(q, xb) - yb) ** 2)

        return jax.value_and_grad(loss_fn)(p)

    strategy = bfopt.adapt_then_combine(
        optax.adam(1e-2),
        bfopt.neighbor_communicator(bf.static_schedule()))
    dist_params = bfopt.replicate(params)
    dist_state = bfopt.init_distributed(strategy, dist_params)
    step = bfopt.make_train_step(grad_fn, strategy)

    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.normal(size=(N, 2, 8)), jnp.float32)
    yb = jnp.zeros((N, 2, 4), jnp.float32)
    losses = []
    for _ in range(30):
        dist_params, dist_state, loss = step(dist_params, dist_state, (xb, yb))
        losses.append(float(np.asarray(jax.block_until_ready(loss)).mean()))
    assert losses[-1] < losses[0] * 0.5, f"no training progress: {losses[::10]}"


def test_haiku_stateful_bn_trains_and_syncs_state():
    """A haiku net with BatchNorm (transform_with_state) trains end-to-end:
    params flow through the strategy, BN running stats thread through
    make_stateful_train_step and gossip to consensus with state_sync
    (the reference leaves per-rank BN buffers local and only syncs at
    restart — SURVEY §2.3's TF layer has the same gap)."""
    def net_fn(x, is_training):
        h = haiku.Linear(16)(x)
        h = haiku.BatchNorm(create_scale=True, create_offset=True,
                            decay_rate=0.9)(h, is_training)
        h = jax.nn.relu(h)
        return haiku.Linear(4)(h)

    net = haiku.without_apply_rng(haiku.transform_with_state(net_fn))
    params, net_state = net.init(
        jax.random.PRNGKey(0), jnp.ones((2, 8)), is_training=True)

    def grad_fn(p, ns, batch):
        xb, yb = batch

        def loss_fn(q):
            out, new_ns = net.apply(q, ns, xb, is_training=True)
            return jnp.mean((out - yb) ** 2), new_ns

        (loss, new_ns), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        return loss, grads, new_ns

    strategy = bfopt.adapt_with_combine(
        optax.adam(1e-2),
        bfopt.neighbor_communicator(bf.static_schedule()))
    dist_params = bfopt.replicate(params)
    dist_ns = bfopt.replicate(net_state)
    dist_state = bfopt.init_distributed(strategy, dist_params)
    step = bfopt.make_stateful_train_step(
        grad_fn, strategy, state_sync="neighbor")

    rng = np.random.default_rng(1)
    # DIFFERENT data per rank: BN stats would drift apart without sync
    xb = jnp.asarray(rng.normal(size=(N, 2, 8)) + np.arange(N)[:, None, None],
                     jnp.float32)
    yb = jnp.zeros((N, 2, 4), jnp.float32)
    losses = []
    for _ in range(40):
        dist_params, dist_ns, dist_state, loss = step(
            dist_params, dist_ns, dist_state, (xb, yb))
        losses.append(float(np.asarray(jax.block_until_ready(loss)).mean()))
    assert losses[-1] < losses[0] * 0.5, f"no progress: {losses[::10]}"

    # BN running stats reached (near-)consensus despite per-rank data shift.
    # Not zero: each step injects a per-rank EMA update (decay 0.9) from
    # rank-shifted data and one gossip round only contracts, so the spread
    # settles at a fixed point h* = 0.1(I - 0.9 W^T)^(-1) W^T m: 0.47 on
    # Exp2(8) with this data shift
    for path, leaf in jax.tree_util.tree_flatten_with_path(dist_ns)[0]:
        arr = np.asarray(leaf, np.float32)
        spread = np.abs(arr - arr.mean(axis=0, keepdims=True)).max()
        assert spread < 0.5, (path, spread)
        assert np.isfinite(arr).all()
    # and they moved away from init (stats actually updated through the scan)
    mean0 = np.asarray(jax.tree.leaves(net_state)[0], np.float32)
    meanT = np.asarray(jax.tree.leaves(dist_ns)[0][0], np.float32)
    assert not np.allclose(mean0, meanT)


@pytest.mark.parametrize("make_strategy", [
    lambda: bfopt.adapt_with_combine(
        optax.adam(5e-3),
        bfopt.neighbor_communicator(bf.static_schedule())),
    lambda: bfopt.win_put_optimizer(optax.adam(5e-3)),
], ids=["cta", "win_put"])
def test_haiku_optimizer_state_broadcast_restart(make_strategy):
    """Restart flow for a second framework under two strategies: train,
    corrupt non-root ranks, re-seed with broadcast_parameters +
    broadcast_optimizer_state (the reference's restart primitives,
    utility.py:26-216), and keep training."""
    def net_fn(x):
        return haiku.nets.MLP([16, 4])(x)

    net = haiku.without_apply_rng(haiku.transform(net_fn))
    params = net.init(jax.random.PRNGKey(0), jnp.ones((2, 8)))

    def grad_fn(p, batch):
        xb, yb = batch
        return jax.value_and_grad(
            lambda q: jnp.mean((net.apply(q, xb) - yb) ** 2))(p)

    strategy = make_strategy()
    dist_params = bfopt.replicate(params)
    dist_state = bfopt.init_distributed(strategy, dist_params)
    step = bfopt.make_train_step(grad_fn, strategy)

    rng = np.random.default_rng(2)
    batch = (jnp.asarray(rng.normal(size=(N, 2, 8)), jnp.float32),
             jnp.zeros((N, 2, 4), jnp.float32))
    for _ in range(10):
        dist_params, dist_state, loss = step(dist_params, dist_state, batch)
    jax.block_until_ready(loss)

    # "crash": every non-root rank loses its params and optimizer state
    root = 3
    wreck = lambda x: x.at[jnp.arange(N) != root].set(0) \
        if jnp.issubdtype(x.dtype, jnp.floating) else x
    dist_params = jax.tree.map(wreck, dist_params)
    dist_state = dist_state._replace(
        opt_state=jax.tree.map(wreck, dist_state.opt_state))

    # restart: re-seed everything from the surviving root
    dist_params = utility.broadcast_parameters(dist_params, root_rank=root)
    dist_state = dist_state._replace(
        opt_state=utility.broadcast_optimizer_state(
            dist_state.opt_state, root_rank=root))
    for leaf in jax.tree.leaves(dist_params):
        arr = np.asarray(leaf)
        for r in range(N):
            np.testing.assert_array_equal(arr[r], arr[root])

    # training resumes and keeps improving
    post = []
    for _ in range(20):
        dist_params, dist_state, loss = step(dist_params, dist_state, batch)
        post.append(float(np.asarray(jax.block_until_ready(loss)).mean()))
    assert post[-1] <= post[0], post[::5]
    assert np.isfinite(post).all()


def test_haiku_broadcast_parameters():
    def net_fn(x):
        return haiku.nets.MLP([4])(x)

    net = haiku.without_apply_rng(haiku.transform(net_fn))
    per_rank = [net.init(jax.random.PRNGKey(r), jnp.ones((1, 3)))
                for r in range(N)]
    dist = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
    synced = utility.broadcast_parameters(dist, root_rank=2)
    for leaf in jax.tree.leaves(synced):
        for r in range(N):
            np.testing.assert_allclose(
                np.asarray(leaf[r]), np.asarray(leaf[2]), rtol=1e-6)
