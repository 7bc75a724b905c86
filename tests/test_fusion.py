"""Fusion bucketing: fused collectives must equal per-leaf collectives.

Model: the reference's fusion tests (torch_ops_test.py:211-285, 905-1115) —
same results with and without the fusion buffer, including dynamic topology
and dst-weight cases.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu import fusion, ops
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import schedule as sch
from bluefog_tpu import topology as tu
from bluefog_tpu.utils import hlo_bytes

N = 8

# per-rank leaves: whole tiles (last dimension a multiple of 128, leading
# product a multiple of the dtype's sublanes), ragged ones, two float
# widths and an integer counter
WHOLE = {"qkv": ((2, 8, 384), jnp.float32), "head": ((16, 256), jnp.float32),
         "half": ((32, 128), jnp.bfloat16)}
RAGGED = {"bias": ((7,), jnp.float32), "narrow": ((3, 50), jnp.float32),
          "scalar": ((), jnp.float32), "odd": ((5, 130), jnp.float32),
          "kernel": ((3, 3, 64, 64), jnp.float32),
          "half_odd": ((4, 3), jnp.bfloat16), "count": ((), jnp.int32)}
TREES = {"small": {"w": ((4, 3), jnp.float32), "b": ((3,), jnp.float32)},
         "tiles_and_ragged": {**WHOLE, **RAGGED}}


def draw(rng, shape, dtype, lead=()):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(0, 9, size=lead + shape), dtype)
    return jnp.asarray(rng.normal(size=lead + shape), dtype)


def dist_tree(rng, name):
    """The tree ``name`` with a leading rank axis on every leaf."""
    return {k: draw(rng, shape, dtype, (N,))
            for k, (shape, dtype) in TREES[name].items()}


def gossip(comm, dist, step=0):
    fn = jax.jit(jax.shard_map(
        lambda t, s: jax.tree.map(
            lambda x: x[None],
            comm(jax.tree.map(lambda x: x[0], t), s[0])),
        mesh=bf.mesh(), in_specs=(P("rank"), P("rank")),
        out_specs=P("rank")))
    return fn(dist, jnp.full((N,), step, jnp.int32))


def assert_same_leaves(per_leaf, fused):
    for a, b in zip(jax.tree.leaves(per_leaf), jax.tree.leaves(fused)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-2 if a.dtype == jnp.bfloat16 else 1e-6)


def assert_roundtrip(out, tree):
    """``out`` is ``tree`` again: structure, dtypes, shapes, every bit."""
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.fixture(autouse=True)
def ctx(cpu_devices):
    bf.init(devices=cpu_devices, nodes_per_machine=1)
    bf.set_topology(tu.ExponentialTwoGraph(N), is_weighted=True)
    yield
    bf.shutdown()


def make_tree(rng):
    return {
        "w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32),
        "h": jnp.asarray(rng.normal(size=(2, 2)), jnp.bfloat16),
        "scalar": jnp.asarray(rng.normal(), jnp.float32),
    }


def test_fuse_unfuse_roundtrip():
    tree = make_tree(np.random.default_rng(0))
    fused = fusion.fuse_tree(tree)
    assert len(fused.buffers) == 2          # one per dtype (f32, bf16)
    assert_roundtrip(fused.unfuse(), tree)


def test_unfuse_traces_to_static_slices():
    """The unpack offsets are compile-time constants, so the traced program
    must contain plain ``slice`` primitives only — a ``dynamic_slice``
    would mean XLA sees data-dependent offsets and inserts bounds clamps
    the scheduler cannot fold away."""
    tree = make_tree(np.random.default_rng(2))

    def roundtrip(t):
        return fusion.fuse_tree(t).unfuse()

    prims = {e.primitive.name
             for e in jax.make_jaxpr(roundtrip)(tree).eqns}
    assert "slice" in prims
    assert "dynamic_slice" not in prims


@pytest.mark.parametrize("wire", [None, "bf16"])
@pytest.mark.parametrize("tree", list(TREES))
def test_fused_communicator_matches_per_leaf(tree, wire):
    dist = dist_tree(np.random.default_rng(1), tree)
    sched = bf.static_schedule()
    results = {fuse: gossip(bfopt.neighbor_communicator(
        sched, fuse=fuse, wire=wire), dist) for fuse in (False, True)}
    assert_same_leaves(results[False], results[True])


def test_fused_training_step_converges():
    """End-to-end: fused CTA strategy trains a small quadratic to consensus."""
    target = jnp.ones((N, 5)) * 3.0

    def grad_fn(params, batch):
        def loss_fn(p):
            return jnp.mean((p["x"] - batch) ** 2)
        return jax.value_and_grad(loss_fn)(params)

    strategy = bfopt.adapt_with_combine(
        optax.sgd(0.3),
        bfopt.neighbor_communicator(bf.static_schedule(), fuse=True))
    dist_params = {"x": jnp.asarray(
        np.random.default_rng(2).normal(size=(N, 1, 5)), jnp.float32)}
    dist_state = bfopt.init_distributed(strategy, dist_params)
    step = bfopt.make_train_step(grad_fn, strategy)
    for _ in range(70):
        dist_params, dist_state, loss = step(
            dist_params, dist_state, target[:, None])
        jax.block_until_ready(loss)
    np.testing.assert_allclose(
        np.asarray(dist_params["x"][:, 0]), np.asarray(target), atol=1e-2)


@pytest.mark.parametrize("tree", list(TREES))
def test_fused_dynamic_schedules(tree):
    topo = tu.ExponentialTwoGraph(N)
    scheds = sch.compile_dynamic_schedules(
        lambda r: tu.GetDynamicOnePeerSendRecvRanks(topo, r), N)
    dist = dist_tree(np.random.default_rng(3), tree)
    for t in range(3):
        results = {fuse: gossip(bfopt.neighbor_communicator(
            schedules=scheds, fuse=fuse), dist, step=t)
            for fuse in (False, True)}
        assert_same_leaves(results[False], results[True])


def test_win_put_optimizer_fused_matches_unfused():
    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: jnp.mean((p["a"] - batch) ** 2)
            + jnp.mean(p["b"] ** 2))(params)

    rng = np.random.default_rng(5)
    params0 = {"a": jnp.asarray(rng.normal(size=(N, 1, 4)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(N, 1, 2)), jnp.float32)}
    target = jnp.ones((N, 1, 4))
    results = {}
    for fuse in (False, True):
        strategy = bfopt.win_put_optimizer(optax.sgd(0.1), fuse=fuse)
        dp = jax.tree.map(lambda x: x, params0)
        ds = bfopt.init_distributed(strategy, dp)
        step = bfopt.make_train_step(grad_fn, strategy)
        for _ in range(4):
            dp, ds, loss = step(dp, ds, target)
            jax.block_until_ready(loss)
        results[fuse] = dp
    for a, b in zip(jax.tree.leaves(results[False]), jax.tree.leaves(results[True])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_push_sum_fused_matches_unfused():
    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: jnp.mean((p["a"] - batch) ** 2))(params)

    rng = np.random.default_rng(6)
    params0 = {"a": jnp.asarray(rng.normal(size=(N, 1, 4)), jnp.float32),
               "b": jnp.asarray(rng.normal(size=(N, 1, 2)), jnp.float32)}
    target = jnp.zeros((N, 1, 4))
    results = {}
    for fuse in (False, True):
        strategy = bfopt.push_sum(optax.sgd(0.05), fuse=fuse)
        dp = jax.tree.map(lambda x: x, params0)
        ds = bfopt.init_distributed(strategy, dp)
        step = bfopt.make_train_step(grad_fn, strategy)
        for _ in range(4):
            dp, ds, loss = step(dp, ds, target)
            jax.block_until_ready(loss)
        results[fuse] = dp
    for a, b in zip(jax.tree.leaves(results[False]), jax.tree.leaves(results[True])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


@pytest.mark.parametrize("tree", list(TREES))
def test_fused_dst_weighted_schedule(tree):
    """Fusion x dst-weighting (reference torch_ops_test.py:905-1115)."""
    from bluefog_tpu.schedule import compile_from_weights
    sched = compile_from_weights(
        N, [0.5] * N,
        [{(r - 1) % N: 0.5} for r in range(N)],
        [{(r + 1) % N: 2.0} for r in range(N)])
    assert sched.uses_dst_weighting
    dist = dist_tree(np.random.default_rng(9), tree)
    results = {fuse: gossip(bfopt.neighbor_communicator(sched, fuse=fuse),
                            dist) for fuse in (False, True)}
    assert_same_leaves(results[False], results[True])
    # oracle: x' = 0.5 x + 0.5 * (2.0 * x_prev)
    for k, v in dist.items():
        if v.dtype != jnp.float32:
            continue
        vals = np.asarray(v)
        for r in range(N):
            expected = 0.5 * vals[r] + 1.0 * vals[(r - 1) % N]
            np.testing.assert_allclose(
                np.asarray(results[True][k][r]), expected, rtol=1e-5,
                atol=1e-6)


# ---------------------------------------------------------------------------
# The tile-ordered bucketing
# ---------------------------------------------------------------------------

def own_tiles(tiled):
    """Per buffer, each leaf's span of it: what ``untile`` takes back."""
    return [[buf[a:b] for a, b in spans]
            for buf, spans in zip(tiled.buffers, tiled.spans)]


@pytest.mark.parametrize("name", list(TREES["tiles_and_ragged"]))
def test_tile_roundtrip_of_one_leaf(name):
    shape, dtype = TREES["tiles_and_ragged"][name]
    x = draw(np.random.default_rng(4), shape, dtype)
    tiled = fusion.tile_tree({"x": x})
    rows = 32 // jnp.dtype(dtype).itemsize
    (buf,) = tiled.buffers
    assert buf.dtype == dtype and buf.shape[1:] == (rows, 128)
    assert tiled.spans == [[(0, buf.shape[0])]]
    n = int(np.prod(shape))
    assert buf.shape[0] == -(-n // (rows * 128))    # padded to whole tiles
    if name in WHOLE:
        # tile t of a [R, C] matrix holds rows [t // (C/128) * rows, +rows)
        # of lanes [t % (C/128) * 128, +128): the order the TPU keeps
        x2 = np.asarray(x, np.float32).reshape(-1, shape[-1])
        per_row = shape[-1] // 128
        for t in (0, 1, buf.shape[0] - 1):
            r0, c0 = t // per_row * rows, t % per_row * 128
            np.testing.assert_array_equal(
                np.asarray(buf[t], np.float32),
                x2[r0:r0 + rows, c0:c0 + 128])
    else:
        flat = np.asarray(buf, np.float32).reshape(-1)
        np.testing.assert_array_equal(
            flat[:n], np.asarray(x, np.float32).reshape(-1))
        assert not flat[n:].any()
    assert_roundtrip(tiled.untile(own_tiles(tiled)), {"x": x})


def test_tile_tree_buckets_by_dtype():
    rng = np.random.default_rng(5)
    tree = {k: draw(rng, shape, dtype)
            for k, (shape, dtype) in TREES["tiles_and_ragged"].items()}
    tiled = fusion.tile_tree(tree)
    # bf16, f32, int32: one buffer each, 16 / 8 / 8 rows a tile
    assert [(b.dtype, b.shape[1:]) for b in tiled.buffers] == [
        (jnp.bfloat16, (16, 128)), (jnp.float32, (8, 128)),
        (jnp.int32, (8, 128))]
    by_dtype = {}
    for shape, dtype in TREES["tiles_and_ragged"].values():
        by_dtype[jnp.dtype(dtype)] = by_dtype.get(jnp.dtype(dtype), 0) + 1
    for buf, spans in zip(tiled.buffers, tiled.spans):
        # a dtype's leaves lie end to end and fill its buffer
        assert len(spans) == by_dtype[buf.dtype]
        assert [a for a, _ in spans] == [0] + [b for _, b in spans[:-1]]
        assert spans[-1][1] == buf.shape[0]
    assert_roundtrip(tiled.untile(own_tiles(tiled)), tree)


# ---------------------------------------------------------------------------
# What the TPU's compiler makes of it: the communicator over the composed
# LM's six parameter leaves (pythia-410m: 405,012,480 f32), compiled from
# shapes alone for a described v5e:2x2
# ---------------------------------------------------------------------------

LM_LEAVES = ((24, 1024, 3072), (24, 1024, 1024), (24, 1024, 4096),
             (24, 4096, 1024), (50304, 1024), (1024, 50304))


@pytest.fixture(scope="module")
def v5e_mesh():
    """Four described (not attached) v5e chips.  Only this file's worker
    loads the TPU compiler, and only once a test here asks for it."""
    from jax.experimental import topologies
    try:
        td = topologies.get_topology_desc("v5e:2x2", platform="tpu")
    except Exception as e:          # no libtpu in this environment
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    return Mesh(np.array(td.devices), ("rank",))


@pytest.mark.parametrize("bucketing", ["tile_order", "flat_control"])
def test_v5e_program_moves_no_leaf_but_onto_the_wire(v5e_mesh, bucketing):
    """In tile order a leaf enters the buffer as a bitcast and the combine
    reads the received buffers in place: beside the schedule's permutes
    the program holds no relayout of a leaf and no second array of the
    buffer's size.  The same tree through the 1-D ``fused_leaf_op`` is
    the control that fails both."""
    sched = sch.compile_topology(tu.ExponentialTwoGraph(4), True)
    if bucketing == "tile_order":
        comm = bfopt.neighbor_communicator(sched)
    else:
        flat = fusion.fused_leaf_op(
            lambda x: ops.neighbor_allreduce(x, sched, axis="rank"))
        comm = lambda params, step: flat(params)
    sh = NamedSharding(v5e_mesh, P("rank"))
    fn = jax.jit(jax.shard_map(
        lambda t: [x[None] for x in comm([x[0] for x in t],
                                         jnp.zeros((), jnp.int32))],
        mesh=v5e_mesh, in_specs=P("rank"), out_specs=P("rank")))
    txt = fn.lower([jax.ShapeDtypeStruct((4,) + s, jnp.float32, sharding=sh)
                    for s in LM_LEAVES]).compile().as_text()
    whole = 4 * sum(int(np.prod(s)) for s in LM_LEAVES)
    counts, wire_bytes = hlo_bytes.wire_stats(txt)
    assert counts == {"collective-permute": sched.num_rounds}
    assert wire_bytes == {"collective-permute": sched.num_rounds * whole}
    made = hlo_bytes.materialized(txt, 1 << 20)
    relayouts = [m for m in made if m[1] in ("copy", "reshape", "transpose")]
    accumulators = [m for m in made if m[2] >= whole
                    and not m[1].startswith("collective-permute")]
    if bucketing == "tile_order":
        assert not relayouts and not accumulators, (relayouts, accumulators)
    else:
        assert len(relayouts) >= 2 * len(LM_LEAVES) and accumulators


@pytest.mark.parametrize("packed_span", ["behind_barrier", "forwarded_control"])
def test_v5e_donated_step_copies_no_mixed_leaf(v5e_mesh, monkeypatch,
                                               packed_span):
    """The delayed-combine step donates its state: the new parameters go
    over the old and the mixed leaves over last step's.  A combine that
    read the old parameters while Adam reads last step's mixed leaves would
    make each want the other's input buffer, and the compiler would break
    the cycle with a copy of every mixed leaf; so the combine reads the
    packed buffer's span, behind a barrier.  The control takes the barrier
    away: XLA forwards the span to the leaf and the copies are back."""
    if packed_span == "forwarded_control":
        monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    sched = sch.compile_topology(tu.ExponentialTwoGraph(4), True)
    strategy = bfopt.adapt_with_combine(
        optax.adam(1e-3), bfopt.neighbor_communicator(sched), delayed=True)
    shapes = {"a": (8, 512, 1024), "b": (2048, 1024)}

    def grad_fn(params, batch):
        scale = batch.mean()
        loss = sum(jnp.vdot(p, p) for p in jax.tree.leaves(params)) * scale
        return loss, jax.tree.map(lambda p: 2 * scale * p, params)

    step = bfopt.make_train_step(grad_fn, strategy, donate=True,
                                 overlap=True, mesh=v5e_mesh)
    sh = NamedSharding(v5e_mesh, P("rank"))
    per_rank = lambda x: jax.ShapeDtypeStruct((4,) + x.shape, x.dtype,
                                              sharding=sh)
    row = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    txt = step.lower(
        jax.tree.map(per_rank, row),
        jax.tree.map(per_rank, jax.eval_shape(strategy.init, row)),
        jax.ShapeDtypeStruct((4, 16), jnp.float32, sharding=sh),
    ).compile().as_text()
    assert hlo_bytes.wire_stats(txt)[0] == {
        "collective-permute": sched.num_rounds}
    copies = sorted(m[2] for m in hlo_bytes.materialized(txt, 1 << 20)
                    if m[1] == "copy")
    leaf_bytes = sorted(4 * int(np.prod(s)) for s in shapes.values())
    assert copies == ([] if packed_span == "behind_barrier" else leaf_bytes)
