"""Window op tests (model: reference test/torch_win_ops_test.py)."""
import numpy as np
import jax.numpy as jnp
import pytest

import bluefog_tpu as bf
from bluefog_tpu import topology as tu

N, DIM = 8, 4


@pytest.fixture(autouse=True)
def ctx(cpu_devices):
    bf.init(devices=cpu_devices)
    bf.set_topology(tu.RingGraph(N, connect_style=0), is_weighted=True)
    yield
    bf.win_free()
    bf.shutdown()


def rank_tensor(val_fn=float):
    return jnp.asarray(
        np.broadcast_to(np.array([val_fn(r) for r in range(N)])[:, None], (N, DIM)),
        dtype=jnp.float32)


def test_win_create_update_default_weights():
    """create + put + update with topology weights == neighbor_allreduce."""
    x = rank_tensor()
    assert bf.win_create(x, "w0", zero_init=True)
    bf.win_put(x, "w0")
    out = bf.win_update("w0")
    W = tu.to_weight_matrix(tu.RingGraph(N, connect_style=0))
    expected = (W.T @ np.arange(N, dtype=np.float64))
    for r in range(N):
        np.testing.assert_allclose(
            np.asarray(out[r]), np.full(DIM, expected[r]), rtol=1e-5)


def test_named_window_wire_plumbs_through_registry():
    """The registry layer (bf.win_put/win_get wire=) really reaches the
    compressed delivery path: int8-wired puts land visibly quantized
    values, a wire=None put through the same window is exact (distinct
    jit-cache entries per wire mode), and the update result stays within
    quantization tolerance of the exact combine."""
    x = rank_tensor(lambda r: 0.1 * r + 0.01)
    # independent windows per mode: win_update folds the combine back into
    # the window value, so reusing one window would entangle the modes
    for name in ("wa", "wb", "wc"):
        assert bf.win_create(x, name, zero_init=True)

    bf.win_put(x, "wa")
    exact = np.asarray(bf.win_update("wa"))

    bf.win_put(x, "wb", wire="int8")
    quant = np.asarray(bf.win_update("wb"))
    np.testing.assert_allclose(quant, exact, rtol=0.1, atol=0.02)
    assert not np.array_equal(quant, exact)      # it really quantized

    # the jit cache did not hand the wire="int8" executable back to a
    # wire=None call (same shapes/schedule, different key)
    bf.win_put(x, "wc")
    again = np.asarray(bf.win_update("wc"))
    np.testing.assert_array_equal(again, exact)

    bf.win_get("wc", wire="bf16")
    got = np.asarray(bf.win_update("wc"))
    assert np.isfinite(got).all()


def test_win_update_given_weights():
    x = rank_tensor()
    bf.win_create(x, "w1", zero_init=True)
    bf.win_put(x, "w1")
    out = bf.win_update(
        "w1",
        self_weight=0.5,
        neighbor_weights=[{(r - 1) % N: 0.25, (r + 1) % N: 0.25} for r in range(N)],
    )
    vals = np.arange(N, dtype=np.float64)
    for r in range(N):
        expected = 0.5 * vals[r] + 0.25 * vals[(r - 1) % N] + 0.25 * vals[(r + 1) % N]
        np.testing.assert_allclose(
            np.asarray(out[r]), np.full(DIM, expected), rtol=1e-5)


def test_win_get():
    x = rank_tensor()
    bf.win_create(x, "wg", zero_init=True)
    bf.win_get("wg")
    out = bf.win_update("wg")  # same combine as after a put of win.value
    W = tu.to_weight_matrix(tu.RingGraph(N, connect_style=0))
    expected = W.T @ np.arange(N, dtype=np.float64)
    for r in range(N):
        np.testing.assert_allclose(
            np.asarray(out[r]), np.full(DIM, expected[r]), rtol=1e-5)


def test_win_accumulate_and_collect():
    """Accumulate twice then collect: mailboxes sum, then clear."""
    x = rank_tensor()
    bf.win_create(x, "wa", zero_init=True)
    bf.win_accumulate(x, "wa")
    bf.win_accumulate(x, "wa")
    out = bf.win_update_then_collect("wa")
    vals = np.arange(N, dtype=np.float64)
    for r in range(N):
        expected = vals[r] + 2 * (vals[(r - 1) % N] + vals[(r + 1) % N])
        np.testing.assert_allclose(
            np.asarray(out[r]), np.full(DIM, expected), rtol=1e-5)
    # collected -> mailboxes cleared: another collect returns just the value
    out2 = bf.win_update_then_collect("wa")
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out), rtol=1e-5)


def test_win_put_partial_destinations():
    """dst_weights restricted to a subset of out-neighbors (dynamic put).

    Uses ASYMMETRIC update weights so a put delivered into the wrong mailbox
    slot changes the result (regression: the delivery schedule used to
    recompute slots over the sub-edge set instead of the window's layout).
    """
    x = rank_tensor()
    bf.win_create(x, "wp", zero_init=True)
    # only send clockwise (drop the counter-clockwise edge), scaled by 0.5
    bf.win_put(x, "wp", dst_weights=[{(r + 1) % N: 0.5} for r in range(N)])
    out = bf.win_update(
        "wp", self_weight=0.5,
        neighbor_weights=[{(r - 1) % N: 1.0, (r + 1) % N: 0.0} for r in range(N)])
    vals = np.arange(N, dtype=np.float64)
    for r in range(N):
        # only the clockwise put (from r-1, weight 1.0, scaled 0.5) lands
        expected = 0.5 * vals[r] + 0.5 * vals[(r - 1) % N]
        np.testing.assert_allclose(
            np.asarray(out[r]), np.full(DIM, expected), rtol=1e-5,
            err_msg=f"rank {r}")


def test_win_put_non_edge_rejected():
    x = rank_tensor()
    bf.win_create(x, "we", zero_init=True)
    with pytest.raises(ValueError, match="not an edge"):
        bf.win_put(x, "we", dst_weights=[{(r + 3) % N: 1.0} for r in range(N)])


def test_associated_p_debiasing():
    """With associated-P enabled, a directed (column-substochastic) put
    channel is de-biased by value/p (reference: mpi_win_ops.cc:384-427)."""
    topo = tu.ExponentialTwoGraph(N)
    bf.set_topology(topo)
    rng = np.random.default_rng(3)
    vals = rng.normal(size=(N, DIM)).astype(np.float32)
    x = jnp.asarray(vals)
    # rank-dependent self weights: row-stochastic (mass conserving) but NOT
    # column-stochastic -> plain gossip would be biased; p corrects it
    a = np.linspace(0.2, 0.7, N)
    outs = [tu.GetOutNeighbors(topo, r) for r in range(N)]
    dsts = [{d: (1 - a[r]) / len(outs[r]) for d in outs[r]} for r in range(N)]
    ones_in = [{s: 1.0 for s in tu.GetInNeighbors(topo, r)} for r in range(N)]
    bf.turn_on_win_ops_with_associated_p()
    try:
        bf.win_create(x, "ap", zero_init=True)
        for _ in range(40):
            bf.win_accumulate(x, "ap", dst_weights=dsts)
            x = bf.synchronize(bf.win_update(
                "ap", self_weight=list(a), neighbor_weights=ones_in,
                reset=True))
        p = np.asarray(bf.win_associated_p("ap"))
        assert not np.allclose(p, 1.0)       # the channel is genuinely biased
        np.testing.assert_allclose(p.sum(), N, rtol=1e-4)  # p-mass conserved
        ratio = np.asarray(x) / p[:, None]
        np.testing.assert_allclose(
            ratio, np.tile(vals.mean(axis=0), (N, 1)), atol=1e-3)
    finally:
        bf.turn_off_win_ops_with_associated_p()


def test_win_version_tracking():
    x = rank_tensor()
    bf.win_create(x, "wv", zero_init=True)
    assert bf.get_win_version("wv").sum() == 0
    bf.win_put(x, "wv")
    v = bf.get_win_version("wv")
    assert v.shape == (N, 2)
    assert (v == 1).all()
    bf.win_put(x, "wv")
    assert (bf.get_win_version("wv") == 2).all()
    bf.win_update_then_collect("wv")
    assert bf.get_win_version("wv").sum() == 0


def test_win_mutex_noop():
    x = rank_tensor()
    bf.win_create(x, "wm")
    with bf.win_mutex("wm"):
        bf.win_put(x, "wm")


def test_push_sum_weight_conservation():
    """The associated-P push-sum invariant (reference :780-863): total mass of
    value and of the p-weight lane is conserved each accumulate+collect round,
    and value/p converges to the global average.

    One round = accumulate scale*x to out-neighbors, then
    x <- scale*x + sum(mailboxes) — expressed as a single
    win_update(self_weight=scale, neighbor_weights=1, reset=True).
    """
    topo = tu.ExponentialTwoGraph(N)
    bf.set_topology(topo)
    rng = np.random.default_rng(7)
    vals = rng.normal(size=(N, DIM)).astype(np.float32)
    global_mean = vals.mean(axis=0)

    # extended tensor: [value..., p]  (reference: optimizers.py:1056-1073)
    ext = jnp.concatenate(
        [jnp.asarray(vals), jnp.ones((N, 1), jnp.float32)], axis=1)
    bf.win_create(ext, "ps", zero_init=True)
    total0 = np.asarray(ext).sum(axis=0)

    out_deg = len(tu.GetOutNeighbors(topo, 0))
    scale = 1.0 / (out_deg + 1)
    dsts = [{d: scale for d in tu.GetOutNeighbors(topo, r)} for r in range(N)]
    ones_in = [{s: 1.0 for s in tu.GetInNeighbors(topo, r)} for r in range(N)]

    x = ext
    for _ in range(25):
        bf.win_accumulate(x, "ps", dst_weights=dsts)
        x = bf.synchronize(bf.win_update(
            "ps", self_weight=scale, neighbor_weights=ones_in, reset=True))
        total = np.asarray(x).sum(axis=0)
        np.testing.assert_allclose(total, total0, rtol=1e-4)  # mass conserved

    ratio = np.asarray(x)[:, :DIM] / np.asarray(x)[:, DIM:]
    np.testing.assert_allclose(ratio, np.tile(global_mean, (N, 1)), atol=1e-3)


def test_win_put_wire_codecs(cpu_devices):
    """win_put with wire compression: bf16 matches the uncompressed put to
    cast tolerance; int8 to quantization tolerance; int dtypes reject."""
    import jax
    import pytest
    from jax.sharding import Mesh, PartitionSpec as P

    import bluefog_tpu.topology as tu
    from bluefog_tpu import schedule as sch
    from bluefog_tpu.ops import windows as wops

    n = 8
    sched = sch.compile_topology(tu.ExponentialTwoGraph(n))
    mesh = Mesh(np.array(cpu_devices[:n]), ("rank",))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(n, 16)), jnp.float32)

    def run(wire):
        def f(xb):
            w = wops.win_create(xb[0], sched)
            w = wops.win_put(w, xb[0], sched, axis="rank", wire=wire)
            return w.recv[None]
        fn = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("rank"), out_specs=P("rank")))
        return np.asarray(fn(x))

    exact = run(None)
    np.testing.assert_allclose(run("bf16"), exact, rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(run("int8"), exact, rtol=0.1, atol=0.05)
    assert not np.array_equal(run("bf16"), exact)   # it really quantized

    with pytest.raises(ValueError, match="real float"):
        def fi(xb):
            w = wops.win_create(xb[0], sched)
            return wops.win_put(w, xb[0], sched, axis="rank",
                                wire="bf16").recv[None]
        jax.jit(jax.shard_map(
            fi, mesh=mesh, in_specs=P("rank"), out_specs=P("rank")))(
            jnp.ones((n, 4), jnp.int32))


# ---------------------------------------------------------------------------
# async-gossip satellites: pull-path allocation pin, collect-mask cache,
# named-window staleness stamps
# ---------------------------------------------------------------------------

def _zero_fills(closed_jaxpr, shape):
    """Eqns (recursively) that broadcast a literal 0 into ``shape``."""
    import jax.extend.core as jcore      # jax 0.9 home of Jaxpr/Literal
    hits = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "broadcast_in_dim":
                inv = eqn.invars[0]
                if (isinstance(inv, jcore.Literal)
                        and np.ndim(inv.val) == 0 and inv.val == 0
                        and tuple(eqn.outvars[0].aval.shape) == shape):
                    hits.append(eqn)
            for v in eqn.params.values():
                items = v if isinstance(v, (list, tuple)) else (v,)
                for u in items:
                    if isinstance(u, jcore.ClosedJaxpr):
                        walk(u.jaxpr)
                    elif isinstance(u, jcore.Jaxpr):
                        walk(u)

    walk(closed_jaxpr.jaxpr)
    return hits


def test_win_pull_skips_window_allocation(monkeypatch):
    """The pull path allocates NO window: no win_create call, no zero-fill
    of the ``[K, ...]`` recv block anywhere in the trace (win_get overwrites
    every slot the combine reads, so the old zero-init was a dead store) —
    and the result still equals the weighted neighbor combine."""
    import jax
    from jax.sharding import PartitionSpec as P
    from bluefog_tpu import schedule as sch
    from bluefog_tpu.ops import windows as wops

    sched = sch.compile_topology(
        tu.RingGraph(N, connect_style=0), weighted=True)
    slots = max(sched.max_in_degree, 1)
    x = rank_tensor()

    def f(xb):
        return wops.win_pull(xb[0], sched)[None]

    def _boom(*a, **k):
        raise AssertionError("win_pull must not allocate a window")

    monkeypatch.setattr(wops, "win_create", _boom)
    sm = jax.shard_map(f, mesh=bf.mesh(), in_specs=P("rank"),
                       out_specs=P("rank"))
    jaxpr = jax.make_jaxpr(sm)(x)
    assert not _zero_fills(jaxpr, (slots, DIM)), (
        "pull path zero-fills its recv block (dead store)")

    out = np.asarray(jax.jit(sm)(x))
    W = tu.to_weight_matrix(tu.RingGraph(N, connect_style=0))
    expected = W.T @ np.arange(N, dtype=np.float64)
    for r in range(N):
        np.testing.assert_allclose(out[r], np.full(DIM, expected[r]),
                                   rtol=1e-5)


def test_collect_masks_cached_per_schedule():
    """The collect combine's unit weight tables are cached per schedule —
    same array OBJECTS on every trace (constant identity is part of the jit
    cache key for donated-carry scans) — and write-protected."""
    from bluefog_tpu import schedule as sch
    from bluefog_tpu.ops import windows as wops

    s1 = sch.compile_topology(tu.ExponentialTwoGraph(N))
    s2 = sch.compile_topology(tu.ExponentialTwoGraph(N))
    a_self, a_slot = wops._collect_masks(s1)
    b_self, b_slot = wops._collect_masks(s1)
    assert a_self is b_self and a_slot is b_slot
    # an equal schedule compiled separately hits the same cache line iff it
    # hashes the same (CommSchedule is frozen/hashable)
    c_self, _ = wops._collect_masks(s2)
    assert (c_self is a_self) == (hash(s1) == hash(s2))
    with pytest.raises(ValueError):
        a_slot[0, 0] = 5.0
    np.testing.assert_allclose(a_self, 1.0)
    K = max(s1.max_in_degree, 1)
    expected = (np.arange(K)[:, None] < s1.in_degree[None, :])
    np.testing.assert_array_equal(a_slot.astype(bool), expected)


def test_win_stamps_and_staleness():
    """Named-window face of the async strategy's per-slot step stamps: a
    full put refreshes every real slot; a partial put ages the slots it
    skipped by exactly one delivery op."""
    x = rank_tensor()
    assert bf.win_create(x, "ws", zero_init=True)
    stamps = bf.get_win_stamps("ws")
    assert stamps.shape[0] == N
    np.testing.assert_array_equal(stamps, 0)
    np.testing.assert_array_equal(bf.win_staleness("ws"), 0)

    bf.win_put(x, "ws")                       # tick 1: every slot stamped
    np.testing.assert_array_equal(bf.win_staleness("ws"), 0)
    real = bf.get_win_stamps("ws") == 1

    # tick 2: clockwise-only put — the counter-clockwise slot ages
    bf.win_put(x, "ws", dst_weights=[{(r + 1) % N: 0.5} for r in range(N)])
    stale = bf.win_staleness("ws")
    assert stale[real].tolist().count(1) == N      # one aged slot per rank
    assert stale[real].tolist().count(0) == N      # one fresh slot per rank
    assert stale[~real].max(initial=0) == 0        # unreal slots report 0

    # the accessor hands out copies, not the live ledger
    view = bf.get_win_stamps("ws")
    view[:] = 99
    assert bf.get_win_stamps("ws").max() <= 2
