"""AOT-compile against an abstract TPU topology: the round-2 overlap proofs.

No TPU hardware is needed: ``jax.experimental.topologies.get_topology_desc``
builds an 8-device v5e mesh description and XLA's real TPU pipeline compiles
against it, so these tests assert properties of the *actual TPU schedule* —
async collective-permute pairs spanning compute (the overlap the reference
gets from its background thread + nonblocking MPI, ``operations.cc:453-520``),
fusion collapsing per-leaf permute chains, and the Pallas flash kernels
lowering through Mosaic.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import schedule as sch
from bluefog_tpu import topology as tu
from bluefog_tpu.ops import ring_attention
from bluefog_tpu.ops import ulysses as ops_ulysses

# compile-heavy: AOT-compiles real v5e TPU schedules (10-15 s each when
# the topology backend is available) — the full-tier overlap proofs
pytestmark = pytest.mark.slow

N = 8

# History: these flash-kernel lowerings used to xfail because the backward
# kernel's bool [QB, 1] -> [QB, Tk] lane-broadcast (the isneginf(lse) guard)
# lowered to a 'tpu.dynamic_gather' on vector<8x128xi1> that Mosaic cannot
# legalize.  ops/pallas_attention.py keeps such tests on [QB, 1] f32 columns
# (a -inf lse becomes a shift of +1e30 that the exp turns into exact zeros),
# so every Pallas kernel in the repo compiles clean for v5e — a regression
# here should go red, no xfail guard.


@pytest.fixture(scope="module")
def tpu_mesh():
    from jax.experimental import topologies
    try:
        td = topologies.get_topology_desc("v5e:2x4", platform="tpu")
    except Exception as e:          # no libtpu in this environment
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    return Mesh(np.array(td.devices), ("rank",))


@pytest.fixture(scope="module")
def tpu_mesh_2d():
    from jax.experimental import topologies
    try:
        td = topologies.get_topology_desc("v5e:2x4", platform="tpu")
    except Exception as e:          # no libtpu in this environment
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    return Mesh(np.array(td.devices).reshape(2, 4), ("machine", "local"))


@pytest.fixture(scope="module")
def tpu_mesh_2x2():
    """The host builders have: four v5e chips, 16 MiB of scoped VMEM each."""
    from jax.experimental import topologies
    try:
        td = topologies.get_topology_desc("v5e:2x2", platform="tpu")
    except Exception as e:          # no libtpu in this environment
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    return Mesh(np.array(td.devices), ("rank",))


def _sharded_sds(tree, mesh):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P("rank"))), tree)


def _compile_cta(mesh, fuse, steps=2, dim=128):
    """Fused CTA train step (2-layer MLP, scan over steps) -> optimized HLO."""
    sched = sch.compile_topology(tu.ExponentialTwoGraph(N))
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.01),
        bfopt.neighbor_communicator(sched, fuse=fuse))

    def grad_fn(params, batch):
        x, y = batch
        def loss(p):
            h = jnp.tanh(x @ p["w1"])
            return jnp.mean((h @ p["w2"] - y).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss)(params)

    def per_rank(params, state, batch):
        params, state, batch = jax.tree.map(
            lambda t: t[0], (params, state, batch))
        def body(carry, b):
            p, s = carry
            loss, grads = grad_fn(p, b)
            p, s = strat.update(grads, s, p)
            return (p, s), loss
        (params, state), losses = jax.lax.scan(
            body, (params, state), batch, length=steps)
        return jax.tree.map(lambda t: t[None], (params, state, losses))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=mesh, in_specs=(P("rank"),) * 3,
        out_specs=(P("rank"),) * 3), donate_argnums=(0, 1))

    params = {"w1": jnp.zeros((N, dim, dim), jnp.bfloat16),
              "w2": jnp.zeros((N, dim, dim), jnp.bfloat16)}
    state0 = strat.init(jax.tree.map(lambda x: x[0], params))
    state = jax.tree.map(lambda x: jnp.broadcast_to(x, (N,) + x.shape), state0)
    batch = tuple(jnp.zeros((N, steps, 16, dim), jnp.bfloat16)
                  for _ in range(2))
    sds = _sharded_sds((params, state, batch), mesh)
    return fn.lower(*sds).compile().as_text()


def _op_lines(txt, opname):
    """Line numbers defining an op (`%x = ... opname(...)`), not uses of it."""
    pat = re.compile(r"= [^=]*\b" + opname + r"\(")
    return [i for i, l in enumerate(txt.splitlines()) if pat.search(l)]


def test_cta_gossip_is_async_and_overlapped(tpu_mesh):
    """The TPU schedule issues all gossip rounds as async start/done pairs
    and places real compute between them (overlap, SURVEY.md §7 hard-part 5)."""
    txt = _compile_cta(tpu_mesh, fuse=True)
    starts = _op_lines(txt, "collective-permute-start")
    dones = _op_lines(txt, "collective-permute-done")
    # Exp2(8) = 3 edge-colored rounds; fusion => one permute chain total,
    # and the rounds are disjoint permutations so XLA runs all 3 concurrently
    assert len(starts) == 3, txt.count("collective-permute")
    assert len(dones) == 3
    # overlap: compute (fused loops/matmuls) scheduled inside the
    # start..done window — communication is hidden behind it
    lines = txt.splitlines()
    window = lines[max(starts) + 1:min(dones)]
    compute = [l for l in window
               if re.search(r"= \S+ (fusion|dot|convolution)\(", l)]
    assert compute, "no compute scheduled between permute start and done"
    # the gossip buffer is the fused bf16 flat buffer, not per-leaf
    assert re.search(r"collective-permute-start[^\n]*bf16", "\n".join(
        lines[starts[0]:starts[0] + 1]))


def test_fusion_collapses_permute_chains(tpu_mesh):
    """fuse=True gossips one flat buffer per dtype: permute count equals the
    schedule's round count instead of rounds x leaves."""
    fused = _compile_cta(tpu_mesh, fuse=True)
    unfused = _compile_cta(tpu_mesh, fuse=False)
    n_fused = len(_op_lines(fused, "collective-permute-start"))
    n_unfused = len(_op_lines(unfused, "collective-permute-start"))
    assert n_fused == 3                      # rounds(Exp2(8)) == 3
    assert n_unfused == 6                    # rounds x 2 leaves
    assert fused.count("all-reduce") == 0    # gossip never falls back


def test_pallas_flash_kernels_lower_for_tpu(tpu_mesh):
    """ring_attention(use_pallas) fwd+bwd compiles through Mosaic for v5e —
    the kernels are real TPU programs, not only interpret-mode constructs."""
    B, T, H, D = 1, N * 512, 4, 64

    def loss(q, k, v):
        out = ring_attention(q, k, v, axis="rank", causal=True,
                             use_pallas=True, pallas_interpret=False)
        return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2), "rank")

    g = jax.value_and_grad(loss, argnums=(0, 1, 2))
    fn = jax.jit(jax.shard_map(
        g, mesh=tpu_mesh, in_specs=(P(None, "rank"),) * 3,
        out_specs=(P(), (P(None, "rank"),) * 3),
        check_vma=False))
    sds = tuple(jax.ShapeDtypeStruct(
        (B, T, H, D), jnp.bfloat16,
        sharding=NamedSharding(tpu_mesh, P(None, "rank"))) for _ in range(3))
    txt = fn.lower(*sds).compile().as_text()
    # one Mosaic custom call for the forward partial kernel, one for backward
    assert txt.count("tpu_custom_call") == 2
    # the ring rotation is ppermute (async on TPU), present in both passes
    assert len(_op_lines(txt, "collective-permute-start")) >= 2


def test_dynamic_one_peer_is_one_permute_per_step(tpu_mesh):
    """Dynamic one-peer gossip compiles to exactly ONE async permute per
    scanned step — communication constant in n (the table in
    docs/PERFORMANCE.md), with the per-step branch select never falling back
    to a gather/allreduce."""
    scheds = sch.compile_dynamic_schedules(
        lambda r: tu.GetDynamicOnePeerSendRecvRanks(
            tu.ExponentialTwoGraph(N), r), N)
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.01), bfopt.neighbor_communicator(schedules=scheds))
    steps = len(scheds)

    def per_rank(params, state, batch):
        params, state, batch = jax.tree.map(
            lambda t: t[0], (params, state, batch))
        def body(carry, b):
            p, s = carry
            loss, grads = jax.value_and_grad(
                lambda q: jnp.mean((b @ q["w"]).astype(jnp.float32) ** 2))(p)
            p, s = strat.update(grads, s, p)
            return (p, s), loss
        (params, state), losses = jax.lax.scan(
            body, (params, state), batch, length=steps)
        return jax.tree.map(lambda t: t[None], (params, state, losses))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),) * 3,
        out_specs=(P("rank"),) * 3))
    params = {"w": jnp.zeros((N, 128, 128), jnp.bfloat16)}
    state0 = strat.init(jax.tree.map(lambda x: x[0], params))
    state = jax.tree.map(lambda x: jnp.broadcast_to(x, (N,) + x.shape), state0)
    batch = jnp.zeros((N, steps, 16, 128), jnp.bfloat16)
    sds = _sharded_sds((params, state, batch), tpu_mesh)
    txt = fn.lower(*sds).compile().as_text()

    starts = _op_lines(txt, "collective-permute-start")
    # every dynamic step is a single permutation of the rank axis: the
    # scan body holds one async permute per branch (or one shared permute
    # with branch-selected source-target pairs), never more than one per
    # step of the period — and no branch degrades to all-gather/all-reduce
    assert 1 <= len(starts) <= steps, txt.count("collective-permute")
    # substring check catches the async -start forms too
    assert txt.count("all-gather") == 0
    assert txt.count("all-reduce") == 0


def test_hierarchical_lowering_splits_axes(tpu_mesh_2d):
    """hierarchical_neighbor_allreduce on the 2-D (machine x local) mesh:
    the intra-machine average lowers to an all-reduce whose replica groups
    stay within each machine's local axis, and the machine-level gossip is
    async permutes — psum rides ICI, gossip rides the cross-machine axis
    (reference: mpi_controller.cc:452-507 three-phase hierarchy)."""
    from bluefog_tpu.ops import collectives as C

    msched = sch.compile_topology(tu.RingGraph(2))

    def per_rank(x):
        x = x[0, 0]
        out = C.hierarchical_neighbor_allreduce(x, msched)
        return out[None, None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh_2d,
        in_specs=(P("machine", "local"),), out_specs=P("machine", "local")))
    x = jax.ShapeDtypeStruct(
        (2, 4, 256, 256), jnp.bfloat16,
        sharding=NamedSharding(tpu_mesh_2d, P("machine", "local")))
    txt = fn.lower(x).compile().as_text()

    ars = [l for l in txt.splitlines()
           if re.search(r"= \S+ all-reduce(-start)?\(", l)]
    assert ars, "intra-machine pmean must lower to an all-reduce"
    # replica groups of the local pmean partition within machines:
    # {0,1,2,3} and {4,5,6,7}, never mixing the two machines
    groups = re.findall(r"replica_groups=\{(.*?)\}", " ".join(ars))
    assert groups
    for g in groups:
        for grp in re.findall(r"\{([\d,]+)\}", "{" + g + "}"):
            members = sorted(int(v) for v in grp.split(","))
            assert members in ([0, 1, 2, 3], [4, 5, 6, 7]), ars
    assert _op_lines(txt, "collective-permute-start"), \
        "machine-level gossip must stay an async permute"


def test_broadcast_is_log_tree_no_reduction(tpu_mesh):
    """broadcast lowers to ceil(log2 n) async permutes and ZERO all-reduces
    on the TPU pipeline (the binomial tree, not the masked-psum formulation)."""
    from bluefog_tpu.ops import collectives as C

    def per_rank(x):
        return C.broadcast(x[0], 3)[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),),
        out_specs=P("rank")))
    x = jax.ShapeDtypeStruct(
        (N, 1024, 1024), jnp.bfloat16,
        sharding=NamedSharding(tpu_mesh, P("rank")))
    txt = fn.lower(x).compile().as_text()
    assert len(_op_lines(txt, "collective-permute-start")) == 3  # log2(8)
    assert txt.count("all-reduce") == 0    # incl. async -start form


def test_int8_wire_shrinks_permute_payload(tpu_mesh):
    """wire="int8" really compresses the TPU wire: the gossip permutes carry
    s8 buffers (plus a 4-byte f32 scale), not bf16/f32 — 2-4x fewer bytes
    per edge in the compiled schedule."""
    sched = sch.compile_topology(tu.ExponentialTwoGraph(N))

    def per_rank(x):
        from bluefog_tpu.ops import collectives as C
        return C.neighbor_allreduce(x[0], sched, wire="int8")[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),),
        out_specs=P("rank")))
    x = jax.ShapeDtypeStruct(
        (N, 1024, 1024), jnp.bfloat16,
        sharding=NamedSharding(tpu_mesh, P("rank")))
    txt = fn.lower(x).compile().as_text()
    starts = _op_lines(txt, "collective-permute-start")
    lines = txt.splitlines()
    payload = [l for l in starts if re.search(r"s8\[", lines[l])]
    # 3 Exp2 rounds x (payload + scale); at least the 3 payload permutes
    # must be s8, and no full-precision f32 payload permute remains
    assert len(payload) == 3, [lines[l] for l in starts]
    assert not any(re.search(r"f32\[\d{4,}", lines[l]) for l in starts)


def test_fp8_wire_shrinks_permute_payload(tpu_mesh):
    """wire="fp8" carries f8e4m3 buffers on the compiled v5e wire — the
    int8 byte footprint with floating relative precision; the barriers
    keep XLA from fusing the casts back into a full-width permute."""
    sched = sch.compile_topology(tu.ExponentialTwoGraph(N))

    def per_rank(x):
        from bluefog_tpu.ops import collectives as C
        return C.neighbor_allreduce(x[0], sched, wire="fp8")[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),),
        out_specs=P("rank")))
    x = jax.ShapeDtypeStruct(
        (N, 1024, 1024), jnp.float32,
        sharding=NamedSharding(tpu_mesh, P("rank")))
    txt = fn.lower(x).compile().as_text()
    starts = _op_lines(txt, "collective-permute-start")
    lines = txt.splitlines()
    payload = [l for l in starts if re.search(r"f8e4m3", lines[l])]
    assert len(payload) == 3, [lines[l][:120] for l in starts]
    assert not any(re.search(r"f32\[\d{4,}", lines[l]) for l in starts)


def test_blocked_wire_payloads_stay_compressed(tpu_mesh):
    """The @B blocked quantizers keep the compiled v5e wire compressed:
    payload permutes are s8 / f8e4m3 in the padded [nb, B] layout with an
    f32 per-block scales vector alongside — the pad/reshape around the
    optimization barriers must not give XLA an excuse to ship full-width
    bytes."""
    sched = sch.compile_topology(tu.ExponentialTwoGraph(N))

    for wire, pat in (("int8@256", r"s8\["), ("fp8@256", r"f8e4m3")):
        def per_rank(x, wire=wire):
            from bluefog_tpu.ops import collectives as C
            return C.neighbor_allreduce(x[0], sched, wire=wire)[None]

        fn = jax.jit(jax.shard_map(
            per_rank, mesh=tpu_mesh, in_specs=(P("rank"),),
            out_specs=P("rank")))
        x = jax.ShapeDtypeStruct(
            (N, 1000, 1001), jnp.float32,       # NOT a multiple of 256
            sharding=NamedSharding(tpu_mesh, P("rank")))
        txt = fn.lower(x).compile().as_text()
        starts = _op_lines(txt, "collective-permute-start")
        lines = txt.splitlines()
        payload = [l for l in starts if re.search(pat, lines[l])]
        assert len(payload) == 3, (wire, [lines[l][:120] for l in starts])
        # the scales vector may permute in f32 (3912 blocks = 4 bytes
        # each); full-width payloads (>= 6 digits of f32) must not
        assert not any(re.search(r"f32\[\d{6,}", lines[l])
                       for l in starts), wire


def test_bf16_wire_halves_permute_payload(tpu_mesh):
    """wire="bf16" on f32 data really halves the TPU wire: the gossip
    permutes carry bf16 buffers.  Guarded by optimization barriers in
    neighbor_allreduce — without them XLA commutes the decode convert
    across the collective-permute and the wire silently reverts to f32
    (observed on the CPU backend's float normalization; the barrier makes
    the codec's placement non-negotiable on every backend)."""
    sched = sch.compile_topology(tu.ExponentialTwoGraph(N))

    def per_rank(x):
        from bluefog_tpu.ops import collectives as C
        return C.neighbor_allreduce(x[0], sched, wire="bf16")[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),),
        out_specs=P("rank")))
    x = jax.ShapeDtypeStruct(
        (N, 1024, 1024), jnp.float32,
        sharding=NamedSharding(tpu_mesh, P("rank")))
    txt = fn.lower(x).compile().as_text()
    starts = _op_lines(txt, "collective-permute-start")
    lines = txt.splitlines()
    payload = [l for l in starts if re.search(r"bf16\[", lines[l])]
    assert len(payload) == 3, [lines[l] for l in starts]    # 3 Exp2 rounds
    assert not any(re.search(r"f32\[\d{4,}", lines[l]) for l in starts)


def test_ulysses_kernels_lower_for_tpu(tpu_mesh):
    """ulysses_attention(use_pallas) fwd+bwd compiles through Mosaic for
    v5e, with the head/sequence re-shard lowering to all-to-all — the
    second SP mode is a real TPU program too."""
    # T and block_q sized to the backward kernel's VMEM budget: ulysses
    # holds the FULL sequence locally (scores [block_q, T] on stack), unlike
    # ring whose K/V chunks shrink with the mesh
    B, T, H, D = 1, N * 256, 8, 64

    def loss(q, k, v):
        out = ops_ulysses.ulysses_attention(
            q, k, v, axis="rank", causal=True, use_pallas=True,
            pallas_block_q=256, pallas_interpret=False)
        return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2), "rank")

    g = jax.value_and_grad(loss, argnums=(0, 1, 2))
    fn = jax.jit(jax.shard_map(
        g, mesh=tpu_mesh, in_specs=(P(None, "rank"),) * 3,
        out_specs=(P(), (P(None, "rank"),) * 3),
        check_vma=False))
    sds = tuple(jax.ShapeDtypeStruct(
        (B, T, H, D), jnp.bfloat16,
        sharding=NamedSharding(tpu_mesh, P(None, "rank"))) for _ in range(3))
    txt = fn.lower(*sds).compile().as_text()
    assert txt.count("tpu_custom_call") == 2      # fwd + bwd Mosaic kernels
    assert "all-to-all" in txt                    # the head/seq re-shard


def test_interleaved_pipeline_lowers_one_ring_permute(tpu_mesh):
    """The interleaved schedule's compiled v5e program carries exactly ONE
    async ring permute in the scanned tick body — per-tick comm is O(1)
    regardless of the chunk count V, and the ring includes the S-1 -> 0
    wrap that advances the chunk index."""
    from bluefog_tpu.parallel.pipeline import pipeline_interleaved_apply

    V, D = 2, 64

    def per_rank(chunks, mbs):
        chunks, mbs = jax.tree.map(lambda t: t[0], (chunks, mbs))
        out = pipeline_interleaved_apply(
            lambda p, x: jnp.tanh(x @ p), chunks, mbs, axis="rank")
        return out[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"), P(None)),
        out_specs=P("rank")))
    sds = (jax.ShapeDtypeStruct(
               (N, V, D, D), jnp.bfloat16,
               sharding=NamedSharding(tpu_mesh, P("rank"))),
           jax.ShapeDtypeStruct(
               (1, N, 4, D), jnp.bfloat16,
               sharding=NamedSharding(tpu_mesh, P(None))))
    txt = fn.lower(*sds).compile().as_text()
    starts = _op_lines(txt, "collective-permute-start") \
        + _op_lines(txt, "collective-permute")
    assert len(starts) == 1, len(starts)
    lines = txt.splitlines()
    assert re.search(r"\{7,0\}", lines[starts[0]]), lines[starts[0]]


def test_strategy_comm_patterns_on_tpu_schedule(tpu_mesh):
    """Every strategy's cross-chip traffic, pinned: the compiled v5e step
    carries exactly the collectives the design promises (counts + payload
    dtypes).  Guards the whole optimizer surface against a silent comm
    regression (e.g. a fusion change splitting the permute chain, or a
    codec upcast like the bf16-wire bug this round)."""
    sched = sch.compile_topology(tu.ExponentialTwoGraph(N), weighted=True)
    dyn = sch.compile_dynamic_schedules(
        lambda r: tu.GetDynamicOnePeerSendRecvRanks(
            tu.ExponentialTwoGraph(N), r), N)
    opt = lambda: optax.sgd(0.05, momentum=0.9)
    # strategy -> (async permute-starts in text, all-reduce count)
    cases = {
        "allreduce": (bfopt.gradient_allreduce(opt()), 0, 1),
        "cta": (bfopt.adapt_with_combine(
            opt(), bfopt.neighbor_communicator(sched)), 3, 0),
        "atc": (bfopt.adapt_then_combine(
            opt(), bfopt.neighbor_communicator(sched)), 3, 0),
        # text carries every lax.switch branch (one executes per step)
        "dynamic": (bfopt.adapt_with_combine(
            opt(), bfopt.neighbor_communicator(schedules=dyn)), 3, 0),
        "win_put": (bfopt.win_put_optimizer(opt(), sched), 3, 0),
        "push_sum": (bfopt.push_sum(opt(), sched), 6, 0),   # value + P lane
        "choco": (bfopt.choco_gossip(opt(), sched), 6, 0),  # diff + zero-self
    }
    dim = 64

    def grad_fn(params, batch):
        x, y = batch

        def loss(p):
            return jnp.mean((jnp.tanh(x @ p["w"]) - y) ** 2)

        return jax.value_and_grad(loss)(params)

    for name, (strat, n_permute, n_allreduce) in cases.items():
        def per_rank(params, state, batch, strat=strat):
            params, state, batch = jax.tree.map(
                lambda t: t[0], (params, state, batch))
            _, grads = grad_fn(params, batch)
            params, state = strat.update(grads, state, params)
            return jax.tree.map(lambda t: t[None], (params, state))

        fn = jax.jit(jax.shard_map(
            per_rank, mesh=tpu_mesh, in_specs=(P("rank"),) * 3,
            out_specs=(P("rank"),) * 2))
        params = {"w": jnp.zeros((N, dim, dim), jnp.float32)}
        state0 = strat.init(jax.tree.map(lambda x: x[0], params))
        state = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (N,) + x.shape), state0)
        batch = tuple(jnp.zeros((N, 8, dim), jnp.float32) for _ in range(2))
        sds = _sharded_sds((params, state, batch), tpu_mesh)
        txt = fn.lower(*sds).compile().as_text()
        starts = (_op_lines(txt, "collective-permute-start")
                  + _op_lines(txt, "collective-permute"))
        ars = (_op_lines(txt, "all-reduce-start")
               + _op_lines(txt, "all-reduce"))
        assert len(starts) == n_permute, (name, len(starts), n_permute)
        assert len(ars) == n_allreduce, (name, len(ars), n_allreduce)
        if name == "choco":       # int8 wire: s8 payloads, none full-width
            lines = txt.splitlines()
            assert sum(bool(re.search(r"s8\[", lines[i]))
                       for i in starts) >= 3, name
            assert not any(re.search(r"f32\[\d{4,}", lines[i])
                           for i in starts), name


def test_flagship_resnet_gossip_step_tpu_schedule(tpu_mesh):
    """The headline path (ResNet + neighbor-allreduce CTA, the wiring of
    perfbench's ``resnet50.train-b256`` cell) compiles for v5e with bf16
    convolutions feeding the MXU and the gossip as async fused permutes —
    the TPU schedule of the graded benchmark, proven without hardware."""
    from bluefog_tpu import models

    model = models.ResNet18(num_classes=10, num_filters=16)
    sched = sch.compile_topology(tu.ExponentialTwoGraph(N), weighted=True)
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.1, momentum=0.9), bfopt.neighbor_communicator(sched))

    x0 = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x0, train=False)
    tstate = {"params": variables["params"], "bs": variables["batch_stats"]}

    def grad_fn(ts, batch):
        images, labels = batch

        def loss(p):
            logits, upd = model.apply(
                {"params": p, "batch_stats": ts["bs"]}, images,
                train=True, mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean(), upd["batch_stats"]

        (l, _), g = jax.value_and_grad(loss, has_aux=True)(ts["params"])
        return l, {"params": g, "bs": jax.tree.map(jnp.zeros_like, ts["bs"])}

    def per_rank(params, state, batch):
        params, state, batch = jax.tree.map(
            lambda t: t[0], (params, state, batch))
        loss, grads = grad_fn(params, batch)
        params, state = strat.update(grads, state, params)
        return jax.tree.map(lambda t: t[None], (params, state, loss))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),) * 3,
        out_specs=(P("rank"),) * 3), donate_argnums=(0, 1))

    dist = jax.tree.map(lambda x: jnp.broadcast_to(x, (N,) + x.shape), tstate)
    state0 = strat.init(tstate)
    dstate = jax.tree.map(lambda x: jnp.broadcast_to(x, (N,) + x.shape),
                          state0)
    batch = (jnp.zeros((N, 2, 32, 32, 3), jnp.float32),
             jnp.zeros((N, 2), jnp.int32))
    sds = _sharded_sds((dist, dstate, batch), tpu_mesh)
    txt = fn.lower(*sds).compile().as_text()

    # gossip: fused per-dtype buffers -> async permute rounds, no allreduce
    starts = _op_lines(txt, "collective-permute-start")
    assert len(starts) == 3, len(starts)          # Exp2(8) edge colors
    assert not _op_lines(txt, "all-reduce") and \
        not _op_lines(txt, "all-reduce-start")
    # MXU path: the conv stack runs in bf16 (model default; a few f32-edge
    # gradient convs at the f32 stem input/head boundary are expected)
    lines = txt.splitlines()
    convs = [lines[i] for i in _op_lines(txt, "convolution")]
    assert convs, "no convolution instructions in the compiled step"
    bf16_convs = sum("bf16" in c for c in convs)
    assert bf16_convs >= 0.7 * len(convs), (bf16_convs, len(convs))
    assert not any("f64" in c for c in convs)
    # overlap: real compute is scheduled inside the permute start..done span
    dones = _op_lines(txt, "collective-permute-done")
    window = lines[max(starts) + 1:min(dones)]
    assert any(re.search(r"= \S+ (fusion|convolution|dot)\(", l)
               for l in window), "gossip not overlapped with compute"


def test_zero_lowering_is_reduce_scatter_all_gather(tpu_mesh):
    """The ZeRO-1 train step compiles to reduce-scatter + all-gather with no
    gradient all-reduce: each chip's optimizer state is the 1/n shard, and
    the collectives are async on the TPU schedule."""
    strat = bfopt.zero_gradient_allreduce(optax.adam(1e-3), axis_size=N)
    dim = 128

    def grad_fn(params, batch):
        x, y = batch
        def loss(p):
            h = jnp.tanh(x @ p["w1"])
            return jnp.mean((h @ p["w2"] - y).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss)(params)

    def per_rank(params, state, batch):
        params, state, batch = jax.tree.map(
            lambda t: t[0], (params, state, batch))
        loss, grads = grad_fn(params, batch)
        params, state = strat.update(grads, state, params)
        return jax.tree.map(lambda t: t[None], (params, state, loss))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),) * 3,
        out_specs=(P("rank"),) * 3), donate_argnums=(0, 1))

    params = {"w1": jnp.zeros((N, dim, dim), jnp.bfloat16),
              "w2": jnp.zeros((N, dim, dim), jnp.bfloat16)}
    state0 = strat.init(jax.tree.map(lambda x: x[0], params))
    state = jax.tree.map(lambda x: jnp.broadcast_to(x, (N,) + x.shape), state0)
    batch = tuple(jnp.zeros((N, 16, dim), jnp.bfloat16) for _ in range(2))
    sds = _sharded_sds((params, state, batch), tpu_mesh)
    txt = fn.lower(*sds).compile().as_text()

    # ZeRO memory property: adam mu/nu enter and leave the program
    # shard-sized (dim*dim*2/N = 4096 elements per dtype bucket, bf16 to
    # match the params), never at the full 32768
    entry = txt.splitlines()[0]
    assert entry.count("bf16[1,4096]") >= 4, entry      # mu + nu in and out
    assert "bf16[1,32768]" not in entry
    # ZeRO dataflow: exactly one reduction of the fused grad buffer (XLA may
    # keep the StableHLO reduce_scatter or decompose it to all-reduce +
    # slice — both carry the fused 32768 bucket once) ...
    reductions = (_op_lines(txt, "reduce-scatter") +
                  _op_lines(txt, "reduce-scatter-start") +
                  _op_lines(txt, "all-reduce") +
                  _op_lines(txt, "all-reduce-start"))
    assert len(reductions) == 1, reductions
    # ... and one all-gather reassembling the updated params
    gathers = (_op_lines(txt, "all-gather") +
               _op_lines(txt, "all-gather-start"))
    assert len(gathers) == 1, gathers
    lines = txt.splitlines()
    assert re.search(r"bf16\[32768\]", lines[gathers[0]])


def test_zigzag_ring_lowers_with_conditional_skip(tpu_mesh):
    """The balanced (zigzag) causal ring compiles for v5e: the three chunk-
    pair partial sites lower through Mosaic, and the i>=s / s>=i visibility
    predicates become real HLO conditionals — devices skip fully-masked
    pairs at runtime instead of computing masked scores."""
    B, T, H, D = 1, N * 256, 4, 64      # per-device block 256 = 2 chunks

    def f(q, k, v):
        return ring_attention(q, k, v, axis="rank", causal=True,
                              layout="zigzag", use_pallas=True,
                              pallas_block_q=128, pallas_interpret=False)

    fn = jax.jit(jax.shard_map(
        f, mesh=tpu_mesh, in_specs=(P(None, "rank"),) * 3,
        out_specs=P(None, "rank"), check_vma=False))
    sds = tuple(jax.ShapeDtypeStruct(
        (B, T, H, D), jnp.bfloat16,
        sharding=NamedSharding(tpu_mesh, P(None, "rank"))) for _ in range(3))
    txt = fn.lower(*sds).compile().as_text()
    assert txt.count("tpu_custom_call") == 3     # lo x lo, hi x lo, hi x hi
    assert "conditional" in txt                  # the visibility skips


def test_zigzag_backward_lowers_through_mosaic(tpu_mesh):
    """grad(zigzag+pallas) compiles for v5e through the dedicated kernel
    backward: 3 forward + 3 backward Mosaic call sites, no dense [C, Tk]
    score matmul in HBM in either direction."""
    B, T, H, D = 1, N * 256, 4, 64

    def loss(q, k, v):
        out = ring_attention(q, k, v, axis="rank", causal=True,
                             layout="zigzag", use_pallas=True,
                             pallas_block_q=128, pallas_interpret=False)
        return jax.lax.psum(jnp.sum(out.astype(jnp.float32) ** 2), "rank")

    g = jax.value_and_grad(loss, argnums=(0, 1, 2))
    fn = jax.jit(jax.shard_map(
        g, mesh=tpu_mesh, in_specs=(P(None, "rank"),) * 3,
        out_specs=(P(), (P(None, "rank"),) * 3),
        check_vma=False))
    sds = tuple(jax.ShapeDtypeStruct(
        (B, T, H, D), jnp.bfloat16,
        sharding=NamedSharding(tpu_mesh, P(None, "rank"))) for _ in range(3))
    txt = fn.lower(*sds).compile().as_text()
    assert txt.count("tpu_custom_call") == 6


def test_choco_step_carries_int8_diffs_on_wire(tpu_mesh):
    """The CHOCO train step's permutes carry s8 payloads (the compressed
    DIFFERENCES) — no full-precision f32/bf16 parameter buffer crosses the
    wire, and the error-feedback state stays device-local."""
    sched = sch.compile_topology(tu.ExponentialTwoGraph(N), weighted=True)
    strat = bfopt.choco_gossip(optax.sgd(0.01), sched, wire="int8")
    dim = 128

    def per_rank(params, state, batch):
        params, state, batch = jax.tree.map(
            lambda t: t[0], (params, state, batch))
        loss, grads = jax.value_and_grad(
            lambda p: jnp.mean((batch @ p["w"]).astype(jnp.float32) ** 2))(
                params)
        params, state = strat.update(grads, state, params)
        return jax.tree.map(lambda t: t[None], (params, state, loss))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),) * 3,
        out_specs=(P("rank"),) * 3), donate_argnums=(0, 1))
    params = {"w": jnp.zeros((N, dim, dim), jnp.float32)}
    state0 = strat.init(jax.tree.map(lambda x: x[0], params))
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (N,) + x.shape), state0)
    batch = jnp.zeros((N, 16, dim), jnp.float32)
    sds = _sharded_sds((params, state, batch), tpu_mesh)
    txt = fn.lower(*sds).compile().as_text()

    starts = _op_lines(txt, "collective-permute-start")
    lines = txt.splitlines()
    payloads = [l for l in starts if re.search(r"s8\[", lines[l])]
    # 3 Exp2 rounds of (s8 payload + f32 scalar scale); every large buffer
    # on the wire is s8 — f32 permutes may only carry the scalar scale
    assert len(payloads) == 3, [lines[l][:100] for l in starts]
    assert not any(re.search(r"f32\[\d{3,}", lines[l]) for l in starts)


def test_win_put_wire_compresses_tpu_payload(tpu_mesh):
    """The window delivery path shares the codec-pinned permute helper:
    win_put(wire="bf16") on f32 windows carries bf16 permute payloads in
    the compiled v5e schedule — never full-width f32 (round-4 feature;
    the shared _wire_ppermute keeps the barrier subtlety in one place)."""
    from bluefog_tpu.ops import windows as wops

    sched = sch.compile_topology(tu.ExponentialTwoGraph(N))

    def per_rank(x):
        w = wops.win_create(x[0], sched)
        w = wops.win_put(w, x[0], sched, axis="rank", wire="bf16")
        return w.recv[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),),
        out_specs=P("rank")))
    x = jax.ShapeDtypeStruct(
        (N, 1024, 1024), jnp.float32,
        sharding=NamedSharding(tpu_mesh, P("rank")))
    txt = fn.lower(x).compile().as_text()
    starts = _op_lines(txt, "collective-permute-start")
    lines = txt.splitlines()
    payload = [l for l in starts if re.search(r"bf16\[", lines[l])]
    assert len(payload) == 3, [lines[l] for l in starts]    # 3 Exp2 rounds
    assert not any(re.search(r"f32\[\d{4,}", lines[l]) for l in starts)


@pytest.mark.parametrize("tile,dtype", [
    (128, jnp.float32), (128, jnp.bfloat16), (8, jnp.bfloat16)])
def test_grouped_moe_kernel_lowers_at_production_width(tpu_mesh_2x2, tile,
                                                       dtype):
    """The dropless grouped-GEMM Pallas kernel (ops/pallas_moe.py) fwd+bwd
    compiles through Mosaic for v5e at the width the graders use on the
    chip, D 1024 x F 4096 (a whole expert matrix per grid step needed
    66 MB of the 16 MiB scoped VMEM; F is blocked on a second grid axis
    now), at the training tile and the decode tile.  The
    scalar-prefetched ``tile_eid`` drives the per-tile expert weight
    BlockSpec index maps, so expert weights stream from HBM block by block
    instead of a gathered ``w[tile_eid]`` copy materializing in full.
    Compiled replicated over the AOT mesh — no collectives, same local
    program one chip runs."""
    from bluefog_tpu.ops.pallas_moe import grouped_ffn_pallas

    n = tpu_mesh_2x2.size
    E_, G, D, F = 8, 16, 1024, 4096

    def loss(xt, w1, w2, eid):
        out = grouped_ffn_pallas(xt, eid, w1, w2, interpret=False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def per_rank(xt, w1, w2, eid):
        xt, w1, w2, eid = jax.tree.map(lambda t: t[0], (xt, w1, w2, eid))
        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(xt, w1, w2, eid)
        return jax.tree.map(lambda t: t[None], (l, g))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh_2x2, in_specs=(P("rank"),) * 4,
        out_specs=P("rank"), check_vma=False))
    sh = NamedSharding(tpu_mesh_2x2, P("rank"))
    sds = (jax.ShapeDtypeStruct((n, G, tile, D), dtype, sharding=sh),
           jax.ShapeDtypeStruct((n, E_, D, F), dtype, sharding=sh),
           jax.ShapeDtypeStruct((n, E_, F, D), dtype, sharding=sh),
           jax.ShapeDtypeStruct((n, G), jnp.int32, sharding=sh))
    txt = fn.lower(*sds).compile().as_text()
    # the forward grouped GEMM is a Mosaic program (backward is XLA
    # scatter-adds by design — see pallas_moe._grouped_bwd)
    assert txt.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("local_len,head_dim,heads,dtype", [
    (local_len, head_dim, 2, jnp.bfloat16)
    for head_dim in (64, 128) for local_len in (2048, 4096, 8192, 16384)
] + [(2048, 64, 16, jnp.float32)],      # the LM training cells' own shape
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_flash_attention_fits_vmem_or_says_so(tpu_mesh_2x2, local_len,
                                              head_dim, heads, dtype):
    """Local flash attention fwd+bwd at the local lengths long-context
    training uses: K and V (dK, dV) rows stay whole in the 16 MiB of
    scoped VMEM and both kernels walk the key axis inside, so the score
    tiles are ``[block_q, block_k]`` whatever the length and the q block
    stays at the caller's 512 rows — and where the whole rows do not fit
    ``_q_blocking`` raises ``ValueError`` at trace time.  Mosaic's
    RESOURCE_EXHAUSTED must never reach the caller."""
    n = tpu_mesh_2x2.size
    B, H = 1, heads

    def loss(q, k, v):
        out = ops_ulysses.local_flash_attention(
            q, k, v, True, head_dim ** -0.5, 512, False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def per_rank(q, k, v):
        q, k, v = q[0], k[0], v[0]
        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return jax.tree.map(lambda t: t[None], (l, g))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh_2x2, in_specs=(P("rank"),) * 3,
        out_specs=P("rank"), check_vma=False))
    sds = tuple(jax.ShapeDtypeStruct(
        (n, B, local_len, H, head_dim), dtype,
        sharding=NamedSharding(tpu_mesh_2x2, P("rank"))) for _ in range(3))
    try:
        txt = fn.lower(*sds).compile().as_text()
    except ValueError as e:
        # the backward keeps K, V, dK and dV rows whole: 8192 is its end
        # (the forward's K and V alone reach 16384), float32 and bfloat16
        # inputs alike
        assert "scoped VMEM limit" in str(e) and local_len > 8192, e
        return
    assert txt.count("tpu_custom_call") == 2       # forward + backward
    from bluefog_tpu.ops import pallas_attention as pa
    kb = pa._k_blocking(local_len)
    assert kb == 512
    for backward in (False, True):
        assert pa._q_blocking(local_len, local_len, head_dim, 512, backward,
                              block_k=kb)[0] == 512


def test_flash_backward_with_offsets_window_and_groups_lowers(tpu_mesh_2x2):
    """The ring's use of the backward kernel: traced q / k offsets, a
    sliding window (so all three key-block loops are traced: the window's
    edge, the blocks under the mask, the diagonal) and grouped K/V heads,
    through Mosaic for v5e."""
    from bluefog_tpu.ops import pallas_attention as pa

    n = tpu_mesh_2x2.size
    B, T, H, Hkv, D = 1, 2048, 4, 2, 128

    def per_rank(q, k, v, do, lse, delta, offs):
        q, k, v, do, lse, delta, offs = jax.tree.map(
            lambda t: t[0], (q, k, v, do, lse, delta, offs))
        grads = pa.attention_block_backward(
            q, k, v, do, lse, delta, offs[0], offs[1], causal=True,
            scale=D ** -0.5, interpret=False, window=1024)
        return jax.tree.map(lambda t: t[None], grads)

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh_2x2, in_specs=(P("rank"),) * 7,
        out_specs=P("rank"), check_vma=False))
    sh = NamedSharding(tpu_mesh_2x2, P("rank"))
    sds = [jax.ShapeDtypeStruct((n,) + shape, dtype, sharding=sh)
           for shape, dtype in (
               ((B, T, H, D), jnp.bfloat16), ((B, T, Hkv, D), jnp.bfloat16),
               ((B, T, Hkv, D), jnp.bfloat16), ((B, T, H, D), jnp.float32),
               ((B, T, H), jnp.float32), ((B, T, H), jnp.float32),
               ((2,), jnp.int32))]
    txt = fn.lower(*sds).compile().as_text()
    assert txt.count("tpu_custom_call") == 1


def test_flash_decode_kernel_lowers_for_tpu(tpu_mesh):
    """The paged flash-decode kernel (ops/pallas_decode.py) compiles through
    Mosaic for v5e on its most demanding configuration: int8 KV pages with
    fused per-token dequant, GQA folding, and the scalar-prefetched
    slot/prefix page indirection driving the KV BlockSpec index maps.
    Compiled replicated over the AOT mesh — no collectives, the same local
    program the serving hot path runs on one chip."""
    from bluefog_tpu.ops import pallas_decode as pd

    S, ROWS, H, Hkv, L, Dh = 8, 16, 8, 4, 1024, 128

    def per_rank(q, kl, vl, ksc, vsc, slots, lens, pslots, plens):
        (q, kl, vl, ksc, vsc, slots, lens, pslots, plens) = jax.tree.map(
            lambda t: t[0],
            (q, kl, vl, ksc, vsc, slots, lens, pslots, plens))
        out = pd.flash_attend_rows(
            q, kl, vl, slots, lens, k_scale=ksc, v_scale=vsc,
            prefix_slots=pslots, prefix_lens=plens, block_k=128,
            interpret=False)
        return out[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"),) * 9,
        out_specs=P("rank"), check_vma=False))
    sh = NamedSharding(tpu_mesh, P("rank"))
    sds = (jax.ShapeDtypeStruct((N, S, H, Dh), jnp.bfloat16, sharding=sh),
           jax.ShapeDtypeStruct((N, ROWS, Hkv, L, Dh), jnp.int8, sharding=sh),
           jax.ShapeDtypeStruct((N, ROWS, Hkv, L, Dh), jnp.int8, sharding=sh),
           jax.ShapeDtypeStruct((N, ROWS, Hkv, L), jnp.float32, sharding=sh),
           jax.ShapeDtypeStruct((N, ROWS, Hkv, L), jnp.float32, sharding=sh),
           jax.ShapeDtypeStruct((N, S), jnp.int32, sharding=sh),
           jax.ShapeDtypeStruct((N, S), jnp.int32, sharding=sh),
           jax.ShapeDtypeStruct((N, S), jnp.int32, sharding=sh),
           jax.ShapeDtypeStruct((N, S), jnp.int32, sharding=sh))
    txt = fn.lower(*sds).compile().as_text()
    assert txt.count("tpu_custom_call") >= 1
    # paged reads: no [S, L] x heads dense gathered-KV copy materializes
    # at full width — the kernel streams (1, 1, block_k, Dh) pages
    assert f"f32[{S},{Hkv},{L},{Dh}]" not in txt.replace(" ", "")


@pytest.mark.parametrize("scan_layers,remat", [
    (False, False),       # unrolled layers
    (True, False),        # the default: scan_layers on
    (True, True),         # long context: scan + remat
])
def test_single_device_lm_pallas_lowers_for_tpu(tpu_mesh, scan_layers,
                                                remat):
    """The Pallas LM on ONE chip (RingTransformerLM with axis=None +
    use_pallas, scanned and/or rematerialized): fwd+bwd compile through
    Mosaic for v5e — proven here
    so the first real-hardware run of local_flash_attention cannot die
    on a lowering bug mid-window.  Compiled replicated over the AOT
    mesh: no collectives, same local program a single chip runs."""
    from bluefog_tpu import models

    T = 1024
    lm = models.RingTransformerLM(
        vocab_size=128, num_layers=2, num_heads=4, d_model=128,
        max_seq_len=T, axis=None, dtype=jnp.bfloat16, rope=True,
        use_pallas=True, pallas_interpret=False,
        scan_layers=scan_layers, remat=remat)
    # init executes eagerly on the host CPU: use the dense clone (the
    # attention has no params, so the tree is identical) — the pallas lm
    # itself is only traced/lowered, never run here
    params = lm.clone(use_pallas=False).init(
        jax.random.key(0), jnp.zeros((1, T), jnp.int32))

    def loss_fn(p, tokens):
        logits = lm.apply(p, tokens, positions=jnp.arange(T))
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    # per-rank shard_map (leading [N] axis, the _sharded_sds pattern) pins
    # the AOT mesh as the lowering target — a bare jit with replicated
    # shardings falls back to the CPU backend and Pallas then refuses
    # interpret=False.  No collectives: each rank runs the same local
    # program a single chip would.  check_vma off: the local kernel's
    # scalar offsets are unvarying (axis=None) while q/k/v vary.
    def per_rank(p, tokens):
        p, tokens = jax.tree.map(lambda t: t[0], (p, tokens))
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens)
        return jax.tree.map(lambda t: t[None], (loss, grads))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=tpu_mesh, in_specs=(P("rank"), P("rank")),
        out_specs=P("rank"), check_vma=False))
    params_N = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (N,) + x.shape), params)
    tokens_N = jnp.zeros((N, 1, T), jnp.int32)
    sds = _sharded_sds((params_N, tokens_N), tpu_mesh)
    txt = fn.lower(*sds).compile().as_text()
    # forward partial kernel + blockwise backward kernel reach Mosaic
    # (>=: XLA may or may not dedupe the per-layer instances)
    assert txt.count("tpu_custom_call") >= 2
    # and no [B,T,H,T] dense score tensor is ever materialized
    assert f"{T},4,{T}" not in txt.replace(" ", "")
