"""Dropless MoE fast path: permutation proofs, EC router, f64 oracle, bytes.

Four layers of proof for the sort-based grouped dispatch and the
expert-choice router:

* pure-helper units: tile layout math, stable grouping, the grouped-GEMM
  impl selector, Pallas-vs-XLA equality (forward AND gradients), and the
  StableHLO dot-FLOP counter that grades the paths;
* **permutation property tests** on a live expert axis: dispatch∘combine
  with an identity grouped_fn is exactly the identity map (bit-for-bit),
  outputs follow any seeded routing (closed form), token order is
  respected, and the adversarial all-tokens-to-one-expert routing that
  makes the capacity path drop loses NOTHING here;
* eager config contracts: dispatch/router/tile mistakes fail with named
  rules (expert choice requires dropless + sp=1);
* float64 trajectory oracles at ep=1 AND ep=2 for BOTH router modes:
  the dropless grouped path matches its dense-equivalent twin
  loss-for-loss to 1e-12 over 12 real-gradient steps (observed ~1e-15),
  a strictly stronger pin than the capacity path's no-drop special case
  — nothing CAN drop; plus AOT proof that dropless keeps every expert
  all_to_all ICI-classified with DCN bytes identical to capacity.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.moe import MoELMConfig, router_expert_choice
from bluefog_tpu.moe.dropless import (dropless_rows, grouped_ffn,
                                      grouped_ffn_xla, sort_by_expert,
                                      tile_layout)
from bluefog_tpu.parallel import compose
from bluefog_tpu.parallel.expert import moe_apply_dropless, moe_dispatch

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

E = 4                # total experts
N = 2                # devices on the expert axis (e_local = 2)
T, D = 16, 3


# --- pure helpers ----------------------------------------------------------

def test_dropless_rows_static_math():
    # worst case: every group wastes tile-1 rows, rounded to whole tiles
    assert dropless_rows(10, 2, 4) == 16      # 10 + 2*3 = 16
    assert dropless_rows(8, 1, 8) == 16       # 8 + 7 -> 16
    assert dropless_rows(8, 2, 1) == 8        # tile=1: no padding at all
    with pytest.raises(ValueError, match="moe_dropless_invalid_tile"):
        dropless_rows(8, 2, 0)


def test_tile_layout_concrete():
    sizes = jnp.asarray([5, 0, 3], jnp.int32)          # ragged + empty group
    pad_start, tile_eid = tile_layout(sizes, tile=4, max_rows=8)
    # groups padded to 8, 0, 4 rows -> starts 0, 8, 8
    np.testing.assert_array_equal(np.asarray(pad_start), [0, 8, 8])
    # buffer is dropless_rows(8, 3, 4) = 20 rows = 5 tiles at offsets
    # 0, 4, 8, 12, 16: group0, group0(pad), group2, tail, tail (clamped)
    np.testing.assert_array_equal(np.asarray(tile_eid), [0, 0, 2, 2, 2])


def test_sort_by_expert_is_stable_grouping():
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, E, size=(32,)), jnp.int32)
    order, sizes, rank = sort_by_expert(idx, E)
    o, s, r = np.asarray(order), np.asarray(sizes), np.asarray(rank)
    assert sorted(o.tolist()) == list(range(32))       # a true permutation
    np.testing.assert_array_equal(
        s, np.bincount(np.asarray(idx), minlength=E))
    sorted_ids = np.asarray(idx)[o]
    assert (np.diff(sorted_ids) >= 0).all()            # grouped
    starts = np.cumsum(s) - s
    np.testing.assert_array_equal(r, np.arange(32) - starts[sorted_ids])
    # stability: equal ids keep their original relative order
    for e in range(E):
        np.testing.assert_array_equal(
            o[sorted_ids == e], np.flatnonzero(np.asarray(idx) == e))


def test_grouped_ffn_impl_selector(monkeypatch):
    xt = jnp.ones((2, 4, D), jnp.float32)
    eid = jnp.zeros((2,), jnp.int32)
    w1 = jnp.ones((E, D, 5), jnp.float32)
    w2 = jnp.ones((E, 5, D), jnp.float32)
    with pytest.raises(ValueError, match="moe_dropless_unknown_impl"):
        grouped_ffn(xt, eid, w1, w2, impl="triton")
    monkeypatch.setenv("BLUEFOG_MOE_GROUPED_IMPL", "nope")
    with pytest.raises(ValueError, match="moe_dropless_unknown_impl"):
        grouped_ffn(xt, eid, w1, w2)
    monkeypatch.setenv("BLUEFOG_MOE_GROUPED_IMPL", "xla")
    np.testing.assert_array_equal(np.asarray(grouped_ffn(xt, eid, w1, w2)),
                                  np.asarray(grouped_ffn_xla(xt, eid, w1,
                                                             w2)))


@pytest.mark.parametrize("F,f_blocks", [(32, 1), (256, 2)])
def test_grouped_ffn_pallas_matches_xla(F, f_blocks, monkeypatch):
    """The Pallas kernel (interpreter mode off-TPU) is a drop-in for the
    XLA path: same forward values, same gradients for x/w1/w2 — the
    custom_vjp backward is the path-identical scatter-add by design.
    The second row shrinks the VMEM budget so F splits into two blocks
    and the output block accumulates across the F grid axis."""
    from bluefog_tpu.ops import pallas_moe
    from bluefog_tpu.ops.pallas_moe import grouped_ffn_pallas

    rng = np.random.default_rng(0)
    G, tile, d = 6, 8, 16
    if f_blocks > 1:
        monkeypatch.setattr(pallas_moe, "_VMEM_BUDGET", 48 * 1024)
    assert F // pallas_moe._block_f(tile, d, F, 4, 4) == f_blocks
    xt = jnp.asarray(rng.normal(size=(G, tile, d)), jnp.float32)
    eid = jnp.asarray(rng.integers(0, E, size=(G,)), jnp.int32)
    w1 = jnp.asarray(rng.normal(size=(E, d, F)), jnp.float32)
    # keep the output O(F=32) at either width: the loss below takes sin()
    w2 = jnp.asarray(rng.normal(size=(E, F, d)) * 32 / F, jnp.float32)

    a = grouped_ffn_xla(xt, eid, w1, w2)
    b = grouped_ffn_pallas(xt, eid, w1, w2, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)

    def loss(f):
        return lambda x_, w1_, w2_: jnp.sum(jnp.sin(f(x_, eid, w1_, w2_)))

    ga = jax.grad(loss(grouped_ffn_xla), argnums=(0, 1, 2))(xt, w1, w2)
    gb = jax.grad(loss(lambda *a_: grouped_ffn_pallas(*a_, interpret=True)),
                  argnums=(0, 1, 2))(xt, w1, w2)
    for u, v in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                   rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="grouped_ffn_pallas"):
        grouped_ffn_pallas(xt, eid[:2], w1, w2, interpret=True)


def test_stablehlo_dot_flops_counter():
    from bluefog_tpu.utils.hlo_bytes import stablehlo_dot_flops

    def f(x, w):
        u = jnp.einsum("gtd,gdf->gtf", x, w)           # batched
        v = x.reshape(10, 16) @ jnp.ones((16, 3), jnp.float32)
        return u, v

    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((5, 2, 16), jnp.float32),
        jax.ShapeDtypeStruct((5, 16, 8), jnp.float32)).as_text()
    assert stablehlo_dot_flops(txt) == 2 * 5 * 2 * 8 * 16 + 2 * 10 * 3 * 16
    # generic (quoted-attribute) MLIR form parses identically
    generic = ('"stablehlo.dot_general"(%0, %1) <{dot_dimension_numbers = '
               "#stablehlo.dot<lhs_batching_dimensions = [0], "
               "rhs_batching_dimensions = [0], "
               "lhs_contracting_dimensions = [2], "
               "rhs_contracting_dimensions = [1]>}> : "
               "(tensor<5x2x16xf32>, tensor<5x16x8xf32>) -> "
               "tensor<5x2x8xf32>")
    assert stablehlo_dot_flops(generic) == 2 * 5 * 2 * 8 * 16
    with pytest.raises(ValueError, match="stablehlo_dot_flops"):
        stablehlo_dot_flops("stablehlo.dot_general mangled")


# --- permutation property tests on a live expert axis ----------------------

def _run_dropless(cpu_devices, x, idx, grouped_fn, tile=4):
    """Drive moe_apply_dropless on an N-device expert axis: ``x`` is
    ``[N, T, D]`` per-device rows, ``idx`` ``[N, T]`` global expert ids."""
    mesh = Mesh(np.array(cpu_devices[:N]), ("expert",))

    def f(xb, ib):
        return moe_apply_dropless(xb[0], ib[0], grouped_fn, None,
                                  axis="expert", num_experts=E,
                                  tile=tile)[None]

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("expert"), P("expert")),
        out_specs=P("expert")))
    return np.asarray(fn(x, idx))


def test_dropless_identity_roundtrip_bit_exact(cpu_devices):
    """dispatch∘combine with the identity grouped_fn IS the identity
    permutation — bit-for-bit, for random AND adversarial routings."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(N, T, D)), jnp.float32)
    for idx_np in (rng.integers(0, E, size=(N, T)),
                   np.zeros((N, T), np.int64),         # all -> expert 0
                   np.full((N, T), E - 1)):            # all -> last expert
        out = _run_dropless(cpu_devices, x,
                            jnp.asarray(idx_np, jnp.int32),
                            lambda p, xt, eids: xt)
        np.testing.assert_array_equal(out, np.asarray(x))


def test_dropless_routes_every_token_no_drops(cpu_devices):
    """Each row is transformed by exactly its chosen expert (scale by
    global expert id + 1 -> closed form), for any seeded routing — and
    the all-to-one-expert routing that makes the CAPACITY path drop
    tokens to zero loses nothing on the dropless path (the contrasting
    oracle the issue asks for)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(N, T, D)), jnp.float32)
    e_local = E // N

    def scale_by_expert(p, xt, eids):
        # eids are LOCAL expert ids on the owning device
        dev = jax.lax.axis_index("expert")
        geid = dev * e_local + eids
        return xt * (geid[:, None, None] + 1.0).astype(xt.dtype)

    idx = jnp.asarray(rng.integers(0, E, size=(N, T)), jnp.int32)
    out = _run_dropless(cpu_devices, x, idx, scale_by_expert)
    np.testing.assert_allclose(
        out, np.asarray(x) * (np.asarray(idx)[..., None] + 1.0), rtol=1e-6)

    hot = jnp.asarray(np.full((N, T), 1), jnp.int32)   # everyone -> expert 1
    out_hot = _run_dropless(cpu_devices, x, hot, scale_by_expert)
    np.testing.assert_allclose(out_hot, np.asarray(x) * 2.0, rtol=1e-6)

    # the capacity path DOES drop under the same hostile routing
    mesh = Mesh(np.array(cpu_devices[:N]), ("expert",))
    cap = T // 2

    def f_cap(xb, ib):
        buf, pos, keep = moe_dispatch(xb[0], ib[0], capacity=cap,
                                      axis="expert", num_experts=E)
        return keep[None]

    keep = np.asarray(jax.jit(jax.shard_map(
        f_cap, mesh=mesh, in_specs=(P("expert"), P("expert")),
        out_specs=P("expert")))(x, hot))
    assert keep.sum() == N * cap                       # half dropped
    assert keep.sum() < N * T


def test_dropless_output_follows_token_order(cpu_devices):
    """Permuting a device's input rows permutes its outputs identically:
    the result is a pure function of (token, its expert), independent of
    where the token sits in the batch."""
    rng = np.random.default_rng(3)
    x = np.asarray(rng.normal(size=(N, T, D)), np.float32)
    idx = rng.integers(0, E, size=(N, T))
    e_local = E // N

    def scale(p, xt, eids):
        dev = jax.lax.axis_index("expert")
        geid = dev * e_local + eids
        return xt * (geid[:, None, None] + 1.0).astype(xt.dtype)

    base = _run_dropless(cpu_devices, jnp.asarray(x),
                         jnp.asarray(idx, jnp.int32), scale)
    perm = rng.permutation(T)
    shuf = _run_dropless(cpu_devices, jnp.asarray(x[:, perm]),
                         jnp.asarray(idx[:, perm], jnp.int32), scale)
    np.testing.assert_allclose(shuf, base[:, perm], rtol=1e-6)


def test_dropless_rejects_out_of_range_routing(cpu_devices):
    """A concrete (trace-time) expert index outside [0, E) fails with the
    named rule instead of silently clipping rows onto the wrong expert."""
    mesh = Mesh(np.array(cpu_devices[:N]), ("expert",))
    bad = jnp.asarray(np.full((T,), E), jnp.int32)     # == E: out of range

    def f(xb):
        return moe_apply_dropless(xb[0], bad, lambda p, xt, e: xt, None,
                                  axis="expert", num_experts=E)[None]

    with pytest.raises(ValueError,
                       match="moe_routing_expert_idx_out_of_range"):
        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("expert"),),
                              out_specs=P("expert")))(
            jnp.ones((N, T, D), jnp.float32))


# --- the expert-choice router (mesh-free) ----------------------------------

def test_router_expert_choice_selects_top_c_per_expert():
    rng = np.random.default_rng(4)
    B, Tl = 2, 12
    x = jnp.asarray(rng.normal(size=(B, Tl, D)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
    C = 3
    logits, probs, sel, gate = router_expert_choice(x, wr, capacity=C)
    assert sel.shape == gate.shape == (B, E, C)
    p = np.asarray(probs)
    for b in range(B):
        for e in range(E):
            # the C selected tokens ARE the top-C by router probability
            top = np.sort(np.argsort(-p[b, :, e])[:C])
            np.testing.assert_array_equal(np.sort(np.asarray(sel)[b, e]),
                                          top)
            np.testing.assert_allclose(
                np.asarray(gate)[b, e], p[b, np.asarray(sel)[b, e], e],
                rtol=1e-6)
    with pytest.raises(ValueError, match="moe_ec_invalid_capacity"):
        router_expert_choice(x, wr, capacity=Tl + 1)
    with pytest.raises(ValueError, match="whole"):
        router_expert_choice(x.reshape(B * Tl, D), wr, capacity=C)


# --- eager config contracts ------------------------------------------------

def test_dropless_config_contracts(cpu_devices):
    m = compose.compose_parallelism(2, 1, 1, 1, 4, num_experts=8,
                                    devices=cpu_devices[:8])
    with pytest.raises(ValueError, match="dispatch"):
        MoELMConfig(num_experts=8, batch=4, dispatch="padded").validate(m)
    with pytest.raises(ValueError, match="router_mode"):
        MoELMConfig(num_experts=8, batch=4,
                    router_mode="switch").validate(m)
    with pytest.raises(ValueError, match="group_tile"):
        MoELMConfig(num_experts=8, batch=4, dispatch="dropless",
                    group_tile=0).validate(m)
    with pytest.raises(ValueError, match="expert_choice"):
        MoELMConfig(num_experts=8, batch=4,
                    router_mode="expert_choice").validate(m)  # w/ capacity
    cfg = MoELMConfig(num_experts=8, batch=4, dispatch="dropless",
                      router_mode="expert_choice")
    cfg.validate(m)
    # C = ceil(k * T / E): the token budget matching top-k active work
    assert cfg.ec_capacity(m) == -(-cfg.top_k * cfg.seq_len // 8)

    m_sp = compose.compose_parallelism(2, 1, 1, 2, 1, num_experts=8,
                                       devices=cpu_devices[:4])
    with pytest.raises(ValueError, match="sp=1"):
        MoELMConfig(num_experts=8, batch=4, dispatch="dropless",
                    router_mode="expert_choice").validate(m_sp)


def test_dropless_config_from_env(monkeypatch):
    monkeypatch.setenv("BLUEFOG_MOE_ROUTER", "expert_choice")
    monkeypatch.setenv("BLUEFOG_MOE_DISPATCH", "dropless")
    monkeypatch.setenv("BLUEFOG_MOE_TILE", "16")
    cfg = MoELMConfig.from_env()
    assert cfg.router_mode == "expert_choice"
    assert cfg.dispatch == "dropless" and cfg.group_tile == 16


# --- float64 trajectory oracles --------------------------------------------

_ORACLE_TEMPLATE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "1"
import json
import jax
import numpy as np
from jax.sharding import PartitionSpec as P
import bluefog_tpu as bf
from bluefog_tpu.moe import MoELMConfig, init_moe_params, make_moe_batch, \\
    make_moe_grad_fn
from bluefog_tpu.parallel import compose

bf.init(platform="cpu")
STEPS, LR = 12, 0.1
ROUTER = %(router)r


def traj(ep, dense_equiv=False):
    cfg = MoELMConfig(layers=2, num_experts=4, top_k=1, dispatch="dropless",
                      router_mode=ROUTER, group_tile=4)
    m = compose.compose_parallelism(2, 2, 1, 1, ep, num_experts=4,
                                    devices=jax.devices()[:4 * ep])
    params = init_moe_params(cfg, m, dtype=np.float64,
                             dense_equiv=dense_equiv)
    batch = make_moe_batch(cfg, m, steps=STEPS)
    gf = make_moe_grad_fn(cfg, m, dense_equiv=dense_equiv)

    def body(p, b):
        q = jax.tree.map(lambda v: v[0], p)

        def step(q, toks):
            loss, g = gf(q, toks)
            return jax.tree.map(lambda a, d: a - LR * d, q, g), loss

        _, losses = jax.lax.scan(step, q, b[0])
        return losses[None]

    f = jax.jit(jax.shard_map(body, mesh=m.mesh, in_specs=P(compose.AXES),
                              out_specs=P(compose.AXES), check_vma=False))
    return np.asarray(f(compose.device_put(m, params),
                        compose.device_put(m, batch)))[0].tolist()

print(json.dumps({"dense": traj(1, dense_equiv=True),
                  "ep1": traj(1), "ep2": traj(2)}))
"""


def _run_oracle(router):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, "-c", _ORACLE_TEMPLATE % {"router": router}],
        cwd=REPO, capture_output=True, text=True, timeout=540, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_dropless_topk_float64_trajectory_oracle():
    """Sort-based grouped dispatch is a pure permutation, so the dropless
    top-1 model matches the dense-equivalent twin to 1e-12 over 12 real
    SGD steps on BOTH the ep=1 and ep=2 carvings — with zero dropped
    tokens by construction (no capacity_factor exists to get wrong).
    Observed agreement ~1e-15."""
    doc = _run_oracle("topk")
    dense, ep1, ep2 = doc["dense"], doc["ep1"], doc["ep2"]
    assert len(dense) == len(ep1) == len(ep2) == 12
    np.testing.assert_allclose(ep1, dense, rtol=0, atol=1e-12)
    np.testing.assert_allclose(ep2, dense, rtol=0, atol=1e-12)
    assert dense[-1] < dense[0]


def test_dropless_expert_choice_float64_trajectory_oracle():
    """Expert-choice routing under the grouped path matches ITS dense
    twin (every expert on every token, top-C outputs selected) to 1e-12
    at ep=1 and ep=2 — EC shards batch rows over ep, so the carving
    cannot change which tokens an expert sees."""
    doc = _run_oracle("expert_choice")
    dense, ep1, ep2 = doc["dense"], doc["ep1"], doc["ep2"]
    assert len(dense) == len(ep1) == len(ep2) == 12
    np.testing.assert_allclose(ep1, dense, rtol=0, atol=1e-12)
    np.testing.assert_allclose(ep2, dense, rtol=0, atol=1e-12)
    assert dense[-1] < dense[0]


# --- AOT: dropless keeps the DCN contract ----------------------------------

_BYTES_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import dataclasses
import jax
import numpy as np
import optax
import bluefog_tpu as bf
import bluefog_tpu.optimizers as bfopt
from bluefog_tpu.moe import MoELMConfig, init_moe_params, make_moe_batch, \\
    make_moe_grad_fn
from bluefog_tpu.parallel import compose
from bluefog_tpu.utils.hlo_bytes import (stablehlo_dot_flops,
                                         stablehlo_wire_stats)

bf.init(platform="cpu")
m = compose.compose_parallelism(2, 2, 1, 1, 2, num_experts=4, wire="bf16")
base = MoELMConfig(layers=2, heads=4, d_model=32, seq_len=32, batch=4,
                   num_experts=4, top_k=1, capacity_factor=1.25)


def stats(cfg):
    grad_fn = make_moe_grad_fn(cfg, m)
    step, strategy = compose.make_train_step(m, grad_fn, optax.adam(1e-2))
    params = compose.device_put(m, init_moe_params(cfg, m))
    state = bfopt.init_distributed(strategy, params)
    toks = compose.device_put(m, make_moe_batch(cfg, m))
    shlo = step.lower(params, state, toks).as_text()
    return dict(stablehlo_wire_stats(shlo, m.slice_size),
                dot_flops=stablehlo_dot_flops(shlo))

cfgs = {
    "capacity": base,
    "dropless_topk": dataclasses.replace(base, dispatch="dropless"),
    "dropless_ec": dataclasses.replace(base, dispatch="dropless",
                                       router_mode="expert_choice")}
out = {}
for name, cfg in cfgs.items():
    s = stats(cfg)
    out[name] = {"dcn": sorted(s["dcn"]), "ici": sorted(s["ici"]),
                 "dcn_bytes": s["dcn_bytes"],
                 "a2a_ici": s["ici"].get("all_to_all", {}).get("count", 0),
                 "a2a_dcn": s["dcn"].get("all_to_all", {}).get("count", 0),
                 "a2a_ici_bytes":
                     s["ici"].get("all_to_all", {}).get("bytes", 0),
                 "dot_flops": s["dot_flops"]}
out["rows"] = {
    "dropless_ec": (base.num_experts // m.ep) * m.ep * (base.batch // m.ep)
                   * cfgs["dropless_ec"].ec_capacity(m),
    "capacity": base.num_experts * base.top_k * base.capacity(m),
    "f_local": base.ffn_mult * base.d_model // m.tp}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def dispatch_programs():
    """The dp2 x pp2 x ep2 step lowered under each dispatch scheme, never
    run, in one subprocess."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, "-c", _BYTES_SCRIPT],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=540, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_dropless_all_to_all_stays_ici_dcn_bytes_identical(dispatch_programs):
    """dp2 x pp2 x ep2: under BOTH dropless modes every expert all_to_all
    (data + the topk path's counts exchange) stays ICI-classified, DCN
    still carries only the gossip permutes, and cross-slice bytes are
    byte-identical to the capacity path — the dispatch scheme moves data
    inside the slice only."""
    doc = dispatch_programs
    for name in ("capacity", "dropless_topk", "dropless_ec"):
        assert doc[name]["dcn"] == ["collective_permute"], (name, doc[name])
        assert doc[name]["a2a_dcn"] == 0
        assert doc[name]["a2a_ici"] >= 2                 # there + back
    assert (doc["dropless_topk"]["dcn_bytes"]
            == doc["capacity"]["dcn_bytes"])
    assert doc["dropless_ec"]["dcn_bytes"] == doc["capacity"]["dcn_bytes"]
    # the topk dropless wire protocol adds the tiny counts all_to_all
    assert doc["dropless_topk"]["a2a_ici"] > doc["capacity"]["a2a_ici"]


def test_expert_choice_dropless_beats_capacity_by_the_padding(
        dispatch_programs):
    """The dispatch head-to-head on the SAME carving: expert choice's
    static groups pad nothing, so against the capacity twin (cf 1.25) it
    runs the padding fraction fewer grouped-GEMM rows, its step's
    dot_general FLOPs fall by at least one forward FFN at that row delta
    (everything outside the MoE sublayer is program-identical), and its
    intra-slice all_to_alls move exactly that fraction fewer bytes."""
    doc = dispatch_programs
    ec, cap, rows = doc["dropless_ec"], doc["capacity"], doc["rows"]
    padding_fraction = 1.0 - 1.0 / 1.25
    assert rows["dropless_ec"] < rows["capacity"]
    assert (rows["dropless_ec"] / rows["capacity"]
            <= 1.0 - padding_fraction + 1e-9)
    d_model = 32
    min_expected_delta = (4 * d_model * rows["f_local"]
                          * (rows["capacity"] - rows["dropless_ec"]))
    assert ec["dot_flops"] < cap["dot_flops"]
    assert cap["dot_flops"] - ec["dot_flops"] >= min_expected_delta > 0
    assert (ec["a2a_ici_bytes"] * rows["capacity"]
            == cap["a2a_ici_bytes"] * rows["dropless_ec"])
