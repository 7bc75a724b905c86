"""Routed MoE on the 5-axis carve: contracts, probe, bytes, f64 oracle.

Four layers of proof for the ``bluefog_tpu.moe`` reference LM:

* eager contract errors at :func:`compose_parallelism` and
  ``MoELMConfig.validate`` — carving mistakes fail with named rules;
* the grading probe's routing-health metrics are sane and global;
* AOT byte attribution at 32 virtual chips with ALL FIVE axes > 1:
  every expert all_to_all is intra-slice, cross-slice bytes per chip
  match the ep=1 carving at the same dp to the byte (E_local held
  constant — weak scaling in experts is DCN-neutral; the only delta is
  the shared router table's E_total growth, asserted exactly), and only
  the gossip permutes carry the DCN wire-codec dtype;
* a float64 trajectory oracle: top-1 no-drop routed MoE matches the
  dense-equivalent model loss-for-loss to 1e-9 over 12 steps, on both
  the ep=1 and ep=2 carvings (observed agreement ~1e-15 — the routed
  dispatch/combine path and the ep gradient recipe are exact).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bluefog_tpu.moe import (MoELMConfig, init_moe_params, make_moe_batch,
                             make_moe_probe, router_topk)
from bluefog_tpu.parallel import compose

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# --- eager contracts -------------------------------------------------------

def test_moe_compose_contract_errors(cpu_devices):
    """ep carving mistakes fail eagerly at compose_parallelism."""
    with pytest.raises(ValueError, match="num_experts"):
        compose.compose_parallelism(2, 1, 1, 1, 4, devices=cpu_devices)
    with pytest.raises(ValueError, match="% ep"):
        compose.compose_parallelism(2, 1, 1, 1, 4, num_experts=6,
                                    devices=cpu_devices)
    with pytest.raises(ValueError, match="capacity_factor"):
        compose.compose_parallelism(2, 1, 1, 1, 4, num_experts=4,
                                    capacity_factor=0.0,
                                    devices=cpu_devices)
    m = compose.compose_parallelism(2, 1, 1, 1, 4, num_experts=8,
                                    capacity_factor=1.5,
                                    devices=cpu_devices)
    d = m.describe()
    assert d["ep"] == 4 and d["num_experts"] == 8
    assert d["capacity_factor"] == 1.5
    assert m.slice_size == 4 and m.size == 8


def test_moe_config_contract_errors(cpu_devices):
    m = compose.compose_parallelism(2, 1, 1, 1, 4, num_experts=8,
                                    devices=cpu_devices)
    with pytest.raises(ValueError, match="top_k"):
        MoELMConfig(num_experts=8, top_k=3).validate(m)
    with pytest.raises(ValueError, match="num_experts"):
        MoELMConfig(num_experts=4).validate(m)       # mesh says 8
    with pytest.raises(ValueError, match="d_model"):
        MoELMConfig(num_experts=8, batch=4, d_model=8).validate(m)
    with pytest.raises(ValueError, match="% ep"):
        MoELMConfig(num_experts=8, batch=2).validate(m)
    cfg = MoELMConfig(num_experts=8, batch=4)
    cfg.validate(m)
    assert cfg.capacity(m) > 0
    assert cfg.n_active_params < cfg.n_params


def test_moe_config_from_env(monkeypatch):
    monkeypatch.setenv("BLUEFOG_MOE_EXPERTS", "16")
    monkeypatch.setenv("BLUEFOG_MOE_TOPK", "2")
    monkeypatch.setenv("BLUEFOG_MOE_CAPACITY_FACTOR", "2.0")
    cfg = MoELMConfig.from_env()
    assert cfg.num_experts == 16 and cfg.top_k == 2
    assert cfg.capacity_factor == 2.0


def test_router_topk_gates(cpu_devices):
    """k=1 gate is the raw top probability; k=2 gates renormalize to 1."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    logits, probs, idx, gate = router_topk(x, wr, top_k=1)
    np.testing.assert_allclose(np.asarray(gate)[:, 0],
                               np.asarray(probs).max(-1), rtol=1e-6)
    _, _, idx2, gate2 = router_topk(x, wr, top_k=2)
    np.testing.assert_allclose(np.asarray(gate2).sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="top_k"):
        router_topk(x, wr, top_k=3)


# --- the probe on a live 3-axis MoE carve ----------------------------------

def test_moe_probe_metrics_are_global(cpu_devices):
    """dp=2 x pp=2 x ep=2: the probe's routing-health metrics are
    replicated (global) across every device and internally consistent —
    usage sums to 1, entropies within [0, log E], dropped in [0, 1]."""
    m = compose.compose_parallelism(2, 2, 1, 1, 2, num_experts=4,
                                    capacity_factor=2.0,
                                    devices=cpu_devices)
    cfg = MoELMConfig(layers=2, num_experts=4, top_k=1,
                      capacity_factor=2.0)
    params = compose.device_put(m, init_moe_params(cfg, m))
    batch = compose.device_put(m, make_moe_batch(cfg, m))
    probe = make_moe_probe(cfg, m)
    out = probe(params, batch)
    assert set(out) >= {"aux_loss", "z_loss", "dropped_fraction",
                        "token_entropy", "usage", "usage_entropy", "ce"}
    usage = np.asarray(out["usage"])
    np.testing.assert_allclose(usage.sum(), 1.0, atol=1e-5)
    assert 0.0 <= float(out["dropped_fraction"]) <= 1.0
    assert 0.0 <= float(out["usage_entropy"]) <= np.log(4) + 1e-6
    assert float(out["aux_loss"]) >= 1.0 - 1e-5     # Switch lower bound
    assert float(out["ce"]) > 0.0


# --- AOT byte attribution: 32 chips, all five axes live --------------------

_MOE_BYTES_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import numpy as np
import optax
import bluefog_tpu as bf
import bluefog_tpu.optimizers as bfopt
from bluefog_tpu.moe import MoELMConfig, init_moe_params, make_moe_batch, \\
    make_moe_grad_fn
from bluefog_tpu.parallel import compose
from bluefog_tpu.utils.hlo_bytes import stablehlo_wire_stats

bf.init(platform="cpu")


def lower(ep, n_experts, n_dev):
    m = compose.compose_parallelism(
        2, 2, 2, 2, ep, num_experts=n_experts, wire="bf16",
        devices=jax.devices()[:n_dev])
    cfg = MoELMConfig(layers=2, heads=4, d_model=32, seq_len=32,
                      batch=4, num_experts=n_experts, top_k=1,
                      capacity_factor=2.0)
    grad_fn = make_moe_grad_fn(cfg, m)
    step, strategy = compose.make_train_step(m, grad_fn, optax.adam(5e-3))
    params = compose.device_put(m, init_moe_params(cfg, m))
    state = bfopt.init_distributed(strategy, params)
    toks = compose.device_put(m, make_moe_batch(cfg, m))
    shlo = step.lower(params, state, toks).as_text()
    st = stablehlo_wire_stats(shlo, m.slice_size)
    return {"ici": {k: v for k, v in st["ici"].items()},
            "dcn": {k: v for k, v in st["dcn"].items()},
            "unknown": st["unknown"],
            "ici_bytes": st["ici_bytes"], "dcn_bytes": st["dcn_bytes"],
            "ici_dtypes": st["ici_dtypes"], "dcn_dtypes": st["dcn_dtypes"]}

# ep=2 with 8 experts vs ep=1 with 4: E_local == 4 on every chip in both
print(json.dumps({"ep2": lower(2, 8, 32), "ep1": lower(1, 4, 16)}))
"""


def test_moe_five_axis_bytes_attribution():
    """dp=2 x pp=2 x tp=2 x sp=2 x ep=2 (32 virtual chips, every axis
    live): the expert all_to_alls are intra-slice by construction,
    cross-slice (DCN) traffic is gossip-only and — with E_local held
    constant — byte-identical to the ep=1 carving at the same dp up to
    the shared router table (whose exact E_total growth is asserted),
    and only the gossip permutes carry the bf16 wire-codec dtype."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, "-c", _MOE_BYTES_SCRIPT],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=420, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    ep2, ep1 = doc["ep2"], doc["ep1"]
    # every collective classified (the slice-major sort keeps groups parsable)
    assert not ep2["unknown"] and not ep1["unknown"]
    # expert + sp all_to_alls exist and are ALL intra-slice
    assert "all_to_all" in ep2["ici"] and ep2["ici"]["all_to_all"]["count"] > 0
    assert "all_to_all" not in ep2["dcn"]
    # DCN traffic is gossip collective_permutes only
    assert set(ep2["dcn"]) == {"collective_permute"}
    # weak scaling in experts: per-chip DCN traffic is the same gossip
    # permutes over the same per-chip shard — the expert FFN blocks
    # contribute byte-identically (E_local == 4 in both carvings).  The
    # ONLY deviation is the router table, a shared [d_model, E_total]
    # leaf that grows with the total expert count: one MoE layer per
    # stage x (8 - 4) extra experts x d_model=32 x 2 bytes (bf16 wire) —
    # counted in the whole (8, 128) f32 tiles a leaf rides the gossip
    # buffer in (fusion.tile_tree pads a leaf that is not made of them):
    # [32, 4] and [32, 8] both fill one, so here the carvings are EQUAL.
    tile = 8 * 128
    router_delta = 1 * (-(-8 * 32 // tile) - -(-4 * 32 // tile)) * tile * 2
    assert ep2["dcn_bytes"] - ep1["dcn_bytes"] == router_delta, (
        ep2["dcn_bytes"], ep1["dcn_bytes"])
    assert (ep2["dcn"]["collective_permute"]["count"]
            == ep1["dcn"]["collective_permute"]["count"])
    # only the gossip wire carries the codec dtype
    assert "bf16" in ep2["dcn_dtypes"]
    assert "bf16" not in ep2["ici_dtypes"], ep2["ici_dtypes"]


# --- 32-chip run: donation, retrace sentinel, learning ---------------------

_MOE_AXIS_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import numpy as np
import optax
import bluefog_tpu as bf
import bluefog_tpu.optimizers as bfopt
from bluefog_tpu.moe import MoELMConfig, init_moe_params, make_moe_batch, \\
    make_moe_grad_fn
from bluefog_tpu.parallel import compose
from bluefog_tpu.utils import metrics as bfm

bf.init(platform="cpu")
m = compose.compose_parallelism(2, 2, 2, 2, 2, num_experts=4, wire="bf16")
cfg = MoELMConfig(layers=2, heads=4, d_model=32, seq_len=32, batch=4,
                  num_experts=4, top_k=1, capacity_factor=2.0)
grad_fn = make_moe_grad_fn(cfg, m)
step, strategy = compose.make_train_step(
    m, grad_fn, optax.adam(1e-2), metrics_every_k=2, metrics_warmup=2)
params = compose.device_put(m, init_moe_params(cfg, m))
state = bfopt.init_distributed(strategy, params)
toks = compose.device_put(m, make_moe_batch(cfg, m))
probe = jax.tree.leaves(params)[0]
losses = []
for _ in range(8):
    params, state, loss = step(params, state, toks)
    losses.append(float(np.asarray(loss).mean()))
print(json.dumps({
    "donation_intact": bool(probe.is_deleted()),
    "retraces": int(bfm.counter("bluefog_retrace_after_warmup_total").total()),
    "losses": losses,
}))
"""


def test_moe_five_axis_donation_and_sentinel():
    """The composed 5-axis MoE step keeps buffer donation intact, never
    retraces after warmup, and the loss decreases — the same invariants
    the dense 4-axis test pins, now with the expert axis live."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, "-c", _MOE_AXIS_SCRIPT],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=540, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["donation_intact"] is True
    assert doc["retraces"] == 0
    assert doc["losses"][-1] < doc["losses"][0], doc["losses"]


# --- the float64 oracle ----------------------------------------------------

_MOE_ORACLE_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "1"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
import bluefog_tpu as bf
from bluefog_tpu.moe import MoELMConfig, init_moe_params, make_moe_batch, \\
    make_moe_grad_fn
from bluefog_tpu.parallel import compose

bf.init(platform="cpu")
cfg = MoELMConfig(layers=2, num_experts=4, top_k=1, capacity_factor=8.0)
STEPS, LR = 12, 0.1


def traj(ep, dense_equiv=False):
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


def test_moe_float64_trajectory_oracle():
    """Top-1 routed MoE with no drops IS the dense mixture: the routed
    path (capacity dispatch, all_to_all, E_local expert blocks, the /ep
    gradient recipe) matches the dense-equivalent model loss-for-loss to
    1e-9 in float64 over 12 SGD steps, on BOTH the ep=1 and ep=2
    carvings.  Any scale bug (double psum over expert, missing 1/ep,
    mis-globalized aux) or dispatch bug (wrong slot, dropped token that
    should be kept) diverges this at step 1; observed agreement is
    ~1e-15."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, "-c", _MOE_ORACLE_SCRIPT],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=540, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    dense, ep1, ep2 = doc["dense"], doc["ep1"], doc["ep2"]
    assert len(dense) == len(ep1) == len(ep2) == 12
    np.testing.assert_allclose(ep1, dense, rtol=0, atol=1e-9)
    np.testing.assert_allclose(ep2, dense, rtol=0, atol=1e-9)
    assert dense[-1] < dense[0]          # and it actually learns

# --- autotune learns the ep axis -------------------------------------------

def test_enumerate_carvings_audits_the_expert_contract():
    """Pure enumeration (no compiles): every ordered 5-axis factorization
    is accounted for, and the MoE carving rules show up as *audited
    rejections* — dp=1 (no gossip axis), ep>1 without a declared expert
    count, and a non-divisible expert count."""
    from bluefog_tpu.autotune import enumerate_carvings

    acc, rej = enumerate_carvings(16, num_experts=8)
    assert all(c.n_chips == 16 for c in acc)
    assert all(c.dp >= 2 for c in acc)
    assert any(c.ep > 1 for c in acc)            # the ep axis is searched
    reasons = {r["reason"].split(":")[0] for r in rej}
    assert "carving_no_gossip_axis" in reasons

    # dense config: any ep>1 candidate is rejected with the named rule
    acc_d, rej_d = enumerate_carvings(16, num_experts=None)
    assert all(c.ep == 1 for c in acc_d)
    assert any(r["reason"].startswith("moe_carving_requires_num_experts")
               for r in rej_d)

    # non-divisible expert count: ep=4 rejected, ep=2 legal (6 % 2 == 0)
    acc_6, rej_6 = enumerate_carvings(16, num_experts=6)
    assert any(c.ep == 2 for c in acc_6)
    assert not any(c.ep == 4 for c in acc_6)
    assert any(r["reason"].startswith("moe_carving_experts_not_divisible")
               for r in rej_6)


def test_tune_carving_picks_low_dcn_expert_carving(cpu_devices):
    """tune_carving on the live 8-device world: real AOT byte counts rank
    the restricted carving space, the winner is a dp=2 composed carving
    (lowest gossip degree -> lowest DCN bytes), the dp=4 carving pays
    more cross-slice bytes, and the contract violations (dp=1, wrong
    device product) are audited, never compiled."""
    import bluefog_tpu as bf
    from bluefog_tpu.autotune import CARVING_PLAN_SCHEMA, tune_carving

    cfg = MoELMConfig(layers=2, heads=4, d_model=32, seq_len=32,
                      batch=4, num_experts=4, top_k=1, capacity_factor=2.0)
    bf.init(devices=cpu_devices)
    try:
        plan = tune_carving(
            cfg, wire="bf16",
            carvings=[(2, 2, 1, 1, 2),      # the 5-axis MoE carve
                      (2, 2, 2, 1, 1),      # tp instead of ep
                      (4, 2, 1, 1, 1),      # more gossip replicas
                      (1, 2, 2, 2, 1),      # no gossip axis -> rejected
                      (2, 2, 1, 1, 4)])     # 16 chips on an 8-chip world
    finally:
        bf.shutdown()

    assert plan["schema"] == CARVING_PLAN_SCHEMA
    json.dumps(plan)                         # JSON-ready, always
    scored = {e["key"]: e for e in plan["audit"]["scored"]}
    rejected = {r["key"]: r["reason"] for r in plan["audit"]["rejected"]}
    assert plan["audit"]["considered"] == len(scored) + len(rejected)
    # 3 legal carvings x 2 dispatch schemes (capacity + dropless)
    assert len(scored) == 6
    assert "carve|dp=2|pp=2|tp=1|sp=1|ep=2|disp=dropless" in scored
    assert scored["carve|dp=2|pp=2|tp=1|sp=1|ep=2|disp=dropless"][
        "dispatch"] == "dropless"
    assert "dispatch" in plan["best"]["config"]

    # the two contract violations never reached a compile
    assert rejected["carve|dp=1|pp=2|tp=2|sp=2|ep=1"].startswith(
        "carving_no_gossip_axis")
    assert rejected["carve|dp=2|pp=2|tp=1|sp=1|ep=4"].startswith(
        "carving_size_mismatch")

    # every scored carving has honest, positive byte counts
    assert all(e["dcn_bytes"] > 0 and e["ici_bytes"] > 0
               for e in scored.values())
    # the expert carving is scored (autotune has learned the ep axis)
    assert "carve|dp=2|pp=2|tp=1|sp=1|ep=2" in scored
    # dp=2 wins on DCN bytes; the dp=4 carving pays gossip degree 2 on a
    # bigger per-chip shard
    best = plan["best"]
    assert best["config"]["dp"] == 2
    assert (scored["carve|dp=4|pp=2|tp=1|sp=1|ep=1"]["dcn_bytes"]
            > best["dcn_bytes_per_step_per_chip"])
