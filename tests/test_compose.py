"""Cross-axis composition: pipeline stages x ring-attention sequence shards.

The scale story no single feature shows: a 2-D (stage x rank) mesh where
decoder blocks are pipelined along ``stage`` while each block's attention
runs ring-parallel over the sequence sharded along ``rank``.  Activations flow
stage-to-stage as ppermutes on one axis; K/V blocks rotate on the other —
both inside one compiled scan.  Output and gradients are pinned to the
dense sequential oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.ops import ring_attention
from bluefog_tpu.parallel.pipeline import last_stage_value, pipeline_apply

S, R = 2, 4            # pipeline stages x sequence-ring size
B, Tl, D, H = 2, 4, 8, 2
T = Tl * R
M = 3                  # microbatches


def _params(rng, n_stage):
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
    return {
        "wqkv": jnp.stack([w(D, 3 * D) for _ in range(n_stage)]),
        "wo": jnp.stack([w(D, D) for _ in range(n_stage)]),
    }


def _block(p, x, attention):
    """One residual attention block; ``attention(q, k, v) -> out``."""
    qkv = x @ p["wqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    t = x.shape[1]
    q = q.reshape(B, t, H, D // H)
    k = k.reshape(B, t, H, D // H)
    v = v.reshape(B, t, H, D // H)
    att = attention(q, k, v).reshape(B, t, D)
    return x + jnp.tanh(att @ p["wo"])


def _dense_attention(q, k, v):
    s = jnp.einsum("bihd,bjhd->bihj", q, k) / np.sqrt(D // H)
    return jnp.einsum("bihj,bjhd->bihd", jax.nn.softmax(s, -1), v)


def _oracle(params, mbs):
    """Sequential composition over full sequences, dense attention."""
    x = mbs                                   # [M, B, T, D]
    for s in range(S):
        p = {kk: vv[s] for kk, vv in params.items()}
        x = jax.vmap(lambda xb: _block(p, xb, _dense_attention))(x)
    return x


def test_pipeline_by_ring_sp_matches_oracle(cpu_devices):
    rng = np.random.default_rng(0)
    params = _params(rng, S)
    mbs = jnp.asarray(rng.normal(size=(M, B, T, D)), jnp.float32)
    mesh = Mesh(np.array(cpu_devices[:S * R]).reshape(S, R), ("stage", "rank"))

    def ring_att(q, k, v):
        return ring_attention(q, k, v, axis="rank", causal=False)

    def stage_fn(p, x):
        return _block(jax.tree.map(lambda t_: t_[0], p), x, ring_att)

    def f(params, mbs):
        out = pipeline_apply(stage_fn, params, mbs[0], axis="stage")
        out = last_stage_value(out, axis="stage")
        return out[None]

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("stage"), P(None, None, None, "rank")),
        out_specs=P(None, None, None, "rank"), check_vma=False))
    out = np.asarray(fn(params, mbs[None]))[0]
    np.testing.assert_allclose(out, np.asarray(_oracle(params, mbs)),
                               rtol=1e-4, atol=1e-5)


def test_pipeline_by_gossip_dp_trains_to_consensus(cpu_devices):
    """Decentralized DP x PP: each rank column holds its OWN params (and
    data shard), stages pipeline along the stage axis, and a neighbor-
    allreduce gossip step over rank mixes each stage's parameters — the
    reference's decentralized training composed with a parallelism mode it
    never had.  Loss must fall and the rank spread must tighten."""
    from bluefog_tpu import schedule as sch
    from bluefog_tpu import topology as tu
    from bluefog_tpu.ops import collectives as C

    rng = np.random.default_rng(2)
    mesh = Mesh(np.array(cpu_devices[:S * R]).reshape(S, R), ("stage", "rank"))
    sched = sch.compile_topology(tu.ExponentialTwoGraph(R), weighted=True)

    # per-(stage, rank) params: decentralized starts differ per rank
    w = jnp.asarray(rng.normal(size=(S, R, D, D)) * 0.4, jnp.float32)
    # teacher: shared across ranks (the consensus target exists)
    tw = jnp.asarray(rng.normal(size=(S, D, D)) * 0.4, jnp.float32)
    x_all = jnp.asarray(rng.normal(size=(R, M, B, D)), jnp.float32)
    y_all = x_all
    for s in range(S):
        y_all = jnp.tanh(y_all @ tw[s])

    def stage_fn(p, x):
        return jnp.tanh(x @ p)

    def step(w, mbs, tgts):
        sid = jax.lax.axis_index("stage")
        local = w[0, 0]                                     # [D, D]

        def loss(w_):
            out = pipeline_apply(stage_fn, w_, mbs[0], axis="stage")
            err = jnp.sum((out - tgts[0]) ** 2)
            return jnp.where(sid == S - 1, err, 0.0) / (M * B * D)

        l, g = jax.value_and_grad(loss)(local)
        new = local - 0.3 * g
        # gossip this stage's params across the rank axis (CTA combine)
        new = C.neighbor_allreduce(new, sched, axis="rank")
        return new[None, None], jax.lax.psum(l, ("stage", "rank"))[None, None]

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("stage", "rank"), P("rank"), P("rank")),
        out_specs=(P("stage", "rank"), P("stage", "rank"))))

    losses = []
    for _ in range(40):
        w, l = fn(w, x_all, y_all)
        losses.append(float(np.asarray(jax.block_until_ready(l))[0, 0]))
    assert losses[-1] < 0.5 * losses[0], losses[::10]
    spread = np.abs(np.asarray(w) - np.asarray(w).mean(axis=1, keepdims=True))
    assert float(spread.max()) < 0.05, spread.max()     # ranks reached consensus


def test_gossip_dp_by_expert_parallel_trains(cpu_devices):
    """Decentralized DP x EP on a (rank x expert) mesh: each rank row holds
    its own router/expert copies and data shard; experts shard over the
    expert axis inside each row; a neighbor-allreduce over rank gossips
    both parameter groups.  The piecewise-linear task only converges if
    dispatch works inside every row while gossip mixes across rows."""
    from bluefog_tpu import schedule as sch
    from bluefog_tpu import topology as tu
    from bluefog_tpu.ops import collectives as C
    from bluefog_tpu.parallel.expert import moe_apply

    Rk, E = 2, 4                 # rank rows x experts per row
    T_, D_ = 16, 4
    rng = np.random.default_rng(5)
    mesh = Mesh(np.array(cpu_devices[:Rk * E]).reshape(Rk, E),
                ("rank", "expert"))
    sched = sch.compile_topology(tu.FullyConnectedGraph(Rk), weighted=True)

    centers = rng.normal(size=(E, D_)) * 4.0
    true_maps = rng.normal(size=(E, D_, D_))

    def batch(seed):
        r = np.random.default_rng(seed)
        c = r.integers(0, E, size=(Rk, T_))
        x = centers[c] + r.normal(size=(Rk, T_, D_)) * 0.2
        y = np.einsum("rtd,rtdh->rth", x, true_maps[c])
        return jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)

    params = {
        # per-rank-row copies (decentralized): leading axis Rk
        "router": jnp.asarray(rng.normal(size=(Rk, D_, E)) * 0.1, jnp.float32),
        # per-(row, expert) weights: [Rk, E, D_, D_]
        "expert": jnp.asarray(rng.normal(size=(Rk, E, D_, D_)) * 0.1,
                              jnp.float32),
    }
    pspec = {"router": P("rank"), "expert": P("rank", "expert")}

    def step(p, x, y):
        router, ew = p["router"][0], p["expert"][0]     # strip rank block
        xb, yb = x[0], y[0]

        def loss_fn(rt, w):
            logits = xb @ rt
            idx = jnp.argmax(logits, axis=-1)
            gate = jax.nn.softmax(logits)[jnp.arange(T_), idx]
            out = moe_apply(xb, idx, lambda wz, t: t @ wz[0], w,
                            capacity=T_, axis="expert")
            return jnp.mean((out * gate[:, None] - yb) ** 2)

        loss, (g_rt, g_w) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(router, ew)
        # within a row the router is replicated over the expert axis
        g_rt = jax.lax.pmean(g_rt, "expert")
        new_rt = router - 0.02 * g_rt
        new_w = ew - 0.02 * g_w
        # decentralized: gossip BOTH groups across the rank rows
        new_rt = C.neighbor_allreduce(new_rt, sched, axis="rank")
        new_w = C.neighbor_allreduce(new_w, sched, axis="rank")
        return ({"router": new_rt[None], "expert": new_w[None]},
                jax.lax.pmean(loss, ("rank", "expert")))

    fn = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(pspec, P("rank"), P("rank")),
        out_specs=(pspec, P())))

    losses = []
    for it in range(120):
        x, y = batch(100 + it)
        params, l = fn(params, x, y)
        losses.append(float(np.asarray(jax.block_until_ready(l))))
    assert losses[-1] < 0.5 * losses[0], losses[::10]
    # rank rows reached consensus through the gossip
    w = np.asarray(params["expert"])
    assert float(np.abs(w[0] - w[1]).max()) < 1e-4


def test_1f1b_with_rank_varying_targets(cpu_devices):
    """pipeline_1f1b_grad on a 2-D mesh where only the TARGETS vary over
    the second axis — data parallelism along `rank` through the hand-rolled
    1F1B backward.  Two properties pinned:

    1. the scan carries inherit targets' varying set (regression: carries
       seeded from dloss_dy diverged from carry0 at trace time);
    2. with VMA checking ON, the vjp of the rank-INVARIANT stage params
       automatically psums the per-rank cotangents — the returned grads are
       the rank-replicated SUM of each rank's oracle grad, i.e. the correct
       data-parallel gradient with no explicit reduction.
    """
    from bluefog_tpu.parallel.pipeline import (
        last_stage_value, pipeline_1f1b_grad)

    rng = np.random.default_rng(4)
    mesh = Mesh(np.array(cpu_devices[:S * R]).reshape(S, R), ("stage", "rank"))
    w = jnp.asarray(rng.normal(size=(S, D, D)) * 0.4, jnp.float32)
    mb = jnp.asarray(rng.normal(size=(M, B, D)), jnp.float32)
    tgt = jnp.asarray(rng.normal(size=(R, M, B, D)), jnp.float32)

    def f(w_, mbs, tgts):
        loss, g = pipeline_1f1b_grad(
            lambda p, x: jnp.tanh(x @ p[0]),
            lambda y, t: jnp.mean((y - t) ** 2),
            w_, mbs[0], tgts[0], axis="stage")
        return last_stage_value(loss, axis="stage")[None], g[:, None]

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("stage"), P(None), P("rank")),
        out_specs=(P(("stage", "rank")), P("stage", "rank"))))
    l, g = fn(w, mb[None], tgt)
    l, g = np.asarray(l), np.asarray(g)     # [S*R], [S, R, D, D]
    assert np.isfinite(l).all() and np.isfinite(g).all()

    def seq_loss(params):
        x = mb
        for s in range(S):
            x = jnp.tanh(x @ params[s])
        return x

    # per-rank losses are local; grads are the rank-summed total
    oracle_sum = 0.0
    for r in range(R):
        lo, go = jax.value_and_grad(lambda ww: jnp.sum(jax.vmap(
            lambda y_, t_: jnp.mean((y_ - t_) ** 2))(
                seq_loss(ww), tgt[r])))(w)
        np.testing.assert_allclose(l[r], float(lo), rtol=1e-5, atol=1e-6)
        oracle_sum = oracle_sum + np.asarray(go)
    for r in range(R):
        np.testing.assert_allclose(g[:, r], oracle_sum, rtol=1e-4,
                                   atol=1e-6, err_msg=f"rank {r}")


def test_pipeline_by_ring_sp_grads_match_oracle(cpu_devices):
    rng = np.random.default_rng(1)
    params = _params(rng, S)
    mbs = jnp.asarray(rng.normal(size=(M, B, T, D)), jnp.float32)
    tgt = jnp.asarray(rng.normal(size=(M, B, T, D)), jnp.float32)
    mesh = Mesh(np.array(cpu_devices[:S * R]).reshape(S, R), ("stage", "rank"))

    def ring_att(q, k, v):
        return ring_attention(q, k, v, axis="rank", causal=False)

    def stage_fn(p, x):
        return _block(jax.tree.map(lambda t_: t_[0], p), x, ring_att)

    def f(params, mbs, tgts):
        sid = jax.lax.axis_index("stage")

        def loss(pp):
            # NO collective inside the differentiated scalar: with
            # check_vma=False (required by ring attention) psum transposes
            # as a cotangent SUM, so a psum'd loss over-counts by the axis
            # size.  The raw pipeline output is zeros off the last stage;
            # masking the local error keeps every cotangent seeded once.
            out = pipeline_apply(stage_fn, pp, mbs[0], axis="stage")
            err = jnp.sum((out - tgts[0]) ** 2)
            return jnp.where(sid == S - 1, err, 0.0) / (M * B * T * D)

        l, g = jax.value_and_grad(loss)(params)
        # outside the AD region: total loss, and the true gradient of the
        # rank-replicated params = sum of per-copy grads (each rank
        # back-propagated its own sequence shard's paths through the ring)
        l = jax.lax.psum(l, ("stage", "rank"))
        g = jax.tree.map(lambda x: jax.lax.psum(x, "rank"), g)
        return l, g

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("stage"), P(None, None, None, "rank"),
                  P(None, None, None, "rank")),
        out_specs=(P(), P("stage")), check_vma=False))
    l, g = fn(params, mbs[None], tgt[None])

    def oracle_loss(pp):
        return jnp.mean((_oracle(pp, mbs) - tgt) ** 2)

    lo, go = jax.value_and_grad(oracle_loss)(params)
    np.testing.assert_allclose(float(np.asarray(l)), float(lo),
                               rtol=1e-5, atol=1e-7)
    for key in ("wqkv", "wo"):
        np.testing.assert_allclose(np.asarray(g[key]), np.asarray(go[key]),
                                   rtol=1e-4, atol=1e-6, err_msg=key)


def test_dp_pp_tp_three_axis_composition(cpu_devices):
    """The full 3-D layout on one mesh (dp=2, stage=2, tp=2): Megatron
    column/row-split MLP blocks inside each pipeline stage, activations
    ppermute along stage, tensor psum along tp, gradients averaged along
    dp.  Forward loss AND first-step gradients pinned to the dense
    oracle; training reduces the loss with the dp pair in lock-step.

    Gradient recipe (cf. the ring-SP test above): the differentiated
    scalar contains NO loss-side collective — the raw pipeline output is
    masked to the last stage and the seed is scaled 1/TP, because the TP
    ranks hold identical replicas of the output (each would seed the
    full cotangent) while the *structural* row-parallel psum inside the
    block transposes as a cotangent sum over tp (check_vma=False).
    Masking + 1/TP makes every cotangent seed exactly once — measured
    1.0x the dense-oracle gradient (unmasked last_stage_value gives
    S*TP = 4x)."""
    DP, ST, TP = 2, 2, 2
    Dd, Hh = 4, 8
    Mm, Bb = 2, 2
    rng = np.random.default_rng(5)
    mesh = Mesh(np.array(cpu_devices[:8]).reshape(DP, ST, TP),
                ("dp", "stage", "tp"))

    # global param arrays [dp, stage, tp, ...]; identical across dp
    w1 = rng.normal(size=(ST, TP, Dd, Hh // TP)).astype(np.float32) * 0.4
    w2 = rng.normal(size=(ST, TP, Hh // TP, Dd)).astype(np.float32) * 0.4
    params = {"w1": jnp.asarray(np.broadcast_to(w1, (DP,) + w1.shape)),
              "w2": jnp.asarray(np.broadcast_to(w2, (DP,) + w2.shape))}
    # per-dp data shards (different), replicated over stage/tp
    data = rng.normal(size=(DP, Mm, Bb, Dd)).astype(np.float32)

    def stage_fn(p, x):
        # Megatron block: column-split W1, row-split W2, one psum over tp
        h = jnp.tanh(x @ p["w1"])
        return x + jax.lax.psum(h @ p["w2"], "tp")

    def train_step(p, mbs):
        # block views: p leaves [1,1,1,...] (dp,stage,tp), mbs [1,Mm,Bb,Dd]
        q = jax.tree.map(lambda t: t[0, 0, 0], p)
        mb = mbs[0]
        sid = jax.lax.axis_index("stage")

        def loss_fn(q_):
            out = pipeline_apply(stage_fn, q_, mb, axis="stage")
            # off-last-stage outputs are zeros -> mask their garbage error;
            # 1/TP seeds the replicated output's cotangent once (docstring)
            err = jnp.mean((out - 1.0) ** 2)
            return jnp.where(sid == ST - 1, err, 0.0) / TP

        loss, g = jax.value_and_grad(loss_fn)(q)
        # outside AD: true loss (replicate it), dp-average the grads
        loss = jax.lax.psum(loss, ("stage", "tp"))
        g = jax.tree.map(lambda t: jax.lax.pmean(t, "dp"), g)
        new = jax.tree.map(lambda a, b: a - 0.2 * b, q, g)
        return (jax.tree.map(lambda t: t[None, None, None], new),
                loss[None, None, None], jax.tree.map(
                    lambda t: t[None, None, None], g))

    fn = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P("dp", "stage", "tp"), P("dp", None, None, None)),
        out_specs=(P("dp", "stage", "tp"), P("dp", "stage", "tp"),
                   P("dp", "stage", "tp")),
        check_vma=False))

    # dense oracle (jax so we can take its gradient too)
    def oracle_loss(wpair, x):
        w1f, w2f = wpair
        for s in range(ST):
            W1 = jnp.concatenate([w1f[s, t] for t in range(TP)], axis=1)
            W2 = jnp.concatenate([w2f[s, t] for t in range(TP)], axis=0)
            x = x + jnp.tanh(x @ W1) @ W2
        return jnp.mean((x - 1.0) ** 2)

    p, losses, g0 = params, [], None
    for _ in range(3):
        p, loss, g = fn(p, jnp.asarray(data))
        loss = np.asarray(loss)
        g0 = g if g0 is None else g0
        losses.append(float(loss.mean()))
    # dp pair stays in lock-step (grads pmean'd from identical init)
    np.testing.assert_array_equal(np.asarray(p["w1"])[0],
                                  np.asarray(p["w1"])[1])
    # loss decreased
    assert losses[-1] < losses[0], losses
    # first-step loss matches the dense oracle's loss per dp shard
    exp0 = np.mean([float(oracle_loss((jnp.asarray(w1), jnp.asarray(w2)),
                                      jnp.asarray(data[d])))
                    for d in range(DP)])
    np.testing.assert_allclose(losses[0], exp0, rtol=1e-5)
    # first-step GRADIENTS match the dense oracle (dp-averaged): the 3-D
    # backward — pipeline transpose x structural tp psum x dp pmean — is
    # exactly the dense gradient, not a multiple of it
    go = [jax.grad(oracle_loss)((jnp.asarray(w1), jnp.asarray(w2)),
                                jnp.asarray(data[d])) for d in range(DP)]
    go_avg = jax.tree.map(lambda a, b: (a + b) / 2, go[0], go[1])
    for key, exp in (("w1", go_avg[0]), ("w2", go_avg[1])):
        np.testing.assert_allclose(
            np.asarray(g0[key])[0], np.asarray(exp), rtol=2e-4,
            atol=1e-6, err_msg=key)


# ---------------------------------------------------------------------------
# parallel/compose: the validated 4-axis production carving (gossip-DP x
# PP x TP x Ulysses).  Contract errors fail at carve time; the full-axis
# step keeps donation + the retrace sentinel; and a float64 trajectory
# oracle pins gossip-DP x PP loss-for-loss against single-axis DP.
# ---------------------------------------------------------------------------
import json
import os
import subprocess
import sys

from bluefog_tpu import topology as tu
from bluefog_tpu.parallel import compose

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_script(script):
    """Run ``script`` in a clean subprocess (it sizes its own device count:
    the conftest's XLA_FLAGS must not leak) and return its last line's JSON."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_") and k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, "-c", script],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=420, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_compose_contract_errors(cpu_devices):
    """Every carving mistake fails eagerly at compose_parallelism, with a
    message naming the rule — not at trace time deep inside shard_map."""
    with pytest.raises(ValueError, match="positive int"):
        compose.compose_parallelism(0, 2, devices=cpu_devices)
    with pytest.raises(ValueError, match="does not match the device count"):
        compose.compose_parallelism(3, 2, devices=cpu_devices)
    with pytest.raises(ValueError, match="no gossip edges"):
        compose.compose_parallelism(1, 2, 2, 2, devices=cpu_devices,
                                    wire="bf16")
    with pytest.raises(ValueError, match="unknown wire codec"):
        compose.compose_parallelism(2, 2, 2, 1, devices=cpu_devices,
                                    wire="nosuch")
    with pytest.raises(ValueError, match="8 nodes but the DP axis has 4"):
        compose.compose_parallelism(4, 2, devices=cpu_devices,
                                    topology=tu.ExponentialTwoGraph(8))


def test_compose_config_contract_errors(cpu_devices):
    m = compose.compose_parallelism(2, 2, 2, 1, devices=cpu_devices)
    with pytest.raises(ValueError, match="% pp"):
        compose.LMConfig(layers=3).validate(m)
    with pytest.raises(ValueError, match="% tp"):
        compose.LMConfig(heads=1).validate(m)
    m_sp = compose.compose_parallelism(2, 1, 1, 4, devices=cpu_devices)
    with pytest.raises(ValueError, match="ulysses"):
        compose.LMConfig(heads=2).validate(m_sp)
    with pytest.raises(ValueError, match="copy lag"):
        compose.LMConfig(seq_len=8, lag=2).validate(m_sp)


def test_compose_effective_mixing_is_kron(cpu_devices):
    """W_dp (x) I_slice over all ranks: doubly-replicated DP consensus,
    spectral gap identical to the DP graph's own."""
    m = compose.compose_parallelism(2, 2, 2, 1, devices=cpu_devices)
    W = m.effective_mixing()
    assert W.shape == (8, 8)
    np.testing.assert_allclose(W.sum(axis=0), 1.0, atol=1e-12)
    Wdp = tu.to_weight_matrix(m.topology)
    np.testing.assert_allclose(W, np.kron(Wdp, np.eye(4)), atol=1e-12)
    assert m.spectral_gap() == pytest.approx(tu.spectral_gap(Wdp))
    d = m.describe()
    assert d["n_chips"] == 8 and d["leader_degree"] == 1
    assert d["gossip_rounds"] == m.schedule.num_rounds


_FULL_AXIS_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import numpy as np
import optax
import bluefog_tpu as bf
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu.parallel import compose
from bluefog_tpu.utils import metrics as bfm

bf.init(platform="cpu")
m = compose.compose_parallelism(2, 2, 2, 2, wire="bf16")
cfg = compose.LMConfig()
grad_fn = compose.make_lm_grad_fn(cfg, m)
step, strategy = compose.make_train_step(
    m, grad_fn, optax.adam(5e-3), metrics_every_k=2, metrics_warmup=2)
params = compose.init_lm_params(cfg, m)
state = bfopt.init_distributed(strategy, params)
toks = compose.make_lm_batch(cfg, m)
params = compose.device_put(m, params)
probe = jax.tree.leaves(params)[0]
losses = []
for _ in range(6):
    params, state, loss = step(params, state, toks)
    losses.append(float(np.asarray(loss).mean()))
print(json.dumps({
    "donation_intact": bool(probe.is_deleted()),
    "retraces": int(bfm.counter("bluefog_retrace_after_warmup_total").total()),
    "losses": losses,
}))
"""


def test_full_four_axis_donation_and_sentinel():
    """dp=2 x pp=2 x tp=2 x sp=2 (16 chips, all four axes live): buffer
    donation survives the composed step and the retrace sentinel stays 0
    after warmup."""
    doc = _run_script(_FULL_AXIS_SCRIPT)
    assert doc["donation_intact"] is True
    assert doc["retraces"] == 0
    assert doc["losses"][-1] < doc["losses"][0], doc["losses"]


_AOT_BYTES_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import optax
import bluefog_tpu as bf
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu.parallel import compose
from bluefog_tpu.utils.hlo_bytes import stablehlo_wire_stats

bf.init(platform="cpu")


def lower(dp, pp, tp, sp, wire):
    m = compose.compose_parallelism(
        dp, pp, tp, sp, wire=wire,
        devices=jax.devices()[:dp * pp * tp * sp])
    cfg = compose.LMConfig(layers=pp, micro=2 * pp)
    cfg.validate(m)
    step, strategy = compose.make_train_step(
        m, compose.make_lm_grad_fn(cfg, m), optax.adam(5e-3), delayed=True)
    params = compose.init_lm_params(cfg, m)
    state = bfopt.init_distributed(strategy, params)
    toks = compose.make_lm_batch(cfg, m)
    params = compose.device_put(m, params)
    st = stablehlo_wire_stats(step.lower(params, state, toks).as_text(),
                              m.slice_size)
    st["slice_size"] = m.slice_size
    st["mesh"] = m.describe()
    return st

print(json.dumps({"dp4": lower(4, 2, 1, 1, "bf16"),
                  "dp8": lower(8, 2, 1, 1, "bf16"),
                  "four_axis": lower(2, 2, 2, 2, "fp8@64")}))
"""


@pytest.fixture(scope="module")
def aot_wire_bytes():
    """Three carvings of the composed LM step lowered, never run, in one
    16-device subprocess: pre-optimization StableHLO states the wire dtypes
    honestly even where the CPU backend would constant-fold the cast."""
    return _run_script(_AOT_BYTES_SCRIPT)


def test_aot_dcn_bytes_follow_leader_degree(aot_wire_bytes):
    """The pod-scale scaling law at the heart of the decentralized claim:
    cross-slice bytes follow DP-leader out-degree (log2 dp for Exp2), not
    total rank count.  dp=4 -> dp=8 doubles the chips but moves the DCN
    byte bill only by 3/2 (degree 2 -> 3), at identical per-round bytes."""
    a, b = aot_wire_bytes["dp4"], aot_wire_bytes["dp8"]
    assert a["mesh"]["n_chips"] == 8 and b["mesh"]["n_chips"] == 16
    assert a["mesh"]["leader_degree"] == 2
    assert b["mesh"]["leader_degree"] == 3

    da, db = a["dcn"], b["dcn"]
    assert set(da) == set(db) == {"collective_permute"}
    # one cross-slice permute per gossip round == per out-edge
    assert da["collective_permute"]["count"] == 2
    assert db["collective_permute"]["count"] == 3
    # same per-chip model shards -> identical bytes per round; the total
    # scales as degree (3/2), NOT as rank count (2x)
    per_round_a = da["collective_permute"]["bytes"] // 2
    per_round_b = db["collective_permute"]["bytes"] // 3
    assert per_round_a == per_round_b > 0
    assert (db["collective_permute"]["bytes"] * 2
            == da["collective_permute"]["bytes"] * 3)


def test_aot_pp_tp_sp_stay_intra_slice(aot_wire_bytes):
    """Full 4-axis carving at 16 chips: every PP ppermute, TP/stage psum
    and Ulysses all_to_all is classified intra-slice at f32; the DCN side
    holds only the gossip permutes, carrying the fp8 codec payload."""
    wb = aot_wire_bytes["four_axis"]
    assert wb["slice_size"] == 8
    assert set(wb["dcn"]) == {"collective_permute"}
    assert "f8E4M3FN" in wb["dcn_dtypes"]     # fp8 payload (+ f32 scales)
    # PP activations, TP/stage reductions and Ulysses head scatter all on
    # the intra-slice side, none downcast by the gossip codec
    assert set(wb["ici"]) >= {"all_reduce", "collective_permute",
                              "all_to_all"}
    assert wb["ici_dtypes"] == ["f32"]
    assert not wb["unknown"]


_ORACLE_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "1"
import json
import jax
import numpy as np
import optax
import bluefog_tpu as bf
from bluefog_tpu import optimizers as bfopt
from bluefog_tpu.parallel import compose

bf.init(platform="cpu")


def run(pp, n_dev):
    m = compose.compose_parallelism(2, pp, devices=jax.devices()[:n_dev])
    cfg = compose.LMConfig(layers=4)
    grad_fn = compose.make_lm_grad_fn(cfg, m)
    step, strategy = compose.make_train_step(m, grad_fn, optax.sgd(0.1))
    params = compose.init_lm_params(cfg, m)
    params = jax.tree.map(lambda x: np.asarray(x, np.float64), params)
    state = bfopt.init_distributed(strategy, params)
    toks = compose.make_lm_batch(cfg, m)
    params = compose.device_put(m, params)
    losses = []
    for _ in range(6):
        params, state, loss = step(params, state, toks)
        losses.append(float(np.asarray(loss).mean()))
    return losses

print(json.dumps({"composed": run(2, 4), "flat": run(1, 2)}))
"""


def test_float64_trajectory_oracle_dp_x_pp_vs_flat_dp():
    """Gossip-DP x PP is loss-for-loss identical to single-axis DP: the
    same 4-layer LM trained as dp=2/pp=2 on a 4-device carve and as
    dp=2/pp=1 on a 2-device carve — same data, same Exp2(2) gossip, same
    sgd — must produce the SAME float64 loss trajectory to ~1e-9.  Any
    scale bug in the pipelined backward (double-psum, missing stage mask,
    mis-seeded cotangent) shows up at step 1; any gossip/layout bug in the
    composed mixing diverges the tail."""
    doc = _run_script(_ORACLE_SCRIPT)
    a, b = doc["composed"], doc["flat"]
    assert len(a) == len(b) == 6
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-9)
    assert a[-1] < a[0]           # and it actually learns
