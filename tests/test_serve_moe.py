"""Expert-parallel MoE serving: decode-regime dropless, spec, refresh.

What is pinned here (ISSUE 19):

* **config surface** — the ``BLUEFOG_SERVE_MOE`` / ``--serve-moe``
  grammar with named malformed-token errors, ServeConfig's eager
  ``moe_serving_ep_mismatch`` check, the engine's knob-vs-model
  cross-validation, and the named ``moe_serving_requires_topk_router``
  refusal for expert-choice models at serve time;
* **decode-regime dropless** — the ``decode_tile`` policy, a T x k
  battery at decode-shaped token counts (T in {1, 4, 8}, k in {1, 2})
  including the adversarial all-tokens-to-one-expert routing, the
  bit-exact identity of dispatch∘combine at tiny T, and small-tile
  Pallas-vs-XLA forward equality (sublane padding under tile < 8);
* **engine correctness** — MoE greedy decode on an ep=2 carving matches
  an independent numpy top-k-mixture reference token-for-token; a
  float64 subprocess oracle pins the dropless grouped path against the
  dense-equivalent (no-drop) mixture to 1e-12 through a real greedy
  decode loop;
* **fused-decode invariants** — KV-cache donation intact and retrace
  sentinel 0 across a mixed-bucket sweep on the MoE engine;
* **speculative decoding** — dense-FFN-draft spec decode emits streams
  bit-identical to plain MoE greedy (the accept rule only ever emits
  target-argmax tokens);
* **weight refresh** — the refresher pulls router + expert tables
  through the combined mesh (MoE leaves need no special casing) and
  refuses ep / num_experts layout mismatches by name;
* **expert-load-aware batching** — the scheduler publishes hot-expert /
  router-entropy gauges from ``engine.moe_load()`` and its admission
  tiebreak prefers the replica with less expert skew;
* **the launcher surface** — ``--serve-moe`` threads into the child's
  ``BLUEFOG_SERVE_MOE``.
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

from bluefog_tpu.moe.dropless import decode_tile, grouped_ffn_xla
from bluefog_tpu.moe.model import MoELMConfig, init_moe_params
from bluefog_tpu.parallel import compose
from bluefog_tpu.parallel.expert import moe_apply_dropless
from bluefog_tpu.serve import (Scheduler, ServeConfig, ServeEngine,
                               WeightRefresher)
from bluefog_tpu.serve.engine import _parse_serve_moe
from bluefog_tpu.utils import flight as bfflight
from bluefog_tpu.utils import metrics as bfm

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

E = 4                               # experts in every battery config


@pytest.fixture(autouse=True)
def _clean():
    bfm.reset_metrics()
    bfflight.reset()
    yield
    bfflight.reset()
    bfm.reset_metrics()


# ---------------------------------------------------------------------------
# Config grammar + eager contracts
# ---------------------------------------------------------------------------

def test_parse_serve_moe_grammar():
    assert _parse_serve_moe("8") == (8, 1, 1, 0)
    assert _parse_serve_moe("8x2") == (8, 2, 1, 0)
    assert _parse_serve_moe("8x2@2") == (8, 2, 2, 0)
    assert _parse_serve_moe("8x2@2:4") == (8, 2, 2, 4)
    assert _parse_serve_moe("16@4") == (16, 1, 4, 0)
    for bad in ("", "x2", "8.5", "8xtwo", "8@zero", "8:none"):
        with pytest.raises(ValueError, match="BLUEFOG_SERVE_MOE"):
            _parse_serve_moe(bad)
    for bad in ("0", "8x0", "8@0", "8:0"):
        with pytest.raises(ValueError, match="must be >= 1"):
            _parse_serve_moe(bad)


def test_serve_config_moe_validation():
    scfg = ServeConfig(moe_experts=8, moe_top_k=2, moe_ep=2, moe_tile=4)
    assert (scfg.moe_experts, scfg.moe_ep) == (8, 2)
    with pytest.raises(ValueError, match="moe_experts must be >= 0"):
        ServeConfig(moe_experts=-1)
    with pytest.raises(ValueError, match="moe_top_k"):
        ServeConfig(moe_experts=8, moe_top_k=3)
    # the ep carve must divide the expert table, offender named
    with pytest.raises(ValueError,
                       match="moe_serving_ep_mismatch.*moe_ep=3"):
        ServeConfig(moe_experts=8, moe_ep=3)
    with pytest.raises(ValueError, match="moe_tile"):
        ServeConfig(moe_experts=8, moe_tile=9)


def test_serve_config_moe_from_env(monkeypatch):
    monkeypatch.setenv("BLUEFOG_SERVE_MOE", "8x2@2:4")
    scfg = ServeConfig.from_env()
    assert (scfg.moe_experts, scfg.moe_top_k, scfg.moe_ep,
            scfg.moe_tile) == (8, 2, 2, 4)
    monkeypatch.setenv("BLUEFOG_SERVE_MOE", "8x2@3")
    with pytest.raises(ValueError, match="moe_serving_ep_mismatch"):
        ServeConfig.from_env()


def _moe_cfg(**kw):
    base = dict(vocab=32, d_model=16, heads=4, layers=2, seq_len=32,
                micro=1, batch=2, num_experts=E, top_k=2,
                dispatch="dropless")
    base.update(kw)
    return MoELMConfig(**base)


def test_expert_choice_refused_at_serve(cpu_devices):
    cfg = _moe_cfg(router_mode="expert_choice")
    m = compose.compose_parallelism(1, 1, 1, 1, 2, num_experts=E,
                                    devices=cpu_devices[:2])
    params = init_moe_params(cfg, m, seed=0)
    with pytest.raises(ValueError,
                       match="moe_serving_requires_topk_router"):
        ServeEngine(m, cfg, params, ServeConfig(
            batch_buckets=(1,), prefill_buckets=(4,), slots=2, max_len=32))


def test_engine_knobs_cross_validated(cpu_devices):
    # a dense model with an MoE ServeConfig is refused by name ...
    dense = compose.LMConfig(vocab=32, d_model=16, heads=4, layers=2,
                             seq_len=32, micro=1, batch=2)
    dm = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    dp = compose.init_lm_params(dense, dm, seed=0)
    with pytest.raises(ValueError, match="drop the knob"):
        ServeEngine(dm, dense, dp, ServeConfig(
            batch_buckets=(1,), prefill_buckets=(4,), slots=2, max_len=32,
            moe_experts=E))
    # ... and declared knobs must agree with the model/carving
    cfg = _moe_cfg()
    m = compose.compose_parallelism(1, 1, 1, 1, 2, num_experts=E,
                                    devices=cpu_devices[:2])
    params = init_moe_params(cfg, m, seed=0)
    with pytest.raises(ValueError, match="moe_experts=8 does not match"):
        ServeEngine(m, cfg, params, ServeConfig(
            batch_buckets=(1,), prefill_buckets=(4,), slots=2, max_len=32,
            moe_experts=8, moe_ep=2))


# ---------------------------------------------------------------------------
# Decode-regime dropless: tile policy + T x k battery
# ---------------------------------------------------------------------------

def test_decode_tile_policy():
    # smallest pow2 covering ceil(max_rows / groups), capped at 8
    assert decode_tile(1, 2) == 1       # one lane, two local experts
    assert decode_tile(4, 2) == 2
    assert decode_tile(8, 2) == 4
    assert decode_tile(64, 2) == 8      # cap: stream wider, not taller
    assert decode_tile(3, 4) == 1
    assert decode_tile(9, 4) == 4       # ceil(9/4)=3 -> next pow2
    with pytest.raises(ValueError, match="moe_dropless_invalid_tile"):
        decode_tile(0, 2)
    with pytest.raises(ValueError, match="moe_dropless_invalid_tile"):
        decode_tile(8, 0)


def _run_dropless(devs, x, idx, grouped_fn, tile):
    """Drive moe_apply_dropless on a 2-device expert axis: ``x`` is
    ``[2, T, D]`` per-device rows, ``idx`` ``[2, T]`` global expert ids."""
    mesh = Mesh(np.array(devs[:2]), ("expert",))

    def f(xb, ib):
        return moe_apply_dropless(xb[0], ib[0], grouped_fn, None,
                                  axis="expert", num_experts=E,
                                  tile=tile)[None]

    fn = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("expert"), P("expert")),
        out_specs=P("expert")))
    return np.asarray(fn(x, idx))


def test_decode_shaped_dropless_battery(cpu_devices):
    """T x k at decode shapes, with the tile the engine would pick:
    identity dispatch∘combine is bit-exact and expert-scaled routing
    follows the closed form — including the hostile all-to-one routing
    that would overflow any capacity path."""
    rng = np.random.default_rng(7)
    D = 8
    for T in (1, 4, 8):
        for k in (1, 2):
            rows = T * k                # choice-major rows one lane sends
            tile = decode_tile(2 * rows, E // 2)
            assert tile <= 8
            x = jnp.asarray(rng.normal(size=(2, rows, D)), jnp.float32)
            routings = [rng.integers(0, E, size=(2, rows)),
                        np.zeros((2, rows), np.int64)]       # hostile
            def scale(p, xt, eids):
                # eids are device-local; globalize before scaling
                geid = jax.lax.axis_index("expert") * (E // 2) + eids
                return xt * (geid + 1)[:, None, None].astype(xt.dtype)

            for idx_np in routings:
                idx = jnp.asarray(idx_np, jnp.int32)
                out = _run_dropless(cpu_devices, x, idx,
                                    lambda p, xt, eids: xt, tile)
                np.testing.assert_array_equal(out, np.asarray(x))
                scaled = _run_dropless(cpu_devices, x, idx, scale, tile)
                np.testing.assert_allclose(
                    scaled, np.asarray(x) * (idx_np + 1)[..., None],
                    rtol=1e-6)


def test_small_tile_pallas_matches_xla():
    """Tiles below the f32 sublane minimum (8) run through the kernel's
    pad-to-sublane path and must agree with the XLA batched einsum."""
    from bluefog_tpu.ops.pallas_moe import grouped_ffn_pallas
    rng = np.random.default_rng(3)
    D, F = 16, 32
    w1 = jnp.asarray(rng.normal(size=(E, D, F)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(E, F, D)), jnp.float32)
    for tile in (1, 2, 4, 8):
        G = 6
        xt = jnp.asarray(rng.normal(size=(G, tile, D)), jnp.float32)
        eid = jnp.asarray(rng.integers(0, E, size=(G,)), jnp.int32)
        ref = grouped_ffn_xla(xt, eid, w1, w2)
        got = grouped_ffn_pallas(xt, eid, w1, w2, interpret=True)
        assert got.shape == ref.shape == (G, tile, D)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The MoE engine: greedy reference, invariants, spec, refresh, scheduler
# ---------------------------------------------------------------------------

_SCFG = dict(batch_buckets=(1, 2), prefill_buckets=(4, 8), slots=4,
             max_len=32, decode_steps_per_call=1,
             moe_experts=E, moe_top_k=2, moe_ep=2)


@pytest.fixture(scope="module")
def moe_engine(cpu_devices):
    """dp=2 x ep=2 greedy MoE engine on 4 virtual devices."""
    cfg = _moe_cfg()
    m = compose.compose_parallelism(2, 1, 1, 1, 2, num_experts=E,
                                    devices=cpu_devices[:4])
    params = init_moe_params(cfg, m, seed=5)
    eng = ServeEngine(m, cfg, params, ServeConfig(**_SCFG))
    eng.warmup()
    return eng


def _np_ln(z):
    mu = z.mean(-1, keepdims=True)
    return (z - mu) / np.sqrt(z.var(-1, keepdims=True) + 1e-6)


def _ref_moe_forward(Pt, m, cfg, toks, ln=_np_ln):
    """Logits ``[T, vocab]`` of the MoE LM on ``toks`` via plain numpy:
    top-k mixture FFN over the full expert table reassembled from the ep
    peers' shards (replica 0 of the stacked tree ``Pt``; pp=tp=1; ``ln``
    is the norm the model was built with)."""
    H, D, E = cfg.heads, cfg.d_model, cfg.num_experts
    hsz = D // H
    k = cfg.top_k
    # replica 0's ep peers are device rows 0..ep-1 (slice-major layout)
    w1 = np.concatenate([Pt["experts"]["w1"][e] for e in range(m.ep)],
                        axis=1)          # [Lps, E, D, F]
    w2 = np.concatenate([Pt["experts"]["w2"][e] for e in range(m.ep)],
                        axis=1)
    wr = Pt["router"]["wr"][0]           # [Lps, D, E]

    def rope(x, pos):
        half = x.shape[-1] // 2
        freqs = 10000.0 ** (-np.arange(half) / half)
        ang = pos[:, None] * freqs[None]
        cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              -1)

    def gelu(g):
        return 0.5 * g * (1 + np.tanh(
            np.sqrt(2 / np.pi) * (g + 0.044715 * g ** 3)))

    def moe_ffn(h, li):
        logits = h @ wr[li]
        z = np.exp(logits - logits.max(-1, keepdims=True))
        probs = z / z.sum(-1, keepdims=True)
        idx = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
        gate = np.take_along_axis(probs, idx, axis=-1)
        if k > 1:
            gate = gate / gate.sum(-1, keepdims=True)
        y = np.zeros_like(h)
        for j in range(k):
            for e in range(E):
                sel = idx[:, j] == e
                if sel.any():
                    y[sel] += gate[sel, j:j + 1] * (
                        gelu(h[sel] @ w1[li, e]) @ w2[li, e])
        return y

    toks = np.asarray(toks)
    T = len(toks)
    pos = np.arange(T)
    x = Pt["shared"]["embed"][0][toks]
    for li in range(cfg.layers):
        h = ln(x)
        qkv = h @ Pt["blocks"]["wqkv"][0][li]
        q, kk, v = np.split(qkv, 3, -1)
        q = rope(q.reshape(T, H, hsz), pos)
        kk = rope(kk.reshape(T, H, hsz), pos)
        v = v.reshape(T, H, hsz)
        s = np.einsum("ihd,jhd->ihj", q * hsz ** -0.5, kk)
        mask = pos[:, None] >= pos[None, :]
        s = np.where(mask[:, None, :], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        att = np.einsum("ihj,jhd->ihd", p, v).reshape(T, D)
        x = x + att @ Pt["blocks"]["wo"][0][li]
        x = x + moe_ffn(ln(x), li)
    return ln(x) @ Pt["shared"]["head"][0]


def _ref_moe_greedy(eng, prompt, steps):
    """Greedy decode through :func:`_ref_moe_forward`: the full forward
    re-run per token."""
    Pt = jax.tree.map(np.asarray, eng.params)
    toks, out = list(prompt), []
    for _ in range(steps):
        nxt = int(np.argmax(
            _ref_moe_forward(Pt, eng.m, eng.cfg, toks)[-1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_moe_engine_matches_mixture_reference(moe_engine):
    """The dropless grouped decode path, end to end through the engine
    (ep=2 dispatch/combine, KV cache, bucketed shapes), emits the same
    greedy stream as the numpy top-k mixture reference."""
    eng = moe_engine
    sched = Scheduler(eng)
    prompts = [[3, 1, 2], [7, 6, 5, 4, 3, 2]]
    reqs = [sched.submit(p, max_new_tokens=5) for p in prompts]
    sched.drain()
    for r, p in zip(reqs, prompts):
        assert r.generated == _ref_moe_greedy(eng, p, 5), p
    sched.close()


def test_moe_mixed_buckets_zero_retrace_and_donation(moe_engine):
    """Every served shape hits a warm bucket across a mixed-length sweep
    — retrace sentinel stays 0 — and the KV cache is donated into each
    fused call (the pre-call buffer dies)."""
    eng = moe_engine
    base = bfm.counter("bluefog_retrace_after_warmup_total").total()
    probe = jax.tree.leaves(eng.cache)[0]
    sched = Scheduler(eng)
    rng = np.random.default_rng(0)
    for n in (2, 4, 3, 7, 8, 5):
        sched.submit(rng.integers(0, eng.cfg.vocab, n).tolist(),
                     max_new_tokens=4)
    sched.drain()
    sched.close()
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == base
    assert probe.is_deleted()
    load = eng.moe_load()
    assert load is not None and len(load) == eng.m.dp
    assert all(abs(sum(r["fractions"]) - 1.0) < 1e-6
               for r in load if r["tokens"])


def test_moe_spec_decode_bit_identical(cpu_devices, moe_engine):
    """Dense-FFN-draft speculative decoding emits token streams
    bit-identical to the plain-greedy MoE engine on the same prompts."""
    cfg = moe_engine.cfg
    m = compose.compose_parallelism(2, 1, 1, 1, 2, num_experts=E,
                                    devices=cpu_devices[:4])
    eng = ServeEngine(m, cfg, init_moe_params(cfg, m, seed=5),
                      ServeConfig(spec_decode=2, spec_stages=1, **_SCFG))
    eng.warmup()
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]]

    def drain(e):
        s = Scheduler(e)
        reqs = [s.submit(p, max_new_tokens=6) for p in prompts]
        s.drain()
        s.close()
        return [r.generated for r in reqs]

    assert drain(eng) == drain(moe_engine)
    drafted = bfm.counter("bluefog_serve_spec_drafted_total", "").total()
    assert drafted > 0


@pytest.mark.parametrize("spec", [0, 2])
def test_moe_write_after_loop_equals_per_layer_append(cpu_devices, spec):
    """MoE serving (the routed carrier in the layer loop; with ``spec``
    the expert-mean draft and its verify too) with a decode token written
    once per lane and tensor after the layer loop, against the same
    engine writing it into every layer inside the loop: the same tokens,
    the same routing load, no retrace, ``cache_writes`` as counted."""
    class AppendPerLayer(ServeEngine):
        _defer_appends = False

    cfg = _moe_cfg()
    m = compose.compose_parallelism(2, 1, 1, 1, 2, num_experts=E,
                                    devices=cpu_devices[:4])
    params = init_moe_params(cfg, m, seed=5)
    extra = dict(spec_decode=spec, spec_stages=1) if spec else {}
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4], [2, 7, 1, 8, 2, 8]]
    out = []
    for cls in (ServeEngine, AppendPerLayer):
        eng = cls(m, cfg, params, ServeConfig(**_SCFG, **extra))
        eng.warmup()
        s = Scheduler(eng)
        reqs = [s.submit(p, max_new_tokens=6) for p in prompts]
        s.drain()
        s.close()
        mem = eng.program_memory()
        out.append(([r.generated for r in reqs],
                    [r["counts"].tolist() for r in eng.moe_load()],
                    {k: v["cache_writes"] for k, v in mem.items()
                     if k.startswith(("decode", "draft"))}))
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    (toks, load, writes), (ref_toks, ref_load, ref_writes) = out
    assert toks == ref_toks and load == ref_load
    k = spec or 1                    # a draft call fuses k one-token steps
    want = {"decode S=1": 2, "decode S=2": 4}
    if spec:
        want.update({"draft S=1": 2 * k, "draft S=2": 4 * k})
    assert writes == want
    assert ref_writes == {name: n * cfg.layers for name, n in want.items()}


def test_refresher_pulls_expert_tables(cpu_devices, moe_engine):
    """The pull-only refresher moves router + expert-table leaves from a
    same-layout training carving — serve tables become bit-identical to
    the (single-replica) training tables."""
    eng = moe_engine
    train_m = compose.compose_parallelism(1, 1, 1, 1, 2, num_experts=E,
                                          devices=cpu_devices[4:6])
    train_params = init_moe_params(eng.cfg, train_m, seed=11)
    ref = WeightRefresher(eng, train_m, every=1)
    ref.pull(train_params, train_step=1)
    got = jax.tree.map(np.asarray, eng.params)
    want = jax.tree.map(np.asarray, train_params)
    for leaf in ("w1", "w2"):
        # serve rows repeat the training slice per replica (dp_train=1)
        np.testing.assert_array_equal(
            got["experts"][leaf],
            np.tile(want["experts"][leaf], (eng.m.dp, 1, 1, 1, 1)))
    np.testing.assert_array_equal(
        got["router"]["wr"],
        np.tile(want["router"]["wr"], (eng.m.dp, 1, 1, 1)))
    # restore the fixture engine's original weights for later tests
    eng.update_params(init_moe_params(eng.cfg, eng.m, seed=5))


def test_refresher_rejects_ep_layout_mismatch(cpu_devices, moe_engine):
    cfg_ep1 = _moe_cfg()
    train_m = compose.compose_parallelism(1, 1, 1, 1, 1, num_experts=E,
                                          devices=cpu_devices[4:5])
    cfg_ep1.validate(train_m)
    with pytest.raises(ValueError, match="ep=1"):
        WeightRefresher(moe_engine, train_m, every=1)


def test_scheduler_expert_load_gauges_and_skew(moe_engine):
    """Fabricated routing stats: the scheduler snapshot publishes the
    hot-expert / entropy gauges and the admission tiebreak prefers the
    replica with the flatter expert histogram."""
    eng = moe_engine
    sched = Scheduler(eng)
    # replica 0 flat (no skew), replica 1 all-on-one-expert (max skew):
    # [E counts..., entropy_sum, live_count]
    eng._route_stats = np.asarray(
        [[2.0, 2.0, 2.0, 2.0, 8.0 * np.log(E), 8.0],
         [8.0, 0.0, 0.0, 0.0, 0.0, 8.0]])
    sched._note_moe_load()
    hot = bfm.gauge("bluefog_serve_hot_expert_fraction", "")
    assert hot.value(replica=0) == pytest.approx(1.0 / E)
    assert hot.value(replica=1) == pytest.approx(1.0)
    ent = bfm.gauge("bluefog_serve_router_entropy", "")
    assert ent.value(replica=0) == pytest.approx(np.log(E))
    assert ent.value(replica=1) == pytest.approx(0.0)
    assert sched._expert_skew(0) == 0
    assert sched._expert_skew(1) == int((1.0 - 1.0 / E) * 8)
    block = sched._flight_block()
    assert block["moe"]["1"]["skew_eighths"] == sched._expert_skew(1)
    sched.close()


# ---------------------------------------------------------------------------
# float64 subprocess oracle: dropless grouped decode == dense mixture
# ---------------------------------------------------------------------------

_F64_ORACLE = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "1"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from bluefog_tpu.moe.layers import moe_ffn_dense, moe_ffn_dropless
from bluefog_tpu.moe.dropless import decode_tile

E, D, F, k = 4, 16, 32, 2
rng = np.random.default_rng(0)
wr = jnp.asarray(rng.normal(size=(D, E)))
w1 = jnp.asarray(rng.normal(size=(E, D, F)) * 0.1)
w2 = jnp.asarray(rng.normal(size=(E, F, D)) * 0.1)
head = jnp.asarray(rng.normal(size=(D, 29)) * 0.1)
embed = jnp.asarray(rng.normal(size=(29, D)))
mesh = Mesh(np.array(jax.devices("cpu")[:1]).reshape(1, 1),
            ("expert", "tp"))   # both FFN paths psum a (size-1) tp axis


def step_fn(kind):
    def f(h):
        if kind == "dense":
            y, _ = moe_ffn_dense(h, wr, w1, w2, top_k=k, axis="expert")
        else:
            y, _ = moe_ffn_dropless(h, wr, w1, w2, num_experts=E,
                                    top_k=k, axis="expert",
                                    tile=decode_tile(h.shape[0] * k, E))
        return y
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                 out_specs=P(), check_rep=False))


def greedy(kind, steps=12):
    # a real autoregressive loop: each step routes the running state
    # through the MoE FFN and emits the argmax token (decode regime:
    # ONE live row per step, the smallest T the tile path ever sees)
    fn = step_fn(kind)
    toks, worst = [3], 0.0
    h = embed[3][None]
    for _ in range(steps):
        y = h + fn(h)
        logits = y @ head
        toks.append(int(jnp.argmax(logits[-1])))
        h = embed[toks[-1]][None] + 0.5 * y[-1:]
    return toks, np.asarray(fn(embed[:8]))


td, yd = greedy("dense")
tg, yg = greedy("dropless")
print(json.dumps({
    "dense": td, "dropless": tg,
    "max_diff": float(np.abs(yd - yg).max()),
    "x64": bool(jnp.zeros(()).dtype == jnp.float64),
}))
"""


@pytest.mark.slow
def test_float64_dropless_vs_dense_mixture_oracle():
    """At float64 the dropless grouped-GEMM decode path is the dense
    (no-drop) top-k mixture: token-identical greedy streams through a
    real decode loop and <= 1e-12 on raw FFN outputs — nothing CAN drop,
    so the only possible divergence is permutation arithmetic."""
    env = {key: v for key, v in os.environ.items()
           if not key.startswith("BLUEFOG_")
           and key not in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_ENABLE_X64")}
    p = subprocess.run([sys.executable, "-c", _F64_ORACLE],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=420, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["x64"], doc
    assert doc["dense"] == doc["dropless"], doc
    assert doc["max_diff"] < 1e-12, doc


# ---------------------------------------------------------------------------
# Launcher surface
# ---------------------------------------------------------------------------

def test_launcher_serve_moe_env():
    from bluefog_tpu.run import launcher
    args = launcher.build_parser().parse_args(
        ["--serve", "--serve-moe", "8x2@2:4", "python", "x.py"])
    env = launcher._child_env(args)
    assert env["BLUEFOG_SERVE_MOE"] == "8x2@2:4"
    args = launcher.build_parser().parse_args(["--serve", "python", "x.py"])
    assert "BLUEFOG_SERVE_MOE" not in launcher._child_env(args)
