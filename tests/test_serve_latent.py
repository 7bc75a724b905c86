"""Serving a latent-attention expert model as one chip's share: the two
attention forms, the latent cache, the group-limited sigmoid router, the
held experts' grouped GEMM, the counters, and what the engine refuses for
this family.  Small sizes, seeded random weights, float32 on the CPU; the
comparison with the plain reference is in tests/perfbench/."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import decoder
from bluefog_tpu.moe import layers as moe_layers
from bluefog_tpu.parallel import compose
from bluefog_tpu.serve import Scheduler, ServeConfig, ServeEngine
from bluefog_tpu.serve import kv_cache as kv
from bluefog_tpu.utils import metrics

CFG = decoder.LatentConfig(
    vocab=128, d_model=48, heads=4, layers=3, q_rank=24, kv_rank=16,
    nope_dim=8, rope_dim=8, v_dim=8, dense_ffn=96, expert_ffn=32,
    num_experts=16, held_experts=4, held_start=4, top_k=4, n_group=2,
    topk_group=1, route_scale=2.5, rope_factor=32.0, rope_orig_len=64,
    rope_mscale_all_dim=1.0)


# the same model with everything the streamed latent programs add: four
# residual streams, two leading dense layers, a router bias, every expert
# of a layer held
STREAMED = dataclasses.replace(
    CFG, layers=4, dense_layers=2, route_bias=True, streams=4, n_group=1,
    topk_group=1, held_experts=16, held_start=0)
FLOAT32 = ("wr", "eb", "h1p", "h1a", "h1b", "h2p", "h2a", "h2b")


def make_params(cfg, seed=0, n=1, dtype=jnp.float32):
    """Seeded leaves at scales that let a fault show: matrices 0.2 normal,
    norm scales and the stream maps' gains 1 + 0.1 normal, the maps'
    biases normal, the router's bias 0.1 normal."""
    key, out = jax.random.key(seed), {}
    for group, leaves in decoder.latent_param_shapes(cfg).items():
        out[group] = {}
        for name, shape in leaves.items():
            key, k = jax.random.split(key)
            z = jax.random.normal(k, shape, jnp.float32)
            z = 1.0 + 0.1 * z if name[0] == "g" or name in ("h1a", "h2a") \
                else z if name in ("h1b", "h2b") \
                else 0.1 * z if name == "eb" else 0.2 * z
            out[group][name] = jnp.broadcast_to(
                z.astype(jnp.float32 if name in FLOAT32 else dtype)[None],
                (n,) + shape)
    return out


def make_engine(cpu_devices, cfg=CFG, seed=0, **scfg):
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    kw = dict(batch_buckets=(4,), prefill_buckets=(8, 16), slots=4,
              max_len=32)
    kw.update(scfg)
    return ServeEngine(m, cfg, make_params(cfg, seed), ServeConfig(**kw))


def full_forward(cfg, params, toks):
    """Logits [T, V] of one whole sequence through the block in its
    unabsorbed form, no cache: what prefill + decode must reproduce."""
    p = jax.tree.map(lambda a: a[0], params)
    pos = jnp.arange(len(toks))
    live = jnp.ones(len(toks), bool)

    def attend_of(lp):
        return lambda qn, qr, lat: (
            decoder.mla_unabsorbed(cfg, lp, qn, qr, lat), None)
    x = decoder.hc_fan_out(cfg, p["shared"]["embed"][jnp.asarray(toks)])
    dense = [p["first"]] + [jax.tree.map(lambda a: a[i], p["dense"])
                            for i in range(cfg.dense_layers - 1)]
    for lp in dense:
        x, _, _ = decoder.latent_block(cfg, lp, x, pos, attend_of(lp),
                                       decoder.dense_gated_ffn)
    for i in range(cfg.expert_layers):
        lp = jax.tree.map(lambda a: a[i], p["blocks"])
        x, _, _ = decoder.latent_block(
            cfg, lp, x, pos, attend_of(lp),
            lambda lp, h: (moe_layers.held_moe_ffn(cfg, lp, h, live)[0],
                           None))
    return np.asarray(decoder.latent_logits(
        cfg, p["shared"], decoder.hc_collapse(cfg, x)))


def test_param_count_matches_the_shapes():
    shapes = decoder.latent_param_shapes(CFG)
    assert decoder.latent_param_count(CFG) == sum(
        int(np.prod(s)) for g in shapes.values() for s in g.values())
    assert shapes["blocks"]["weg"] == (2, 4, 48, 32)
    assert shapes["blocks"]["wr"] == (2, 48, 16)       # the router's full width
    assert "wr" not in shapes["first"] and "wg" in shapes["first"]


def test_yarn_ladder_blends_between_its_two_turn_counts():
    f = np.asarray(decoder.yarn_freqs(CFG))
    plain = 10000.0 ** (-np.arange(4) * 2 / 8)
    assert f[0] == pytest.approx(plain[0])              # fast pair: kept
    assert f[-1] == pytest.approx(plain[-1] / 32)       # slow pair: / factor
    assert np.all(f <= plain * (1 + 1e-6)) and np.all(f >= plain / 32 * (1 - 1e-6))
    off = dataclasses.replace(CFG, rope_factor=1.0)
    np.testing.assert_allclose(decoder.yarn_freqs(off), plain, rtol=1e-6)
    assert off.softmax_scale == pytest.approx(16 ** -0.5)
    m = 0.1 * np.log(32.0) + 1
    assert CFG.softmax_scale == pytest.approx(16 ** -0.5 * m * m)


def test_rms_norm_applies_its_scale():
    x = jax.random.normal(jax.random.key(0), (5, 48))
    g = 1.0 + 0.1 * jax.random.normal(jax.random.key(1), (48,))
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6) * g
    np.testing.assert_allclose(decoder.rms_norm(x, g), want, rtol=1e-5)
    assert not np.allclose(decoder.rms_norm(x, jnp.ones(48)), want, rtol=1e-3)


def test_absorbed_attention_equals_unabsorbed():
    """Decode's form (scores against the cached vector itself) and
    prefill's (keys and values rebuilt per head) are one function."""
    p = jax.tree.map(lambda a: a[0], make_params(CFG)["first"])
    T = 11
    h = jax.random.normal(jax.random.key(3), (T, CFG.d_model))
    qn, qr, lat = decoder.mla_project(CFG, p, h, jnp.arange(T))
    want = decoder.mla_unabsorbed(CFG, p, qn, qr, lat)       # [T, H * v]
    # the last token as a decode step over a cache that holds the others
    cache = {"ckv": jnp.zeros((2, 3, 16, CFG.kv_rank)),
             "kr": jnp.zeros((2, 3, 16, CFG.rope_dim))}
    cache = kv.latent_prefill(cache, 1, 2, jnp.pad(lat[:T - 1],
                                                   ((0, 16 - T + 1), (0, 0))))
    u, _ = kv.latent_attend_slots(
        decoder.mla_absorb_q(CFG, p, qn[-1:]), qr[-1:], cache, 1,
        jnp.array([2]), jnp.array([T - 1]), lat[-1:], CFG.softmax_scale)
    got = decoder.mla_unabsorb_out(CFG, p, u)
    np.testing.assert_allclose(got[0], want[-1], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("against", ["oracle", "staged"])
@pytest.mark.parametrize("layer", ["leading_layer", "scanned_layer"])
def test_latent_attend_slots_reads_in_place(layer, against):
    """The latent decode read (two score parts on one vector a position
    that every head shares, the compressed vectors again as the values,
    the token beside the pages) against the float64 oracle and against
    its own staged form, at the leading layer's static index 0 and at a
    scanned index under ``jit``.  Lanes in arbitrary slot order with the
    trash row among them twice, a length of 0, a lane at ``max_len -
    1``."""
    from attend_oracle import token_beside_pages_oracle
    layers, rows, L, C, R, H = 3, 6, 16, 12, 4, 5
    rng = np.random.default_rng(layers + (against == "staged"))
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    cache = {"ckv": normal(layers, rows, L, C), "kr": normal(layers, rows, L, R)}
    slots = jnp.array([3, 5, 0, 5, 1], jnp.int32)
    lens = jnp.array([L - 1, 0, 0, 2, 7], jnp.int32)
    S, live = slots.shape[0], np.asarray(slots) < rows - 1
    q_abs, q_rope, new = normal(S, H, C), normal(S, H, R), normal(S, C + R)
    scale = 0.31
    if layer == "leading_layer":
        at = 0
        got, met = kv.latent_attend_slots(q_abs, q_rope, cache, 0, slots,
                                          lens, new, scale)
        assert met == rows * L              # every row whole, in place
    else:
        at = 2

        @jax.jit
        def scanned(q_abs, q_rope, cache, slots, lens, new):
            def body(_, i):
                return None, kv.latent_attend_slots(
                    q_abs, q_rope, cache, i, slots, lens, new, scale)[0]
            return jax.lax.scan(body, None, jnp.arange(layers))[1]
        got = scanned(q_abs, q_rope, cache, slots, lens, new)[at]
    if against == "oracle":
        pages = {n: t[at][:, None] for n, t in cache.items()}
        tok = {"ckv": new[:, None, :C], "kr": new[:, None, C:]}
        want = token_beside_pages_oracle(
            [q_abs, q_rope], [pages["ckv"], pages["kr"]], pages["ckv"],
            slots, lens, [tok["ckv"], tok["kr"]], tok["ckv"], scale)
    else:
        want, met = kv.latent_attend_slots(q_abs, q_rope, cache, at, slots,
                                           lens, new, scale, stage=True)
        assert met == S * L                 # the lanes' rows alone
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-6)


def test_latent_cache_lands_tokens_once_per_lane_after_the_layers():
    cc = kv.LatentCacheConfig(layers=3, slots=4, max_len=8, kv_rank=4,
                              rope_dim=2)
    assert (cc.rows, cc.trash_slot) == (5, 4)
    assert cc.bytes_per_token() == 3 * 6 * 4
    assert cc.bytes() == 5 * 8 * cc.bytes_per_token()
    assert cc.shapes() == {"ckv": (3, 5, 8, 4), "kr": (3, 5, 8, 2)}
    cache = {k: jnp.zeros(s) for k, s in cc.shapes().items()}
    new = jnp.arange(3 * 3 * 6, dtype=jnp.float32).reshape(3, 3, 6) + 1
    out = kv.latent_append_tokens(cache, jnp.array([2, 0, 4]),
                                  jnp.array([5, 0, 9]), new)
    out = jnp.concatenate([out["ckv"], out["kr"]], -1)
    np.testing.assert_array_equal(out[:, 2, 5], new[:, 0])
    np.testing.assert_array_equal(out[:, 0, 0], new[:, 1])
    # a position past the end goes to the trash row; nothing else is touched
    assert float(jnp.abs(out[:, :4]).sum()) == float(
        jnp.abs(new[:, :2]).sum())


def numpy_route(s, k, n_group, topk_group):
    T, E = s.shape
    g = s.reshape(T, n_group, E // n_group)
    score = np.sort(g, -1)[..., -2:].sum(-1)
    kept = np.argsort(-score, -1)[:, :topk_group]
    mask = np.zeros((T, n_group), bool)
    np.put_along_axis(mask, kept, True, 1)
    masked = np.where(np.repeat(mask, E // n_group, 1), s, -1.0)
    idx = np.argsort(-masked, -1)[:, :k]
    return idx, np.take_along_axis(s, idx, 1)


@pytest.mark.parametrize("n_group,topk_group,k", [(2, 1, 4), (4, 2, 3),
                                                  (8, 4, 2), (1, 1, 5)])
def test_router_against_numpy_topk_with_groups(n_group, topk_group, k):
    x = jax.random.normal(jax.random.key(5), (40, 48))
    wr = 0.3 * jax.random.normal(jax.random.key(6), (48, 16))
    s, idx, w = moe_layers.router_sigmoid_grouped(
        x, wr, top_k=k, n_group=n_group, topk_group=topk_group,
        route_scale=2.5)
    want_s = 1 / (1 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(wr))))
    np.testing.assert_allclose(s, want_s, rtol=1e-5)
    want_idx, top = numpy_route(np.asarray(s), k, n_group, topk_group)
    assert np.array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(
        2.5 * top / top.sum(-1, keepdims=True), -1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)


def test_router_keeps_float32_scores_for_bfloat16_tokens():
    x = jax.random.normal(jax.random.key(5), (8, 48)).astype(jnp.bfloat16)
    wr = 0.3 * jax.random.normal(jax.random.key(6), (48, 16))
    s, _, w = moe_layers.router_sigmoid_grouped(
        x, wr, top_k=4, n_group=2, topk_group=1, route_scale=2.5)
    assert s.dtype == jnp.float32 and w.dtype == jnp.float32


@pytest.mark.parametrize("dense", [False, True])
@pytest.mark.parametrize("held_start", [0, 4, 12])
def test_held_experts_compute_their_pairs_and_nothing_else(held_start, dense):
    """Both forms: every token through every held expert, and the pairs
    through the grouped kernel over a stack of layers of which the second
    is meant."""
    T, D, F, Eh, k = 24, 48, 32, 4, 4
    ks = jax.random.split(jax.random.key(held_start), 6)
    x = jax.random.normal(ks[0], (T, D))
    wr = 0.3 * jax.random.normal(ks[1], (D, 16))
    stack = [0.2 * jax.random.normal(kk, (2, Eh) + shape) for kk, shape in
             zip(ks[2:5], ((D, F), (D, F), (F, D)))]
    wg, wu, wd = (w[1] for w in stack)
    _, idx, w = moe_layers.router_sigmoid_grouped(
        x, wr, top_k=k, n_group=2, topk_group=1, route_scale=2.5)
    if dense:
        y, pairs = jax.jit(lambda *a: moe_layers.held_expert_ffn(
            *a, held_start=held_start))(x, idx, w, wg, wu, wd)
    else:
        y, pairs = jax.jit(lambda *a: moe_layers.held_expert_ffn_grouped(
            *a, held_start=held_start))(x, idx, w, *stack, jnp.int32(1))
    want, n = np.zeros((T, D)), 0
    for t in range(T):
        for j in range(k):
            e = int(idx[t, j]) - held_start
            if 0 <= e < Eh:
                n += 1
                a = np.asarray(x[t]) @ np.asarray(wg[e])
                want[t] += float(w[t, j]) * (
                    (a / (1 + np.exp(-a))) * (np.asarray(x[t]) @ np.asarray(
                        wu[e]))) @ np.asarray(wd[e])
    assert int(pairs) == n
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)


def test_tokens_that_are_not_live_reach_no_expert():
    p = jax.tree.map(lambda a: a[0, 0], make_params(CFG)["blocks"])
    h = jax.random.normal(jax.random.key(9), (6, CFG.d_model))
    live = jnp.array([True, False, True, True, False, True])
    y, idx, _ = moe_layers.held_moe_ffn(CFG, p, h, live)
    shared = decoder.gated_ffn(h, p["wsg"], p["wsu"], p["wsd"])
    assert np.all(np.asarray(idx)[~np.asarray(live)] == -1)
    np.testing.assert_allclose(y[1], shared[1], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("CFG", [CFG, STREAMED], ids=["one_stream",
                                                        "four_streams"])
def test_prefill_then_decode_through_the_scheduler_match_the_full_forward(
        cpu_devices, CFG):
    eng = make_engine(cpu_devices, CFG)
    eng.warmup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab, n).tolist() for n in (5, 12, 9)]
    sched = Scheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=6) for p in prompts]
    sched.drain()
    sched.close()
    for slot, (prompt, req) in enumerate(zip(prompts, reqs)):
        assert req.state == "done" and len(req.generated) == 6
        want = full_forward(CFG, eng.params, prompt + list(req.generated))
        _, got = eng.prefill(0, slot, prompt)
        np.testing.assert_allclose(got, want[len(prompt) - 1], rtol=1e-3,
                                   atol=2e-5)
        for j, t in enumerate(req.generated):
            row = want[len(prompt) - 1 + j]
            assert row.max() - row[int(t)] <= 1e-4 * np.abs(row).max()
    assert metrics.counter(
        "bluefog_retrace_after_warmup_total").total() == 0


def test_decode_counts_held_work_and_cache_writes(cpu_devices):
    metrics.reset_metrics()
    eng = make_engine(cpu_devices)
    toks = np.array([[3, 5, 0, 0]], np.int32)
    for slot, p in enumerate(([1, 2, 3], [4, 5, 6, 7, 8])):
        eng.prefill(0, slot, p)
    slots = np.array([[0, 1, 4, 4]], np.int32)          # two live, two trash
    lens = np.array([[3, 5, 0, 0]], np.int32)
    eng.decode(toks, slots, lens)
    load = eng.moe_load()[0]
    # every selection of the two live lanes in both expert layers
    assert load["tokens"] == 4 and load["counts"].sum() == 4 * CFG.top_k
    pairs = metrics.counter("bluefog_serve_moe_held_pairs_total").total()
    held = slice(CFG.held_start, CFG.held_start + CFG.held_experts)
    assert pairs == load["counts"][held].sum()
    # four lanes: every lane through every held expert
    assert metrics.counter("bluefog_serve_moe_rows_total").total() == \
        4 * CFG.held_experts * (CFG.layers - 1)
    assert metrics.counter(
        "bluefog_serve_moe_decode_calls_total").total() == 1
    assert metrics.gauge("bluefog_serve_cache_bytes_per_token").value() == \
        CFG.layers * CFG.latent_dim * 4
    mem = eng.program_memory()
    # one write a lane and tensor for all layers; a prompt one a layer
    assert mem["decode S=4"]["cache_writes"] == 4 * 2
    assert mem["prefill Tpad=8"]["cache_writes"] == CFG.layers * 2
    assert {k: v.shape for k, v in eng.cache.items()} == {
        "ckv": (1, CFG.layers, 5, 32, CFG.kv_rank),
        "kr": (1, CFG.layers, 5, 32, CFG.rope_dim)}


class _StagedRead(ServeEngine):
    """The latent engine whose decode attention gathers each lane's row of
    compressed vectors before it attends over it: the form the in-place
    read replaces, kept as the reference."""
    _read_in_place = False


@pytest.mark.parametrize("steps", [1, 2])
def test_in_place_read_engine_serves_the_staged_engines_tokens(cpu_devices,
                                                               steps):
    """Engine against engine, differing only in how decode attention
    meets the latent cache: the same greedy tokens, ``program_memory``
    names the form, and the positions counter advances by layers x rows
    x max_len a fused step in place, by the lanes' rows staged."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab, n).tolist() for n in (5, 12, 9, 3)]
    toks, per_call = {}, {}
    for cls in (ServeEngine, _StagedRead):
        m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
        eng = cls(m, CFG, make_params(CFG), ServeConfig(
            batch_buckets=(4,), prefill_buckets=(8, 16), slots=4,
            max_len=32, decode_steps_per_call=steps))
        eng.warmup()
        form = "in_place" if cls is ServeEngine else "staged"
        assert eng.program_memory()["decode S=4"]["read"] == form
        sched = Scheduler(eng)
        reqs = [sched.submit(p, max_new_tokens=6) for p in prompts]
        sched.drain()
        sched.close()
        toks[form] = [r.generated for r in reqs]
        read = metrics.counter("bluefog_serve_cache_positions_read_total")
        before = read.value(kind="latent")
        eng.decode(np.zeros((1, 4), np.int32), np.full((1, 4), 4, np.int32),
                   np.zeros((1, 4), np.int32))
        per_call[form] = read.value(kind="latent") - before
    assert toks["in_place"] == toks["staged"]
    assert per_call == {"in_place": CFG.layers * steps * 5 * 32,
                        "staged": CFG.layers * steps * 4 * 32}


def test_decode_steps_per_call_fuses_the_same_tokens(cpu_devices):
    one = make_engine(cpu_devices)
    two = make_engine(cpu_devices, decode_steps_per_call=2)
    prompt = [7, 1, 9, 4]
    outs = []
    for eng, calls in ((one, 2), (two, 1)):
        first, _ = eng.prefill(0, 0, prompt)
        toks = np.array([[first, 0, 0, 0]], np.int32)
        slots = np.array([[0, 4, 4, 4]], np.int32)
        lens, got = np.array([[4, 0, 0, 0]], np.int32), []
        for _ in range(calls):
            gen = eng.decode(toks, slots, lens)
            got += [int(t) for t in gen[0, :, 0]]
            toks[0, 0], lens = got[-1], lens + gen.shape[1] * (slots < 4)
        outs.append(got)
    assert outs[0] == outs[1]


@pytest.mark.parametrize("name,carve,scfg", [
    ("latent_serving_carving", (1, 2, 1, 1), {}),
    ("latent_serving_carving", (1, 1, 2, 1), {}),
    ("latent_serving_carving", (1, 1, 1, 2), {}),
    ("latent_serving_decode_kernel", (1, 1, 1, 1),
     {"decode_kernel": "pallas"}),
    ("latent_serving_kv_dtype", (1, 1, 1, 1), {"kv_dtype": "int8"}),
    ("latent_serving_spec_decode", (1, 1, 1, 1), {"spec_decode": 2}),
    ("latent_serving_prefix_pages", (1, 1, 1, 1),
     {"prefix_pages": 2, "prefix_page_tokens": 8}),
])
def test_what_the_latent_programs_do_not_do_is_refused_by_name(
        cpu_devices, name, carve, scfg):
    dp, pp, tp, ep = carve
    m = compose.compose_parallelism(dp, pp, tp, 1, ep, num_experts=16,
                                    devices=cpu_devices[:dp * pp * tp * ep])
    kw = dict(batch_buckets=(4,), prefill_buckets=(8, 16), slots=4,
              max_len=32)
    kw.update(scfg)
    with pytest.raises(ValueError, match=name):
        ServeEngine(m, CFG, make_params(CFG, n=m.size), ServeConfig(**kw))


@pytest.mark.parametrize("bad,match", [
    (dict(n_group=3), "latent_router_groups"),
    (dict(top_k=9), "latent_router_groups"),
    (dict(held_start=14), "latent_held_experts"),
    (dict(layers=1), "leading dense layer"),
    (dict(dense_layers=3), "latent_dense_layers"),
    (dict(dense_layers=0), "latent_dense_layers"),
    (dict(streams=0), "latent_streams"),
    (dict(streams=4, sinkhorn_iters=0), "latent_streams"),
    (dict(streams=4, hc_eps=0.0), "latent_streams"),
])
def test_latent_config_refuses_what_it_cannot_mean(cpu_devices, bad, match):
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **bad).validate(m)


def test_unabsorbed_attention_in_chunks_of_heads_is_the_same(monkeypatch):
    """Past SCORE_BYTES the heads go through a chunk at a time."""
    p = jax.tree.map(lambda a: a[0], make_params(CFG)["first"])
    h = jax.random.normal(jax.random.key(4), (12, CFG.d_model))
    qn, qr, lat = decoder.mla_project(CFG, p, h, jnp.arange(12))
    whole = decoder.mla_unabsorbed(CFG, p, qn, qr, lat)
    for limit in (2 * 12 * 12 * 4, 1):               # two heads; one head
        monkeypatch.setattr(decoder, "SCORE_BYTES", limit)
        np.testing.assert_allclose(decoder.mla_unabsorbed(CFG, p, qn, qr, lat),
                                   whole, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# residual streams, several leading dense layers, a router bias
# ---------------------------------------------------------------------------

def test_streamed_param_shapes_count_the_maps_the_dense_layers_and_the_bias():
    shapes = decoder.latent_param_shapes(STREAMED)
    assert list(shapes) == ["first", "dense", "blocks", "shared"]
    n, D = 4, STREAMED.d_model
    for half in "12":
        assert shapes["first"][f"h{half}p"] == (n, D, n * n + 2 * n)
        assert shapes["dense"][f"h{half}a"] == (1, 3)
        assert shapes["blocks"][f"h{half}b"] == (2, n * n + 2 * n)
    assert shapes["dense"]["wg"] == (1, D, STREAMED.dense_ffn)
    assert shapes["blocks"]["eb"] == (2, 16) and "eb" not in shapes["first"]
    assert STREAMED.expert_layers == 2 and STREAMED.streams == 4
    maps = 2 * (n * D * 24 + 3 + 24)
    plain = dataclasses.replace(STREAMED, streams=1, route_bias=False)
    assert decoder.latent_param_count(STREAMED) == \
        decoder.latent_param_count(plain) + 4 * maps + 2 * 16
    # one stream, one dense layer, no bias: the tree a.x-k1's programs take
    assert list(decoder.latent_param_shapes(CFG)) == ["first", "blocks",
                                                      "shared"]
    assert CFG.streams == 1


def stream_maps(seed=0, n=4, D=48, alpha=(1.0, 1.0, 1.0)):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (0.2 * jax.random.normal(ks[0], (n, D, n * n + 2 * n)),
            jnp.asarray(alpha, jnp.float32),
            jax.random.normal(ks[1], (n * n + 2 * n,)),
            jax.random.normal(ks[2], (n, 9, D)))


def test_the_remix_is_doubly_stochastic_and_the_maps_are_the_equations():
    phi, alpha, b, xs = stream_maps()
    pre, post, res = decoder.hc_coefficients(STREAMED, phi, alpha, b, xs)
    assert pre.shape == post.shape == (4, 9) and res.shape == (4, 4, 9)
    assert pre.dtype == post.dtype == res.dtype == jnp.float32
    # twenty rounds end on the rows: those sum to one; the columns do at
    # the median token, and within a few percent where a token's logits
    # spread widest (20,000 draws at these scales: 4e-5 at the median,
    # 0.044 at the worst)
    np.testing.assert_allclose(np.asarray(res).sum(1), 1.0, atol=1e-5)
    cols = np.abs(np.asarray(res).sum(0) - 1.0).max(0)          # [tokens]
    assert np.median(cols) < 1e-3 and cols.max() < 0.1, cols
    assert np.all(np.asarray(res) > 0)
    # the equations in numpy, token by token
    X = np.asarray(xs, np.float64)
    for t in range(9):
        v = X[:, t].reshape(-1)
        c = (v / np.sqrt(np.mean(v * v) + STREAMED.eps)) @ np.asarray(
            phi, np.float64).reshape(-1, 24)
        sig = lambda z: 1 / (1 + np.exp(-z))
        np.testing.assert_allclose(pre[:, t], sig(c[:4] + b[:4]), rtol=1e-4)
        np.testing.assert_allclose(post[:, t], 2 * sig(c[4:8] + b[4:8]),
                                   rtol=1e-4)
        m = np.exp(np.clip(c[8:] + np.asarray(b[8:]), -30, 30)).reshape(4, 4)
        for _ in range(20):
            m = m / (m.sum(0, keepdims=True) + 1e-6)
            m = m / (m.sum(1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(res[:, :, t], m, rtol=1e-3, atol=1e-6)
    # one round is another matrix, and the gains are not decoration
    one = decoder.hc_coefficients(
        dataclasses.replace(STREAMED, sinkhorn_iters=1), phi, alpha, b, xs)[2]
    assert float(jnp.max(jnp.abs(one - res))) > 1e-2
    flat = decoder.hc_coefficients(STREAMED, phi, jnp.zeros(3), b, xs)
    assert float(jnp.max(jnp.abs(flat[0] - pre))) > 1e-2


def test_the_remix_stays_finite_at_the_clip(scale=1e4):
    """Inputs of 1e4 change nothing (the flattened streams are normed);
    gains of 1e4 drive every logit to the clip at +-30, where the rounds
    still end on rows that sum to one."""
    phi, _, b, xs = stream_maps(1)
    calm = decoder.hc_coefficients(STREAMED, phi, jnp.ones(3), b, xs)
    loud = decoder.hc_coefficients(STREAMED, phi, jnp.ones(3), b, scale * xs)
    for a, c in zip(loud, calm):
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-6)
    pre, post, res = decoder.hc_coefficients(
        STREAMED, phi, jnp.full((3,), 1e4), b, xs)
    for a in (pre, post, res):
        assert np.all(np.isfinite(np.asarray(a)))
    np.testing.assert_allclose(np.asarray(res).sum(1), 1.0, atol=1e-5)


def test_the_mix_reads_a_combination_and_writes_the_remix():
    phi, alpha, b, xs = stream_maps(2)
    pre, post, res = decoder.hc_coefficients(STREAMED, phi, alpha, b, xs)
    y = jax.random.normal(jax.random.key(7), (9, 48))
    h = decoder.hc_mix(pre[None], xs)[0]
    out = decoder.hc_mix(res, xs, post, y)
    assert h.shape == (9, 48) and out.shape == xs.shape
    np.testing.assert_allclose(
        h, np.einsum("it,itd->td", pre, xs), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        out, np.einsum("ijt,jtd->itd", res, xs)
        + np.asarray(post)[:, :, None] * np.asarray(y)[None],
        rtol=1e-5, atol=1e-6)
    # the streams stay in their own dtype
    assert decoder.hc_mix(res, xs.astype(jnp.bfloat16), post,
                          y).dtype == jnp.bfloat16
    fan = decoder.hc_fan_out(STREAMED, y)
    assert fan.shape == (4, 9, 48)
    np.testing.assert_allclose(decoder.hc_collapse(STREAMED, fan), 4 * y,
                               rtol=1e-6)
    assert decoder.hc_fan_out(CFG, y) is y and decoder.hc_collapse(CFG, y) is y


def test_the_router_bias_moves_selections_and_never_weights():
    x = jax.random.normal(jax.random.key(5), (200, 48))
    wr = 0.3 * jax.random.normal(jax.random.key(6), (48, 16))
    bias = 0.1 * jax.random.normal(jax.random.key(7), (16,))
    kw = dict(top_k=4, n_group=1, topk_group=1, route_scale=2.0)
    s0, idx0, w0 = moe_layers.router_sigmoid_grouped(x, wr, **kw)
    s, idx, w = moe_layers.router_sigmoid_grouped(x, wr, bias=bias, **kw)
    np.testing.assert_array_equal(s, s0)            # the scores are raw
    by = np.asarray(s) + np.asarray(bias)
    assert np.array_equal(np.sort(idx, -1),
                          np.sort(np.argsort(-by, -1)[:, :4], -1))
    same = np.all(np.sort(idx, -1) == np.sort(idx0, -1), -1)
    assert 0.1 < 1 - same.mean() < 0.9              # a visible share moves
    # weights: the RAW scores of the taken, normalised, times the scale
    raw = np.take_along_axis(np.asarray(s), np.asarray(idx), 1)
    np.testing.assert_allclose(w, 2.0 * raw / raw.sum(-1, keepdims=True),
                               rtol=1e-6)
    # where the bias moved nothing the weights are the unbiased router's
    order, order0 = np.argsort(idx, -1), np.argsort(idx0, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, 1)[same],
        np.take_along_axis(np.asarray(w0), order0, 1)[same], rtol=1e-6)
    # a zero bias is no bias
    _, idxz, wz = moe_layers.router_sigmoid_grouped(
        x, wr, bias=jnp.zeros(16), **kw)
    np.testing.assert_array_equal(idxz, idx0)
    np.testing.assert_allclose(wz, w0, rtol=1e-6)


def test_the_streamed_engine_reports_its_streams(cpu_devices):
    metrics.reset_metrics()
    eng = make_engine(cpu_devices, STREAMED)
    assert metrics.gauge("bluefog_serve_residual_streams").value() == 4
    eng.prefill(0, 0, [1, 2, 3])
    mem = eng.program_memory()
    # every layer's vectors land: two dense layers, two expert layers
    assert mem["prefill Tpad=8"]["cache_writes"] == 4 * 2
    assert eng.cache["ckv"].shape == (1, 4, 5, 32, STREAMED.kv_rank)
    plain = make_engine(cpu_devices)
    assert metrics.gauge("bluefog_serve_residual_streams").value() == 1


def test_a_streamed_model_is_refused_what_the_latent_programs_refuse(
        cpu_devices):
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    kw = dict(batch_buckets=(4,), prefill_buckets=(8,), slots=4, max_len=32)
    for knob, name in ((dict(spec_decode=2), "latent_serving_spec_decode"),
                       (dict(kv_dtype="int8"), "latent_serving_kv_dtype"),
                       (dict(prefix_pages=1, prefix_page_tokens=4),
                        "latent_serving_prefix_pages")):
        with pytest.raises(ValueError, match=name):
            ServeEngine(m, STREAMED, make_params(STREAMED),
                        ServeConfig(**kw, **knob))
