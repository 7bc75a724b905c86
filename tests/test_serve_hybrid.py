"""Serving a model with two kinds of attention layer as one chip's share:
the hybrid block (grouped-query heads, QK-norm, the norm on each sublayer's
output, rotary on the window layers only), the blocked whole-sequence
attentions, the two-kind cache (rows beside rings), decode read in place,
the counters, and what the engine refuses for this family.  Small sizes,
seeded random weights, float32 on the CPU; the comparison with the plain
reference is in tests/perfbench/."""
import dataclasses
import hashlib

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

W = 4
PLAN = (("window", "dense"), ("window", "experts"), ("window", "experts"),
        ("full", "experts"), ("window", "experts"))
CFG = decoder.HybridConfig(
    vocab=128, d_model=48, heads=4, kv_heads=2, head_dim=8, window=W,
    plan=PLAN, dense_ffn=96, expert_ffn=32, num_experts=16, held_experts=4,
    held_start=4, top_k=4, route_scale=2.5)


def make_params(cfg, seed=0, n=1, dtype=jnp.float32):
    key = jax.random.key(seed)

    def group(leaves):
        nonlocal key
        out = {}
        for name, shape in leaves.items():
            key, k = jax.random.split(key)
            z = jax.random.normal(k, shape, jnp.float32)
            z = 1.0 + 0.1 * z if name.startswith("g") else 0.2 * z
            out[name] = jnp.broadcast_to(
                z.astype(jnp.float32 if name == "wr" else dtype)[None],
                (n,) + shape)
        return out
    shapes = decoder.hybrid_param_shapes(cfg)
    return {"layers": tuple(group(l) for l in shapes["layers"]),
            "shared": group(shapes["shared"])}


def make_engine(cpu_devices, cfg=CFG, seed=0, **scfg):
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    kw = dict(batch_buckets=(4,), prefill_buckets=(8, 16), slots=4,
              max_len=40)
    kw.update(scfg)
    return ServeEngine(m, cfg, make_params(cfg, seed), ServeConfig(**kw))


def dense_attention(q, k, v, window=0):
    """[T, H, Dh] x [T, Hkv, Dh]: K and V REPEATED per group, one [T, T]
    mask: the oracle of the blocked and grouped forms."""
    T, H, Dh = q.shape
    k, v = (jnp.repeat(a, H // k.shape[1], axis=1) for a in (k, v))
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = s <= t
    if window:
        keep = keep & (t - s < window)
    sc = jnp.einsum("thd,shd->hts", q, k) * Dh ** -0.5
    p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), -1)
    return jnp.einsum("hts,shd->thd", p, v)


def full_forward(cfg, params, toks):
    """Logits [T, V] of one whole sequence through the block with dense
    masked attention, no cache: what prefill + decode must reproduce."""
    p = jax.tree.map(lambda a: a[0], params)
    pos = jnp.arange(len(toks))
    live = jnp.ones(len(toks), bool)
    x = p["shared"]["embed"][jnp.asarray(toks)]
    for lp, (kind, ffn_kind) in zip(p["layers"], cfg.plan):
        win = cfg.window if kind == "window" else 0
        ffn = decoder.dense_gated_ffn if ffn_kind == "dense" else (
            lambda lp, h: (moe_layers.held_moe_ffn(cfg, lp, h, live)[0], None))
        x, _, _ = decoder.hybrid_block(
            cfg, lp, x, pos, kind,
            lambda q, k, v: (dense_attention(q, k, v, win), None), ffn)
    return np.asarray(decoder.latent_logits(cfg, p["shared"], x))


def qkv(T, H=4, Hkv=2, Dh=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (T, H, Dh)),
            jax.random.normal(ks[1], (T, Hkv, Dh)),
            jax.random.normal(ks[2], (T, Hkv, Dh)))


def test_param_count_matches_the_shapes_and_the_plan():
    shapes = decoder.hybrid_param_shapes(CFG)
    assert decoder.hybrid_param_count(CFG) == sum(
        int(np.prod(s)) for g in shapes["layers"] + (shapes["shared"],)
        for s in g.values())
    first, second = shapes["layers"][:2]
    assert "wg" in first and "wr" not in first
    assert second["wr"] == (48, 16)                # the router's full width
    assert second["weg"] == (4, 48, 32)            # the held experts alone
    assert first["wq"] == (48, 32) and first["wk"] == (48, 16)   # 4 on 2 heads
    assert first["gq"] == first["gk"] == (8,)      # one scale for all heads
    assert (CFG.layers, CFG.expert_layers) == (5, 4)
    assert (CFG.layers_of("window"), CFG.layers_of("full")) == (4, 1)
    assert [CFG.index_in_kind(i) for i in range(5)] == [0, 1, 2, 0, 3]


def test_the_published_sizes_count_what_the_issue_counted():
    plan = tuple((k, "dense" if i == 0 else "experts") for i, k in enumerate(
        ("window", "window", "window", "full") * 2))
    big = decoder.HybridConfig(
        vocab=19200, d_model=6144, heads=64, kv_heads=8, head_dim=128,
        window=128, plan=plan, dense_ffn=18432, expert_ffn=2048,
        num_experts=128, held_experts=8, top_k=8, route_scale=2.5)
    assert decoder.hybrid_param_count(big) == 3_865_419_776     # 7.73 GB bf16
    cc = kv.HybridCacheConfig(full_layers=2, window_layers=6, slots=48,
                              max_len=8704, window=128, kv_heads=8,
                              head_dim=128, dtype=jnp.bfloat16)
    assert cc.shapes()["k"] == (2, 49, 8, 8704, 128)
    assert cc.shapes()["kw"] == (6, 49, 8, 128, 128)   # 128 positions a slot
    assert cc.bytes_per_token() == 2 * 4096
    assert cc.bytes_per_slot() == {"full": 2 * 8704 * 4096,
                                   "window": 6 * 128 * 4096}
    assert cc.bytes() == 49 * (2 * 8704 + 6 * 128) * 4096 == 3_647_995_904
    assert cc.bytes() == sum(int(np.prod(s)) * 2 for s in cc.shapes().values())
    # as eight full layers it would not fit beside the weights
    assert 49 * 8 * 8704 * 4096 > 13.9e9


@pytest.mark.parametrize("bad, name", [
    (dict(kv_heads=3), "hybrid_grouped_heads"),
    (dict(plan=(("ring", "dense"),)), "hybrid_layer_plan"),
    (dict(plan=()), "hybrid_layer_plan"),
    (dict(held_start=14), "hybrid_held_experts"),
    (dict(top_k=17), "hybrid_router_groups")])
def test_config_refusals_are_named(bad, name):
    with pytest.raises(ValueError, match=name):
        dataclasses.replace(CFG, **bad).validate(None)


@pytest.mark.parametrize("T", [8, 13, 32])
def test_a_prompts_attentions_equal_the_dense_masks(T):
    """The band of blocks (window layers) and the flash forward kernel as
    the engine calls it (full layers; interpreted on the CPU) against one
    [T, T] mask over K and V repeated per group."""
    q, k, v = qkv(T, seed=T)
    np.testing.assert_allclose(ServeEngine._flash_causal(q, k, v),
                               dense_attention(q, k, v), atol=2e-6)
    np.testing.assert_allclose(decoder.window_attention(q, k, v, W),
                               dense_attention(q, k, v, W), atol=2e-6)
    # a window as long as the sequence is the causal form
    np.testing.assert_allclose(decoder.window_attention(q, k, v, 32),
                               dense_attention(q, k, v), atol=2e-6)


def test_the_band_is_chunked_when_its_scores_outgrow_their_room(monkeypatch):
    q, k, v = qkv(32, seed=5)
    whole = decoder.window_attention(q, k, v, W)
    monkeypatch.setattr(decoder, "SCORE_BYTES", 4 * W * 2 * W * 4 * 2)
    np.testing.assert_allclose(decoder.window_attention(q, k, v, W), whole,
                               atol=2e-6)


def filled_cache(T, ring, seed=0, rows=3, Hkv=2, Dh=8):
    """One layer's pages [rows, Hkv, L, Dh] holding T positions of row 1:
    a full row of 16, or a ring of W as prefill would leave it."""
    _, k, v = qkv(T, seed=seed)
    cc = kv.HybridCacheConfig(full_layers=1, window_layers=1, slots=rows - 1,
                              max_len=16, window=W, kv_heads=Hkv, head_dim=Dh)
    cache = {n: jnp.zeros(s) for n, s in cc.shapes().items()}
    kind = "window" if ring else "full"
    pad = (-T) % 8
    kp, vp = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)), constant_values=9.0)
              for a in (k, v))
    cache = kv.hybrid_prefill(cache, kind, 0, jnp.int32(1), kp, vp,
                              jnp.int32(T))
    names = kv.KIND_TENSORS[kind]
    return cache[names[0]][0], cache[names[1]][0], k, v


@pytest.mark.parametrize("T", [2, 4, 5, 11])
def test_a_prompt_lands_in_the_ring_at_p_mod_window(T):
    kt, _, k, _ = filled_cache(T, ring=True)
    for p in range(max(0, T - W), T):
        np.testing.assert_array_equal(kt[1, :, p % W], k[p])
    assert float(jnp.abs(kt[0]).max()) == 0            # other rows untouched


@pytest.mark.parametrize("T", [1, 3, 4, 7, 12])
def test_ring_cache_equals_a_full_cache_under_a_band_mask(T):
    """Decode attention of the token at position T over a ring of W equals
    the dense band over every position: the ring holds exactly what the
    band lets the query see, in any order."""
    q, k_all, v_all = qkv(T + 1, seed=T)
    kt, vt, _, _ = filled_cache(T, ring=True, seed=T)
    new = {"k": k_all[T][None], "v": v_all[T][None]}
    got, met = kv.attend_slots(q[T][None], kt, vt, jnp.array([1]),
                               jnp.array([T]), new, ring=True)
    assert met == 3 * W                     # every row's ring, in place
    got = got[0]
    want = dense_attention(q, k_all, v_all, W)[T]
    np.testing.assert_allclose(got, want, atol=2e-6)
    # and over the full layer's row: every earlier position
    kf, vf, _, _ = filled_cache(T, ring=False, seed=T)
    got, met = kv.attend_slots(q[T][None], kf, vf, jnp.array([1]),
                               jnp.array([T]), new)
    assert met == 3 * 16
    np.testing.assert_allclose(got[0], dense_attention(q, k_all, v_all)[T],
                               atol=2e-6)


@pytest.mark.parametrize("rows", [6, 16])
@pytest.mark.parametrize("kind", ["window", "full"])
def test_lanes_in_any_slot_order_meet_their_own_row(kind, rows):
    """``attend_slots`` over several lanes at once, laid out by row: slots
    in arbitrary order with the trash row (the last) among them twice, a
    length of 0, a lane at the last position, a ring that has wrapped;
    every live lane against the dense band (or causal) oracle over its
    own whole sequence.  With 16 rows the five lanes are under a third
    of them and their rows are staged."""
    L = W if kind == "window" else 16
    cc = kv.HybridCacheConfig(full_layers=1, window_layers=1, slots=rows - 1,
                              max_len=16, window=W, kv_heads=2, head_dim=8)
    cache = {n: jnp.zeros(s) for n, s in cc.shapes().items()}
    slots, lens = [3, rows - 1, 0, rows - 1, 1], [15, 0, 0, 2, 7]
    seqs = [qkv(n + 1, seed=10 + i) for i, n in enumerate(lens)]
    for slot, n, (_, k, v) in zip(slots, lens, seqs):
        if n:
            pad = ((0, 16 - n), (0, 0), (0, 0))
            cache = kv.hybrid_prefill(
                cache, kind, 0, jnp.int32(slot), jnp.pad(k[:n], pad),
                jnp.pad(v[:n], pad), jnp.int32(n))
    kn, vn = kv.KIND_TENSORS[kind]
    new = {"k": jnp.stack([k[n] for n, (_, k, _) in zip(lens, seqs)]),
           "v": jnp.stack([v[n] for n, (_, _, v) in zip(lens, seqs)])}
    got, met = kv.attend_slots(
        jnp.stack([q[n] for n, (q, _, _) in zip(lens, seqs)]),
        cache[kn][0], cache[vn][0], jnp.array(slots), jnp.array(lens), new,
        ring=kind == "window")
    assert met == (rows if rows == 6 else len(slots)) * L
    for i in (0, 2, 4):                     # the live lanes
        want = dense_attention(*seqs[i], W if kind == "window" else 0)[-1]
        np.testing.assert_allclose(got[i], want, atol=2e-6)


def test_grouped_heads_equal_repeated_keys_and_values():
    """Four query heads on two compact kv heads give what four heads on K
    and V repeated in memory give, in the cache's in-place decode form and
    against attend_rows' staged form."""
    T = 9
    q, k_all, v_all = qkv(T + 1, seed=2)
    kf, vf, _, _ = filled_cache(T, ring=False, seed=2)
    slots, lens = jnp.array([1, 2]), jnp.array([T, 0])
    qs = jnp.stack([q[T], q[0]])
    new = {"k": jnp.stack([k_all[T], k_all[0]]),
           "v": jnp.stack([v_all[T], v_all[0]])}
    grouped, met = kv.attend_slots(qs, kf, vf, slots, lens, new)
    assert met == 3 * 16            # every row, in place
    rep = lambda a: jnp.repeat(a, 2, axis=-3 if a.ndim == 4 else -2)
    repeated, _ = kv.attend_slots(qs, rep(kf), rep(vf), slots, lens,
                                  {n: rep(a) for n, a in new.items()})
    np.testing.assert_allclose(grouped, repeated, atol=1e-6)
    # lanes under a third of the rows: theirs alone are staged and met
    k7, v7, _, _ = filled_cache(T, ring=False, seed=2, rows=7)
    few, met = kv.attend_slots(qs, k7, v7, slots, lens, new)
    assert met == 2 * 16
    np.testing.assert_allclose(few, grouped, atol=1e-6)
    staged = kv.attend_rows(qs, kf, vf, slots, lens, new=new)
    np.testing.assert_allclose(grouped, staged, atol=2e-6)
    # the lane of length 0 sees its own token alone
    np.testing.assert_allclose(grouped[1], jnp.repeat(v_all[0], 2, axis=0),
                               atol=1e-6)


def test_tokens_land_once_per_lane_in_both_kinds():
    cc = kv.HybridCacheConfig(full_layers=1, window_layers=2, slots=3,
                              max_len=16, window=W, kv_heads=2, head_dim=8)
    cache = {n: jnp.zeros(s) for n, s in cc.shapes().items()}
    slots, lens = jnp.array([0, 2, cc.trash_slot]), jnp.array([6, 3, 0])
    new = {n: jnp.full((s[0], 3) + s[2:3] + s[4:], 1.0 + i)
           for i, (n, s) in enumerate(cc.shapes().items())}
    out = kv.hybrid_append_tokens(cache, slots, lens, new)
    assert float(out["k"][0, 0, 0, 6, 0]) == 1.0       # a row: at length
    assert float(out["kw"][1, 0, 0, 6 % W, 0]) == 3.0  # a ring: length mod W
    assert float(out["vw"][0, 2, 1, 3, 0]) == 4.0
    assert float(jnp.abs(out["k"][0, 1]).max()) == 0   # a row nobody named
    assert int((out["kw"] != 0).sum()) == 3 * 2 * 2 * 8   # lanes x layers x kv


def test_hybrid_block_order_norm_on_the_output_and_rope_by_kind():
    lp = jax.tree.map(lambda a: a[0], make_params(CFG)["layers"][1])
    x = jax.random.normal(jax.random.key(1), (6, 48))
    pos = jnp.arange(6) + 3
    seen = {}

    def attend(q, k, v):
        seen["q"], seen["k"] = q, k
        return jnp.ones_like(q), None
    for kind in ("window", "full"):
        y, _, _ = decoder.hybrid_block(CFG, lp, x, pos, kind, attend,
                                       lambda lp, h: (jnp.zeros_like(h), 0))
        q0 = decoder.rms_norm((x @ lp["wq"]).reshape(6, 4, 8), lp["gq"],
                              CFG.eps)
        if kind == "window":
            q0 = decoder.rope(q0, pos, CFG.rope_base)
        np.testing.assert_allclose(seen["q"], q0, atol=1e-6)
        assert seen["k"].shape == (6, 2, 8)
        # the sublayer's OUTPUT is normed, then added to the stream itself
        att = decoder.rms_norm(jnp.ones((6, 32)) @ lp["wo"], lp["g1"],
                               CFG.eps)
        np.testing.assert_allclose(y, x + att, atol=1e-5)


def test_engine_through_the_scheduler_matches_the_whole_sequence(cpu_devices):
    """Prompts longer than the window and more decode steps than the
    window: every ring wraps and its seam is crossed, and prefill then
    decode reproduce the whole-sequence forward (dense masks, no cache)."""
    metrics.mark_steady_state(False)
    eng = make_engine(cpu_devices)
    eng.warmup()
    retraces = metrics.counter("bluefog_retrace_after_warmup_total").total()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, n).tolist() for n in (3, 9, 14, 6)]
    sched = Scheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=3 * W) for p in prompts]
    sched.drain()
    sched.close()
    for p, r in zip(prompts, reqs):
        assert r.state == "done" and len(r.generated) == 3 * W
        logits = full_forward(CFG, eng.params, p + r.generated)
        for j, t in enumerate(r.generated):
            row = logits[len(p) - 1 + j]
            assert row.max() - row[t] < 1e-4, (len(p), j)
    first, last = eng.prefill(0, 0, prompts[2])
    np.testing.assert_allclose(
        np.asarray(last), full_forward(CFG, eng.params, prompts[2])[-1],
        atol=1e-4)
    # 0 retraces over the warmed run
    assert metrics.counter(
        "bluefog_retrace_after_warmup_total").total() == retraces
    metrics.mark_steady_state(False)


def test_cache_shapes_writes_and_gauges(cpu_devices):
    eng = make_engine(cpu_devices)
    cc = eng.cache_cfg
    assert isinstance(cc, kv.HybridCacheConfig)
    assert {n: a.shape[1:] for n, a in eng.cache.items()} == {
        "k": (1, 5, 2, 40, 8), "v": (1, 5, 2, 40, 8),
        "kw": (4, 5, 2, W, 8), "vw": (4, 5, 2, W, 8)}
    assert sum(a.nbytes for a in eng.cache.values()) == cc.bytes()
    # decode: one write per lane and tensor after the layers; a prompt: the
    # K and V of each layer's own kind
    assert eng._cache_writes("decode", 4) == 4 * 4
    assert eng._cache_writes("prefill", 1) == 2 * 5
    for kind, size in cc.bytes_per_slot().items():
        assert metrics.gauge("bluefog_serve_cache_bytes_per_slot").value(
            kind=kind) == size
    assert metrics.gauge("bluefog_serve_cache_bytes_per_token").value() \
        == cc.bytes_per_token() == 1 * 2 * 2 * 8 * 4
    eng.warmup()
    mem = eng.program_memory()
    assert mem["decode S=4"]["cache_writes"] == 16
    assert mem["decode S=4"]["read"] == "in_place"
    assert mem["prefill Tpad=8"]["alias_bytes"] >= cc.bytes()
    metrics.mark_steady_state(False)


def test_held_work_mark_counts_positions_by_kind(cpu_devices, monkeypatch):
    eng = make_engine(cpu_devices)
    seen = []
    real = eng._stage

    def stage(name, **attrs):
        if name == "held_work":
            seen.append(attrs)
        return real(name, **attrs)
    monkeypatch.setattr(eng, "_stage", stage)
    read = metrics.counter("bluefog_serve_cache_positions_read_total")
    before = {k: read.value(kind=k) for k in ("full", "window")}
    for slot, n in ((0, 9), (1, 2)):
        eng.prefill(0, slot, list(range(1, n + 1)))
    trash = eng.cache_cfg.trash_slot
    eng.decode(np.array([[5, 6, 0, 0]]), np.array([[0, 1, trash, trash]]),
               np.array([[9, 2, 0, 0]]))
    mark, = seen
    assert mark["positions"] == 10 + 3
    assert mark["positions_window"] == W + 3        # each lane at most W
    # counted by the program beside its einsum: the lanes fill the slots,
    # so every row of the cache, whole, and every ring
    assert mark["positions_read_full"] == 5 * 40
    assert mark["positions_read_window"] == 5 * W
    assert mark["rows"] == 4 * 4 * 4                # lanes x held x layers
    assert 0 <= mark["pairs"] <= 2 * 4 * 4
    assert read.value(kind="full") - before["full"] == 5 * 40 * 1
    assert read.value(kind="window") - before["window"] == 5 * W * 4


def test_decode_hands_out_its_logits_and_nobody_pays_for_them(cpu_devices):
    """What the decode program chose its tokens from stays on the device:
    a call still reads back two arrays (tokens, carrier), converting the
    logits is a third, and they are the whole-sequence forward's at the
    lanes' positions (a ring wrapped, a row read)."""
    eng = make_engine(cpu_devices)
    assert eng.decode_logits(0) is None
    crossed = metrics.counter("bluefog_serve_host_arrays_total")
    out = lambda: crossed.value(program="decode", direction="out")
    prompts = {0: list(range(1, 10)), 2: [7, 8]}
    first = {slot: eng.prefill(0, slot, p)[0] for slot, p in prompts.items()}
    trash, before = eng.cache_cfg.trash_slot, out()
    eng.decode(np.array([[first[0], 0, first[2], 0]]),
               np.array([[0, trash, 2, trash]]), np.array([[9, 0, 2, 0]]))
    assert out() - before == 2
    slots, rows = eng.decode_logits(0)
    assert list(slots) == [0, trash, 2, trash]
    assert rows.shape == (1, 4, CFG.vocab) and out() - before == 2
    got = np.asarray(rows)
    assert out() - before == 3
    for lane, slot in ((0, 0), (2, 2)):
        seq = prompts[slot] + [first[slot]]
        np.testing.assert_allclose(
            got[0, lane], full_forward(CFG, eng.params, seq)[-1], atol=1e-4)


def test_a_bucket_under_a_third_of_the_rows_stages_its_lanes(cpu_devices):
    """One lane of four slots: the decode program meets that lane's row
    and ring alone, and reads what the full bucket reads for it."""
    eng = make_engine(cpu_devices, batch_buckets=(1, 4))
    seen = []
    real = eng._stage

    def stage(name, **attrs):
        if name == "held_work":
            seen.append(attrs)
        return real(name, **attrs)
    eng._stage = stage
    prompt = list(range(1, 12))
    first, _ = eng.prefill(0, 3, prompt)
    trash = eng.cache_cfg.trash_slot
    one = eng.decode(np.array([[first]]), np.array([[3]]), np.array([[11]]))
    rows_one = np.asarray(eng.decode_logits(0)[1])[0, 0]
    eng.prefill(0, 3, prompt)
    four = eng.decode(np.array([[first, 0, 0, 0]]),
                      np.array([[3] + [trash] * 3]),
                      np.array([[11, 0, 0, 0]]))
    rows_four = np.asarray(eng.decode_logits(0)[1])[0, 0]
    assert one[0, 0, 0] == four[0, 0, 0]
    np.testing.assert_allclose(rows_one, rows_four, atol=1e-5)
    assert [m["positions_read_full"] for m in seen] == [1 * 40, 5 * 40]
    assert [m["positions_read_window"] for m in seen] == [1 * W, 5 * W]


@pytest.mark.parametrize("kw, name", [
    (dict(decode_kernel="pallas"), "hybrid_serving_decode_kernel"),
    (dict(kv_dtype="int8"), "hybrid_serving_kv_dtype"),
    (dict(spec_decode=2), "hybrid_serving_spec_decode"),
    (dict(prefix_pages=2, prefix_page_tokens=8),
     "hybrid_serving_prefix_pages")])
def test_fast_paths_are_refused_by_name(cpu_devices, kw, name):
    with pytest.raises(ValueError, match=name):
        make_engine(cpu_devices, **kw)


@pytest.mark.parametrize("pp, tp, ep", [(2, 1, 1), (1, 2, 1), (1, 1, 2)])
def test_carvings_are_refused_by_name(cpu_devices, pp, tp, ep):
    m = compose.compose_parallelism(1, pp, tp, 1, ep, num_experts=16,
                                    devices=cpu_devices[:pp * tp * ep])
    with pytest.raises(ValueError, match="hybrid_serving_carving"):
        ServeEngine(m, CFG, make_params(CFG, n=m.size), ServeConfig(
            batch_buckets=(4,), prefill_buckets=(8,), slots=4, max_len=40))


def test_one_block_definition_serves_prefill_decode_and_the_check(
        cpu_devices, monkeypatch):
    """Every serving program of the family goes through
    decoder.hybrid_block: an edit there reaches prefill, decode and the
    whole-sequence check alike."""
    calls = []
    real = decoder.hybrid_block

    def spy(cfg, lp, x, positions, kind, attend, ffn):
        calls.append((kind, x.shape))
        return real(cfg, lp, x, positions, kind, attend, ffn)
    monkeypatch.setattr(decoder, "hybrid_block", spy)
    eng = make_engine(cpu_devices, prefill_buckets=(8,))
    eng.prefill(0, 0, [1, 2, 3])
    assert calls == [(k, (8, 48)) for k, _ in PLAN]
    del calls[:]
    trash = eng.cache_cfg.trash_slot
    eng.decode(np.array([[5, 0, 0, 0]]), np.array([[0] + [trash] * 3]),
               np.array([[3, 0, 0, 0]]))
    assert calls == [(k, (4, 48)) for k, _ in PLAN]
    del calls[:]
    full_forward(CFG, eng.params, [1, 2, 3, 4])
    assert calls == [(k, (4, 48)) for k, _ in PLAN]


def test_a_recycled_slot_forgets_its_ring(cpu_devices):
    """A ring is never zeroed: the next prompt overwrites what it reads,
    and its length masks the rest."""
    eng = make_engine(cpu_devices)
    long, short = list(range(1, 15)), [7, 8]
    eng.prefill(0, 0, long)
    _, after_long = eng.prefill(0, 0, short)
    fresh = make_engine(cpu_devices)
    _, alone = fresh.prefill(0, 0, short)
    np.testing.assert_array_equal(np.asarray(after_long), np.asarray(alone))
    trash = eng.cache_cfg.trash_slot
    lanes = (np.array([[5, 0, 0, 0]]), np.array([[0] + [trash] * 3]),
             np.array([[2, 0, 0, 0]]))
    np.testing.assert_array_equal(eng.decode(*lanes), fresh.decode(*lanes))


# sha256 of what the ONE-kind cache's page math gives for a seeded input,
# recorded at the parent commit (4f68b88, before this file's PR touched
# serve/kv_cache.py): the dense decoder's cell runs these functions
ONE_KIND_BITS = (
    "790fb73e8f03b15f9cb4cfd70ed5c63c8b8459dcd41146c286d594e995289b4d")


def test_the_one_kind_cache_is_bit_identical_to_the_parents():
    cc = kv.KVCacheConfig(layers=3, slots=4, max_len=16, kv_heads=2,
                          head_dim=8)
    cache = kv.init_cache(cc)
    ks = jax.random.split(jax.random.key(42), 6)
    for layer in range(3):
        cache = kv.layer_prefill(
            cache, layer, 1, jax.random.normal(ks[0], (8, 2, 8)) + layer,
            jax.random.normal(ks[1], (8, 2, 8)) - layer)
    slots, lens = jnp.array([1, 2, cc.trash_slot]), jnp.array([5, 0, 0])
    new = kv.token_pages(jax.random.normal(ks[2], (3, 3, 2, 8)),
                         jax.random.normal(ks[3], (3, 3, 2, 8)), "raw",
                         jnp.float32)
    q = jax.random.normal(ks[4], (3, 4, 8))
    att = kv.attend_rows(q, cache["k"], cache["v"], slots, lens, layer=1,
                         new={n: a[1] for n, a in new.items()})
    cache = kv.append_tokens(cache, slots, lens, new)
    after = kv.attend_rows(q, cache["k"], cache["v"], slots, lens + 1,
                           layer=2)
    digest = hashlib.sha256()
    for a in (att, after, cache["k"], cache["v"]):
        digest.update(np.asarray(a).tobytes())
    assert (cc.bytes(), cc.bytes_per_token()) == (3 * 5 * 16 * 2 * 8 * 4 * 2,
                                                  2 * 3 * 2 * 8 * 4)
    assert digest.hexdigest() == ONE_KIND_BITS
