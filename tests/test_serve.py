"""bluefog_tpu.serve: the decentralized inference engine.

What is pinned here:

* **engine correctness** — greedy decode through the bucketed
  prefill+decode engine (gossip-DP axis = replica axis, PP ppermute
  cycle, TP psum, slotted KV cache) matches an independent per-tp-rank
  numpy dense reference token-for-token, on both replicas, with mixed
  prompt lengths and batch buckets;
* **zero retraces** — after ``warmup()`` every served shape hits a
  declared bucket; the retrace sentinel stays 0 across the whole battery;
* **KV slot reuse** — a slot that served one request and was evicted
  produces bit-identical output for the next request (stale rows are
  masked, never read);
* **the float64 decode oracle** — ``RingTransformerLM``'s cached decode
  path (``cache=``/``init_decode_cache``) is logit-identical to the full
  forward at float64, including grouped-query attention and rope;
* **the train→serve estate** — 8 virtual ranks: 2 training replicas
  (pp=2) gossiping while 2 serving replicas answer 16 concurrent
  requests, with :class:`WeightRefresher` pulling fresh params
  mid-traffic (staleness gauge rises with train steps, drops to 0 on
  pull) and KV donation intact;
* **the chaos drill** — a serving replica killed mid-stream: survivors
  complete their requests, the refresher pulls through the healed
  topology, and the flight bundle + postmortem blame the right rank
  (the ``serve`` block carries the last-request ids);
* **serving checkpoints** — ``save_for_serving``/``load_for_serving``
  round-trip params-only snapshots, reject training state, skip torn
  directories;
* **the launcher surface** — ``bfrun-tpu --serve`` env plumbing and the
  no-command default to ``python -m bluefog_tpu.serve``.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bluefog_tpu import checkpoint
from bluefog_tpu.parallel import compose
from bluefog_tpu.serve import (Scheduler, ServeConfig, ServeEngine,
                               SlotAllocator, WeightRefresher)
from bluefog_tpu.serve.engine import _parse_buckets
from bluefog_tpu.serve.kv_cache import (KVCacheConfig, append_tokens,
                                        attend_rows, init_cache,
                                        layer_append, token_pages)
from bluefog_tpu.utils import chaos as bfchaos
from bluefog_tpu.utils import flight as bfflight
from bluefog_tpu.utils import metrics as bfm
from attend_oracle import token_beside_pages_oracle
from test_tracing_stage import bf_events, inside

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _clean():
    bfm.reset_metrics()
    bfchaos.uninstall()
    bfflight.reset()
    yield
    bfchaos.uninstall()
    bfflight.reset()
    bfm.reset_metrics()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name.replace("/", "_") + "_mod", os.path.join(REPO, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Config + allocator units
# ---------------------------------------------------------------------------

def test_parse_buckets():
    assert _parse_buckets("1,2,4@8,16") == ((1, 2, 4), (8, 16))
    assert _parse_buckets("1,8") == ((1, 8), ())
    with pytest.raises(ValueError, match="expected"):
        _parse_buckets("a,b@c")


def test_serve_config_validation():
    with pytest.raises(ValueError, match="ascending"):
        ServeConfig(batch_buckets=(4, 2))
    with pytest.raises(ValueError, match="resident slot"):
        ServeConfig(batch_buckets=(1, 16), slots=8)
    with pytest.raises(ValueError, match="exceeds max_len"):
        ServeConfig(prefill_buckets=(8, 128), max_len=64)
    with pytest.raises(ValueError, match="at least one"):
        ServeConfig(batch_buckets=())
    cfg = ServeConfig()
    assert cfg.batch_bucket_for(3) == 4
    assert cfg.prefill_bucket_for(9) == 16
    with pytest.raises(ValueError, match="exceed"):
        cfg.batch_bucket_for(99)


def test_serve_config_from_env(monkeypatch):
    monkeypatch.setenv("BLUEFOG_SERVE_BUCKETS", "1,2@4,32")
    cfg = ServeConfig.from_env(slots=4)
    assert cfg.batch_buckets == (1, 2)
    assert cfg.prefill_buckets == (4, 32)
    assert cfg.slots == 4


def test_slot_allocator_and_gauges():
    a = SlotAllocator(3, replica=1)
    assert [a.alloc(), a.alloc(), a.alloc()] == [0, 1, 2]
    assert a.alloc() is None
    a.free(1)
    assert a.alloc() == 1                       # lowest-free-first
    with pytest.raises(ValueError):
        a.free(7)
    assert a.in_use == 3 and a.occupancy == 1.0
    g = bfm.get_metric("bluefog_serve_kv_slots_in_use")
    assert g is not None and g.value(replica=1) == 3.0


def test_attend_rows_matches_dense_gqa():
    """attend_rows (gather + GQA repeat + masked softmax) == a numpy dense
    reference over the valid prefix, garbage rows masked out."""
    rng = np.random.default_rng(0)
    S, L, H, Hkv, Dh = 3, 8, 4, 2, 6
    kl = rng.normal(size=(5, Hkv, L, Dh)).astype(np.float32)
    vl = rng.normal(size=(5, Hkv, L, Dh)).astype(np.float32)
    q = rng.normal(size=(S, H, Dh)).astype(np.float32)
    slots = np.array([4, 0, 2], np.int32)
    lens = np.array([3, 7, 1], np.int32)        # attend over rows 0..lens
    out = np.asarray(attend_rows(q, kl, vl, slots, lens))
    for i in range(S):
        n = lens[i] + 1
        # [n, Hkv, Dh] -> repeat to [n, H, Dh] for the dense reference
        k = np.repeat(kl[slots[i], :, :n].transpose(1, 0, 2),
                      H // Hkv, axis=1)
        v = np.repeat(vl[slots[i], :, :n].transpose(1, 0, 2),
                      H // Hkv, axis=1)
        s = np.einsum("hd,lhd->hl", q[i] * Dh ** -0.5, k)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hl,lhd->hd", p, v)
        np.testing.assert_allclose(out[i], want, rtol=2e-5, atol=2e-6)


def test_kv_cache_shapes():
    cfg = KVCacheConfig(layers=2, slots=4, max_len=8, kv_heads=2, head_dim=4)
    c = init_cache(cfg)
    assert c["k"].shape == (2, 5, 2, 8, 4)      # slots + 1 trash row
    assert cfg.trash_slot == 4
    assert cfg.bytes() == 2 * 2 * 5 * 8 * 2 * 4 * 4


_DEFER_LANES = {
    # case: (slots, lengths, q heads per kv head, prefix rows, prefix lens)
    # with 4 request slots, 1 prefix page (row 4), trash row 5, max_len 16
    "plain": ([0, 2, 1], [3, 7, 0], 1, None, None),
    "duplicate_trash_lanes": ([1, 5, 5, 5], [5, 0, 0, 2], 1, None, None),
    "at_and_past_max_len": ([0, 1, 2, 3], [15, 16, 19, 4], 1, None, None),
    "gqa": ([3, 0], [9, 2], 2, None, None),
    "prefix_page": ([0, 2], [6, 3], 1, [4, 5], [4, 0]),
}


@pytest.mark.parametrize("store", ["raw", "int8", "fp8"])
@pytest.mark.parametrize("case", sorted(_DEFER_LANES))
def test_deferred_append_equals_per_layer_append(store, case):
    """A decode token handed to the attention beside the pages
    (``attend_rows(new=...)``) and written once per lane and tensor after
    the layers (``append_tokens``) against the form it replaces in the
    engine's XLA path, ``layer_append`` then ``attend_rows`` layer by
    layer: the same attention output on every live lane, bit for bit, and
    the same final cache, every row of it."""
    import jax.numpy as jnp
    if store == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 dtype in this jax build")
    slots, lens, group, prows, plens = _DEFER_LANES[case]
    cc = KVCacheConfig(layers=3, slots=4, max_len=16, kv_heads=2,
                       head_dim=8, store=store, prefix_slots=1)
    rng = np.random.default_rng(sorted(_DEFER_LANES).index(case))
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = init_cache(cc)["k"].dtype
    # a cache full of earlier tokens, as their writes would have left it
    full = token_pages(normal(cc.layers, cc.rows, cc.max_len, 2, 8),
                       normal(cc.layers, cc.rows, cc.max_len, 2, 8),
                       store, dt)
    cache = {name: jnp.swapaxes(t, 2, 3) for name, t in full.items()}
    slots, lens = jnp.asarray(slots, jnp.int32), jnp.asarray(lens, jnp.int32)
    pre = {} if prows is None else dict(
        prefix_slots=jnp.asarray(prows, jnp.int32),
        prefix_lens=jnp.asarray(plens, jnp.int32))
    S = slots.shape[0]
    want_cache, want, got, news = cache, [], [], []
    for layer in range(cc.layers):
        q = normal(S, 2 * group, 8)
        k, v = normal(S, 2, 8), normal(S, 2, 8)
        want_cache = layer_append(want_cache, layer, slots, lens, k, v, store)
        want.append(attend_rows(
            q, want_cache["k"], want_cache["v"], slots, lens,
            k_scale=want_cache.get("k_scale"),
            v_scale=want_cache.get("v_scale"), layer=layer, **pre))
        new = token_pages(k, v, store, dt)
        got.append(attend_rows(
            q, cache["k"], cache["v"], slots, lens,
            k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
            layer=layer, new=new, **pre))
        news.append(new)
    got_cache = append_tokens(
        cache, slots, lens,
        {name: jnp.stack([n[name] for n in news]) for name in cache})
    f32 = lambda t: np.asarray(t.astype(jnp.float32))
    assert sorted(got_cache) == sorted(want_cache)
    for name in cache:
        np.testing.assert_array_equal(f32(got_cache[name]),
                                      f32(want_cache[name]))
        # and it is a write: the lanes' tokens are in, nothing else moved
        assert (f32(got_cache[name]) != f32(cache[name])).any()
    # lanes on the trash row attend over whichever of them wrote last
    # there when the write comes first, and over their own token when it
    # waits: no one reads what they produce
    live = np.asarray(slots) < cc.slots
    np.testing.assert_array_equal(f32(jnp.stack(got))[:, live],
                                  f32(jnp.stack(want))[:, live])


# ---------------------------------------------------------------------------
# The engine vs a per-tp-rank dense numpy reference (dp=2 x pp=2 x tp=2)
# ---------------------------------------------------------------------------

_CFG = dict(vocab=32, d_model=32, heads=4, layers=4, seq_len=32)


_IN_PLACE_SHAPES = {
    # case: (kv heads, q heads per kv head, head_dim, max_len, whether a
    # TPU keeps the positions, not head_dim, in its lanes)
    "one_head_a_kv_head": (2, 1, 128, 24, False),
    "grouped": (2, 3, 8, 16, True),
    # the cell's page: head_dim 64 under max_len 1024
    "positions_minor": (2, 1, 64, 1024, True),
}


@pytest.mark.parametrize("against", ["oracle", "staged", "scanned_layer"])
@pytest.mark.parametrize("case", sorted(_IN_PLACE_SHAPES))
def test_attend_layer_reads_in_place(case, against):
    """``attend_layer`` (the dense family's decode read: queries laid out
    by row, the stacked cache at a layer index, the token beside the
    pages) against the float64 dense oracle, against the staged form it
    replaces (``attend_rows(new=...)``), and with the layer a scanned
    index under ``jit`` as the engine's layer loop hands it over.  Lanes
    in arbitrary slot order with the trash row (the last) among them
    twice, a length of 0, a lane at ``max_len - 1``."""
    import jax
    import jax.numpy as jnp
    from bluefog_tpu.serve import kv_cache as kv
    Hkv, G, Dh, L, minor = _IN_PLACE_SHAPES[case]
    assert kv._positions_minor(Dh, L) == minor
    layers, rows = 3, 6
    rng = np.random.default_rng(sorted(_IN_PLACE_SHAPES).index(case))
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    kl, vl = (normal(layers, rows, Hkv, L, Dh) for _ in range(2))
    slots = jnp.array([3, 5, 0, 5, 1], jnp.int32)
    lens = jnp.array([L - 1, 0, 0, 2, 7], jnp.int32)
    S, live = slots.shape[0], np.asarray(slots) < rows - 1
    q = normal(S, Hkv * G, Dh)
    new = token_pages(normal(S, Hkv, Dh), normal(S, Hkv, Dh), "raw",
                      jnp.float32)
    got, met = kv.attend_layer(q, kl, vl, 1, slots, lens, new)
    assert met == rows * L                  # every row whole, in place
    if against == "oracle":
        want = token_beside_pages_oracle(
            [q], [kl[1]], vl[1], slots, lens, [new["k"]], new["v"],
            Dh ** -0.5)
    elif against == "staged":
        want = attend_rows(q, kl, vl, slots, lens, layer=1, new=new)
    else:
        want = got

        @jax.jit
        def scanned(q, kl, vl, slots, lens, new):
            def body(_, layer):
                return None, kv.attend_layer(q, kl, vl, layer, slots, lens,
                                             new)[0]
            return jax.lax.scan(body, None, jnp.arange(layers))[1]
        got = scanned(q, kl, vl, slots, lens, new)[1]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-6)


_BOUNDED_READS = {
    # order: (kv heads, head_dim): three steps of 128 in a row of 384
    "head_dim_minor": (2, 128),
    "positions_minor": (1, 64),
}


@pytest.mark.parametrize("longest", [127, 128, 129, 256, 383])
@pytest.mark.parametrize("order", sorted(_BOUNDED_READS))
def test_attend_layer_stops_at_the_longest_live_lane(order, longest,
                                                     monkeypatch):
    """The dense in-place read of pages kept BY HEAD takes positions ``[0,
    bound)`` of every row, ``bound`` the least of ``read_bounds(max_len)``
    that covers the longest LIVE lane: in both by-head page orders, with
    that lane one under, on and one over a step's edge (and at the next
    edge, and at the row's end), beside a lane on the trash row that
    carries a stale length of the whole row, in a cache of more rows than
    lanes.  Equal to the read of the whole rows (one bound) to float32
    round-off, with every position past the bound NaN in the pages the
    bounded read is handed: none of them is read.  ``live_bound`` chooses
    the same bound from numpy arrays (the host's count) and traced ones
    (the program's), and the positions met are rows x bound.  (Token rows
    stop at each LANE's own last block:
    ``test_attend_layer_stops_at_each_lanes_own_last_block``.)"""
    import jax
    import jax.numpy as jnp
    from bluefog_tpu.serve import kv_cache as kv
    (Hkv, Dh), L, layers, rows = _BOUNDED_READS[order], 384, 2, 6
    assert kv.page_order(Hkv, Dh, L) == order
    assert kv.read_bounds(L) == (128, 256, 384)
    rng = np.random.default_rng(longest)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    kl, vl = (normal(layers, rows, Hkv, L, Dh) for _ in range(2))
    slots = jnp.array([3, 5, 0, 1], jnp.int32)          # 5: the trash row
    lens = jnp.array([longest, L - 1, 0, 40], jnp.int32)
    live = np.asarray(slots) != rows - 1
    bound = -(-longest // 128) * 128
    assert int(kv.live_bound(np.asarray(lens), live, L)) == bound
    assert int(jax.jit(lambda n, a: kv.live_bound(n, a, L))(
        lens, jnp.asarray(live))) == bound
    # a call with no live lane reads the first step, whatever is stale
    assert int(kv.live_bound(np.asarray(lens), np.zeros(4, bool), L)) == 128
    q = normal(4, Hkv * 2, Dh)
    new = token_pages(normal(4, Hkv, Dh), normal(4, Hkv, Dh), "raw",
                      jnp.float32)
    past = (jnp.arange(L) >= bound).reshape(1, L, 1)
    got, met = jax.jit(kv.attend_layer)(
        q, jnp.where(past, jnp.nan, kl), jnp.where(past, jnp.nan, vl), 1,
        slots, lens, new)
    assert int(met) == rows * bound
    assert int(met) == kv.dense_positions_met(
        np.asarray(lens), live, rows, L, False)
    monkeypatch.setattr(kv, "read_bounds", lambda max_len: (max_len,))
    want, whole = kv.attend_layer(q, kl, vl, 1, slots, lens, new)
    assert whole == rows * L
    assert np.isfinite(np.asarray(got)[live]).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-6, atol=2e-7)


_PER_LANE_READS = {
    # case: (kv heads, head_dim, q heads a kv head, queries' dtype, pages')
    "float32": (2, 64, 1, "float32", "float32"),
    "float32_grouped": (2, 64, 3, "float32", "float32"),
    "float32_on_bfloat16_pages": (2, 64, 1, "float32", "bfloat16"),
    # the cell's: queries and pages in one dtype, the scale a power of two
    "bfloat16": (2, 64, 1, "bfloat16", "bfloat16"),
    "bfloat16_grouped": (4, 32, 2, "bfloat16", "bfloat16"),
    # heads that fill no whole tile of 16: the spare ones are zero
    "bfloat16_twenty_heads": (4, 32, 5, "bfloat16", "bfloat16"),
}


@pytest.mark.parametrize("case", sorted(_PER_LANE_READS))
def test_attend_layer_stops_at_each_lanes_own_last_block(case, monkeypatch):
    """The dense in-place read of TOKEN ROWS that hold more than one block
    takes, of each lane's row, the blocks of 128 positions that hold its
    own cached positions and nothing else (the kernel
    ``pallas_decode.attend_live_blocks``, here in interpreter mode): lanes
    at 0, 1, 127, 128, 129, 640 and 1,023 positions in ONE batch beside a
    dead lane on the trash row that carries a stale length of the whole
    row, in a cache of two more rows than lanes; NaN in the pages past
    EACH lane's own last block and in every position of the rows that hold
    no lane and of the trash row: none of it is read.  Equal to the read of
    the whole rows (a row of one block: XLA's, no kernel) to float32
    round-off, or to the bit once both are rounded to bfloat16; the
    positions met are the sum of the lanes' blocks, the same from numpy
    arrays (the host's count), from traced ones and from the program."""
    import jax
    import jax.numpy as jnp
    from bluefog_tpu.serve import kv_cache as kv
    Hkv, Dh, G, qdt, pdt = _PER_LANE_READS[case]
    L, layers = 1024, 2
    assert kv.page_order(Hkv, Dh, L) == "token_rows"
    assert kv.read_block(L) == 128
    lens = np.array([0, 1, 127, 128, 129, 640, 1023, L - 1], np.int32)
    slots = np.array([3, 8, 0, 6, 1, 7, 4, 9], np.int32)    # 9: the trash row
    S, rows = len(slots), len(slots) + 2                # rows 2, 5: no lane
    live = slots != rows - 1
    blocks = -(-lens // 128) * live
    assert blocks.tolist() == [0, 1, 1, 1, 2, 5, 8, 0]
    np.testing.assert_array_equal(kv.live_blocks(lens, live, 128), blocks)
    np.testing.assert_array_equal(jax.jit(
        lambda n, a: kv.live_blocks(n, a, 128))(lens, live), blocks)
    rng = np.random.default_rng(sorted(_PER_LANE_READS).index(case))
    normal = lambda dt, *shape: jnp.asarray(
        rng.normal(size=shape), jnp.float32).astype(dt)
    kl, vl = (normal(pdt, layers, rows, L, Hkv * Dh) for _ in range(2))
    own = np.zeros(rows, np.int32)
    own[slots[live]] = 128 * blocks[live]
    past = jnp.asarray(np.arange(L)[None, :] >= own[:, None])[None, :, :,
                                                              None]
    assert int(past.sum()) == rows * L - 128 * blocks.sum()
    q = normal(qdt, S, Hkv * G, Dh)
    new = token_pages(normal(pdt, S, Hkv, Dh), normal(pdt, S, Hkv, Dh),
                      "raw", jnp.dtype(pdt))
    got, met = jax.jit(kv.attend_layer)(
        q, jnp.where(past, jnp.nan, kl), jnp.where(past, jnp.nan, vl), 1,
        slots, lens, new)
    assert int(met) == 128 * blocks.sum() == kv.dense_positions_met(
        lens, live, rows, L, True)
    monkeypatch.setattr(kv, "read_block", lambda max_len: max_len)
    want, whole = kv.attend_layer(q, kl, vl, 1, slots, lens, new)
    assert whole == rows * L == kv.dense_positions_met(
        lens, live, rows, L, True)
    assert got.dtype == want.dtype == jnp.dtype(qdt)
    got, want = (np.asarray(a.astype(jnp.float32))[live] for a in (got, want))
    assert np.isfinite(got).all()
    if qdt == "bfloat16":
        # float32 results a round-off apart may round to neighbours
        assert (got != want).mean() < 0.01
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)
    else:
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-7)


_PAGE_ORDERS = {
    # case: (kv heads, head_dim, max_len, the order its pages lie in)
    # the dense serving cell: 16 heads of 64 side by side fill 1,024 lanes
    "cell_16_heads_of_64": (16, 64, 1024, "token_rows"),
    # the hybrid cell's full layers and its rings: head_dim fills the lanes
    "full_layers_of_128": (8, 128, 8704, "head_dim_minor"),
    "rings_of_128": (8, 128, 128, "head_dim_minor"),
    # one kv head of 64 on a tp rank: nothing to put beside it
    "one_head_of_64": (1, 64, 1024, "positions_minor"),
    # the padding-driven cases ``_positions_minor`` decided alone
    "padding_8_under_16": (2, 8, 16, "positions_minor"),
    "padding_128_over_24": (2, 128, 24, "head_dim_minor"),
    "three_heads_of_64": (3, 64, 1024, "positions_minor"),
    "two_heads_of_64": (2, 64, 16, "token_rows"),
    "head_dim_256": (4, 256, 512, "head_dim_minor"),
}


@pytest.mark.parametrize("store", ["raw", "int8"])
@pytest.mark.parametrize("case", sorted(_PAGE_ORDERS))
def test_page_order_is_a_function_of_the_shapes(case, store):
    """The order a dense cache's pages lie in, from ``kv_heads``,
    ``head_dim`` and ``max_len`` alone: the stored shape follows it, the
    scales of a quantized store stay ``[layers, rows, kv_heads,
    max_len]``, ``bytes()`` is what the stored arrays hold in every
    order, and ``page_orders()`` (``program_memory()``'s word for every
    tensor) says so by name."""
    from bluefog_tpu.serve import kv_cache as kv
    Hkv, Dh, L, order = _PAGE_ORDERS[case]
    assert kv.page_order(Hkv, Dh, L) == order
    if order != "token_rows" and Dh % 128:
        assert kv._positions_minor(Dh, L) == (order == "positions_minor")
    cc = KVCacheConfig(layers=2, slots=2, max_len=L, kv_heads=Hkv,
                       head_dim=Dh, store=store, prefix_slots=1)
    assert cc.page_order == order
    pay = (2, 4, L, Hkv * Dh) if order == "token_rows" \
        else (2, 4, Hkv, L, Dh)
    want = {"k": pay, "v": pay}
    if store == "int8":
        want["k_scale"] = want["v_scale"] = (2, 4, Hkv, L)
    assert cc.shapes() == want
    got = jax.eval_shape(lambda: init_cache(cc))
    assert {k: v.shape for k, v in got.items()} == want
    assert cc.bytes() == sum(int(np.prod(v.shape)) * v.dtype.itemsize
                             for v in got.values())
    assert cc.page_orders() == {
        name: order if name in ("k", "v") else "positions_minor"
        for name in want}


def test_page_orders_of_the_other_two_caches():
    """``page_orders()`` at the two other serving cells' shapes: the
    latent cache's compressed vectors fill the lanes and its 64 rotary
    dimensions lie with the positions minor; both kinds of the hybrid
    cache are kept by head with ``head_dim`` 128 minor."""
    from bluefog_tpu.serve import kv_cache as kv
    lat = kv.LatentCacheConfig(layers=6, slots=128, max_len=2560,
                               kv_rank=512, rope_dim=64)
    assert lat.page_orders() == {"ckv": "head_dim_minor",
                                 "kr": "positions_minor"}
    hyb = kv.HybridCacheConfig(full_layers=2, window_layers=6, slots=48,
                               max_len=8704, window=128, kv_heads=8,
                               head_dim=128)
    assert hyb.page_orders() == dict.fromkeys(
        ("k", "v", "kw", "vw"), "head_dim_minor")


def _token_row_caches(store, rng, layers=3, slots=4, L=16, Hkv=2, Dh=64):
    """One cache full of earlier tokens in both orders: token rows as
    ``init_cache`` lays them out at these shapes, and the same pages kept
    by head (the order every function here also takes, by the tensors'
    rank): ``(config, token rows, by head)``."""
    import jax.numpy as jnp
    cc = KVCacheConfig(layers=layers, slots=slots, max_len=L, kv_heads=Hkv,
                       head_dim=Dh, store=store, prefix_slots=1)
    assert cc.page_order == "token_rows"
    dt = init_cache(cc)["k"].dtype
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    full = token_pages(normal(layers, cc.rows, L, Hkv, Dh),
                       normal(layers, cc.rows, L, Hkv, Dh), store, dt)
    by_head = {n: jnp.swapaxes(t, 2, 3) for n, t in full.items()}
    rows = {n: t.reshape(layers, cc.rows, L, Hkv * Dh) if n in ("k", "v")
            else by_head[n] for n, t in full.items()}
    assert {n: t.shape for n, t in rows.items()} == cc.shapes()
    return cc, rows, by_head


def _assert_same_pages(rows, by_head, head_dim):
    """A token-row cache holds, seen through the logical view, exactly
    what the cache kept by head holds."""
    import jax.numpy as jnp
    from bluefog_tpu.serve import kv_cache as kv
    assert sorted(rows) == sorted(by_head)
    for n, t in rows.items():
        seen = kv.logical_pages(t, head_dim, True) if n in ("k", "v") else t
        np.testing.assert_array_equal(
            np.asarray(seen.astype(jnp.float32)),
            np.asarray(by_head[n].astype(jnp.float32)))


_ROW_WRITES = {
    # case: (slots, positions) of five lanes over 4 request slots, one
    # prefix row (4) and the trash row (5) of 16 positions
    "plain": ([3, 0, 2, 1, 4], [15, 0, 7, 2, 9]),
    "trash_row_twice": ([3, 5, 0, 5, 1], [15, 0, 3, 2, 7]),
    "at_and_past_max_len": ([0, 1, 2, 3, 5], [16, 19, 15, 4, 16]),
    "chunk_straddles_max_len": ([0, 1, 2, 3, 5], [13, 14, 12, 0, 15]),
}


@pytest.mark.parametrize("store", ["raw", "int8"])
@pytest.mark.parametrize("write", ["append_tokens", "layer_prefill",
                                   "layer_append_chunk"])
@pytest.mark.parametrize("case", sorted(_ROW_WRITES))
def test_writes_land_the_same_in_token_rows(case, write, store):
    """Every landing in a dense cache, on token rows against the same
    pages kept by head: a token per lane after the layers
    (``append_tokens``), a prompt's block as the projection leaves it
    (``layer_prefill``), a chunk of four positions per lane
    (``layer_append_chunk``: straddling ``max_len``, on the trash row, at
    ``max_len``) leave the same pages under the logical view, scales
    included, and do write."""
    import jax.numpy as jnp
    from bluefog_tpu.serve.kv_cache import layer_append_chunk, layer_prefill
    rng = np.random.default_rng(sorted(_ROW_WRITES).index(case))
    cc, rows, by_head = _token_row_caches(store, rng)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    slots, pos = (jnp.asarray(x, jnp.int32) for x in _ROW_WRITES[case])
    S, Hkv, Dh = slots.shape[0], cc.kv_heads, cc.head_dim
    if write == "append_tokens":
        new = token_pages(normal(cc.layers, S, Hkv, Dh),
                          normal(cc.layers, S, Hkv, Dh), store,
                          rows["k"].dtype)
        land = lambda c: append_tokens(c, slots, pos, new)
    elif write == "layer_prefill":
        k, v = normal(8, Hkv, Dh), normal(8, Hkv, Dh)
        land = lambda c: layer_prefill(c, 1, slots[0], k, v, store)
    else:
        k, v = normal(S, 4, Hkv, Dh), normal(S, 4, Hkv, Dh)
        land = lambda c: layer_append_chunk(c, 2, slots, pos, k, v, store)
    got, want = land(rows), land(by_head)
    _assert_same_pages(got, want, Dh)
    assert any((np.asarray(got[n].astype(jnp.float32))
                != np.asarray(rows[n].astype(jnp.float32))).any()
               for n in got)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("against", ["oracle", "by_head", "staged",
                                     "scanned_layer"])
@pytest.mark.parametrize("group", [1, 4])
def test_attend_layer_meets_token_rows_as_they_lie(group, against, dtype):
    """``attend_layer`` over token rows (the queries spread
    block-diagonally over a row's lanes, every head's probabilities on
    the whole row of values, the head's own lanes kept) against the
    float64 oracle on the logical view, against the by-head read of the
    same pages, against the staged form through the logical view, and
    with the layer a scanned index under ``jit``; one q head a kv head and
    four (``H`` = 4 x ``Hkv``).  In bfloat16 the scale of 0.125 folds
    into bfloat16 queries exactly, so the by-head read (float32 queries)
    sums the same exact products: equal to float32 rounding either
    way."""
    import jax.numpy as jnp
    from bluefog_tpu.serve import kv_cache as kv
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(group)
    cc, rows, by_head = _token_row_caches("raw", rng)
    rows, by_head = ({n: t.astype(dt) for n, t in c.items()}
                     for c in (rows, by_head))
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dt)
    slots = jnp.array([3, 5, 0, 5, 1], jnp.int32)
    lens = jnp.array([cc.max_len - 1, 0, 0, 2, 7], jnp.int32)
    S, Hkv, Dh = slots.shape[0], cc.kv_heads, cc.head_dim
    live = np.asarray(slots) < cc.trash_slot
    q = normal(S, Hkv * group, Dh)
    new = token_pages(normal(S, Hkv, Dh), normal(S, Hkv, Dh), "raw", dt)
    got, met = kv.attend_layer(q, rows["k"], rows["v"], 1, slots, lens, new)
    assert met == cc.rows * cc.max_len and got.dtype == dt
    if against == "oracle":
        want = token_beside_pages_oracle(
            [q.astype(jnp.float32)], [by_head["k"][1].astype(jnp.float32)],
            by_head["v"][1].astype(jnp.float32), slots, lens,
            [new["k"].astype(jnp.float32)], new["v"].astype(jnp.float32),
            Dh ** -0.5)
    elif against == "by_head":
        want, _ = kv.attend_layer(q, by_head["k"], by_head["v"], 1, slots,
                                  lens, new)
    elif against == "staged":
        want = attend_rows(q, rows["k"], rows["v"], slots, lens, layer=1,
                           new=new)
    else:
        want = got

        @jax.jit
        def scanned(q, kl, vl, slots, lens, new):
            def body(_, layer):
                return None, kv.attend_layer(q, kl, vl, layer, slots, lens,
                                             new)[0]
            return jax.lax.scan(body, None, jnp.arange(cc.layers))[1]
        got = scanned(q, rows["k"], rows["v"], slots, lens, new)[1]
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)
    f32 = lambda t: np.asarray(jnp.asarray(t).astype(jnp.float32))
    np.testing.assert_allclose(f32(got)[live], f32(want)[live], **tol)


@pytest.mark.parametrize("group", [1, 4])
def test_float32_probabilities_weigh_bfloat16_token_rows(group):
    """The value product over bfloat16 token rows takes the float32
    probabilities whole (as their three bfloat16 pieces, rows of one
    matmul): the result is the one the same pages give as float32, where
    the probabilities meet them at full precision, to float32 rounding;
    probabilities rounded to bfloat16 on the way in would stand 4e-3
    off."""
    import jax.numpy as jnp
    from bluefog_tpu.serve import kv_cache as kv
    rng = np.random.default_rng(7 + group)
    cc, rows, _ = _token_row_caches("raw", rng, L=64)
    bf = {n: t.astype(jnp.bfloat16) for n, t in rows.items()}
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    slots = jnp.array([3, 5, 0, 5, 1], jnp.int32)
    lens = jnp.array([cc.max_len - 1, 0, 40, 2, 17], jnp.int32)
    S, Hkv, Dh = slots.shape[0], cc.kv_heads, cc.head_dim
    live = np.asarray(slots) < cc.trash_slot
    q = normal(S, Hkv * group, Dh)
    kn, vn = normal(S, Hkv, Dh), normal(S, Hkv, Dh)
    run = lambda dt: np.asarray(kv._attend_dense(
        q * 0.125, bf["k"].astype(dt), bf["v"].astype(dt), 1, slots, lens,
        kn, vn)[0])
    got, want = run(jnp.bfloat16), run(jnp.float32)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("store", ["raw", "int8"])
@pytest.mark.parametrize("read", ["attend_rows", "attend_rows_prefix",
                                  "attend_chunk_prefix"])
def test_staged_reads_see_token_rows_through_the_logical_view(read, store):
    """The readers that stage (``attend_rows``, ``attend_chunk``: prefix
    rows, a quantized store and its scales, a chunk of queries) give, on
    token rows, bit for bit what they give on the same pages kept by
    head: they meet them through one logical view."""
    import jax.numpy as jnp
    from bluefog_tpu.serve.kv_cache import attend_chunk
    rng = np.random.default_rng(5)
    cc, rows, by_head = _token_row_caches(store, rng)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    slots = jnp.array([3, 5, 0, 5, 1], jnp.int32)
    lens = jnp.array([12, 0, 9, 2, 7], jnp.int32)
    live = np.asarray(slots) < cc.trash_slot
    pre = dict(prefix_slots=jnp.array([4, 5, 4, 5, 5], jnp.int32),
               prefix_lens=jnp.array([4, 0, 8, 0, 0], jnp.int32)) \
        if read.endswith("prefix") else {}
    S, Hkv, Dh = slots.shape[0], cc.kv_heads, cc.head_dim
    if read.startswith("attend_rows"):
        q = normal(S, 2 * Hkv, Dh)
        new = token_pages(normal(S, Hkv, Dh), normal(S, Hkv, Dh), store,
                          rows["k"].dtype)
        run = lambda c: attend_rows(
            q, c["k"], c["v"], slots, lens, k_scale=c.get("k_scale"),
            v_scale=c.get("v_scale"), layer=1, new=new, **pre)
    else:
        q = normal(S, 4, 2 * Hkv, Dh)
        run = lambda c: attend_chunk(q, c, slots, lens, layer=2, **pre)
    np.testing.assert_array_equal(np.asarray(run(rows))[live],
                                  np.asarray(run(by_head))[live])


def test_attend_layer_stages_a_bucket_under_a_third_of_the_rows():
    """Two lanes of seven rows: three passes over theirs cost less than
    one over all, so the lanes' rows are staged (``attend_rows``), as
    ``read_in_place`` says from the shapes alone."""
    import jax.numpy as jnp
    from bluefog_tpu.serve import kv_cache as kv
    assert kv.read_in_place(2, 6) and not kv.read_in_place(2, 7)
    rng = np.random.default_rng(5)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    kl, vl = normal(2, 7, 2, 16, 8), normal(2, 7, 2, 16, 8)
    slots, lens = jnp.array([4, 1]), jnp.array([9, 0])
    q = normal(2, 4, 8)
    new = token_pages(normal(2, 2, 8), normal(2, 2, 8), "raw", jnp.float32)
    got, met = kv.attend_layer(q, kl, vl, 1, slots, lens, new)
    assert met == 2 * 16
    np.testing.assert_array_equal(
        got, attend_rows(q, kl, vl, slots, lens, layer=1, new=new))


@pytest.fixture(scope="module")
def engine(cpu_devices):
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(2, 2, 2, 1, devices=cpu_devices)
    params = compose.init_lm_params(cfg, m, seed=3)
    scfg = ServeConfig(batch_buckets=(1, 2), prefill_buckets=(4, 8),
                       slots=4, max_len=32, decode_steps_per_call=1)
    eng = ServeEngine(m, cfg, params, scfg)
    eng.warmup()
    return eng


def _np_ln(z):
    mu = z.mean(-1, keepdims=True)
    return (z - mu) / np.sqrt(z.var(-1, keepdims=True) + 1e-6)


def _ref_forward(P, m, cfg, toks, ln=_np_ln):
    """Logits ``[T, vocab]`` of the dense LM on ``toks`` via plain numpy:
    per-tp-rank matmuls summed, dense causal attention (replica 0's rows
    of the stacked tree ``P``; ``ln`` is the norm the model was built
    with)."""
    Lps = cfg.layers // m.pp
    H, D = cfg.heads, cfg.d_model
    Hl, hsz = H // m.tp, D // H

    def dev(stage, t):
        return (stage * m.tp + t) * m.sp        # replica 0's shard row

    def rope(x, pos):
        half = x.shape[-1] // 2
        freqs = 10000.0 ** (-np.arange(half) / half)
        ang = pos[:, None] * freqs[None]
        cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    toks = np.asarray(toks)
    T = len(toks)
    pos = np.arange(T)
    x = P["shared"]["embed"][0][toks]
    for l in range(cfg.layers):
        st, li = l // Lps, l % Lps
        h = ln(x)
        delta = np.zeros_like(x)
        for t in range(m.tp):
            d = dev(st, t)
            qkv = h @ P["blocks"]["wqkv"][d][li]
            q, k, v = np.split(qkv, 3, -1)
            q = rope(q.reshape(T, Hl, hsz), pos)
            k = rope(k.reshape(T, Hl, hsz), pos)
            v = v.reshape(T, Hl, hsz)
            s = np.einsum("ihd,jhd->ihj", q * hsz ** -0.5, k)
            mask = pos[:, None] >= pos[None, :]
            s = np.where(mask[:, None, :], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            att = np.einsum("ihj,jhd->ihd", p, v).reshape(T, Hl * hsz)
            delta += att @ P["blocks"]["wo"][d][li]
        x = x + delta
        h = ln(x)
        delta = np.zeros_like(x)
        for t in range(m.tp):
            d = dev(st, t)
            g = h @ P["blocks"]["w1"][d][li]
            g = 0.5 * g * (1 + np.tanh(
                np.sqrt(2 / np.pi) * (g + 0.044715 * g ** 3)))
            delta += g @ P["blocks"]["w2"][d][li]
        x = x + delta
    return ln(x) @ P["shared"]["head"][0]


def _ref_greedy(eng, prompt, steps, ln=_np_ln):
    """Greedy decode through :func:`_ref_forward`: the full forward
    re-run per token."""
    P = jax.tree.map(np.asarray, eng.params)
    toks, out = list(prompt), []
    for _ in range(steps):
        nxt = int(np.argmax(
            _ref_forward(P, eng.m, eng.cfg, toks, ln)[-1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_engine_greedy_matches_dense_reference(engine):
    """Both replicas, mixed prompt lengths and batch buckets: token
    sequences identical to the numpy reference; zero retraces."""
    eng = engine
    base = bfm.counter("bluefog_retrace_after_warmup_total").total()
    steps = 6
    prompt = [5, 11, 2, 7, 19, 3]
    want = _ref_greedy(eng, prompt, steps)
    idle_t, idle_s, idle_l = eng.idle_lane()

    nxt, logits = eng.prefill(0, 0, prompt)
    assert logits.shape == (eng.cfg.vocab,)
    got, lens, tok = [nxt], len(prompt), nxt
    for _ in range(steps - 1):
        gen = eng.decode(np.array([[tok], [idle_t]], np.int32),
                         np.array([[0], [idle_s]], np.int32),
                         np.array([[lens], [idle_l]], np.int32))
        tok = int(gen[0, -1, 0])
        got.append(tok)
        lens += 1
    assert got == want

    # second request on replica 1, shorter prompt (smaller prefill
    # bucket), decoded in the 2-lane batch bucket alongside replica 0
    p2 = [9, 1, 4]
    w2 = _ref_greedy(eng, p2, steps)
    t2, _ = eng.prefill(1, 2, p2)
    g2, l2 = [t2], len(p2)
    for _ in range(steps - 1):
        gen = eng.decode(np.array([[tok, idle_t], [t2, idle_t]], np.int32),
                         np.array([[0, idle_s], [2, idle_s]], np.int32),
                         np.array([[lens, idle_l], [l2, idle_l]], np.int32))
        t2 = int(gen[1, -1, 0])
        g2.append(t2)
        l2 += 1
        lens += 1
    assert g2 == w2
    assert bfm.counter(
        "bluefog_retrace_after_warmup_total").total() == base


def test_kv_slot_reuse_after_evict(engine):
    """A slot that served one request is reused for another: the second
    request's tokens are identical to running it in a never-used slot —
    stale KV rows beyond `lens` are masked, never read."""
    eng = engine
    idle_t, idle_s, idle_l = eng.idle_lane()

    def rollout(prompt, slot, steps=5):
        nxt, _ = eng.prefill(0, slot, prompt)
        out, lens, tok = [nxt], len(prompt), nxt
        for _ in range(steps - 1):
            gen = eng.decode(np.array([[tok], [idle_t]], np.int32),
                             np.array([[slot], [idle_s]], np.int32),
                             np.array([[lens], [idle_l]], np.int32))
            tok = int(gen[0, -1, 0])
            out.append(tok)
            lens += 1
        return out

    rollout([7, 7, 7, 7, 7, 7, 7], 1)           # dirty slot 1 (long ctx)
    dirty = rollout([3, 1, 4], 1)               # reuse slot 1 (shorter)
    fresh = rollout([3, 1, 4], 3)               # never-used slot
    assert dirty == fresh
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0


def test_bucketed_shapes_never_retrace(engine):
    """Every declared bucket visited twice (prefill lengths straddling
    both pad buckets, decode at 1 and 2 lanes): the jit caches stay at
    their post-warmup size."""
    eng = engine
    snap = (eng._prefill_jit._cache_size(), eng._decode_jit._cache_size())
    idle_t, idle_s, idle_l = eng.idle_lane()
    for rep in range(2):
        for prompt in ([1, 2], [1, 2, 3, 4], [1] * 5, [1] * 8):
            eng.prefill(rep, 0, prompt)
        for S in eng.scfg.batch_buckets:
            toks = np.full((2, S), idle_t, np.int32)
            slots = np.full((2, S), idle_s, np.int32)
            lens = np.full((2, S), idle_l, np.int32)
            toks[rep, 0], slots[rep, 0], lens[rep, 0] = 1, 0, 3
            eng.decode(toks, slots, lens)
    assert (eng._prefill_jit._cache_size(),
            eng._decode_jit._cache_size()) == snap
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    with pytest.raises(ValueError, match="exceeds the largest"):
        eng.prefill(0, 0, list(range(9)))       # undeclared shape refused


# ---------------------------------------------------------------------------
# Float64 decode oracle: the models/transformer cached-decode path
# ---------------------------------------------------------------------------

_ORACLE_SCRIPT = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "1"
import json
import jax
import jax.numpy as jnp
import numpy as np
from bluefog_tpu.models.transformer import (RingTransformerLM,
                                            init_decode_cache)


def max_diff(num_kv_heads):
    model = RingTransformerLM(vocab_size=61, num_layers=2, num_heads=4,
                              num_kv_heads=num_kv_heads, d_model=32,
                              max_seq_len=64, rope=True,
                              dtype=jnp.float64)
    B, T = 2, 12
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 61, (B, T)), jnp.int32)
    vars_ = model.init(jax.random.PRNGKey(0), toks[:, :1])
    full = model.apply(vars_, toks)                     # [B, T, V]

    # prefill the first 4 tokens as one cached chunk, then decode the
    # rest token by token; every step must match the full forward's
    # logits at that position exactly
    cache = init_decode_cache(model, B, 64)
    logits, cache = model.apply(vars_, toks[:, :4], pos_offset=0,
                                cache=cache)
    worst = float(jnp.abs(logits - full[:, :4]).max())
    for t in range(4, T):
        logits, cache = model.apply(vars_, toks[:, t:t + 1], pos_offset=t,
                                    cache=cache)
        worst = max(worst, float(jnp.abs(logits[:, 0] - full[:, t]).max()))
    return worst


print(json.dumps({"mha": max_diff(None), "gqa": max_diff(2)}))
"""


def test_engine_call_crosses_the_host_boundary_once_each_way(engine,
                                                            tmp_path):
    """Warm, a scheduler's decode call stages ONE array and ONE is read
    back for it (a step later: the call runs ahead), and an admission's
    prefill the same: the lanes' sampler keys and pending tokens stay on
    the device, the admitted slot's key is made inside the prefill program
    (no eager ``jax.random`` program, no ``seed_slot`` stage), and the
    prefill's logits cross only when somebody converts them."""
    eng = engine
    moved = bfm.counter("bluefog_serve_host_arrays_total")
    dispatched = bfm.counter("bluefog_serve_decode_calls_total")

    def crossings():
        return {(p, d): moved.value(program=p, direction=d)
                for p in ("decode", "prefill") for d in ("in", "out")}
    sched = Scheduler(eng)
    rng = np.random.default_rng(5)
    reqs = [sched.submit(rng.integers(0, _CFG["vocab"], n).tolist(),
                         max_new_tokens=4) for n in (3, 8, 5, 2, 6)]
    before, steps, calls = crossings(), 0, dispatched.total()
    jax.profiler.start_trace(str(tmp_path))
    try:
        while not sched.done:
            steps += 1
            assert steps < 100, "scheduler failed to drain"
            sched.step()
    finally:
        jax.profiler.stop_trace()
    sched.close()
    assert all(len(r.generated) == 4 for r in reqs)
    after = crossings()
    calls = dispatched.total() - calls
    assert 0 < calls < steps        # the last step only reads a call back
    assert {k: after[k] - before[k] for k in after} == {
        ("decode", "in"): calls, ("decode", "out"): calls,
        ("prefill", "in"): len(reqs), ("prefill", "out"): len(reqs)}

    # the logits a prefill returns are a value on the device
    first, logits = eng.prefill(1, 0, reqs[0].prompt)
    assert first == reqs[0].generated[0] and logits.shape == (_CFG["vocab"],)
    held = crossings()
    assert held[("prefill", "in")] == after[("prefill", "in")] + 1
    assert held[("prefill", "out")] == after[("prefill", "out")] + 1
    row = np.asarray(logits, np.float32)
    assert int(row.argmax()) == first
    assert crossings()[("prefill", "out")] == held[("prefill", "out")] + 1

    ev = bf_events(tmp_path, ("bf:", "PjitFunction("))
    in_step = [e for e in ev if e[0] == "bf:serve.step"]
    assert len(in_step) == steps
    assert [e for e in ev if e[0] == "bf:engine.seed_slot"] == []
    # every jitted call the runtime saw between a step's two ends
    programs = {e[0] for e in ev if e[0].startswith("PjitFunction(")
                and any(s[1] <= e[1] and e[2] <= s[2] for s in in_step)}
    assert programs == {"PjitFunction(_decode_body)",
                        "PjitFunction(_prefill_body)",
                        "PjitFunction(_feed_body)"}
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0


def test_float64_decode_oracle():
    """The cached decode path is logit-identical (float64, ~1e-12) to the
    full forward, for both MHA and grouped-query attention — the numeric
    foundation the serve engine's correctness claim stands on."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_")
           and k not in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_ENABLE_X64")}
    p = subprocess.run([sys.executable, "-c", _ORACLE_SCRIPT],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=420, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["mha"] < 1e-12, doc
    assert doc["gqa"] < 1e-12, doc


# ---------------------------------------------------------------------------
# The 8-rank train→serve estate
# ---------------------------------------------------------------------------

def _estate(cpu_devices, seed=99):
    """2 training replicas (pp=2) on devices 0-3, 2 serving replicas
    (pp=2) on devices 4-7; deliberately different initial weights so a
    pull is observable."""
    import optax
    import bluefog_tpu.optimizers as bfopt

    cfg = compose.LMConfig(**_CFG)
    train_m = compose.compose_parallelism(2, 2, 1, 1,
                                          devices=cpu_devices[:4])
    serve_m = compose.compose_parallelism(2, 2, 1, 1,
                                          devices=cpu_devices[4:])
    grad_fn = compose.make_lm_grad_fn(cfg, train_m)
    step, strategy = compose.make_train_step(
        train_m, grad_fn, optax.sgd(0.05))
    train_params = compose.init_lm_params(cfg, train_m, seed=1)
    state = bfopt.init_distributed(strategy, train_params)
    toks = compose.make_lm_batch(cfg, train_m)
    train_params = compose.device_put(train_m, train_params)

    scfg = ServeConfig(batch_buckets=(1, 2, 4), prefill_buckets=(4, 8),
                       slots=4, max_len=32)
    eng = ServeEngine(serve_m, cfg,
                      compose.init_lm_params(cfg, serve_m, seed=seed), scfg)
    eng.warmup()
    return cfg, train_m, (step, state, train_params, toks), eng


def test_e2e_serving_while_training_advances(cpu_devices, tmp_path):
    """16 concurrent requests drain while the training fleet advances and
    the refresher pulls mid-traffic: staleness rises with train steps and
    drops to 0 on pull, pulled weights equal the training average, the KV
    donation stays intact, and nothing retraces — all under a profiler
    session, whose trace holds the program's stage spans, nested, with
    their entry attributes."""
    cfg, train_m, (step, state, train_params, toks), eng = \
        _estate(cpu_devices)
    refresher = WeightRefresher(eng, train_m, every=2)
    sched = Scheduler(eng)
    cache_probe = eng.cache["k"]

    rng = np.random.default_rng(0)
    reqs = [sched.submit(rng.integers(0, cfg.vocab,
                                      int(rng.integers(2, 9))).tolist(),
                         max_new_tokens=int(rng.integers(2, 6)))
            for _ in range(16)]
    assert sched.pending + sched.in_flight == 16

    train_done, stal_seen, pulls = 0, [], 0
    guard = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        while not sched.done:
            guard += 1
            assert guard < 500, "scheduler failed to drain"
            sched.step()
            if train_done < 4:
                train_params, state, _ = step(train_params, state, toks)
                train_done += 1
                refresher.note_train_step(train_done)
                stal_seen.append(refresher.staleness())
                if refresher.maybe_refresh(train_params, train_done):
                    pulls += 1
                    assert refresher.staleness() == 0.0   # drops on pull
    finally:
        jax.profiler.stop_trace()

    assert len(sched.completed) == 16
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)
    assert pulls >= 1 and max(stal_seen) >= 1.0
    assert int(bfm.counter("bluefog_tokens_generated_total").total()) == \
        sum(r.max_new_tokens for r in reqs)

    # a pull delivers the training average at matching slice offsets
    refresher.pull(train_params, train_done)
    tp = np.asarray(train_params["blocks"]["wqkv"])
    sp = np.asarray(eng.params["blocks"]["wqkv"])
    for j in range(4):
        o = j % train_m.slice_size
        want = (tp[o] + tp[train_m.slice_size + o]) / 2
        np.testing.assert_allclose(sp[j], want, rtol=1e-5, atol=1e-7)

    assert cache_probe.is_deleted()               # donated into decode
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    sched.close()

    # the stage spans, unarmed, in the profiler's trace: step > decode_call
    # > stage_in, dispatch, collect; step > admit > prefill > prefill_call.
    # The decode runs one call ahead: a step in which every live lane's
    # last token is in flight only reads (decode_drain), and the call
    # after it, like the first, finds nothing in flight
    ev = bf_events(tmp_path)
    steps = [e for e in ev if e[0] == "bf:serve.step"]
    assert len(steps) == guard
    assert steps[0][3] == {}
    calls = inside(ev, "bf:engine.decode_call", "bf:serve.step")
    drains = inside(ev, "bf:engine.decode_drain", "bf:serve.step")
    assert drains and len(calls) + len(drains) == guard
    # a decode token is written once per lane and tensor after each stage's
    # layer loop (pp = 2 hops), whatever the number of layers; what the
    # dense program's attention meets of the cache rides the same span
    assert all(e[3] == {"S": e[3]["S"], "cache_writes": e[3]["S"] * 2 * 2,
                        "ahead": e[3]["ahead"],
                        "positions_read": e[3]["positions_reserved"],
                        "positions_reserved": e[3]["positions_reserved"]}
               and e[3]["positions_reserved"] > 0 for e in calls)
    assert [e[3]["ahead"] for e in calls].count(0) == len(drains)
    for name in ("stage_in", "dispatch", "collect"):
        assert len(inside(ev, "bf:engine." + name,
                          "bf:engine.decode_call")) == len(calls), name
    assert len(inside(ev, "bf:engine.collect",
                      "bf:engine.decode_drain")) == len(drains)
    prefills = inside(ev, "bf:serve.prefill", "bf:serve.admit")
    assert sorted(e[3]["prompt_len"] for e in prefills) == sorted(
        len(r.prompt) for r in reqs)
    assert all(set(e[3]) == {"prompt_len", "waited_us"}
               and e[3]["waited_us"] >= 0 for e in prefills)
    inner = inside(ev, "bf:engine.prefill_call", "bf:serve.prefill")
    assert len(inner) == 16
    assert all(e[3]["tokens"] <= e[3]["Tpad"] for e in inner)
    # the slot's sampler key is made inside the prefill program
    assert [e for e in ev if e[0] == "bf:engine.seed_slot"] == []
    packs = inside(ev, "bf:serve.pack", "bf:serve.step")
    assert len(packs) == len(inside(ev, "bf:serve.deliver",
                                    "bf:serve.step")) == len(calls)
    assert all(1 <= e[3]["lanes"] <= e[3]["S"] for e in packs)
    assert len(inside(ev, "bf:train.dispatch", "bf:train.train_step")) == 4


def test_chaos_drill_kill_serving_replica(cpu_devices, tmp_path):
    """A serving replica dies mid-stream (chaos kill on its lead rank):
    its in-flight requests requeue at the head of the queue and EVERY
    request completes on the survivors — zero failures — the refresher
    keeps pulling through the healed topology, and the flight bundle +
    postmortem blame the right rank, with the serve block carrying the
    requeued count."""
    cfg, train_m, (step, state, train_params, toks), eng = \
        _estate(cpu_devices)
    refresher = WeightRefresher(eng, train_m, every=2)
    sched = Scheduler(eng)
    n_train = train_m.size
    dead_replica = 1
    dead_rank = n_train + dead_replica * eng.m.slice_size   # its lead rank

    for i in range(8):
        sched.submit([1 + i, 2, 3, 4], max_new_tokens=4)
    sched.step()                                  # everything in flight
    victims = [r for r in sched._active[dead_replica].values()]
    assert victims, "replica 1 should hold lanes before the kill"

    bfchaos.install(f"kill:step=2,rank={dead_rank}")
    train_done = 0
    try:
        for s in range(1, 4):
            train_params, state, _ = step(train_params, state, toks)
            train_done = s
        raise AssertionError("chaos kill never fired")
    except bfchaos.RankKilled as e:
        assert e.rank == dead_rank
        replica = (e.rank - n_train) // eng.m.slice_size
        lost = sched.fail_replica(replica)
        refresher.mark_dead_serve_replica(replica)
    bfchaos.uninstall()

    assert sorted(r.id for r in lost) == sorted(r.id for r in victims)
    # evicted requests went to the HEAD of the queue, stamped as requeued
    assert all(r.state == "queued" and r.requeued == 1 for r in lost)
    assert [r.id for r in list(sched._queue)[:len(lost)]] == \
        [r.id for r in lost]
    assert sched.requeued_total == len(lost)
    assert bfm.counter("bluefog_requests_total").value(
        status="requeued") == len(lost)
    sched.drain()
    # zero failed requests across the event: the victims re-ran on the
    # survivor and every request completed in full
    assert len(sched.completed) == 8 and not sched.failed
    assert all(r.replica == 0 for r in sched.completed)
    assert all(len(r.generated) == r.max_new_tokens
               for r in sched.completed)
    assert all(r.requeued == 1 for r in lost)

    refresher.pull(train_params, train_done)      # healed topology pulls
    assert refresher.staleness() == 0.0

    bundle_path = tmp_path / "flight_rank0.json"
    bfflight.dump(str(bundle_path), reason="chaos drill")
    bundle = json.loads(bundle_path.read_text())
    sv = bundle["serve"]
    assert sv["dead_replicas"] == [dead_replica]
    assert sv["failed"] == [] and sv["requeued"] == len(lost)
    assert sv["last_request_ids"]["0"], sv

    pm = _load_tool("tools/postmortem")
    report = pm.analyze({0: bundle})
    assert report["verdict"]["first_failed_rank"] == dead_rank
    assert report["serve"]["dead_replicas"] == [dead_replica]
    assert report["serve"]["failed_request_ids"] == []
    sched.close()


# ---------------------------------------------------------------------------
# Serving checkpoints
# ---------------------------------------------------------------------------

def test_serving_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path / "ckpt")
    params = {"blocks": {"w": np.arange(12, dtype=np.float32).reshape(3, 4)},
              "shared": {"e": np.ones((2, 2), np.float32)}}
    p = checkpoint.save_for_serving(d, params, step=7)
    assert os.path.basename(p) == "serving_step_7"
    checkpoint.save_for_serving(d, params, step=9)
    assert checkpoint.all_serving_steps(d) == [7, 9]
    assert checkpoint.latest_serving_step(d) == 9
    got, step = checkpoint.load_for_serving(d)
    assert step == 9
    np.testing.assert_array_equal(got["blocks"]["w"], params["blocks"]["w"])

    # torn export (no completion marker): skipped, older snapshot wins
    torn = os.path.join(d, "serving_step_11")
    os.makedirs(torn)
    assert checkpoint.latest_serving_step(d) == 9
    assert checkpoint.all_serving_steps(d, include_incomplete=True) == \
        [7, 9, 11]
    _, step = checkpoint.load_for_serving(d)
    assert step == 9


def test_serving_checkpoint_rejects_training_state(tmp_path):
    d = str(tmp_path / "ckpt")
    params = {"w": np.ones(3, np.float32)}
    with pytest.raises(ValueError, match="training tuple"):
        checkpoint.save_for_serving(d, (params, {"opt": 1}), step=0)
    with pytest.raises(ValueError, match="training state"):
        checkpoint.save_for_serving(d, {"params": params, "opt_state": 1},
                                    step=0)
    assert checkpoint.load_for_serving(d) == (None, None)


# ---------------------------------------------------------------------------
# Launcher surface
# ---------------------------------------------------------------------------

def test_launcher_serve_env(monkeypatch):
    from bluefog_tpu.run import launcher
    args = launcher.build_parser().parse_args(
        ["--serve", "--serve-buckets", "1,2,4@8,64",
         "--refresh-every", "5", "python", "serve.py"])
    env = launcher._child_env(args)
    assert env["BLUEFOG_SERVE"] == "1"
    assert env["BLUEFOG_SERVE_BUCKETS"] == "1,2,4@8,64"
    assert env["BLUEFOG_REFRESH_EVERY"] == "5"
    # without --serve none of the serving env leaks into the child
    args = launcher.build_parser().parse_args(["python", "x.py"])
    env = launcher._child_env(args)
    assert "BLUEFOG_SERVE" not in env


def test_launcher_serve_defaults_to_demo(monkeypatch):
    from bluefog_tpu.run import launcher
    calls = {}

    def fake_call(cmd, env=None):
        calls["cmd"], calls["env"] = cmd, env
        return 0

    monkeypatch.setattr(launcher.subprocess, "call", fake_call)
    assert launcher.main(["--serve"]) == 0
    assert calls["cmd"] == [sys.executable, "-m", "bluefog_tpu.serve"]
    assert calls["env"]["BLUEFOG_SERVE"] == "1"
    # an explicit command wins over the demo default
    assert launcher.main(["--serve", "python", "my_server.py"]) == 0
    assert calls["cmd"] == ["python", "my_server.py"]
