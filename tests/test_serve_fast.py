"""bluefog_tpu.serve fast path: speculative decoding, prefix pages, int8 KV.

What is pinned here:

* **speculative bit-identity** — ``ServeEngine.spec_decode`` through the
  scheduler produces EXACTLY the plain-greedy token streams (the accept
  rule emits target-argmax tokens only; speculation changes how many
  arrive per call, never which);
* **zero retraces under speculation** — draft + verify-chunk programs are
  compiled at warmup for every batch bucket; a sweep over all buckets
  leaves the retrace sentinel at 0;
* **prefix copy-on-write** — two requests sharing a sealed prefix page
  and then diverging produce byte-identical streams to an engine with
  sharing disabled (sharers can never contaminate each other, and a hit
  is actually recorded);
* **the float64 quantization oracle** — int8/fp8 page storage bounds the
  attention-output drift vs raw float64 pages (int8/fp8 < 5e-2 on unit
  normal kv; raw is exact to 1e-12) — the documented drift bound the KV
  bytes/token halving is priced against;
* **fused sampling determinism** — re-seeding a slot replays the exact
  sampled stream (per-slot PRNG keys live in the decode scan carry);
* **allocator scaling** — the heap free-list stays fast at 50k slots
  (the microbench assert behind the O(log n) claim);
* **config surface** — ``_parse_buckets`` / ``from_env`` reject malformed
  ``BLUEFOG_SPEC_DECODE`` / ``BLUEFOG_KV_DTYPE`` / ``BLUEFOG_PREFIX_PAGES``
  specs naming the offending token and the expected grammar; the
  greedy-only speculation rule; ``DraftCarve`` / ``decoder.rope``
  units;
* **one block definition** — a swapped ``decoder.norm`` reaches every
  program of the composed LM, and the block's parameter shapes are
  counted in one place.
"""
import dataclasses
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import decoder
from bluefog_tpu.parallel import compose
from bluefog_tpu.parallel.compose import draft_carve
from bluefog_tpu.serve import Scheduler, ServeConfig, ServeEngine
from bluefog_tpu.serve.engine import _parse_buckets
from bluefog_tpu.serve.kv_cache import (KVCacheConfig, PrefixCache,
                                        SlotAllocator, attend_rows,
                                        dequantize_rows, quantize_rows,
                                        store_dtype)
from bluefog_tpu.utils import flight as bfflight
from bluefog_tpu.utils import metrics as bfm

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_CFG = dict(vocab=32, d_model=32, heads=4, layers=4, seq_len=32)


@pytest.fixture(autouse=True)
def _clean():
    bfm.reset_metrics()
    bfflight.reset()
    yield
    bfflight.reset()
    bfm.reset_metrics()


# ---------------------------------------------------------------------------
# Quantized page storage units
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_int8():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 7, 2, 8)), jnp.float32)
    q, scale = quantize_rows(x, "int8")
    assert q.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    back = dequantize_rows(q, scale, jnp.float32)
    err = float(jnp.abs(back - x).max())
    amax = float(jnp.abs(x).max())
    assert err <= amax / 127.0 + 1e-6          # half-ulp of the amax grid
    assert err > 0                             # it actually quantized


def test_quantize_roundtrip_fp8():
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 dtype in this jax build")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 3, 8)), jnp.float32)
    q, scale = quantize_rows(x, "fp8")
    assert q.dtype == jnp.float8_e4m3fn
    back = dequantize_rows(q, scale, jnp.float32)
    # e4m3 keeps ~2 significant digits; amax-scaled error stays relative
    assert float(jnp.abs(back - x).max()) < 0.1 * float(jnp.abs(x).max())


def test_quantize_raw_identity():
    x = jnp.ones((2, 3, 4))
    q, scale = quantize_rows(x, "raw")
    assert scale is None and q is x
    assert dequantize_rows(q, None, jnp.float32).dtype == jnp.float32
    with pytest.raises(ValueError, match="unknown KV store"):
        quantize_rows(x, "int4")
    with pytest.raises(ValueError, match="unknown KV store"):
        store_dtype("nvfp4")


def test_kv_config_quantized_bytes():
    kw = dict(layers=2, slots=4, max_len=16, kv_heads=2, head_dim=8)
    raw = KVCacheConfig(**kw)
    q8 = KVCacheConfig(store="int8", **kw)
    assert not raw.quantized and q8.quantized
    # f32 payload: 4 B/elem; int8 payload: 1 B/elem + one f32 scale per
    # (position, head) — at head_dim 8 that is (8 + 4) / 32 of raw
    assert raw.bytes_per_token() == 2 * 2 * 2 * 8 * 4
    assert q8.bytes_per_token() == 2 * 2 * 2 * (8 + 4)
    assert q8.bytes_per_token() <= raw.bytes_per_token() // 2
    assert q8.bytes() < raw.bytes()
    # prefix pages add physical rows behind the request slots
    pc = KVCacheConfig(prefix_slots=2, **kw)
    assert pc.rows == 4 + 2 + 1 and pc.trash_slot == 6
    assert pc.prefix_row(0) == 4 and pc.prefix_row(1) == 5
    with pytest.raises(ValueError, match="out of range"):
        pc.prefix_row(2)


def test_quantized_kv_float64_drift_oracle():
    """attend_rows over int8/fp8 pages vs raw float64 pages: the drift
    bound docs/SERVING.md quotes for the bytes/token halving."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BLUEFOG_")
           and k not in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_ENABLE_X64")}
    p = subprocess.run([sys.executable, "-c", _DRIFT_SCRIPT],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=420, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["raw"] < 1e-12, doc             # raw pages are exact
    assert 0 < doc["int8"] < 5e-2, doc         # the SERVING.md drift bound
    if doc["fp8"] is not None:
        assert 0 < doc["fp8"] < 1e-1, doc      # e4m3: ~2 significant digits


_DRIFT_SCRIPT = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "1"
import json
import jax.numpy as jnp
import numpy as np
from bluefog_tpu.serve.kv_cache import attend_rows, quantize_rows

rng = np.random.default_rng(0)
S, L, H, D = 3, 24, 4, 16
slots = jnp.arange(S, dtype=jnp.int32)
lengths = jnp.asarray([7, 15, 23], jnp.int32)
q = jnp.asarray(rng.normal(size=(S, H, D)))
k = jnp.asarray(rng.normal(size=(S, H, L, D)))
v = jnp.asarray(rng.normal(size=(S, H, L, D)))
ref = attend_rows(q, k, v, slots, lengths)          # float64 raw oracle


def drift(store):
    qk, sk = quantize_rows(k, store)
    qv, sv = quantize_rows(v, store)
    out = attend_rows(q, qk, qv, slots, lengths, k_scale=sk, v_scale=sv)
    return float(jnp.abs(out - ref).max())


fp8 = drift("fp8") if hasattr(jnp, "float8_e4m3fn") else None
raw = float(jnp.abs(
    attend_rows(q, k.astype(jnp.float64), v.astype(jnp.float64),
                slots, lengths) - ref).max())
print(json.dumps({"raw": raw, "int8": drift("int8"), "fp8": fp8}))
"""


# ---------------------------------------------------------------------------
# PrefixCache + SlotAllocator units
# ---------------------------------------------------------------------------

def test_prefix_cache_admit_seal_acquire_release():
    pc = PrefixCache(pages=2, page_tokens=4, first_row=8, replica=1)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]        # share_len = 8 (two pages)
    assert pc.match(prompt) is None
    assert pc.acquire(prompt) is None            # miss counted
    row, plen = pc.admit(prompt)
    assert row == 8 and plen == 8
    assert pc.acquire(prompt) is None            # admitted but not sealed
    pc.seal(row)
    got = pc.acquire(prompt)
    assert got == (8, 8)
    hits = bfm.get_metric("bluefog_serve_prefix_hits_total")
    misses = bfm.get_metric("bluefog_serve_prefix_misses_total")
    assert hits.total() == 1 and misses.total() == 2
    # attach refcounts without touching hit/miss metrics
    pc.attach(row)
    assert hits.total() == 1
    pc.release(row)
    pc.release(row)
    with pytest.raises(ValueError, match="not acquired"):
        pc.release(row)
    # whole pages only, with >= 1 token left over for the request
    assert pc._share_len([1, 2, 3, 4]) == 0      # no leftover token
    assert pc._share_len([1, 2, 3, 4, 5]) == 4
    assert pc.admit([1, 2, 3]) is None
    d = pc.describe()
    assert d["resident"][0]["sealed"] and d["resident"][0]["digest"]


def test_prefix_cache_lru_eviction():
    pc = PrefixCache(pages=2, page_tokens=2, first_row=4)
    r0, _ = pc.admit([1, 1, 9])
    pc.seal(r0)
    r1, _ = pc.admit([2, 2, 9])
    pc.seal(r1)
    assert pc.in_use == 2
    pc.acquire([1, 1, 9])                        # refs r0; r1 is idle LRU
    r2, _ = pc.admit([3, 3, 9])
    assert r2 == r1                              # evicted the idle entry
    assert pc.match([2, 2, 9]) is None
    assert pc.match([1, 1, 9]) is not None
    pc.seal(r2)
    pc.acquire([3, 3, 9])
    assert pc.admit([4, 4, 9]) is None           # everything pinned
    # re-admitting a resident prefix reuses its row instead of a new one
    assert pc.admit([1, 1, 9]) == (r0, 2)


def test_slot_allocator_heap_microbench():
    """50k alloc + 50k free through the heap free-list in well under a
    second — the O(log n) bound behind paged-sharing slot counts (the
    sorted-list predecessor was O(n log n) per free)."""
    n = 50_000
    a = SlotAllocator(n)
    t0 = time.perf_counter()
    slots = [a.alloc() for _ in range(n)]
    for s in slots:
        a.free(s)
    dt = time.perf_counter() - t0
    assert a.in_use == 0
    assert dt < 2.0, f"alloc/free of {n} slots took {dt:.2f}s"
    # lowest-free-first survives the heap rewrite (slot-reuse tests pin it)
    b = SlotAllocator(4)
    assert [b.alloc() for _ in range(4)] == [0, 1, 2, 3]
    b.free(2)
    b.free(0)
    assert b.alloc() == 0 and b.alloc() == 2


# ---------------------------------------------------------------------------
# Config surface: bucket grammar, env parsing, fast-path validation
# ---------------------------------------------------------------------------

def test_parse_buckets_names_offending_token():
    with pytest.raises(ValueError, match=r"bad batch bucket token 'x'"):
        _parse_buckets("1,x@8")
    with pytest.raises(ValueError, match=r"bad prefill bucket token 'q'"):
        _parse_buckets("1,2@8,q")
    with pytest.raises(ValueError, match="expected"):
        _parse_buckets("1@2@3")
    with pytest.raises(ValueError, match="must be >= 1"):
        _parse_buckets("0,2@8")


@pytest.mark.parametrize("var,val,tok", [
    ("BLUEFOG_SPEC_DECODE", "x", "'x'"),
    ("BLUEFOG_SPEC_DECODE", "3@y", "'y'"),
    ("BLUEFOG_KV_DTYPE", "int4", "'int4'"),
    ("BLUEFOG_PREFIX_PAGES", "q", "'q'"),
    ("BLUEFOG_PREFIX_PAGES", "2xz", "'z'"),
    ("BLUEFOG_DECODE_KERNEL", "mosaic", "'mosaic'"),
    ("BLUEFOG_DECODE_KERNEL", "pallas@w", "'w'"),
])
def test_from_env_rejects_bad_specs(monkeypatch, var, val, tok):
    monkeypatch.setenv(var, val)
    with pytest.raises(ValueError) as e:
        ServeConfig.from_env()
    msg = str(e.value)
    assert var in msg and tok in msg and "expected" in msg


def test_from_env_fast_paths(monkeypatch):
    monkeypatch.setenv("BLUEFOG_SPEC_DECODE", "3@1")
    monkeypatch.setenv("BLUEFOG_KV_DTYPE", "int8")
    monkeypatch.setenv("BLUEFOG_PREFIX_PAGES", "2x8")
    monkeypatch.setenv("BLUEFOG_DECODE_KERNEL", "pallas@8")
    cfg = ServeConfig.from_env()
    assert cfg.spec_decode == 3 and cfg.spec_stages == 1
    assert cfg.kv_dtype == "int8"
    assert cfg.prefix_pages == 2 and cfg.prefix_page_tokens == 8
    assert cfg.decode_kernel == "pallas" and cfg.decode_block_k == 8
    # explicit overrides beat the env
    assert ServeConfig.from_env(spec_decode=0).spec_decode == 0
    assert ServeConfig.from_env(decode_kernel="xla").decode_kernel == "xla"


def test_serve_config_fast_validation():
    with pytest.raises(ValueError, match="greedy-only"):
        ServeConfig(spec_decode=2, temperature=0.5)
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeConfig(kv_dtype="int4")
    with pytest.raises(ValueError, match="prefix_page_tokens"):
        ServeConfig(prefix_pages=1, prefix_page_tokens=32,
                    prefill_buckets=(8, 16))
    with pytest.raises(ValueError, match="top_p"):
        ServeConfig(top_p=0.0)
    with pytest.raises(ValueError, match="temperature"):
        ServeConfig(temperature=-0.1)
    with pytest.raises(ValueError, match="decode_kernel"):
        ServeConfig(decode_kernel="cuda")
    with pytest.raises(ValueError, match="does not tile"):
        ServeConfig(decode_kernel="pallas", decode_block_k=24, max_len=64)
    with pytest.raises(ValueError, match="sublane"):
        ServeConfig(decode_kernel="pallas", decode_block_k=4, max_len=64)
    with pytest.raises(ValueError, match="mid-block"):
        ServeConfig(decode_kernel="pallas", decode_block_k=16,
                    prefix_pages=1, prefix_page_tokens=8)
    # block_k clamps to short caches: one block covering max_len is legal
    assert ServeConfig(decode_kernel="pallas", max_len=32,
                       prefill_buckets=(8, 16)).decode_block_k == 128
    assert ServeConfig(decode_steps_per_call=2).decode_window == 2
    assert ServeConfig(spec_decode=3).decode_window == 4


def test_draft_carve(cpu_devices):
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(2, 2, 2, 1, devices=cpu_devices)
    dc = draft_carve(m, cfg, 1)
    assert dc.layers == 2 and dc.total_layers == 4
    assert dc.logit_stage == 1                  # one hop past stage 0
    assert 0.0 < dc.cost_fraction < 1.0
    full = draft_carve(m, cfg, 2)               # identity draft
    assert full.logit_stage == 0 and full.n_params == cfg.n_params
    assert full.cost_fraction == 1.0
    with pytest.raises(ValueError, match="draft stages"):
        draft_carve(m, cfg, 0)
    with pytest.raises(ValueError, match="draft stages"):
        draft_carve(m, cfg, 3)
    assert "stages" in dc.describe()


@pytest.mark.parametrize("rank", ["T", "S", "S,T"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rope_position_ranks(rank, dtype):
    """The one ``rope`` at each rank of positions the programs use — ``[T]``
    against ``[B, T, H, Dh]`` (training, prefill), ``[S]`` against ``[S, H,
    Dh]`` (decode), ``[S, T]`` against ``[S, T, H, Dh]`` (verify, chunked
    prefill) — gives every token the bits the ``[S, T]`` grid gives it."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 5, 2, 8)), dtype)
    pos = jnp.asarray(rng.integers(0, 30, (3, 5)), jnp.int32)
    grid = np.asarray(decoder.rope(x, pos).astype(jnp.float32))
    assert (grid != np.asarray(x.astype(jnp.float32))).any()
    if rank == "T":                              # row s == [T] at pos[s]
        got = [decoder.rope(x[s:s + 1], pos[s])[0] for s in range(3)]
    elif rank == "S":                            # column t == [S] at pos[:, t]
        got = jnp.stack([decoder.rope(x[:, t], pos[:, t])
                         for t in range(5)], axis=1)
    else:                                        # the grid, lanes flattened
        got = decoder.rope(x.reshape(15, 2, 8), pos.reshape(15)).reshape(
            x.shape)
    np.testing.assert_array_equal(
        np.asarray(jnp.asarray(got).astype(jnp.float32)), grid)
    with pytest.raises(ValueError, match="even head_dim"):
        decoder.rope(x[..., :7], pos)


def test_block_param_shapes_agree(cpu_devices):
    """The block's parameter shapes are known in one place: what
    ``init_lm_params`` and ``init_moe_params`` allocate, summed over the
    owners, is what ``n_params`` says, and the draft's count is its
    blocks' plus the shared leaves."""
    from bluefog_tpu.moe import MoELMConfig, init_moe_params
    cfg = compose.LMConfig(**_CFG)
    D, F = cfg.d_model, cfg.ffn_mult * cfg.d_model
    per_block = decoder.block_param_count(cfg)
    assert per_block == D * 3 * D + D * D + D * F + F * D
    assert list(decoder.block_param_shapes(cfg)) == list(
        decoder.ATTENTION_LEAVES + decoder.FFN_LEAVES)
    shared = 2 * cfg.vocab * D

    def owned(tree, m, leaves):
        """Elements over the (stage, tp, expert) owners of replica 0:
        rows of one slice at sp index 0."""
        rows = [i for i in range(m.slice_size)
                if np.unravel_index(i, (m.pp, m.tp, m.sp, m.ep))[2] == 0]
        return sum(int(np.prod(tree[g][k].shape[1:])) * len(rows)
                   for g, k in leaves)

    m = compose.compose_parallelism(1, 2, 2, 2, devices=cpu_devices)
    params = compose.init_lm_params(cfg, m)
    for k, shape in decoder.block_param_shapes(cfg, m.tp).items():
        assert params["blocks"][k].shape == (
            m.size, cfg.layers // m.pp) + shape
    blocks = owned(params, m, [("blocks", k) for k in params["blocks"]])
    assert blocks == cfg.layers * per_block
    assert blocks + shared == cfg.n_params
    for stages in (1, 2):
        assert draft_carve(m, cfg, stages).n_params == (
            stages * (cfg.layers // m.pp) * per_block + shared)

    mm = compose.compose_parallelism(1, 2, 2, 1, 2, num_experts=4,
                                     devices=cpu_devices)
    mcfg = MoELMConfig(num_experts=4, top_k=2, **_CFG)
    mp = init_moe_params(mcfg, mm)
    # the router is replicated over tp and ep, the attention leaves over ep
    attn = owned(mp, mm, [("blocks", k) for k in mp["blocks"]]) // mm.ep
    router = owned(mp, mm, [("router", "wr")]) // (mm.tp * mm.ep)
    experts = owned(mp, mm, [("experts", "w1"), ("experts", "w2")])
    assert attn + router + experts + shared == mcfg.n_params
    assert mcfg.n_params - mcfg.n_active_params == (
        mcfg.layers * (mcfg.num_experts - mcfg.top_k)
        * decoder.block_param_count(mcfg, decoder.FFN_LEAVES))


def _rms(xp):
    """A norm a model configuration might ask for in place of the default
    (no mean subtraction, a per-channel scale), on ``xp`` = jnp for the
    program, np for its oracle."""
    return lambda z: (z / xp.sqrt((z * z).mean(-1, keepdims=True) + 1e-6)
                      * (0.25 + xp.arange(z.shape[-1]) % 4))


def _streamed_block_is_one_definition(cpu_devices, monkeypatch):
    """The streamed latent model's three paths (prefill, decode, the
    whole-sequence forward the checks use) all go through
    ``decoder.latent_block`` with the streams, stream axis major, and
    through ``decoder.hc_coefficients`` twice a layer: an edit to either
    reaches every one of them."""
    import test_serve_latent as tl
    cfg, blocks, maps = tl.STREAMED, [], []
    real_block, real_maps = decoder.latent_block, decoder.hc_coefficients

    def block(cfg_, lp, x, *rest):
        blocks.append(x.shape)
        return real_block(cfg_, lp, x, *rest)

    def coefficients(cfg_, phi, alpha, bias, xs):
        maps.append(xs.shape)
        return real_maps(cfg_, phi, alpha, bias, xs)
    monkeypatch.setattr(decoder, "latent_block", block)
    monkeypatch.setattr(decoder, "hc_coefficients", coefficients)
    eng = tl.make_engine(cpu_devices, cfg, prefill_buckets=(8,))
    # the scan traces its body once: the two dense layers, one expert layer
    traced = 1 + (cfg.dense_layers - 1) + 1
    eng.prefill(0, 0, [1, 2, 3])
    assert blocks == [(4, 8, cfg.d_model)] * traced
    assert maps == [(4, 8, cfg.d_model)] * 2 * traced
    del blocks[:], maps[:]
    trash = eng.cache_cfg.trash_slot
    eng.decode(np.array([[5, 0, 0, 0]]), np.array([[0] + [trash] * 3]),
               np.array([[3, 0, 0, 0]]))
    assert blocks == [(4, 4, cfg.d_model)] * traced
    assert maps == [(4, 4, cfg.d_model)] * 2 * traced
    del blocks[:], maps[:]
    tl.full_forward(cfg, eng.params, [1, 2, 3, 4, 5])
    assert blocks == [(4, 5, cfg.d_model)] * cfg.layers
    assert maps == [(4, 5, cfg.d_model)] * 2 * cfg.layers


@pytest.mark.parametrize("program", ["train", "moe_train", "prefill",
                                     "decode", "chunk", "latent_streams"])
def test_one_block_definition(cpu_devices, monkeypatch, program):
    """A configuration's change is ONE edit: with ``decoder.norm`` swapped
    before a program is built, each of the composed LM's five programs
    follows its independent numpy oracle given the same norm, and leaves
    the oracle of the norm it was built without.  The streamed latent
    block's programs are held to one definition by who they call."""
    if program == "latent_streams":
        return _streamed_block_is_one_definition(cpu_devices, monkeypatch)
    from test_serve import _np_ln, _ref_forward
    from test_serve_moe import _ref_moe_forward
    from jax.sharding import PartitionSpec as P
    monkeypatch.setattr(decoder, "norm", _rms(jnp))
    prompt = [5, 11, 2, 7, 19, 3]

    def copy_loss(forward, rows, lag):          # the copy task's mean CE
        def ce(toks):
            lg = forward(toks)[lag:]
            lg = lg - lg.max(-1, keepdims=True)
            logp = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
            return -logp[np.arange(len(toks) - lag), toks[:-lag]]
        return np.mean([ce(r) for r in rows])

    if program == "train":
        cfg = compose.LMConfig(micro=2, **_CFG)
        m = compose.compose_parallelism(1, 2, 2, 1, devices=cpu_devices[:4])
        params = compose.device_put(m, compose.init_lm_params(cfg, m, seed=3))
        toks = compose.make_lm_batch(cfg, m, seed=1)
        grad_fn = compose.make_lm_grad_fn(cfg, m)
        loss = jax.jit(jax.shard_map(
            lambda p, t: grad_fn(jax.tree.map(lambda v: v[0], p),
                                 t[0])[0][None],
            mesh=m.mesh, in_specs=P(compose.AXES), out_specs=P(compose.AXES),
            check_vma=False))(params, toks)
        got = float(np.asarray(loss)[0])
        Pn = jax.tree.map(np.asarray, params)
        rows = np.asarray(toks)[0].reshape(-1, cfg.seq_len)
        oracle = lambda ln: copy_loss(
            lambda t: _ref_forward(Pn, m, cfg, t, ln), rows, cfg.lag)
    elif program == "moe_train":
        from bluefog_tpu.moe import (MoELMConfig, init_moe_params,
                                     make_moe_batch, make_moe_probe)
        cfg = MoELMConfig(vocab=32, d_model=16, heads=4, layers=2,
                          seq_len=16, micro=1, batch=2, num_experts=4,
                          top_k=2, dispatch="dropless")
        m = compose.compose_parallelism(1, 1, 1, 1, 2, num_experts=4,
                                        devices=cpu_devices[:2])
        params = compose.device_put(m, init_moe_params(cfg, m, seed=5))
        batch = make_moe_batch(cfg, m, seed=1)   # [ep, micro, batch/ep, T]
        got = make_moe_probe(cfg, m)(params, batch)["ce"]
        Pn = jax.tree.map(np.asarray, params)
        rows = np.asarray(batch).reshape(-1, cfg.seq_len)
        oracle = lambda ln: copy_loss(
            lambda t: _ref_moe_forward(Pn, m, cfg, t, ln), rows, cfg.lag)
    else:
        cfg = compose.LMConfig(**_CFG)
        m = compose.compose_parallelism(1, 2, 2, 1, devices=cpu_devices[:4])
        eng = ServeEngine(
            m, cfg, compose.init_lm_params(cfg, m, seed=3),
            ServeConfig(batch_buckets=(1,), prefill_buckets=(8,), slots=2,
                        max_len=32, decode_steps_per_call=1,
                        prefix_pages=int(program == "chunk"),
                        prefix_page_tokens=4))
        Pn = jax.tree.map(np.asarray, eng.params)
        logits = lambda toks, ln: _ref_forward(Pn, m, cfg, toks, ln)
        one = lambda v: np.full((1, 1), v, np.int32)
        if program == "chunk":                  # every position's argmax
            got = eng._chunk_call(np.asarray([[prompt]], np.int32), one(0),
                                  one(0), None, None)[0, 0].tolist()
            oracle = lambda ln: logits(prompt, ln).argmax(-1).tolist()
        elif program == "prefill":              # the last position's logits
            tok, got = eng.prefill(0, 0, prompt)
            oracle = lambda ln: logits(prompt, ln)[-1]
            assert tok == int(np.argmax(oracle(_rms(np))))
        else:                                   # greedy tokens after it
            toks = prompt + [eng.prefill(0, 0, prompt)[0]]
            for _ in range(5):
                toks.append(int(eng.decode(one(toks[-1]), one(0),
                                           one(len(toks) - 1))[0, -1, 0]))
            got = toks[-5:]
            oracle = lambda ln: [int(np.argmax(logits(toks[:i], ln)[-1]))
                                 for i in range(len(toks) - 5, len(toks))]
    want, other = oracle(_rms(np)), oracle(_np_ln)
    if isinstance(want, list):
        assert got == want and got != other, (got, want, other)
    else:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        assert np.abs(np.asarray(want) - np.asarray(other)).max() > 1e-2


# ---------------------------------------------------------------------------
# The fast engine on the 8-rank virtual mesh (dp=2, pp=2, tp=2)
# ---------------------------------------------------------------------------

_SCFG = dict(batch_buckets=(1, 2), prefill_buckets=(4, 8), slots=4,
             max_len=32, decode_steps_per_call=1)


@pytest.fixture(scope="module")
def fast_setup(cpu_devices):
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(2, 2, 2, 1, devices=cpu_devices)
    params = compose.init_lm_params(cfg, m, seed=3)
    fast = ServeEngine(m, cfg, params, ServeConfig(
        spec_decode=2, spec_stages=1, prefix_pages=2, prefix_page_tokens=4,
        **_SCFG))
    fast.warmup()
    plain = ServeEngine(m, cfg, params, ServeConfig(**_SCFG))
    plain.warmup()
    return cfg, m, fast, plain


def _drain(engine, prompts, max_new=6):
    sched = Scheduler(engine)
    reqs = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    guard = 0
    while not sched.done:
        guard += 1
        assert guard < 10_000, "scheduler failed to drain"
        sched.step()
    sched.close()
    return reqs


def test_spec_decode_bit_identical_to_greedy(fast_setup):
    """The tentpole pin: speculative streams ARE the greedy streams."""
    _, _, fast, plain = fast_setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, _CFG["vocab"],
                            int(n)).tolist() for n in (3, 5, 8, 4, 6)]
    want = [r.generated for r in _drain(plain, prompts)]
    bfm.reset_metrics()
    got = [r.generated for r in _drain(fast, prompts)]
    assert got == want
    drafted = bfm.get_metric("bluefog_serve_spec_drafted_total")
    accepted = bfm.get_metric("bluefog_serve_spec_accepted_total")
    assert drafted is not None and drafted.total() > 0
    assert accepted is not None and 0 <= accepted.total() <= drafted.total()


def test_spec_bucket_sweep_zero_retraces(fast_setup):
    """Every draft + verify shape was compiled at warmup: sweeping all
    batch buckets (live + trash lanes) never retraces."""
    _, _, fast, _ = fast_setup
    sizes = fast._jit_sizes()
    nxt, _ = fast.prefill(0, 0, [5, 6, 7])
    for S in fast.scfg.batch_buckets:
        toks = np.zeros((fast.m.dp, S), np.int32)
        slots = np.full((fast.m.dp, S), fast.cache_cfg.trash_slot, np.int32)
        lens = np.zeros((fast.m.dp, S), np.int32)
        toks[0, 0], slots[0, 0], lens[0, 0] = nxt, 0, 3
        emitted, counts = fast.spec_decode(toks, slots, lens)
        assert emitted.shape == (fast.m.dp, S, fast.scfg.spec_decode + 1)
        assert 1 <= int(counts[0, 0]) <= fast.scfg.spec_decode + 1
        assert all(int(t) >= 0 for t in emitted[0, 0, :counts[0, 0]])
        assert all(int(t) == -1 for t in emitted[0, 0, counts[0, 0]:])
        nxt = int(emitted[0, 0, counts[0, 0] - 1])
        lens[0, 0] += int(counts[0, 0])
    assert fast._jit_sizes() == sizes
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0


def test_prefix_cow_no_cross_contamination(fast_setup):
    """Two sharers of one sealed prefix page diverge into private slots:
    both streams byte-match the engine with sharing disabled."""
    _, _, fast, plain = fast_setup
    shared = [3, 1, 4, 1]                        # one page (page_tokens=4)
    a = shared + [5, 9, 2]
    b = shared + [6, 5, 3, 5]
    want = [r.generated for r in _drain(plain, [a, b])]
    bfm.reset_metrics()
    reqs = _drain(fast, [a, b])
    assert [r.generated for r in reqs] == want
    hits = bfm.get_metric("bluefog_serve_prefix_hits_total")
    assert hits is not None and hits.total() >= 1
    assert any(r.prefix_len == 4 for r in reqs)


# ---------------------------------------------------------------------------
# The cache stays where it is: every engine program carries the donated
# cache through its layer loop and updates it in place
# ---------------------------------------------------------------------------

_INPLACE = dict(batch_buckets=(1, 2), prefill_buckets=(4, 8), slots=8,
                max_len=128)
_INPLACE_KINDS = {
    "raw": {},
    "int8": dict(kv_dtype="int8"),
    "fast": dict(spec_decode=2, spec_stages=1, prefix_pages=2,
                 prefix_page_tokens=4),
    "fast_int8": dict(spec_decode=2, spec_stages=1, prefix_pages=2,
                      prefix_page_tokens=4, kv_dtype="int8"),
}


def _state_bytes(cc):
    """What a program's donated state holds on one device: the cache and
    the sampler-key table (two uint32 a row)."""
    return cc.bytes() + cc.rows * 2 * 4


@pytest.fixture(scope="module")
def inplace_engines(cpu_devices):
    """pp = 1 engines, warmed lazily and kept for the module."""
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    params = compose.init_lm_params(cfg, m, seed=3)
    built = {}

    def get(kind):
        if kind not in built:
            built[kind] = ServeEngine(m, cfg, params, ServeConfig(
                **_INPLACE, **_INPLACE_KINDS[kind]))
            built[kind].warmup()
        return built[kind]
    return get


@pytest.mark.parametrize("kind,family,count", [
    ("raw", "decode", 2), ("raw", "prefill", 2),
    ("int8", "decode", 2), ("int8", "prefill", 2),
    ("fast", "draft", 2), ("fast", "chunk", 4),
    ("fast_int8", "draft", 2), ("fast_int8", "chunk", 4),
])
def test_engine_programs_update_cache_in_place(inplace_engines, kind,
                                               family, count):
    """The compiled programs alias their cache and key-table outputs to the
    donated inputs (every byte of them) and hold no buffer as large as one layer's
    pages: the compiler's temporaries are activations, the weights of one
    layer and the rows of the lanes.  A layer loop that scans OVER the
    cache (xs in, ys out) fails both ways: its stacked output is a fresh
    buffer of the whole cache."""
    eng = inplace_engines(kind)
    cc = eng.cache_cfg
    layer_pages = cc.bytes() // cc.layers
    programs = {k: v for k, v in eng.program_memory().items()
                if k.startswith(family)}
    assert len(programs) == count, sorted(eng.program_memory())
    for name, mem in programs.items():
        assert mem["alias_bytes"] == _state_bytes(cc), (name, mem)
        assert mem["temp_bytes"] < layer_pages, (name, mem, layer_pages)


@pytest.fixture(scope="module")
def v5e_chip():
    """One described (not attached) v5e chip as a 1x1x1x1 carving.  Only
    this file's worker loads the TPU compiler, and only once a test here
    asks for it."""
    from jax.experimental import topologies
    try:
        td = topologies.get_topology_desc("v5e:2x2", platform="tpu")
    except Exception as e:          # no libtpu in this environment
        pytest.skip(f"TPU AOT topology unavailable: {e}")
    return compose.compose_parallelism(1, 1, 1, 1, devices=td.devices[:1])


def _v5e_program(m, program, store, fast):
    """Compile one engine program at the serving cell's sizes (pythia-410m:
    24 layers, 16 heads of 64, vocab 50304; 32 slots x 1024, 32 lanes,
    bf16) for the described chip, from shapes alone."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.serve import kv_cache as kv
    L, D, H, V, S, dt = 24, 1024, 16, 50304, 32, jnp.bfloat16
    lm = compose.LMConfig(vocab=V, d_model=D, heads=H, layers=L,
                          ffn_mult=4, seq_len=2048)
    extra = dict(spec_decode=4, spec_stages=1, prefix_pages=2,
                 prefix_page_tokens=64) if fast else {}
    scfg = ServeConfig(batch_buckets=(S,), prefill_buckets=(64, 512),
                       slots=32, max_len=1024, dtype=dt, kv_dtype=store,
                       **extra)
    eng = ServeEngine.__new__(ServeEngine)      # bodies only: no arrays
    eng._moe, eng._moe_chunk_tile = False, None
    eng.m, eng.cfg, eng.scfg = m, lm, scfg
    eng.draft = draft_carve(m, lm, 1) if fast else None
    sh = NamedSharding(m.mesh, m.spec)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        (1,) + tuple(shape), dtype, sharding=sh)
    i32 = lambda *shape: sds(shape, jnp.int32)
    params = {"blocks": {"wqkv": sds((L, D, 3 * D), dt),
                         "wo": sds((L, D, D), dt),
                         "w1": sds((L, D, 4 * D), dt),
                         "w2": sds((L, 4 * D, D), dt)},
              "shared": {"embed": sds((V, D), dt), "head": sds((D, V), dt)}}
    cc = KVCacheConfig(layers=L, slots=32, max_len=1024, kv_heads=H,
                       head_dim=D // H, dtype=dt, store=store,
                       prefix_slots=scfg.prefix_pages)
    cache = {k: sds(v.shape, v.dtype)
             for k, v in jax.eval_shape(lambda: kv.init_cache(cc)).items()}
    keys = sds((cc.rows, 2), jnp.uint32)
    # the one staged array of a call: tokens, then 4 integers a lane or a
    # prompt (6 a chunk's lane)
    body, staged = {
        "decode": (eng._decode_body, i32(S, 1 + 4)),
        "prefill64": (eng._prefill_body, i32(64 + 4)),
        "prefill512": (eng._prefill_body, i32(512 + 4)),
        "draft": (eng._draft_body, i32(S, 1 + 4)),
        "verify": (eng._chunk_body, i32(S, 5 + 6)),
        "chunk512": (eng._chunk_body, i32(1, 512 + 6)),
    }[program]
    with pytest.MonkeyPatch.context() as mp:
        # the kernels' wrappers ask the default backend whether to lower
        # for the chip or for the interpreter: the chip described, here
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return eng._build(body).lower(params, cache, keys,
                                      staged).compile(), cc


_SLOW = pytest.mark.slow
# on the chip the key table's 66 words are padded to whole tiles
_V5E_KEY_TABLE = 4096


@pytest.mark.parametrize("program,store,fast", [
    ("decode", "raw", False), ("prefill64", "raw", False),
    ("prefill512", "raw", False),
    pytest.param("decode", "raw", True, marks=_SLOW),
    pytest.param("draft", "raw", True, marks=_SLOW),
    pytest.param("verify", "raw", True, marks=_SLOW),
    pytest.param("chunk512", "raw", True, marks=_SLOW),
    pytest.param("decode", "int8", False, marks=_SLOW),
    pytest.param("prefill64", "int8", False, marks=_SLOW),
    pytest.param("verify", "int8", True, marks=_SLOW),
])
def test_engine_programs_in_place_on_v5e(v5e_chip, program, store, fast):
    """What the chip's own compiler builds at the serving cell's sizes:
    the output cache is the donated input, and no instruction outside a
    fusion makes a buffer half as large as the K tensor (a copy of the
    cache into another axis order, a layer loop's stacked output, a
    gather's four-way cut of its operand).  The plain programs the cell
    runs hold less than one layer's pages in all their temporaries.
    The axis order the compiler picks for the carried cache differs from
    program to program when left to it (the 64-token prefill copied the
    cache where the 512-token one did not), so each is pinned here."""
    from bluefog_tpu.utils.hlo_bytes import materialized
    compiled, cc = _v5e_program(v5e_chip, program, store, fast)
    mem = compiled.memory_analysis()
    assert _state_bytes(cc) <= mem.alias_size_in_bytes \
        <= cc.bytes() + _V5E_KEY_TABLE
    k_bytes = cc.bytes() // 2 if store == "raw" else \
        cc.layers * cc.rows * cc.kv_heads * cc.max_len * cc.head_dim
    assert materialized(compiled.as_text(), k_bytes // 2) == []
    if store == "raw" and not fast:
        assert mem.temp_size_in_bytes < cc.bytes() // cc.layers


def test_the_delta_rule_kernel_lowers_for_v5e_at_the_cells_sizes(v5e_chip):
    """A prompt's delta rule at ``solar-open2``'s sizes (a block of 1,024
    positions, 64 heads of 128 key and 128 value channels, chunks of 16,
    bfloat16 activations) through Mosaic for the described chip: one kernel,
    its blocks lane-aligned as the arrays lie and its VMEM under the scoped
    limit, and nothing of ``[block, heads, 128]`` size made beside it (no
    transpose before the kernel, no copy after it)."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.ops import pallas_delta
    T, H, K = 1024, 64, 128
    sh = NamedSharding(v5e_chip.mesh, v5e_chip.spec)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        (1,) + shape, dtype, sharding=sh)
    wide = lambda dtype: sds((T, H * K), dtype)

    def per_chip(q, k, v, g, beta, state):
        out = pallas_delta.delta_rule(q[0], k[0], v[0], g[0], beta[0],
                                      state[0], chunk=16, interpret=False)
        return jax.tree.map(lambda t: t[None], out)
    compiled = jax.jit(jax.shard_map(
        per_chip, mesh=v5e_chip.mesh, in_specs=(v5e_chip.spec,) * 6,
        out_specs=v5e_chip.spec, check_vma=False)).lower(
        wide(jnp.bfloat16), wide(jnp.bfloat16), wide(jnp.bfloat16),
        wide(jnp.float32), sds((T, H), jnp.float32),
        sds((H, K, K), jnp.float32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < T * H * K


def test_the_lm_steps_read_out_holds_the_logits_once_on_v5e(v5e_chip):
    """``make_lm_grad_fn`` at ``pythia-410m``'s read-out sizes (2,048
    positions of 1,024 against 50,304 columns, float32, one layer) compiled
    for the described chip: the float32 logits are the ONE array of their
    size the gradient program writes.  The slice ``[:, :, lag:]`` was a copy
    of all of them (2,046 rows) and ``take_along_axis`` came back as a
    scatter-add into a second array and a bfloat16 copy of it; now
    ``softmax - onehot`` is made inside both backward products."""
    from jax.sharding import NamedSharding
    m, T, D, V = v5e_chip, 2048, 1024, 50304
    lm = compose.LMConfig(vocab=V, d_model=D, heads=16, layers=1, seq_len=T,
                          micro=1, batch=1)
    sh = NamedSharding(m.mesh, m.spec)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        (1,) + tuple(shape), dtype, sharding=sh)
    params = {"blocks": {k: sds((1,) + v, jnp.float32) for k, v in
                         decoder.block_param_shapes(lm).items()},
              "shared": {"embed": sds((V, D), jnp.float32),
                         "head": sds((D, V), jnp.float32)}}
    grad_fn = compose.make_lm_grad_fn(lm, m)

    def per_device(p, t):
        loss, g = grad_fn(jax.tree.map(lambda v: v[0], p), t[0])
        return loss[None], jax.tree.map(lambda v: v[None], g)
    text = jax.jit(jax.shard_map(
        per_device, mesh=m.mesh, in_specs=m.spec, out_specs=m.spec,
        check_vma=False)).lower(
            params, sds((1, 1, T), jnp.int32)).compile().as_text()
    entry = text[text.index("ENTRY"):]
    # what an instruction of the entry computation WRITES (a parameter, a
    # bitcast, a tuple or an element of one names an array, it makes none)
    made = [shape for shape, op in re.findall(
        r" = (\(.*?\)|\S+) ([a-z\-]+)\(", entry)
        if op not in ("parameter", "bitcast", "tuple", "get-tuple-element")]
    sized = [a for shape in made
             for a in re.findall(r"([a-z]+\d+)\[[\d,]*204[68],50304\]", shape)]
    assert sized == ["f32"], sized


@pytest.fixture(scope="module")
def v5e_decode(v5e_chip):
    """The cell's decode program compiled for the described chip, once per
    store for the module: ``(compiled text, memory analysis, cache
    config)``."""
    built = {}

    def get(store):
        if store not in built:
            compiled, cc = _v5e_program(v5e_chip, "decode", store, False)
            built[store] = (compiled.as_text(), compiled.memory_analysis(),
                            cc)
        return built[store]
    return get


def _loop_body(hlo_txt):
    """The lines of the (one) ``while`` body of a compiled decode program
    of one step a call: the layer loop."""
    import re
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo_txt))
    assert len(bodies) == 1, bodies
    return _computation(hlo_txt, bodies.pop())


def _computation(hlo_txt, name):
    """The lines of one named computation of a compiled module."""
    start = hlo_txt.index(f"%{name} (")
    return hlo_txt[start:hlo_txt.index("\n}\n", start)].splitlines()


def _results_of_shape(hlo_lines, dims):
    """Those of ``hlo_lines`` (a compiled module's instructions, inside
    fusions too) whose result is an array of exactly ``dims``, whatever
    its dtype."""
    import re
    shape = re.compile(r"^\(?\w+\[%s\]" % ",".join(map(str, dims)))
    return [ln for ln in hlo_lines
            if " = " in ln and shape.match(ln.split(" = ", 1)[1])]


_DECODE_CHECKS = ["no_write_in_the_loop", "one_write_per_lane_after_it",
                  "aliased_whole", "temporaries", "no_half_tensor",
                  "a_window_is_a_token_row"]


@pytest.mark.parametrize("store,check", [
    ("raw", c) for c in _DECODE_CHECKS + ["no_rows_staged"]] + [
    pytest.param("int8", c, marks=_SLOW)
    for c in _DECODE_CHECKS + ["rows_staged_on_chip"]])
def test_decode_writes_after_layer_loop_on_v5e(v5e_decode, store, check):
    """The decode program at the serving cell's sizes, as the chip's own
    compiler builds it: the layer loop's body updates no cache-shaped
    buffer, and after it comes exactly one ``dynamic-update-slice`` per
    lane and tensor (32 x 2; x 4 with the scales of a quantized store),
    where the per-layer append made 24 times that in the loop.  The
    writes did not come back under another name: the donated cache is
    aliased whole, all temporaries together stay under one layer's pages
    (every lane's update window laid out at once, each padded from one
    position to the 128 of a tile, was 280 MB), nothing materialises half
    a K tensor.  The raw store's attention reads the pages where they
    lie, token rows ``[max_len, kv_heads * head_dim]`` = ``[1024, 1024]``
    (``kv_cache.page_order``): no instruction, inside a fusion or out,
    makes the lanes' staged rows ``[32, 1024, 1024]`` or the logical view
    of them ``[32, 16, 1024, 64]``, none outside a fusion a layer's pages
    ``[33, 1024, 1024]``, and the loop's body holds NO conditional and ONE
    kernel call (``pallas_decode.attend_live_blocks``, through Mosaic),
    under the ``cache.read`` scope, whose operands the two stacked tensors
    are, whole and as the loop carries them: a lane's blocks are fetched
    from where they lie by the kernel's own copies.  one pass each).  Each of the 64 windows
    written is a token's row of every layer, ``[24, 1, 1, 1024]`` with
    the 1,024 minor as in the cache: 8 tiles a layer, where the same
    token in a cache with the positions minor touched 64, the property
    the write's cost rests on.  The quantized store still stages: each
    tensor's rows (32 lanes x 1 MB, built by one row read per lane) have
    their turn in the chip's on-chip memory (``S(1)`` in the layout; V's
    are read once K's are dead), not a round trip through HBM per
    layer."""
    import re
    from bluefog_tpu.utils.hlo_bytes import loop_writes, materialized
    txt, mem, cc = v5e_decode(store)
    lanes = 32
    scales = cc.layers * cc.rows * cc.kv_heads * cc.max_len
    pages = scales * cc.head_dim
    inside = outside = 0
    for elements in (pages,) + ((scales,) if cc.quantized else ()):
        i, o = loop_writes(txt, elements)
        inside, outside = inside + i, outside + o
    row = cc.shapes()["k"][2:]                  # a row as it is stored
    assert cc.page_order == "token_rows" and row == (1024, 1024)
    if check == "no_write_in_the_loop":
        assert inside == 0
    elif check == "one_write_per_lane_after_it":
        assert outside == lanes * (4 if cc.quantized else 2)
    elif check == "aliased_whole":
        assert _state_bytes(cc) <= mem.alias_size_in_bytes \
            <= cc.bytes() + _V5E_KEY_TABLE
    elif check == "temporaries":
        assert mem.temp_size_in_bytes < cc.bytes() // cc.layers
    elif check == "no_half_tensor":
        k_bytes = pages * jnp.dtype(store_dtype(store, cc.dtype)).itemsize
        assert materialized(txt, k_bytes // 2) == []
    elif check == "a_window_is_a_token_row":
        # the update operand of every write of a whole K or V tensor
        # (under whatever view: the last of each carries a leading 1)
        defs = {ln.split(" = ")[0].replace("ROOT", "").strip():
                ln.split(" = ", 1)[1] for ln in txt.splitlines()
                if " = " in ln}
        whole = re.compile(r"\w+\[(?:1,)?%d,%d,1024,1024\]\S* "
                           r"dynamic-update-slice\([^,]+, ([^,]+),"
                           % (cc.layers, cc.rows))
        windows = [defs[m.group(1)] for m in map(whole.match, defs.values())
                   if m]
        assert len(windows) == 2 * lanes
        for w in windows:           # bf16[24,1,1,1024]{3,2,1,0:T(...)...}
            dims, minor = re.match(r"\w+\[([\d,]+)\]\{(\d)", w).groups()
            dims = dims.split(",")
            assert dims[-4:] == [str(cc.layers), "1", "1", "1024"] and \
                int(minor) == len(dims) - 1, w[:80]
    elif check == "no_rows_staged":
        logical = (cc.kv_heads, cc.max_len, cc.head_dim)
        for staged in ((lanes,) + row, (lanes,) + logical):
            assert _results_of_shape(txt.splitlines(), staged) == []
        assert materialized(txt, 1, (cc.rows,) + row) == []
        assert materialized(txt, 1, (cc.layers, cc.rows) + row) == []
        # the loop's body chooses nothing and hands both stacked tensors,
        # as it carries them, to its one kernel call
        body = _loop_body(txt)
        stacked = [ln.split(" = ")[0].strip() for ln in _results_of_shape(
            body, (cc.layers, cc.rows) + row)]
        calls = [ln for ln in body if "tpu_custom_call" in ln]
        assert [ln for ln in body if " conditional(" in ln] == []
        assert len(stacked) == 2 and len(calls) == 1
        assert all(f"{t})" in calls[0] or f"{t}," in calls[0]
                   for t in stacked), calls[0][:400]
        assert "cache.read/pallas_call" in calls[0]
        assert txt.count("tpu_custom_call") == 1
    else:
        # the lanes' rows as they are staged: token rows, their lanes
        # split into heads on the way to the logical view
        rows = _results_of_shape(
            _loop_body(txt),
            (lanes, cc.max_len, cc.kv_heads, cc.head_dim))
        assert len(rows) >= 2 * lanes, len(rows)
        off_chip = [ln.split(" = ")[0].strip() for ln in rows
                    if "S(1)" not in ln.split(" = ")[1].split(" ")[0]]
        assert off_chip == []


def _v5e_latent_decode(m):
    """The latent decode program at the ``a.x-k1`` cell's sizes (6 layers,
    129 rows x 2,560, ranks 512 + 64, 128 lanes of 64 heads, bf16) for
    the described chip, from shapes alone."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.models import decoder
    from bluefog_tpu.serve import kv_cache as kv
    S, dt = 128, jnp.bfloat16
    cfg = decoder.LatentConfig(
        vocab=20480, d_model=7168, heads=64, layers=6, q_rank=1536,
        kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, dense_ffn=18432,
        expert_ffn=2048, num_experts=192, held_experts=12, top_k=8,
        n_group=8, topk_group=4, route_scale=2.5, rope_factor=32.0,
        rope_mscale_all_dim=1.0)
    eng = ServeEngine.__new__(ServeEngine)      # bodies only: no arrays
    eng._moe, eng._latent, eng._hybrid, eng._share = False, True, False, True
    eng.m, eng.cfg = m, cfg
    eng.scfg = ServeConfig(batch_buckets=(S,), prefill_buckets=(256, 2048),
                           slots=128, max_len=2560, dtype=dt)
    sh = NamedSharding(m.mesh, m.spec)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        (1,) + tuple(shape), dtype, sharding=sh)
    params = {g: {k: sds(shape, jnp.float32 if k == "wr" else dt)
                  for k, shape in grp.items()}
              for g, grp in decoder.latent_param_shapes(cfg).items()}
    cc = kv.LatentCacheConfig(layers=cfg.layers, slots=128, max_len=2560,
                              kv_rank=cfg.kv_rank, rope_dim=cfg.rope_dim,
                              dtype=dt)
    cache = {k: sds(shape, dt) for k, shape in cc.shapes().items()}
    return eng._build(eng._latent_decode_body).lower(
        params, cache, sds((cc.rows, 2), jnp.uint32),
        sds((S, 1 + 4), jnp.int32)).compile(), cc


@pytest.fixture(scope="module")
def v5e_latent_decode(v5e_chip):
    compiled, cc = _v5e_latent_decode(v5e_chip)
    return compiled.as_text(), compiled.memory_analysis(), cc


@pytest.mark.parametrize("check", [
    "no_rows_staged", "no_layer_copied", "aliased_whole"])
def test_latent_decode_reads_in_place_on_v5e(v5e_latent_decode, check):
    """The latent decode program at the ``a.x-k1`` cell's sizes, as the
    chip's own compiler builds it: no instruction, inside a fusion or
    out, makes the lanes' staged rows (``[128, 2560, 512]`` compressed
    vectors, 336 MB a layer written and read again, and ``[128, 2560,
    64]`` rotary keys), none outside a fusion a layer of either tensor
    or a copy of the whole (the layer's slice reaches the matmuls inside
    their fusions), and the donated cache is the output."""
    from bluefog_tpu.utils.hlo_bytes import materialized
    txt, mem, cc = v5e_latent_decode
    lanes = 128
    if check == "no_rows_staged":
        for dim in (cc.kv_rank, cc.rope_dim):
            assert _results_of_shape(
                txt.splitlines(), (lanes, cc.max_len, dim)) == []
    elif check == "no_layer_copied":
        for dim in (cc.kv_rank, cc.rope_dim):
            for lead in ((cc.rows,), (cc.layers, cc.rows)):
                assert materialized(
                    txt, 1, lead + (cc.max_len, dim)) == []
        # what is left: float32 scores and probabilities [129, 64, 2560]
        # and the layers' weights on their way in, under a quarter of
        # the cache (the staged form's temporaries were 0.96 GB)
        assert mem.temp_size_in_bytes < cc.bytes() // 4
    else:
        assert cc.bytes() <= mem.alias_size_in_bytes \
            <= cc.bytes() + _V5E_KEY_TABLE


@pytest.mark.parametrize("family", ["dense", "latent"])
def test_scope_table_names_the_decode_programs_the_chip_compiles(
        family, request):
    """The two decode programs at their cells' sizes as the chip's own
    compiler builds them (compiled once for the tests above): at least
    0.9 of their fusions, dots, custom calls and in-place writes, by
    count, fall under a scope of the vocabulary, every scope of the
    family's decode vocabulary is there, and the 64 (dense) / 256
    (latent) token writes after the layer loop are ``cache.write``."""
    from bluefog_tpu.utils import hlo_bytes, tracing
    txt = request.getfixturevalue(
        "v5e_decode")("raw")[0] if family == "dense" else \
        request.getfixturevalue("v5e_latent_decode")[0]
    tab = tracing._scope_table(txt)
    _, comps = hlo_bytes.op_names(txt)
    heavy = [n for ops in comps.values() for n, r in ops.items()
             if r[0] in ("fusion", "dot", "custom-call",
                         "dynamic-update-slice", "convolution")]
    named = [n for n in heavy if tab["ops"][n][0]]
    assert len(named) >= 0.9 * len(heavy), (len(named), len(heavy))
    want = {"dense": {"attn.project", "cache.read", "cache.write", "ffn",
                      "readout"},
            "latent": {"mla.project", "mla.attend", "cache.read",
                       "cache.write", "ffn", "moe.route", "moe.experts",
                       "moe.shared", "readout"}}[family]
    assert want <= {scope for scope, _ in tab["ops"].values()}
    writes = [n for n in heavy if tab["ops"][n] == ("cache.write", "")]
    assert len(writes) >= {"dense": 64, "latent": 256}[family]


def test_cache_copy_gauge_set_at_warmup(cpu_devices):
    """bluefog_serve_cache_copy_bytes{program} / ..._alias_bytes{program}
    carry what ``program_memory`` holds, one series per warmed program,
    and a steady-state call adds none."""
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    eng = ServeEngine(m, cfg, compose.init_lm_params(cfg, m, seed=3),
                      ServeConfig(**_INPLACE))
    eng.warmup()
    mem = eng.program_memory()
    assert sorted(mem) == ["decode S=1", "decode S=2", "prefill Tpad=4",
                           "prefill Tpad=8"]
    copy = bfm.get_metric("bluefog_serve_cache_copy_bytes")
    alias = bfm.get_metric("bluefog_serve_cache_alias_bytes")
    writes = bfm.get_metric("bluefog_serve_cache_writes_per_call")
    for name, row in mem.items():
        assert copy.value(program=name) == row["temp_bytes"]
        assert alias.value(program=name) == row["alias_bytes"] \
            == _state_bytes(eng.cache_cfg)
        assert writes.value(program=name) == row["cache_writes"]
    # lanes x 2 tensors once after the layer loop for a decode token; a
    # prefill writes its one row once per layer (4) and tensor
    assert {k: v["cache_writes"] for k, v in mem.items()} == {
        "decode S=1": 2, "decode S=2": 4, "prefill Tpad=4": 8,
        "prefill Tpad=8": 8}
    eng.prefill(0, 0, [5, 6, 7])
    assert eng.program_memory() == mem
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0


def test_pp2_tokens_equal_pp1(cpu_devices):
    """The same model carved over two pipeline stages (where the stage-id
    select keeps one stage's cache writes per hop) and over one (where
    the carried cache goes straight through) serves the same tokens and
    the same prefill logits, bit for bit."""
    cfg = compose.LMConfig(**_CFG)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, _CFG["vocab"], int(n)).tolist()
               for n in (3, 7, 5, 8)]
    out = {}
    for pp in (1, 2):
        m = compose.compose_parallelism(1, pp, 1, 1,
                                        devices=cpu_devices[:pp])
        # init_lm_params draws [pp, tp, layers/pp, ...] from one stream,
        # so both carvings hold the same 4 layers in the same order
        eng = ServeEngine(m, cfg, compose.init_lm_params(cfg, m, seed=3),
                          ServeConfig(**_SCFG))
        eng.warmup()
        logits = eng.prefill(0, 0, prompts[0])[1]
        out[pp] = ([r.generated for r in _drain(eng, prompts)], logits)
    assert out[1][0] == out[2][0]
    np.testing.assert_array_equal(out[1][1], out[2][1])


class _AppendPerLayer(ServeEngine):
    """The engine with a decode token written into every layer's pages
    inside the layer loop, before that layer's XLA attention reads them:
    the form the write after the loop replaces (and what the Pallas path
    still does), kept as the reference."""
    _defer_appends = False


_DEFER_ENGINES = {
    # name: ((dp, pp, tp), ServeConfig overrides, cache writes of one
    # 2-lane decode call: deferred, per layer)
    "dp2_pp2_tp2": ((2, 2, 2), {}, (2 * 2 * 2, 2 * 2 * 4)),
    "int8": ((1, 1, 1), dict(kv_dtype="int8"), (2 * 4, 2 * 4 * 4)),
    "spec_prefix_pp2": ((1, 2, 1), dict(
        spec_decode=2, spec_stages=1, prefix_pages=2,
        prefix_page_tokens=4), (2 * 2 * 2, 2 * 2 * 4)),
    "two_tokens_a_call": ((1, 1, 1), dict(decode_steps_per_call=2),
                          (2 * 2 * 2, 2 * 2 * 4 * 2)),
}


@pytest.mark.parametrize("name", sorted(_DEFER_ENGINES))
def test_write_after_loop_engine_equals_per_layer_engine(cpu_devices, name):
    """Engine against engine, differing only in when a decode token
    reaches the cache: the same tokens for every request, the same cache
    in every row a request can read, no retrace, and ``cache_writes`` as
    the shapes count them (lanes x tensors x stage hops x fused steps,
    times the stage's layers when the write is in the loop)."""
    (dp, pp, tp), extra, counted = _DEFER_ENGINES[name]
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(dp, pp, tp, 1,
                                    devices=cpu_devices[:dp * pp * tp])
    params = compose.init_lm_params(cfg, m, seed=3)
    rng = np.random.default_rng(11)
    shared = [3, 1, 4, 1]                        # one page (page_tokens=4)
    prompts = [rng.integers(0, _CFG["vocab"], int(n)).tolist()
               for n in (3, 7, 5, 8, 4)] + [shared + [5, 9, 2],
                                            shared + [6, 5, 3, 5]]
    out = []
    # both through ONE read form, the staged one: a token already written
    # is not beside the pages, so the per-layer engine stages, and floats
    # compared to the bit must have been summed in one order
    for cls in (_StagedRead, _AppendPerLayer):
        eng = cls(m, cfg, params, ServeConfig(**{**_SCFG, **extra}))
        eng.warmup()
        toks = [r.generated for r in _drain(eng, prompts, max_new=7)]
        cache = {k: np.asarray(v.astype(jnp.float32))[
            :, :, :eng.cache_cfg.trash_slot] for k, v in eng.cache.items()}
        out.append((toks, cache,
                    eng.program_memory()["decode S=2"]["cache_writes"]))
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    (toks, cache, writes), (ref_toks, ref_cache, ref_writes) = out
    assert toks == ref_toks
    assert (writes, ref_writes) == counted
    for k in ref_cache:
        np.testing.assert_array_equal(cache[k], ref_cache[k])


class _StagedRead(ServeEngine):
    """The engine whose decode attention stages each lane's row before it
    attends over it (``attend_rows``): the form the in-place read
    replaces on a raw cache without prefix pages, kept as the
    reference."""
    _read_in_place = False


_READ_ENGINES = {
    # name: ((dp, pp, tp), ServeConfig overrides)
    "one_chip": ((1, 1, 1), {}),
    "dp2_pp2_tp2": ((2, 2, 2), {}),
    "two_tokens_a_call": ((1, 1, 1), dict(decode_steps_per_call=2)),
    "spec_draft": ((1, 2, 1), dict(spec_decode=2, spec_stages=1)),
}


@pytest.mark.parametrize("name", sorted(_READ_ENGINES))
def test_in_place_read_engine_serves_the_staged_engines_tokens(cpu_devices,
                                                               name):
    """Engine against engine, differing only in how decode attention
    meets the cache: the same greedy tokens for every request (the sums
    run in another order, so floats are held at the oracle tests'
    tolerance there and to the token here), ``program_memory`` names the
    form per decode program (a one-lane bucket of five rows is under a
    third of them and stages by its shapes), and the positions counter
    advances by layers x rows x max_len a fused step where the read is in
    place, by the lanes' rows where it is staged."""
    (dp, pp, tp), extra = _READ_ENGINES[name]
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(dp, pp, tp, 1,
                                    devices=cpu_devices[:dp * pp * tp])
    params = compose.init_lm_params(cfg, m, seed=3)
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, _CFG["vocab"], int(n)).tolist()
               for n in (3, 7, 5, 8, 4, 6)]
    toks, per_call = {}, {}
    for cls in (ServeEngine, _StagedRead):
        eng = cls(m, cfg, params, ServeConfig(**{**_SCFG, **extra}))
        eng.warmup()
        form = "in_place" if cls is ServeEngine else "staged"
        mem = eng.program_memory()
        assert mem["decode S=2"]["read"] == form
        assert mem["decode S=1"]["read"] == "staged"
        assert "read" not in mem["prefill Tpad=4"]
        if eng.scfg.spec_decode:
            assert mem["draft S=2"]["read"] == form
        toks[form] = [r.generated for r in _drain(eng, prompts, max_new=7)]
        read = bfm.counter("bluefog_serve_cache_positions_read_total")
        before = read.value(kind="full")
        tok, slot, ln = eng.idle_lane()
        full = lambda v: np.full((dp, 2), v, np.int32)
        eng.decode(full(tok), full(slot), full(ln))
        per_call[form] = read.value(kind="full") - before
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    assert toks["in_place"] == toks["staged"]
    cc, steps = eng.cache_cfg, eng.scfg.decode_steps_per_call
    assert per_call == {
        "in_place": dp * cfg.layers * steps * cc.rows * cc.max_len,
        "staged": dp * cfg.layers * steps * 2 * cc.max_len}


_ROW_ENGINES = {
    # name: ((dp, pp, tp), heads of 64, ServeConfig overrides, how the
    # decode program reads the cache).  In place the sums run in another
    # order, and a staged read's logical view is a turned operand of its
    # matmuls under jit: floats to a tolerance, tokens to the token
    "one_chip": ((1, 1, 1), 2, {}, "in_place"),
    "gossip2_tp2": ((2, 1, 2), 4, {}, "in_place"),
    "two_tokens_a_call": ((1, 1, 1), 2, dict(decode_steps_per_call=2),
                          "in_place"),
    "int8": ((1, 1, 1), 2, dict(kv_dtype="int8"), "staged"),
    "spec_prefix_pp2": ((1, 2, 1), 2, dict(
        spec_decode=2, spec_stages=1, prefix_pages=2,
        prefix_page_tokens=4), "staged"),
    "flash_kernel": ((1, 1, 1), 2, dict(decode_kernel="pallas",
                                        decode_block_k=8), "staged"),
}


@pytest.mark.parametrize("name", sorted(_ROW_ENGINES))
def test_token_row_engine_serves_the_by_head_engines_tokens(
        cpu_devices, monkeypatch, name):
    """Engine against engine, differing only in the order the dense
    cache's pages lie in: heads of 64 whose product fills the lanes are
    kept as token rows (``kv_cache.page_order``), and the same model over
    a cache held by head (the order forced for the reference) serves the
    same greedy tokens for every request, through every path that meets
    the cache: the in-place read, the staged read of a quantized store,
    prefix pages with copy-on-write suffixes, the draft and verify
    chunks, the flash-decode kernel's view.  ``program_memory()`` names
    the order of every tensor, and the two caches hold the same pages
    under the logical view."""
    from bluefog_tpu.serve import kv_cache as kv
    (dp, pp, tp), heads, extra, form = _ROW_ENGINES[name]
    cfg = compose.LMConfig(vocab=32, d_model=64 * heads, heads=heads,
                           layers=2, seq_len=32)
    m = compose.compose_parallelism(dp, pp, tp, 1,
                                    devices=cpu_devices[:dp * pp * tp])
    params = compose.init_lm_params(cfg, m, seed=3)
    rng = np.random.default_rng(23)
    shared = [3, 1, 4, 1]                        # one page (page_tokens=4)
    prompts = [rng.integers(0, 32, int(n)).tolist()
               for n in (3, 7, 5, 8, 4)] + [shared + [5, 9, 2],
                                            shared + [6, 5, 3, 5]]
    by_head = lambda kv_heads, head_dim, max_len: "positions_minor"
    out = {}
    for order in ("token_rows", "positions_minor"):
        if order != "token_rows":
            monkeypatch.setattr(kv, "page_order", by_head)
        eng = ServeEngine(m, cfg, params, ServeConfig(**{**_SCFG, **extra}))
        cc = eng.cache_cfg
        assert cc.page_order == order
        assert eng.cache["k"].shape[1:] == cc.shapes()["k"] == (
            (cc.layers, cc.rows, cc.max_len, cc.kv_heads * 64)
            if order == "token_rows" else
            (cc.layers, cc.rows, cc.kv_heads, cc.max_len, 64))
        eng.warmup()
        mem = eng.program_memory()
        assert all(row["pages"] == cc.page_orders() for row in mem.values())
        assert mem["decode S=2"]["pages"]["k"] == order
        assert mem["decode S=2"]["read"] == form
        toks = [r.generated for r in _drain(eng, prompts, max_new=7)]
        # per device: what every reader but the in-place one sees
        pages = {k: np.stack([np.asarray(
            (kv.logical_pages(t, 64, True) if k in ("k", "v") else t
             ).astype(jnp.float32))[:, :cc.trash_slot] for t in v])
            for k, v in eng.cache.items()}
        out[order] = (toks, pages)
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    (toks, pages), (ref_toks, ref_pages) = out.values()
    assert toks == ref_toks
    for k in ref_pages:
        np.testing.assert_allclose(pages[k], ref_pages[k], rtol=1e-3,
                                   atol=1e-5)


_BOUNDED_ENGINES = {
    # name: (heads, d_model, fused steps a call, (dp, pp, tp), more of the
    # ServeConfig): heads of 64 side by side are token rows (two of them a
    # tp rank where tp is 2), four heads of 8 are kept by head, positions
    # minor
    "token_rows": (2, 128, 1, (1, 1, 1), {}),
    "token_rows_two_tokens_a_call": (2, 128, 2, (1, 1, 1), {}),
    "token_rows_tp2": (4, 256, 1, (1, 1, 2), {}),
    "token_rows_draft": (2, 128, 1, (1, 2, 1),
                         dict(spec_decode=2, spec_stages=1)),
    "by_head": (4, 32, 1, (1, 1, 1), {}),
}


@pytest.mark.parametrize("name", sorted(_BOUNDED_ENGINES))
def test_dense_decode_read_stops_where_the_live_lanes_end(
        cpu_devices, monkeypatch, name):
    """A dense engine whose rows hold two blocks of 128 positions decodes
    across the block's edge (a prompt of 120 tokens beside short ones, 13
    new tokens each; with two tokens a call the edge falls between a
    call's two fused steps) and serves the greedy tokens of the engine
    that reads every row whole (a row of one block: one bound, no kernel).
    TOKEN ROWS stop at each lane's own last block, inside the kernel
    (interpreter mode here) of the ONE decode program of the bucket, at tp
    1 and 2 and as the speculative draft; pages kept BY HEAD at the
    batch's longest live lane, chosen by the one conditional of the
    program's one layer loop (the slice of a layer's K and of its V pages
    at each of ``read_bounds(max_len)`` stands in its lowered text once).
    ``program_memory`` names the step, and the positions counter advances,
    call by call, by what the host reckons from the lengths it staged,
    each fused step its own: layers x the live lanes' whole blocks, or
    layers x rows x the bound; under layers x rows x ``max_len`` a step,
    and what the ``decode_call`` spans carry as ``positions_read`` beside
    ``positions_reserved``."""
    import re
    from bluefog_tpu.serve import kv_cache as kv
    heads, d_model, steps, (dp, pp, tp), extra = _BOUNDED_ENGINES[name]
    by_head, draft = name == "by_head", "spec_decode" in extra
    cfg = compose.LMConfig(vocab=32, d_model=d_model, heads=heads, layers=2,
                           seq_len=32)
    m = compose.compose_parallelism(dp, pp, tp, 1,
                                    devices=cpu_devices[:dp * pp * tp])
    params = compose.init_lm_params(cfg, m, seed=3)
    scfg = ServeConfig(batch_buckets=(2,), prefill_buckets=(8, 128), slots=2,
                       max_len=256, decode_steps_per_call=steps, **extra)
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, 32, int(n)).tolist() for n in (120, 5, 7)]
    assert kv.read_bounds(scfg.max_len) == (128, 256)
    assert kv.read_block(scfg.max_len) == 128
    read = bfm.counter("bluefog_serve_cache_positions_read_total")
    toks = {}
    for form in ("bounded", "whole"):
        if form == "whole":
            monkeypatch.setattr(kv, "read_bounds", lambda max_len: (max_len,))
            monkeypatch.setattr(kv, "read_block", lambda max_len: max_len)
        eng = ServeEngine(m, cfg, params, scfg)
        eng.warmup()
        if form == "bounded":
            cc = eng.cache_cfg
            assert (cc.page_order == "token_rows") == (not by_head)
            if by_head:
                text, lanes = eng.decode_lowered_text(), cc.shapes()["k"][-1]
                for b in (128, 256):
                    pages = f"1x{cc.rows}x{cc.kv_heads}x{b}x{lanes}"
                    assert len(re.findall(
                        r"dynamic_slice[^\n]*-> tensor<%sx" % pages,
                        text)) == 2
            for program in ["decode S=2"] + ["draft S=2"] * draft:
                mem = eng.program_memory()[program]
                assert (mem["read"], mem["read_step"]) == ("in_place", 128)
            want, spans = [], []
            decode, stage = eng.decode, eng._stage

            def reckoned(tokens, slots, lens, *a, **k):
                # by hand, replica 0 (the only one), a fused step at a time
                live = np.asarray(slots)[0] != cc.trash_slot
                at = [np.asarray(lens)[0][live] + i for i in range(steps)]
                want.append(cfg.layers * sum(
                    cc.rows * min(max(-(-int(n.max(initial=0)) // 128), 1)
                                  * 128, 256) if by_head
                    else int((-(-n // 128)).sum()) * 128 for n in at))
                return decode(tokens, slots, lens, *a, **k)

            def staged(stage_name, **attrs):
                if stage_name == "decode_call":
                    spans.append(attrs)
                return stage(stage_name, **attrs)
            monkeypatch.setattr(eng, "decode", reckoned)
            monkeypatch.setattr(eng, "_stage", staged)
            before = read.value(kind="full")
        toks[form] = [r.generated for r in _drain(eng, prompts, max_new=13)]
        if form == "bounded":
            counted = read.value(kind="full") - before
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
    assert toks["bounded"] == toks["whole"]
    assert all(len(t) == 13 for t in toks["bounded"])
    if draft:       # its rounds are draft and verify calls: no decode call
        assert want == []
        return
    # a call's count is published when ITS tokens are collected (a call
    # later while the scheduler runs one ahead): the sums agree
    whole = cfg.layers * cc.rows * steps * 256
    assert counted == sum(want) < whole * len(want)
    assert [a["positions_read"] for a in spans] == want
    assert {a["positions_reserved"] for a in spans} == {whole}
    # the long lane reads one block until its 129th position is cached,
    # then two; the short one that follows it one again; and with two
    # tokens a call one call straddles the edge
    lanes = cc.rows if by_head else 2
    one, both = (cfg.layers * steps * 128 * n
                 for n in ((lanes, 2 * lanes) if by_head else (2, 3)))
    assert want[0] == one and both in want
    assert want[-1] == (one if by_head else one // 2)   # the last lane alone
    assert (steps == 1) == (set(want) <= {one // 2, one, both})


@pytest.mark.parametrize("kind,form", [
    ("raw", "in_place"), ("prefix_pages", "staged"), ("int8", "staged"),
    ("pallas", "staged")])
def test_read_form_is_a_function_of_the_cache_alone(cpu_devices, kind, form):
    """What keeps the staged read is what the engine can see of its
    cache, no knob: shared prefix pages (several lanes attend one row),
    a quantized store (scales beside the pages), the flash-decode
    kernel's order (the token written before it is read)."""
    extra = {"raw": {}, "int8": dict(kv_dtype="int8"),
             "prefix_pages": dict(prefix_pages=1, prefix_page_tokens=4),
             "pallas": dict(decode_kernel="pallas", decode_block_k=8)}[kind]
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    eng = ServeEngine(m, cfg, compose.init_lm_params(cfg, m, seed=3),
                      ServeConfig(**{**_SCFG, "batch_buckets": (2, 4),
                                     **extra}))
    tok, slot, ln = eng.idle_lane()
    for S in (2, 4):
        full = lambda v: np.full((1, S), v, np.int32)
        eng.decode(full(tok), full(slot), full(ln))
    mem = eng.program_memory()
    # two lanes of 5 rows (6 with the prefix page) are a third or more
    assert [mem[f"decode S={S}"]["read"] for S in (2, 4)] == [form] * 2


@pytest.fixture(scope="module")
def flash_setup(cpu_devices):
    """Two engines differing ONLY in decode_kernel: every fast path on
    (spec decode + shared prefix pages), xla vs pallas flash decode."""
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(2, 2, 2, 1, devices=cpu_devices)
    params = compose.init_lm_params(cfg, m, seed=3)
    common = dict(batch_buckets=(1, 2), prefill_buckets=(4, 8, 16),
                  slots=4, max_len=32, decode_steps_per_call=1,
                  spec_decode=2, spec_stages=1,
                  prefix_pages=2, prefix_page_tokens=8)
    flash = ServeEngine(m, cfg, params, ServeConfig(
        decode_kernel="pallas", decode_block_k=8, **common))
    flash.warmup()
    ref = ServeEngine(m, cfg, params, ServeConfig(**common))
    ref.warmup()
    return flash, ref


def test_flash_decode_engine_bit_identical(flash_setup):
    """The serving acceptance gate for the Pallas flash-decode kernel:
    with identical configs, the kernel engine's token streams ARE the XLA
    engine's streams — through 1-token decode (flash_attend_rows), the
    k-token speculative verify (flash_attend_chunk), ragged mixed-length
    batches, and prefix-hit lanes routed through the shared page."""
    flash, ref = flash_setup
    rng = np.random.default_rng(13)
    shared = rng.integers(0, _CFG["vocab"], 8).tolist()   # one sealed page
    prompts = [rng.integers(0, _CFG["vocab"], int(n)).tolist()
               for n in (3, 5, 8, 14)]
    sharers = [shared + [5, 9, 2], shared + [6, 5, 3, 5]]
    want = [r.generated for r in _drain(ref, prompts)]
    want += [r.generated for r in _drain(ref, sharers)]
    got = [r.generated for r in _drain(flash, prompts)]
    bfm.reset_metrics()
    got += [r.generated for r in _drain(flash, sharers)]
    assert got == want
    # the prefix-hit kernel path really engaged (a sharer rode the page)
    hits = bfm.get_metric("bluefog_serve_prefix_hits_total")
    assert hits is not None and hits.total() >= 1
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0


def test_sampling_determinism(cpu_devices):
    """temperature > 0: per-slot PRNG keys ride the decode-scan carry —
    the same seed and the same admission sequence replay the exact
    sampled stream (each admission folds a counter into the key, so
    slot reuse by a LATER request never replays an earlier one)."""
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(2, 2, 2, 1, devices=cpu_devices)
    params = compose.init_lm_params(cfg, m, seed=3)
    eng = ServeEngine(m, cfg, params, ServeConfig(
        temperature=0.9, top_p=0.8, seed=11, **_SCFG))
    eng.warmup()

    def run():
        eng._seed_count = 0                      # replay the admission order
        nxt, _ = eng.prefill(0, 0, [5, 6, 7])
        out, pos = [nxt], 3
        for _ in range(6):
            toks = np.zeros((m.dp, 1), np.int32)
            slots = np.full((m.dp, 1), eng.cache_cfg.trash_slot, np.int32)
            lens = np.zeros((m.dp, 1), np.int32)
            toks[0, 0], slots[0, 0], lens[0, 0] = out[-1], 0, pos
            gen = eng.decode(toks, slots, lens)
            out.append(int(gen[0, 0, 0]))
            pos += 1
        return out

    first, second = run(), run()
    assert first == second
    assert all(0 <= t < _CFG["vocab"] for t in first)
    # greedy config rejects a sampled-only code path ever engaging
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0


# ---------------------------------------------------------------------------
# The sampled stream, replayed on the host from the key schedule alone
# ---------------------------------------------------------------------------

_STREAM = dict(batch_buckets=(2,), prefill_buckets=(8, 16), slots=2,
               max_len=32)


def _replayed(ref, scfg, rows, replica, slot, count, prompt, n):
    """The ``1 + n`` tokens of a request that was the engine's ``count``-th
    admission, into ``slot`` of ``replica``: its first token greedy, then
    each one drawn on the host from the key the schedule gives the slot
    (``fold_in(fold_in(PRNGKey(seed), replica * rows + slot), count)``,
    one ``split`` a sampled token), over the logits a greedy engine of the
    same weights gives for the sequence so far."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(scfg.seed), replica * rows + slot), count)
    out = []
    for i in range(1 + n):
        lg = jnp.asarray(np.asarray(
            ref.prefill(replica, 0, prompt + out)[1], np.float32))
        if i == 0:
            out.append(int(jnp.argmax(lg)))
            continue
        use, key = jax.random.split(key)
        lg = lg / scfg.temperature
        if scfg.top_p < 1.0:
            srt = jnp.sort(lg)[::-1]
            probs = jax.nn.softmax(srt)
            kept = srt[(jnp.cumsum(probs) - probs) < scfg.top_p]
            lg = jnp.where(lg >= kept.min(), lg, -jnp.inf)
        out.append(int(jax.random.categorical(use, lg)))
    return out


@pytest.mark.parametrize("case", ["slot_reused", "prefix_hit"])
@pytest.mark.parametrize("dp", [1, 2])
@pytest.mark.parametrize("top_p", [1.0, 0.8])
def test_sampled_stream_is_the_key_schedule_replayed(cpu_devices, top_p, dp,
                                                     case):
    """temperature 0.8: every request's tokens are what its slot's key
    gives, whoever holds the keys between calls.  The key is made from
    the seed, the slot's id over all replicas and the admission's number
    (the warm-up's prefills count; a sealed prefix does not), so a slot's
    second request draws another stream than its first, and a prefix hit
    admitted through ``chunk_prefill`` draws the stream a whole prefill
    would have."""
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(dp, 1, 1, 1, devices=cpu_devices[:dp])
    params = compose.init_lm_params(cfg, m, seed=3)
    prefix = dict(prefix_pages=2, prefix_page_tokens=4) \
        if case == "prefix_hit" else {}
    scfg = ServeConfig(temperature=0.8, top_p=top_p, seed=11, **prefix,
                       **_STREAM)
    eng = ServeEngine(m, cfg, params, scfg)
    eng.warmup()
    ref = ServeEngine(m, cfg, params, ServeConfig(**_STREAM))
    cc, last = eng.cache_cfg, dp - 1
    admitted = len(scfg.prefill_buckets)          # the warm-up's prefills
    reqs = []

    def admit(replica, slot, prompt, lane, row=None):
        nonlocal admitted
        admitted += 1
        if row is None:
            first, _ = eng.prefill(replica, slot, prompt)
        else:
            first = eng.chunk_prefill(replica, slot, prompt[4:], 4, row)
        reqs.append(dict(replica=replica, slot=slot, lane=lane, row=row,
                         count=admitted, prompt=prompt, out=[first]))
        return reqs[-1]

    def decode(*live):
        toks = np.zeros((dp, 2), np.int32)
        slots = np.full((dp, 2), cc.trash_slot, np.int32)
        lens, prows, plens = toks.copy(), slots.copy(), toks.copy()
        for q in live:
            at = q["replica"], q["lane"]
            toks[at], slots[at] = q["out"][-1], q["slot"]
            lens[at] = len(q["prompt"]) + len(q["out"]) - 1
            if q["row"] is not None:
                prows[at], plens[at] = q["row"], 4
        gen = eng.decode(toks, slots, lens,
                         *((prows, plens) if prefix else ()))
        for q in live:
            q["out"].append(int(gen[q["replica"], 0, q["lane"]]))

    if case == "slot_reused":
        a = admit(0, 0, [5, 6, 7], lane=0)
        b = admit(last, 1, [9, 2, 4, 8, 1], lane=1)
        for _ in range(4):
            decode(a, b)
        c = admit(0, 0, [5, 6, 7], lane=0)       # a's slot, a's prompt
        for _ in range(4):
            decode(c, b)
        assert c["out"] != a["out"]
    else:
        shared = [3, 1, 4, 1]                    # one page
        eng.seal_prefix(last, cc.slots, shared)
        d = admit(last, 1, shared + [5, 9, 2], lane=0, row=cc.slots)
        e = admit(0, 0, [7, 7, 1], lane=1)
        for _ in range(5):
            decode(d, e)
    for q in reqs:
        assert q["out"] == _replayed(
            ref, scfg, cc.rows, q["replica"], q["slot"], q["count"],
            q["prompt"], len(q["out"]) - 1), q
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
