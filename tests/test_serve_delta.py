"""Serving the single-mixer model with its second recurrent kind: the gated
delta rule with a decay per channel (projections through low-rank pairs,
three convolutions side by side, L2-normed q and k, the chunked form and
the single step, the per-head norm and gate), a matrix state a head in the
cache's recurrent tensor, attention under a sigmoid gate and past one block
of keys, gated SiLU experts on the hidden state itself, and what the
configuration refuses.  Small sizes, seeded random weights, float32 on the
CPU; the comparison with the plain reference is in tests/perfbench/."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import decoder
from bluefog_tpu.moe import layers as moe_layers
from bluefog_tpu.ops import pallas_delta
from bluefog_tpu.parallel import compose
from bluefog_tpu.serve import Scheduler, ServeConfig, ServeEngine
from bluefog_tpu.serve import kv_cache as kv
from bluefog_tpu.utils import metrics
from test_serve_ssm import dense_attention

PLAN = ("full", "experts", "delta", "experts", "delta", "experts")
CFG = decoder.SsmConfig(
    vocab=128, d_model=48, plan=PLAN, ssm_heads=4, ssm_head_dim=8,
    ssm_groups=1, ssm_state=12, heads=4, kv_heads=2, head_dim=8, latent=0,
    expert_ffn=32, shared_ffn=40, num_experts=16, held_experts=4,
    held_start=4, top_k=4, route_scale=1.0, chunk=4,
    expert_form="gated_silu", attn_gate=True, delta_rank=6,
    delta_beta_max=2.0)


def draw(name, key, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("g", "gf", "g_o"):
        return 1.0 + 0.1 * z
    if name == "eb":
        return 0.1 * z
    if name == "w_conv":
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(0.001), np.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return 0.2 * z


def make_params(cfg, seed=0, n=1):
    key = jax.random.key(seed)

    def group(leaves):
        nonlocal key
        out = {}
        for name, shape in leaves.items():
            key, k = jax.random.split(key)
            out[name] = jnp.broadcast_to(draw(name, k, shape)[None],
                                         (n,) + shape)
        return out
    shapes = decoder.ssm_param_shapes(cfg)
    return {"layers": tuple(group(l) for l in shapes["layers"]),
            "shared": group(shapes["shared"])}


def make_engine(cpu_devices, cfg=CFG, seed=0, **scfg):
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    kw = dict(batch_buckets=(4,), prefill_buckets=(8, 16), slots=4,
              max_len=40)
    kw.update(scfg)
    return ServeEngine(m, cfg, make_params(cfg, seed), ServeConfig(**kw))


def delta_token_by_token(cfg, lp, h):
    """A delta mixer over the normed ``h`` [T, D], one token at a time
    through the single-step forms: the oracle of the chunked form."""
    prev = jnp.zeros((1, cfg.conv_kernel - 1, cfg.conv_dim))
    S = jnp.zeros((1, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
    qkv, f, b, z = decoder.delta_project(cfg, lp, h)
    g, beta = decoder.delta_discretize(cfg, lp, f, b)
    os = []
    for t in range(h.shape[0]):
        out, prev = decoder.mamba_conv(cfg, lp, qkv[t:t + 1], prev)
        o, S = decoder.delta_step(S, g[t:t + 1], beta[t:t + 1],
                                  *decoder.delta_split(cfg, out))
        os.append(o)
    return (decoder.delta_gate_out(cfg, lp, jnp.concatenate(os), z), S[0],
            prev[0])


def full_forward(cfg, params, toks):
    """Logits [T, V] of one whole sequence, no cache, the delta rule token
    by token: what prefill + decode must reproduce (padded to a whole 16,
    so that every call's eager operations have shapes the first compiled)."""
    p = jax.tree.map(lambda a: a[0], params)
    n, toks = len(toks), list(toks) + [0] * (-len(toks) % 16)
    x = p["shared"]["embed"][jnp.asarray(toks)]
    for lp, kind in zip(p["layers"], cfg.plan):
        if kind == "delta":
            mix = lambda h, lp=lp: (delta_token_by_token(cfg, lp, h)[0], None)
        elif kind == "full":
            def mix(h, lp=lp):
                q, k, v = decoder.gqa_project(cfg, lp, h)
                return decoder.gqa_out(cfg, lp, dense_attention(q, k, v),
                                       h), None
        else:
            mix = lambda h, lp=lp: (moe_layers.held_moe_ffn(
                cfg, lp, h, form=cfg.expert_form)[0], None)
        x, _ = decoder.mixer_block(cfg, lp, x, kind, mix)
    return np.asarray(decoder.latent_logits(cfg, p["shared"], x))[:n]


def test_param_count_matches_the_shapes_and_the_plan():
    shapes = decoder.ssm_param_shapes(CFG)
    assert len(shapes["layers"]) == len(PLAN)
    D, H, K, V, r = 48, 4, 8, 12, 6
    delta = shapes["layers"][2]
    assert delta == {
        "g": (D,), "w_in": (D, H * (2 * K + V)), "w_conv": (H * (2 * K + V), 4),
        "wfa": (D, r), "wfb": (r, H * K), "A_log": (H,), "dt_bias": (H * K,),
        "wb": (D, H), "wga": (D, r), "wgb": (r, H * V), "g_o": (V,),
        "w_out": (H * V, D)}
    assert shapes["layers"][0]["wgate"] == (D, 32)       # the gate's own
    # gated SiLU experts on the hidden state itself: no latent projections
    assert set(shapes["layers"][1]) == {
        "g", "wr", "weg", "weu", "wed", "wsg", "wsu", "wsd", "eb"}
    assert shapes["layers"][1]["weg"] == (4, D, 32)
    assert decoder.ssm_param_count(CFG) == sum(
        int(np.prod(s)) for grp in shapes["layers"] + (shapes["shared"],)
        for s in grp.values())
    assert CFG.recurrent == "delta" and CFG.conv_dim == H * (2 * K + V)


def test_the_cells_cut_counts_what_the_issue_counted():
    """The published widths at the cell's cut: 6 delta mixers of
    137,732,288, 2 gated attention mixers of 109,051,904, 8 expert layers
    of 20 held experts, an eighth of the vocabulary; 7.80 GB in bfloat16."""
    big = decoder.SsmConfig(
        vocab=24576, d_model=4096,
        plan=("full", "experts", "delta", "experts", "delta", "experts",
              "delta", "experts") * 2,
        ssm_heads=64, ssm_head_dim=128, ssm_groups=1, ssm_state=128,
        heads=64, kv_heads=8, head_dim=128, latent=0, expert_ffn=1280,
        shared_ffn=1280, num_experts=320, held_experts=20, top_k=8,
        route_scale=1.0, chunk=16, expert_form="gated_silu", attn_gate=True,
        delta_rank=128, delta_beta_max=2.0)
    big.validate(None)
    count = lambda grp: sum(int(np.prod(s)) for s in grp.values())
    shapes = decoder.ssm_param_shapes(big)
    assert count(shapes["layers"][2]) - 4096 == 137_732_288
    assert count(shapes["layers"][0]) - 4096 == 109_051_904
    assert count(shapes["layers"][1]) + 4096 == 331_620_672
    assert decoder.ssm_param_count(big) == 3_898_793_600
    cc = kv.SsmCacheConfig.of(big, 32, 16640, jnp.bfloat16)
    assert cc.shapes()["ssm"] == (6, 33, 64, 128, 128)
    assert cc.shapes()["conv"] == (6, 33, 3, 24576)
    per_slot = cc.bytes_per_slot()
    assert per_slot["ssm"] == 6 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert per_slot["full"] == 16640 * 2 * 2 * 8 * 128 * 2


def _delta_inputs(T, strong, seed=0):
    """(lp, qkv, f, b): one delta layer's convolution and decay leaves and
    its raw projections over ``T`` positions; with ``strong`` the raw
    decays reach far below ``g = -20`` a step."""
    H, K = CFG.ssm_heads, CFG.ssm_head_dim
    keys = jax.random.split(jax.random.key(seed), 6)
    lp = {"A_log": draw("A_log", keys[0], (H,)),
          "dt_bias": jax.random.normal(keys[1], (H * K,)),
          "w_conv": draw("w_conv", keys[2], (CFG.conv_dim, CFG.conv_kernel))}
    qkv = jax.random.normal(keys[3], (T, CFG.conv_dim))
    f = jax.random.normal(keys[4], (T, H * K)) * (8.0 if strong else 1.0) \
        + (3.0 if strong else 0.0)
    return lp, qkv, f, 2.0 * jax.random.normal(keys[5], (T, H))


def _token_by_token(lp, qkv, f, b, true_len):
    """(o [true_len, H, V], the state, the kept inputs) of the first
    ``true_len`` positions through the single-step forms."""
    g, beta = decoder.delta_discretize(CFG, lp, f, b)
    prev = jnp.zeros((1, CFG.conv_kernel - 1, CFG.conv_dim))
    S = jnp.zeros((1, CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state))
    os = []
    for t in range(true_len):
        out, prev = decoder.mamba_conv(CFG, lp, qkv[t:t + 1], prev)
        o, S = decoder.delta_step(S, g[t:t + 1], beta[t:t + 1],
                                  *decoder.delta_split(CFG, out))
        os.append(o[0])
    return jnp.stack(os), S[0], prev[0], g


@pytest.mark.parametrize("strong", [False, True])
@pytest.mark.parametrize("T,true_len", [(8, 8), (11, 11), (16, 2), (16, 9),
                                        (37, 37), (37, 30)])
def test_the_chunked_delta_rule_is_the_recurrence_for_any_length_and_decay(
        T, true_len, strong, monkeypatch):
    """Lengths that are no whole chunk, prompts that end before their
    padding (one shorter than the convolution's taps), blocks of four chunks
    in tiles of two (so three blocks hand a state and the convolution's last
    inputs on at 37, each through two tiles of one span of two chunks), and
    decays down to ``exp(-200)`` a step: finite, equal to the token-by-token
    recurrence, and the state and the kept inputs are those after the last
    REAL token."""
    monkeypatch.setattr(decoder, "_DELTA_BLOCK", 4 * CFG.chunk)
    monkeypatch.setattr(pallas_delta, "_TILE", 2 * CFG.chunk)
    lp, qkv, f, b = _delta_inputs(T, strong)
    o, S, kept = jax.jit(lambda *a: decoder.delta_scan_chunked(CFG, lp, *a))(
        qkv, f, b, jnp.int32(true_len))
    want, St, prev, g = _token_by_token(lp, qkv, f, b, true_len)
    assert float(g.max()) <= 0.0
    if strong:
        assert float(g.min()) < -20.0
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(o[:true_len], want, atol=2e-5)
    np.testing.assert_allclose(S, St, atol=5e-5)
    np.testing.assert_array_equal(kept, prev)


def test_every_exponent_the_chunked_form_takes_is_at_most_zero(monkeypatch):
    """No ``exp(-cumsum g)`` anywhere: each argument of ``exp`` in the
    chunked form, under decays past ``exp(-20)`` a step, is ``<= 0``.  The
    kernel's arithmetic is a plain function of arrays
    (``pallas_delta.span_terms``, which the kernel calls on what it reads
    from its refs); it runs here eagerly on each head's span of four
    chunks, the last three positions padding."""
    seen = []
    real = jnp.exp

    def watched(x):
        seen.append(float(jnp.max(x)))
        return real(x)
    lp, qkv, f, b = _delta_inputs(16, strong=True)
    lp = {**lp, "A_log": jnp.zeros_like(lp["A_log"])}
    monkeypatch.setattr(decoder.jnp, "exp", watched)
    with jax.disable_jit():
        q, k, v = decoder.delta_split(
            CFG, decoder.mamba_conv(CFG, lp, qkv, true_len=jnp.int32(13))[0])
        g, beta = decoder.delta_discretize(CFG, lp, f, b, jnp.arange(16) < 13)
        assert float(g.min()) < -20.0
        for h in range(CFG.ssm_heads):
            terms, = pallas_delta.span_terms(
                [q[:, h]], [k[:, h]], [v[:, h]], [g[:, h]],
                [beta[:, h, None]])
            assert all(bool(jnp.isfinite(t).all()) for t in terms)
    assert len(seen) >= 8 and max(seen) <= 0.0


def test_a_step_of_zero_passes_the_state_unchanged():
    H, K, V = CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state
    S = jax.random.normal(jax.random.key(0), (3, H, K, V))
    z = lambda *s: jnp.zeros((3, H) + s)
    o, new = decoder.delta_step(S, z(K), z(), z(K), z(K), z(V))
    np.testing.assert_array_equal(new, S)
    assert not o.any()


def test_a_padded_prompt_leaves_the_state_and_inputs_of_its_last_real_token(
        cpu_devices):
    """What the slot holds after a prompt of 6 tokens padded to 8: in every
    delta layer the state and the three kept inputs of the same 6 tokens
    through the single-step forms."""
    eng = make_engine(cpu_devices)
    prompt = [5, 17, 3, 99, 41, 7]
    eng.prefill(0, 2, prompt)
    p = jax.tree.map(lambda a: a[0], eng.params)
    x = p["shared"]["embed"][jnp.asarray(prompt)]
    at = 0
    for lp, kind in zip(p["layers"], CFG.plan):
        if kind == "delta":
            h = decoder.rms_norm(x, lp["g"], CFG.eps)
            y, S, kept = delta_token_by_token(CFG, lp, h)
            np.testing.assert_allclose(eng.cache["ssm"][0, at, 2], S,
                                       atol=2e-5)
            np.testing.assert_allclose(eng.cache["conv"][0, at, 2], kept,
                                       atol=1e-5)
            x, at = x + y, at + 1
        elif kind == "full":
            h = decoder.rms_norm(x, lp["g"], CFG.eps)
            q, k, v = decoder.gqa_project(CFG, lp, h)
            x = x + decoder.gqa_out(CFG, lp, dense_attention(q, k, v), h)
        else:
            x = x + moe_layers.held_moe_ffn(
                CFG, lp, decoder.rms_norm(x, lp["g"], CFG.eps),
                form="gated_silu")[0]
    assert at == 2


def assert_greedy(cfg, params, prompt, generated):
    want = full_forward(cfg, params, list(prompt) + list(generated[:-1]))
    assert [int(t) for t in generated] == [
        int(want[len(prompt) - 1 + j].argmax())
        for j in range(len(generated))]
    return want


def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        cpu_devices):
    """A plan with all three kinds; a prompt that is no whole chunk, padded
    to its bucket, then decode steps that read and write the matrix state:
    the logits at every position are the whole sequence's, computed token
    by token with no cache."""
    eng = make_engine(cpu_devices)
    prompt = [5, 17, 3, 99, 41, 7, 64]
    tok, last = eng.prefill(0, 2, prompt)
    seq, trash, got = list(prompt) + [tok], eng.cache_cfg.trash_slot, []
    for step in range(4):
        toks = np.array([[0, seq[-1], 0, 0]], np.int32)
        slots = np.array([[trash, 2, trash, trash]], np.int32)
        lens = np.array([[0, len(seq) - 1, 0, 0]], np.int32)
        gen = eng.decode(toks, slots, lens)
        got.append(np.asarray(eng.decode_logits(0)[1])[0, 1])
        seq.append(int(gen[0, 0, 1]))
    want = assert_greedy(CFG, eng.params, prompt, seq[len(prompt):])
    last_at = len(prompt) - 1
    np.testing.assert_allclose(np.asarray(last), want[last_at], rtol=2e-4,
                               atol=2e-5)
    for j, logits in enumerate(got, start=1):
        np.testing.assert_allclose(logits, want[last_at + j], rtol=2e-4,
                                   atol=2e-5)


def test_a_readmitted_slot_carries_nothing_over(cpu_devices):
    """Two requests in turn through ONE slot give what each gives alone in
    a fresh engine: a prompt overwrites the slot's matrix states and kept
    inputs whole."""
    def serve(eng, prompt, n=3):
        tok, last = eng.prefill(0, 0, prompt)
        out, trash = [tok], eng.cache_cfg.trash_slot
        for i in range(n):
            gen = eng.decode(np.array([[out[-1], 0, 0, 0]]),
                             np.array([[0] + [trash] * 3]),
                             np.array([[len(prompt) + i, 0, 0, 0]]))
            out.append(int(gen[0, 0, 0]))
        return out, np.asarray(last)

    first, second = list(range(1, 14)), [7, 8]
    eng = make_engine(cpu_devices)
    serve(eng, first)
    after, logits = serve(eng, second)
    alone, alone_logits = serve(make_engine(cpu_devices), second)
    assert after == alone
    np.testing.assert_array_equal(logits, alone_logits)


def test_a_decode_call_leaves_the_rows_it_does_not_name(cpu_devices):
    eng = make_engine(cpu_devices)
    eng.prefill(0, 0, [3, 1, 4, 1, 5])
    tok, _ = eng.prefill(0, 3, [2, 7, 1, 8])
    before = {k: np.asarray(v) for k, v in eng.cache.items()}
    trash = eng.cache_cfg.trash_slot
    eng.decode(np.array([[tok, 0, 0, 0]]), np.array([[3] + [trash] * 3]),
               np.array([[4, 0, 0, 0]]))
    after = {k: np.asarray(v) for k, v in eng.cache.items()}
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(after[name][0, :, :3],
                                      before[name][0, :, :3])
        assert (after[name][0, :, 3] != before[name][0, :, 3]).any()


def test_the_scheduler_serves_more_requests_than_slots(cpu_devices):
    eng = make_engine(cpu_devices)
    eng.warmup()
    sched = Scheduler(eng)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab, n).tolist()
               for n in (3, 9, 5, 12, 2, 7)]
    reqs = [sched.submit(p, max_new_tokens=4) for p in prompts]
    sched.drain()
    sched.close()
    for p, r in zip(prompts, reqs):
        assert r.state == "done" and len(r.generated) == 4
        assert_greedy(CFG, eng.params, p, r.generated)
    assert metrics.counter("bluefog_retrace_after_warmup_total").total() == 0


def test_a_prompt_attended_in_two_key_blocks_equals_the_same_in_one(
        monkeypatch):
    """Past one block of keys the flash kernel meets a prompt's keys a
    block at a time and a query's partials are merged: 3 blocks of 8 over
    20 positions (the last block short) against one block of all."""
    keys = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(keys[0], (20, 4, 8))
    k, v = (jax.random.normal(kk, (20, 2, 8)) for kk in keys[1:])
    whole = ServeEngine._flash_causal(q, k, v)
    np.testing.assert_allclose(whole, dense_attention(q, k, v), atol=2e-6)
    assert ServeEngine._key_blocks(20) == 1
    monkeypatch.setattr(ServeEngine, "_FLASH_KEY_BLOCK", 8)
    assert ServeEngine._key_blocks(20) == 3
    np.testing.assert_allclose(ServeEngine._flash_causal(q, k, v), whole,
                               atol=2e-6)


def test_a_long_prompt_through_the_engine_in_two_key_blocks(
        cpu_devices, monkeypatch):
    """The same engine with a block of 8 keys: a 13-token prompt padded to
    16 meets its keys in two blocks, the first token and the logits are
    those of one block, and the span and the counter say 2."""
    prompt = list(range(3, 16))
    tok, last = make_engine(cpu_devices).prefill(0, 1, prompt)
    monkeypatch.setattr(ServeEngine, "_FLASH_KEY_BLOCK", 8)
    eng = make_engine(cpu_devices)
    marks, real = [], eng._stage
    monkeypatch.setattr(eng, "_stage", lambda name, **a: (
        marks.append((name, a)), real(name, **a))[1])
    blocks = metrics.counter("bluefog_serve_prefill_key_blocks_total", "")
    before = blocks.total()
    tok2, last2 = eng.prefill(0, 1, prompt)
    assert tok2 == tok
    np.testing.assert_allclose(np.asarray(last2), np.asarray(last),
                               atol=2e-5)
    (_, attrs), = [m for m in marks if m[0] == "prefill_call"]
    assert attrs["key_blocks"] == 2 and attrs["Tpad"] == 16
    assert blocks.total() - before == 2


def gated_oracle(cfg, lp, h, start, shared=True):
    """The expert layer computed densely: every expert on every token."""
    s = jax.nn.sigmoid(h @ lp["wr"])
    _, idx = jax.lax.top_k(s + lp["eb"], cfg.top_k)
    sel = jnp.any(idx[..., None] == jnp.arange(cfg.num_experts), axis=1)
    w = jnp.where(sel, s, 0.0)
    w = cfg.route_scale * w / jnp.sum(w, -1, keepdims=True)
    y = sum(w[:, start + e, None] * decoder.gated_ffn(
        h, lp["weg"][e], lp["weu"][e], lp["wed"][e])
        for e in range(lp["weg"].shape[0]))
    if shared:
        y = y + decoder.gated_ffn(h, lp["wsg"], lp["wsu"], lp["wsd"])
    return y


@pytest.mark.parametrize("grouped", [False, True])
def test_the_gated_experts_on_the_hidden_state_both_forms(grouped):
    lp = jax.tree.map(lambda a: a[0], make_params(CFG, 3)["layers"][1])
    h = jax.random.normal(jax.random.key(5), (8, CFG.d_model))
    if grouped:
        stack = {**lp, **{k: lp[k][None] for k in ("weg", "weu", "wed")}}
        y, idx, _ = moe_layers.held_moe_ffn(CFG, stack, h, None, jnp.int32(0),
                                            form="gated_silu")
    else:
        y, idx, _ = moe_layers.held_moe_ffn(CFG, lp, h, form="gated_silu")
    np.testing.assert_allclose(y, gated_oracle(CFG, lp, h, CFG.held_start),
                               atol=2e-5)


def test_a_prompts_expert_layer_in_chunks_is_the_layer_whole(
        cpu_devices, monkeypatch):
    """Past ``_PROMPT_FFN_CHUNK`` tokens a prompt's expert layers run that
    many tokens at a time: the same logits, the same selections."""
    prompt = list(range(2, 15))
    eng = make_engine(cpu_devices)
    tok, last = eng.prefill(0, 0, prompt)
    chosen = np.asarray(eng.prefill_chosen(0))
    monkeypatch.setattr(ServeEngine, "_PROMPT_FFN_CHUNK", 4)
    eng = make_engine(cpu_devices)
    tok2, last2 = eng.prefill(0, 0, prompt)
    assert tok2 == tok
    np.testing.assert_allclose(np.asarray(last2), np.asarray(last),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(eng.prefill_chosen(0)), chosen)


@pytest.mark.parametrize("change,what", [
    (dict(plan=("delta", "ssm")), "ssm_recurrent_kinds"),
    (dict(expert_form="gelu"), "ssm_expert_form"),
    (dict(latent=-1), "ssm_latent"),
    (dict(delta_rank=0), "ssm_delta_rank"),
    (dict(chunk=6), "ssm_delta_chunk"),
    (dict(plan=("delta", "window")), "ssm_layer_plan"),
])
def test_the_config_names_its_refusals(change, what):
    with pytest.raises(ValueError, match=what):
        dataclasses.replace(CFG, **change).validate(None)


@pytest.mark.parametrize("scfg,what", [
    (dict(prefix_pages=2, prefix_page_tokens=4), "ssm_serving_prefix_pages"),
    (dict(spec_decode=2), "ssm_serving_spec_decode"),
])
def test_fast_paths_are_refused_by_name(cpu_devices, scfg, what):
    with pytest.raises(ValueError, match=what):
        make_engine(cpu_devices, **scfg)


def test_the_marks_the_gauges_and_the_counter(cpu_devices, monkeypatch):
    eng = make_engine(cpu_devices)
    cc = eng.cache_cfg
    assert cc.ssm_layers == 2 and cc.shapes()["ssm"][2:] == (4, 8, 12)
    gauge = metrics.gauge("bluefog_serve_cache_bytes_per_slot", "")
    assert gauge.value(kind="ssm") == cc.bytes_per_slot()["ssm"] \
        == 2 * (4 * 8 * 12 * 4 + 3 * CFG.conv_dim * 4)
    tok, _ = eng.prefill(0, 1, [4, 5, 6])
    marks, real = [], eng._stage
    monkeypatch.setattr(eng, "_stage", lambda name, **a: (
        marks.append((name, a)), real(name, **a))[1])
    updates = metrics.counter("bluefog_serve_state_updates_total", "")
    before = updates.total()
    trash = cc.trash_slot
    eng.decode(np.array([[tok, 0, 0, 0]]), np.array([[1] + [trash] * 3]),
               np.array([[3, 0, 0, 0]]))
    (_, attrs), = [m for m in marks if m[0] == "held_work"]
    assert attrs["state_lanes"] == 1 and attrs["positions"] == 4
    assert attrs["rows"] == 4 * CFG.held_experts * CFG.expert_layers
    assert updates.total() - before == 2            # the delta layers
    mem = eng.program_memory()
    assert mem["decode S=4"]["state_bytes"] == cc.rows * \
        cc.bytes_per_slot()["ssm"]
    # K and V of one attention layer a lane, a state and its kept inputs a
    # delta layer: per lane from a prompt, whole by a decode step
    assert mem["prefill Tpad=8"]["cache_writes"] == 2 * (1 + 2)
    assert mem["decode S=4"]["cache_writes"] == 2 * (4 + 2)
