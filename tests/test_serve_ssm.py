"""Serving a model whose every layer is one mixer as one chip's share: the
Mamba-2 mixer's parts (projection, convolution, chunked scan and single
step, gate and grouped norm), the third kind of cache tensor (a fixed-size
recurrent state and the convolution's kept inputs per slot, no positions),
continuous batching over it, the ``relu^2`` experts in their latent, the
counters and marks, and what the engine refuses for this family.  Small
sizes, seeded random weights, float32 on the CPU; the comparison with the
plain reference is in tests/perfbench/."""
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
from bluefog_tpu.utils import metrics, tracing

PLAN = ("ssm", "experts", "ssm", "full", "experts")
CFG = decoder.SsmConfig(
    vocab=128, d_model=48, plan=PLAN, ssm_heads=8, ssm_head_dim=12,
    ssm_groups=2, ssm_state=8, heads=4, kv_heads=2, head_dim=8, latent=16,
    expert_ffn=32, shared_ffn=40, num_experts=16, held_experts=4,
    held_start=4, top_k=4, route_scale=5.0, chunk=4)


def draw(name, key, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("g", "gf", "g_y", "Dskip"):
        return 1.0 + 0.1 * z
    if name in ("b_conv", "eb"):
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


def mamba_token_by_token(cfg, lp, h):
    """A Mamba mixer over the normed ``h`` [T, D], one token at a time
    through the single-step forms: the oracle of the chunked scan."""
    T = h.shape[0]
    prev = jnp.zeros((1, cfg.conv_kernel - 1, cfg.conv_dim))
    S = jnp.zeros((1, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
    z, xbc, dt = decoder.mamba_project(cfg, lp, h)
    ys = []
    for t in range(T):
        out, prev = decoder.mamba_conv(cfg, lp, xbc[t:t + 1], prev)
        x, B, C = decoder.mamba_split(cfg, out)
        log_a, dx = decoder.mamba_discretize(lp, x, dt[t:t + 1])
        y, S = decoder.mamba_step(cfg, S, log_a, dx, B, C)
        ys.append(y + lp["Dskip"][:, None] * x)
    y = jnp.concatenate(ys)
    return decoder.mamba_gate_out(cfg, lp, y, z), S[0], prev[0]


def dense_attention(q, k, v):
    T, H, Dh = q.shape
    k, v = (jnp.repeat(a, H // k.shape[1], axis=1) for a in (k, v))
    keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    sc = jnp.einsum("thd,shd->hts", q, k) * Dh ** -0.5
    p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), -1)
    return jnp.einsum("hts,shd->thd", p, v)


def full_forward(cfg, params, toks):
    """Logits [T, V] of one whole sequence, no cache, the recurrence token
    by token: what prefill + decode must reproduce.  (The model is causal:
    the sequence is padded to a whole 16, so that every call's eager
    operations have shapes the first call compiled.)"""
    p = jax.tree.map(lambda a: a[0], params)
    n, toks = len(toks), list(toks) + [0] * (-len(toks) % 16)
    return _full_forward(cfg, p, toks)[:n]


def _full_forward(cfg, p, toks):
    x = p["shared"]["embed"][jnp.asarray(toks)]
    for lp, kind in zip(p["layers"], cfg.plan):
        if kind == "ssm":
            mix = lambda h, lp=lp: (mamba_token_by_token(cfg, lp, h)[0], None)
        elif kind == "full":
            def mix(h, lp=lp):
                q, k, v = decoder.gqa_project(cfg, lp, h)
                return dense_attention(q, k, v).reshape(
                    len(toks), -1) @ lp["wo"], None
        else:
            mix = lambda h, lp=lp: (moe_layers.held_moe_ffn(
                cfg, lp, h, form="relu2")[0], None)
        x, _ = decoder.mixer_block(cfg, lp, x, kind, mix)
    return np.asarray(decoder.latent_logits(cfg, p["shared"], x))


def test_param_count_matches_the_shapes_and_the_plan():
    shapes = decoder.ssm_param_shapes(CFG)
    assert decoder.ssm_param_count(CFG) == sum(
        int(np.prod(s)) for g in shapes["layers"] + (shapes["shared"],)
        for s in g.values())
    ssm, experts, _, full, _ = shapes["layers"]
    assert ssm["w_in"] == (48, 2 * 96 + 2 * 2 * 8 + 8)     # z, x B C, dt
    assert ssm["w_conv"] == (96 + 32, 4) and ssm["g_y"] == (96,)
    assert experts["wr"] == (48, 16) and experts["eb"] == (16,)
    assert experts["we1"] == (4, 16, 32) and experts["we2"] == (4, 32, 16)
    assert experts["wdn"] == (48, 16) and experts["ws1"] == (48, 40)
    assert full["wq"] == (48, 32) and full["wk"] == (48, 16)
    assert "gq" not in full                     # nothing is normed or turned
    assert (CFG.layers, CFG.expert_layers) == (5, 2)
    assert [CFG.index_in_kind(i) for i in range(5)] == [0, 0, 1, 0, 1]
    assert (CFG.d_inner, CFG.conv_dim) == (96, 128)


def test_the_cells_cut_counts_what_the_issue_counted():
    """One period of the published pattern at the published widths, 128 of
    512 experts and a quarter of the vocabulary held: 4,648 M."""
    cfg = decoder.SsmConfig(
        vocab=32768, d_model=4096,
        plan=tuple({"M": "ssm", "E": "experts", "*": "full"}[c]
                   for c in "MEMEMEM*EME"),
        ssm_heads=128, ssm_head_dim=64, ssm_groups=8, ssm_state=128,
        heads=32, kv_heads=2, head_dim=128, latent=1024, expert_ffn=2688,
        shared_ffn=5376, num_experts=512, held_experts=128, top_k=22,
        route_scale=5.0)
    shapes = decoder.ssm_param_shapes(cfg)
    count = lambda g: sum(int(np.prod(s)) for s in g.values())
    assert round(count(shapes["layers"][0]) / 1e6, 2) == 109.64
    assert round(count(shapes["layers"][7]) / 1e6, 2) == 35.66
    experts = shapes["layers"][1]
    routed = int(np.prod(experts["we1"])) + int(np.prod(experts["we2"]))
    assert round((count(experts) - routed) / 1e6, 2) == 54.53
    assert round(routed / 128 / 1e6, 3) == 5.505
    assert decoder.ssm_param_count(cfg) == 4_648_163_712


@pytest.mark.parametrize("T,true_len", [(8, 8), (11, 11), (16, 5), (16, 9),
                                        (3, 2), (12, 12)])
def test_the_chunked_scan_is_the_recurrence_and_keeps_the_last_real_token(
        T, true_len):
    """Lengths that are not whole chunks of 4, and padded prompts: the
    chunked scan's outputs at the real positions, its state and the kept
    convolution inputs are those of the recurrence run token by token over
    the REAL tokens alone."""
    lp = jax.tree.map(lambda a: a[0], make_params(CFG, 3)["layers"][0])
    h = jax.random.normal(jax.random.key(T), (T, CFG.d_model))
    z, xbc, dt = decoder.mamba_project(CFG, lp, h)
    conv, kept = decoder.mamba_conv(CFG, lp, xbc, true_len=jnp.int32(true_len))
    y, state = decoder.mamba_scan_chunked(
        CFG, lp, *decoder.mamba_split(CFG, conv), dt, jnp.int32(true_len))
    got = decoder.mamba_gate_out(CFG, lp, y, z)
    want, S, prev = mamba_token_by_token(CFG, lp, h[:true_len])
    np.testing.assert_allclose(got[:true_len], want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(state, S, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(kept, prev)


def test_a_step_of_zero_passes_the_state_unchanged():
    S = jax.random.normal(jax.random.key(0), (3, 8, 12, 8))
    zeros = lambda *s: jnp.zeros(s)
    B = jax.random.normal(jax.random.key(1), (3, 2, 8))
    y, new = decoder.mamba_step(CFG, S, zeros(3, 8), zeros(3, 8, 12), B, B)
    np.testing.assert_array_equal(new, S)
    assert y.shape == (3, 8, 12)


def test_cache_config_counts_a_state_without_positions():
    cc = kv.SsmCacheConfig(full_layers=1, ssm_layers=2, slots=4, max_len=40,
                           kv_heads=2, head_dim=8, ssm_heads=8,
                           ssm_head_dim=12, ssm_state=8, conv_taps=3,
                           conv_dim=128, dtype=jnp.bfloat16)
    assert cc.shapes() == {"k": (1, 5, 2, 40, 8), "v": (1, 5, 2, 40, 8),
                           "ssm": (2, 5, 8, 12, 8), "conv": (2, 5, 3, 128)}
    assert cc.dtypes()["ssm"] == jnp.float32     # whatever is served
    assert cc.dtypes()["conv"] == jnp.bfloat16
    assert cc.bytes_per_token() == 2 * 2 * 8 * 2     # the attention layer's
    per_slot = cc.bytes_per_slot()
    assert per_slot == {"full": 40 * 64,
                        "ssm": 2 * (8 * 12 * 8 * 4 + 3 * 128 * 2)}
    assert cc.bytes() == 5 * sum(per_slot.values())
    assert cc.page_orders()["ssm"] == "state"
    assert (cc.rows, cc.trash_slot, cc.prefix_slots) == (5, 4, 0)


def assert_greedy(cfg, params, prompt, generated, rtol=2e-4):
    """Every generated token is the whole-sequence forward's choice at the
    position before it (one teacher-forced pass: the model is causal)."""
    want = full_forward(cfg, params, list(prompt) + list(generated[:-1]))
    assert [int(t) for t in generated] == [
        int(want[len(prompt) - 1 + j].argmax())
        for j in range(len(generated))]
    return want


def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        cpu_devices):
    """A prompt that is no whole chunk, padded to its bucket, then decode
    steps that read and write the state: the logits at every position are
    the whole sequence's, computed token by token with no cache."""
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


def test_what_the_programs_hand_out_of_their_selections(cpu_devices):
    eng = make_engine(cpu_devices)
    assert eng.decode_logits(0) is None and eng.decode_chosen(0) is None
    assert eng.prefill_chosen(0) is None
    tok, _ = eng.prefill(0, 1, [9, 8, 7])
    chosen = np.asarray(eng.prefill_chosen(0))
    assert chosen.shape == (2, 8, 4)            # expert layers, Tpad, top_k
    assert (chosen[:, :3] >= 0).all() and (chosen[:, 3:] == -1).all()
    trash = eng.cache_cfg.trash_slot
    eng.decode(np.array([[tok, 0, 0, 0]]), np.array([[1] + [trash] * 3]),
               np.array([[3, 0, 0, 0]]))
    slots, chosen = eng.decode_chosen(0)
    chosen = np.asarray(chosen)
    assert chosen.shape == (1, 2, 4, 4)         # steps, layers, S, top_k
    assert list(slots) == [1, trash, trash, trash]
    assert (chosen[0, :, 0] >= 0).all() and (chosen[0, :, 1:] == -1).all()


def test_a_readmitted_slot_carries_nothing_over(cpu_devices):
    """Continuous batching over the state: two requests in turn through
    ONE slot give what each gives alone in a fresh engine; a prompt
    overwrites the slot's state and convolution inputs whole."""
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
    """Every row's state is passed over where it lies; a row no lane names
    (an idle slot, or one whose request waits) comes out as it went in."""
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
    """``Scheduler`` as it is entered for every family: requests queue,
    slots are readmitted, and every request's tokens are the greedy ones
    of the whole-sequence forward (the decode runs one call ahead; a
    state lives on the device between calls)."""
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


def relu2_oracle(cfg, lp, h, start, shared=True):
    """The expert layer computed densely: every expert on every token."""
    s = jax.nn.sigmoid(h @ lp["wr"])
    _, idx = jax.lax.top_k(s + lp["eb"], cfg.top_k)
    sel = (idx[..., None] == jnp.arange(cfg.num_experts)).any(1)
    w = cfg.route_scale * jnp.where(sel, s, 0) / jnp.sum(
        jnp.where(sel, s, 0), -1, keepdims=True)
    lat = h @ lp["wdn"]
    r = sum(w[:, start + j, None] * decoder.relu2_ffn(
        lat, lp["we1"][j], lp["we2"][j]) for j in range(lp["we1"].shape[0]))
    y = r @ lp["wup"]
    return y + decoder.relu2_ffn(h, lp["ws1"], lp["ws2"]) if shared else y


@pytest.mark.parametrize("grouped", [False, True])
def test_the_relu2_experts_in_their_latent_both_forms(grouped):
    lp = jax.tree.map(lambda a: a[0], make_params(CFG, 5)["layers"][1])
    h = jax.random.normal(jax.random.key(2), (11, CFG.d_model))
    if grouped:
        lp = {**lp, "we1": lp["we1"][None], "we2": lp["we2"][None]}
    y, idx, weight = moe_layers.held_moe_ffn(
        CFG, lp, h, layer=jnp.int32(0) if grouped else None, form="relu2")
    if grouped:
        lp = {**lp, "we1": lp["we1"][0], "we2": lp["we2"][0]}
    np.testing.assert_allclose(y, relu2_oracle(CFG, lp, h, CFG.held_start),
                               rtol=2e-4, atol=2e-5)
    assert idx.shape == weight.shape == (11, 4)
    np.testing.assert_allclose(weight.sum(-1), CFG.route_scale, rtol=1e-5)


def test_the_four_shares_of_an_expert_layer_add_up_to_the_whole():
    """Chips that hold experts 0-3, 4-7, 8-11 and 12-15 under the same
    16-wide router, the shared expert counted once: their parts, each
    through the shared up-projection, sum to the layer with all 16."""
    whole = dataclasses.replace(CFG, held_experts=16, held_start=0)
    lp = jax.tree.map(lambda a: a[0], make_params(whole, 6)["layers"][1])
    h = jax.random.normal(jax.random.key(4), (9, CFG.d_model))
    want = moe_layers.held_moe_ffn(whole, lp, h, form="relu2")[0]
    shared = decoder.relu2_ffn(h, lp["ws1"], lp["ws2"])
    total = shared
    for start in (0, 4, 8, 12):
        cut = {**lp, "we1": lp["we1"][start:start + 4],
               "we2": lp["we2"][start:start + 4]}
        part = moe_layers.held_moe_ffn(
            dataclasses.replace(CFG, held_start=start), cut, h,
            form="relu2")[0]
        total = total + part - shared
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_an_unknown_expert_form_is_refused():
    x = jnp.ones((2, 4))
    w = jnp.ones((1, 4, 4))
    idx, weight = jnp.zeros((2, 1), jnp.int32), jnp.ones((2, 1))
    with pytest.raises(ValueError, match="form is one of"):
        moe_layers.held_expert_ffn(x, idx, weight, w, None, w, held_start=0,
                                   form="gelu")


@pytest.mark.parametrize("scfg,what", [
    (dict(prefix_pages=2, prefix_page_tokens=4), "ssm_serving_prefix_pages"),
    (dict(spec_decode=2), "ssm_serving_spec_decode"),
    (dict(kv_dtype="int8"), "ssm_serving_kv_dtype"),
    (dict(decode_kernel="pallas"), "ssm_serving_decode_kernel"),
])
def test_fast_paths_are_refused_by_name(cpu_devices, scfg, what):
    with pytest.raises(ValueError, match=what):
        make_engine(cpu_devices, **scfg)


def test_a_carving_is_refused_by_name(cpu_devices):
    m = compose.compose_parallelism(1, 1, 2, 1, devices=cpu_devices[:2])
    with pytest.raises(ValueError, match="ssm_serving_carving"):
        ServeEngine(m, CFG, make_params(CFG, n=2), ServeConfig(
            batch_buckets=(4,), prefill_buckets=(8,), slots=4, max_len=40))


@pytest.mark.parametrize("change,what", [
    (dict(plan=("ssm", "window")), "ssm_layer_plan"),
    (dict(ssm_groups=3), "ssm_head_groups"),
    (dict(conv_kernel=1), "ssm_conv_kernel"),
    (dict(kv_heads=3), "ssm_grouped_heads"),
    (dict(top_k=17), "ssm_router_top_k"),
    (dict(held_start=14), "ssm_held_experts"),
])
def test_the_config_names_its_refusals(change, what):
    with pytest.raises(ValueError, match=what):
        dataclasses.replace(CFG, **change).validate(None)


def test_the_marks_the_gauges_and_the_counter(cpu_devices, monkeypatch):
    eng = make_engine(cpu_devices)
    cc = eng.cache_cfg
    gauge = metrics.gauge("bluefog_serve_cache_bytes_per_slot", "")
    assert gauge.value(kind="ssm") == cc.bytes_per_slot()["ssm"]
    assert gauge.value(kind="full") == cc.bytes_per_slot()["full"]
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
    # the in-place read met every row of the one attention layer whole
    assert attrs["positions_read_full"] == cc.rows * cc.max_len
    assert updates.total() - before == 1 * CFG.layers_of("ssm")
    mem = eng.program_memory()
    assert mem["decode S=4"]["state_bytes"] == cc.rows * \
        cc.bytes_per_slot()["ssm"]
    assert mem["decode S=4"]["pages"]["ssm"] == "state"
    # K and V of one attention layer a lane, a state and its kept inputs
    # a state-space layer: per lane from a prompt, whole by a decode step
    assert mem["prefill Tpad=8"]["cache_writes"] == 2 * (1 + 2)
    assert mem["decode S=4"]["cache_writes"] == 2 * (4 + 2)


def test_no_instruction_of_a_mamba_mixer_lands_under_another_layers_scope():
    """``ffn``, ``attn.*`` and ``cache.*`` keep meaning what they mean: a
    Mamba layer, over a prompt and over a state, compiles to instructions
    under ``ssm.project``, ``ssm.conv`` and ``ssm.scan`` alone."""
    lp = jax.tree.map(lambda a: a[0], make_params(CFG, 1)["layers"][0])
    cc = kv.SsmCacheConfig(full_layers=1, ssm_layers=2, slots=4, max_len=16,
                           kv_heads=2, head_dim=8, ssm_heads=8,
                           ssm_head_dim=12, ssm_state=8, conv_taps=3,
                           conv_dim=CFG.conv_dim)
    cache = {k: jnp.zeros(s, cc.dtypes()[k]) for k, s in cc.shapes().items()}

    def prompt(lp, x, cache):
        def mix(h):
            z, xbc, dt = decoder.mamba_project(CFG, lp, h)
            xbc, kept = decoder.mamba_conv(CFG, lp, xbc, true_len=jnp.int32(6))
            y, state = decoder.mamba_scan_chunked(
                CFG, lp, *decoder.mamba_split(CFG, xbc), dt, jnp.int32(6))
            return decoder.mamba_gate_out(CFG, lp, y, z), kv.ssm_prefill(
                cache, 1, jnp.int32(2), state, kept)
        return decoder.mixer_block(CFG, lp, x, "ssm", mix)

    def token(lp, x, cache):
        slots = jnp.array([2, 0, 4, 4])

        def mix(h):
            z, xbc, dt = decoder.mamba_project(CFG, lp, h)
            xbc, nc = kv.ssm_conv_step(
                cache, 1, slots, xbc,
                lambda xbc, prev: decoder.mamba_conv(CFG, lp, xbc, prev))
            x, B, C = decoder.mamba_split(CFG, xbc)
            log_a, dx = decoder.mamba_discretize(lp, x, dt)
            y, nc = kv.ssm_state_step(
                nc, 1, slots, lambda *a: decoder.mamba_step(CFG, *a), log_a,
                dx, B, C)
            return decoder.mamba_gate_out(CFG, lp, y, z), nc
        return decoder.mixer_block(CFG, lp, x, "ssm", mix)

    for fn, T in ((prompt, 8), (token, 4)):
        x = jnp.ones((T, CFG.d_model))
        tab = tracing._scope_table(
            jax.jit(fn).lower(lp, x, cache).compile().as_text())
        scopes = {scope for scope, _ in tab["ops"].values()} - {""}
        assert scopes <= {"ssm.project", "ssm.conv", "ssm.scan"}, scopes
        assert {"ssm.project", "ssm.scan"} <= scopes
