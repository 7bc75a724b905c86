"""Serving a latent model of shortcut-connected double layers as one chip's
share: two latent attentions with their own cached vectors and two dense
FFNs a layer, ONE expert layer whose result joins a sublayer later, a
softmax router whose last outputs are identity experts, no shared expert,
the scales behind the low-rank paths.  The engine against the plain
reference (perfbench/reference/latent_scmoe.py) by LOGITS, prefill and then
decode through the cache; the shares of an expert layer against the uncut
layer; the controls that must fail, one mechanism each; what the
configuration refuses.  Small sizes, seeded random weights, float32 on the
CPU."""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bluefog_tpu.models import decoder  # noqa: E402
from bluefog_tpu.moe import layers as moe_layers  # noqa: E402
from bluefog_tpu.parallel import compose  # noqa: E402
from bluefog_tpu.serve import Scheduler, ServeConfig, ServeEngine  # noqa: E402
from bluefog_tpu.serve import kv_cache as kv  # noqa: E402
from bluefog_tpu.utils import metrics, tracing  # noqa: E402
from perfbench.reference import latent_scmoe as reference  # noqa: E402

# the reference's side: the source's key names (8 experts and 4 identity
# experts under a router of 12, the chip holds experts 0-3)
SRC = {"hidden_size": 64, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
       "num_layers": 2, "num_attention_heads": 4, "kv_lora_rank": 16,
       "q_lora_rank": 24, "qk_rope_head_dim": 8, "v_head_dim": 8,
       "qk_nope_head_dim": 8, "mla_scale_q_lora": True,
       "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
       "zero_expert_num": 4, "moe_topk": 3, "rms_norm_eps": 1e-5,
       "rope_theta": 1e7, "vocab_size": 128}
CFG = decoder.LatentConfig(
    vocab=128, d_model=64, heads=4, layers=2, q_rank=24, kv_rank=16,
    nope_dim=8, rope_dim=8, v_dim=8, dense_ffn=96, expert_ffn=32,
    num_experts=12, held_experts=4, held_start=0, top_k=3, n_group=1,
    topk_group=1, route_scale=6.0, rope_base=1e7, eps=1e-5, dense_layers=0,
    route_bias=True, shortcut=True, router="softmax", zero_experts=4,
    shared_expert=False, q_scale=math.sqrt(64 / 24), kv_scale=2.0)
TOL = 1e-4          # of the largest reference logit; sound reads 1e-6


def make_params(cfg, seed=0):
    """Seeded leaves at scales that let a fault show: matrices 0.2 normal,
    norm scales 1 + 0.1 normal, the router's bias 0.03 normal (a third of
    a score of 1 / 12)."""
    key, out = jax.random.key(seed), {}
    for group, leaves in decoder.latent_param_shapes(cfg).items():
        out[group] = {}
        for name, shape in leaves.items():
            key, k = jax.random.split(key)
            z = jax.random.normal(k, shape, jnp.float32)
            out[group][name] = (1.0 + 0.1 * z if name[0] == "g" else
                                0.03 * z if name == "eb" else 0.2 * z)[None]
    return out


def make_engine(cpu_devices, cfg=CFG, **scfg):
    m = compose.compose_parallelism(1, 1, 1, 1, devices=cpu_devices[:1])
    kw = dict(batch_buckets=(4,), prefill_buckets=(16,), slots=4, max_len=32)
    kw.update(scfg)
    return ServeEngine(m, cfg, make_params(cfg), ServeConfig(**kw))


def serve(eng, prompts, outputs):
    """The requests through a Scheduler stepped here: per request its
    generated tokens, its prefill logits and ``{j: the decode program's
    logits generated[j] was chosen from}``."""
    sched = Scheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=outputs) for p in prompts]
    logits = [{} for _ in reqs]
    while not sched.done:
        before = [len(r.generated) for r in reqs]
        sched.step()
        handed = eng.decode_logits(0)
        if handed is None:
            continue
        lane = {int(s): i for i, s in enumerate(handed[0])}
        rows = np.asarray(handed[1])
        for r, n0, keep in zip(reqs, before, logits):
            for j in range(max(n0, 1), len(r.generated)):
                keep[j] = rows[j - max(n0, 1), lane[r.slot]]
    sched.close()
    first = [np.asarray(eng.prefill(0, r.slot, p)[1])
             for r, p in zip(reqs, prompts)]
    return [([int(t) for t in r.generated], f, d)
            for r, f, d in zip(reqs, first, logits)]


def reference_logits(params, toks, **kw):
    p = jax.tree.map(lambda a: a[0], params)
    leaves = lambda i: tuple({k: v[i] for k, v in p[g].items()}
                             for g in ("blocks", "blocks2"))
    return np.asarray(reference.forward(SRC, leaves, p["shared"],
                                        jnp.asarray(toks), **kw)[0])


def worst_errors(eng, prompts, outputs=6):
    """(prefill, decode): the largest |engine - reference| over the
    prompts' prefill logits and over every decoded position's, as a share
    of the largest reference logit."""
    pre = dec = 0.0
    for prompt, (gen, first, logits) in zip(prompts,
                                            serve(eng, prompts, outputs)):
        assert len(gen) == outputs and sorted(logits) == list(
            range(1, outputs))
        want = reference_logits(eng.params, prompt + gen)
        scale, last = np.abs(want).max(), len(prompt) - 1
        pre = max(pre, np.abs(first - want[last]).max() / scale)
        dec = max([dec] + [np.abs(logits[j] - want[last + j]).max() / scale
                           for j in logits])
    return pre, dec


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab, n).tolist() for n in lengths]


def test_param_shapes_hold_two_halves_a_layer_and_no_shared_expert():
    shapes = decoder.latent_param_shapes(CFG)
    assert list(shapes) == ["blocks", "blocks2", "shared"]     # no leading
    half = {"g1", "wqa", "gq", "wqb", "wkva", "gkv", "wkvb", "wo", "g2",
            "wg", "wu", "wd"}
    assert set(shapes["blocks2"]) == half
    assert set(shapes["blocks"]) == half | {"wr", "eb", "weg", "weu", "wed"}
    assert shapes["blocks"]["wr"] == (2, 64, 12)       # all 12 outputs
    assert shapes["blocks"]["weg"] == (2, 4, 64, 32)   # identity: no leaf
    assert CFG.attn_layers == 4 and CFG.expert_layers == 2
    assert decoder.latent_param_count(CFG) == sum(
        int(np.prod(s)) for g in shapes.values() for s in g.values())
    shared = decoder.latent_param_shapes(
        dataclasses.replace(CFG, shared_expert=True))
    assert {"wsg", "wsu", "wsd"} <= set(shared["blocks"])


@pytest.mark.parametrize("lengths", [(7,), (5, 12, 9), (16, 3, 8, 11)],
                         ids=["one_lane", "three_lanes", "four_lanes"])
def test_prefill_then_decode_through_the_cache_match_the_reference_by_logits(
        cpu_devices, lengths):
    eng = make_engine(cpu_devices)
    eng.warmup()
    pre, dec = worst_errors(eng, prompts_of(lengths))
    assert pre < TOL and dec < TOL, (pre, dec)
    assert metrics.counter(
        "bluefog_retrace_after_warmup_total").total() == 0


def _no_identity(h, idx, weight, first):
    return jnp.zeros_like(h)


def _join_after_the_first_half(cfg, lp, lp2, x, positions, attend,
                               attend2_of, moe):
    def first_ffn(lp, h):
        m, faux = moe(lp, h)
        return decoder.dense_gated_ffn(lp, h)[0] + m, faux
    x, aux, faux = decoder.latent_block(cfg, lp, x, positions, attend,
                                        first_ffn)
    x, aux2, _ = decoder.latent_block(cfg, lp2, x, positions,
                                      attend2_of(aux),
                                      decoder.dense_gated_ffn)
    return x, (aux, aux2), faux


def _bias_in_the_weights(x, wr, *, top_k, route_scale, bias=None):
    s, idx, _ = _SOUND["router_softmax"](x, wr, top_k=top_k,
                                         route_scale=route_scale, bias=bias)
    return s, idx, route_scale * jnp.take_along_axis(s + bias, idx, -1)


def _first_halfs_vectors(q_abs, q_rope, cache, layer, *rest, **kw):
    return _SOUND["latent_attend_slots"](q_abs, q_rope, cache,
                                         layer - layer % 2, *rest, **kw)


_SOUND = {"router_softmax": moe_layers.router_softmax,
          "latent_attend_slots": kv.latent_attend_slots}
# name -> (what is altered, which comparison has to fail)
CONTROLS = {
    "identity_outputs_dropped": (
        (moe_layers, "zero_expert_part", _no_identity), "both"),
    "experts_joined_after_the_first_half": (
        (decoder, "latent_double_block", _join_after_the_first_half), "both"),
    "q_scale_left_out": ({"q_scale": 1.0}, "both"),
    "kv_scale_left_out": ({"kv_scale": 1.0}, "both"),
    "bias_added_to_the_weights": (
        (moe_layers, "router_softmax", _bias_in_the_weights), "both"),
    # a prompt attends over its own sequence: only decode reads the cache
    "second_attention_reads_the_firsts_cached_vectors": (
        (kv, "latent_attend_slots", _first_halfs_vectors), "decode"),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_program_without_one_mechanism_fails_the_comparison(
        cpu_devices, monkeypatch, control):
    altered, fails = CONTROLS[control]
    cfg = CFG
    if isinstance(altered, dict):
        cfg = dataclasses.replace(CFG, **altered)
    else:
        monkeypatch.setattr(*altered)
    pre, dec = worst_errors(make_engine(cpu_devices, cfg),
                            prompts_of((5, 12, 9)), outputs=4)
    assert dec > 10 * TOL, (control, pre, dec)
    assert (pre > 10 * TOL) == (fails == "both"), (control, pre, dec)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Guide section 4: what the two chips' held experts give, with the
    identity experts' part (which every chip computes alike) counted once,
    is the uncut reference's MoE(h); the program's own layer gives each
    share."""
    lp = jax.tree.map(lambda a: a[0, 0], make_params(
        dataclasses.replace(CFG, held_experts=8))["blocks"])
    h = jax.random.normal(jax.random.key(5), (9, CFG.d_model))
    whole, (_, _, picked, _) = reference.moe(SRC, lp, h)
    cut = lambda a, lo: a[lo:lo + 4]
    shares = []
    for lo in (0, 4):
        mine = {**lp, **{k: cut(lp[k], lo) for k in ("weg", "weu", "wed")}}
        y, idx, _ = moe_layers.held_moe_ffn(
            dataclasses.replace(CFG, held_start=lo), mine, h)
        np.testing.assert_array_equal(np.sort(idx, -1), np.sort(picked, -1))
        part, _ = reference.moe(SRC, mine, h, held_start=lo)
        np.testing.assert_allclose(y, part, rtol=1e-4, atol=1e-5)
        shares.append(y)
    zero = reference.moe(SRC, {**lp, **{k: lp[k][:0] for k in (
        "weg", "weu", "wed")}}, h)[0]
    assert np.abs(zero).max() > 0.1        # identity experts were selected
    np.testing.assert_allclose(shares[0] + shares[1] - zero, whole,
                               rtol=1e-4, atol=1e-5)
    # a share that left the identity part out would not add up
    assert np.abs(shares[0] + shares[1] - 2 * zero - whole).max() > 0.1


def test_the_softmax_router_selects_by_bias_and_weighs_by_raw_scores():
    x = jax.random.normal(jax.random.key(1), (7, 16))
    wr = jax.random.normal(jax.random.key(2), (16, 12))
    bias = 0.05 * jax.random.normal(jax.random.key(3), (12,))
    s, idx, w = moe_layers.router_softmax(x, wr, top_k=3, route_scale=6.0,
                                          bias=bias)
    p = np.asarray(jax.nn.softmax(np.asarray(x) @ np.asarray(wr), -1))
    np.testing.assert_allclose(s, p, rtol=1e-5)
    want = np.argsort(-(p + np.asarray(bias)), -1)[:, :3]
    np.testing.assert_array_equal(idx, want)
    assert not np.array_equal(want, np.argsort(-p, -1)[:, :3])   # bias moved
    np.testing.assert_allclose(w, 6.0 * np.take_along_axis(p, want, -1),
                               rtol=1e-5)                  # no renormalising
    assert s.dtype == jnp.float32 and moe_layers.router_softmax(
        x.astype(jnp.bfloat16), wr, top_k=3, route_scale=6.0)[0].dtype \
        == jnp.float32


def test_decode_counts_identity_pairs_and_the_cache_counts_sublayers(
        cpu_devices):
    metrics.reset_metrics()
    eng = make_engine(cpu_devices)
    for slot, p in enumerate(([1, 2, 3], [4, 5, 6, 7, 8])):
        eng.prefill(0, slot, p)
    eng.decode(np.array([[3, 5, 0, 0]], np.int32),
               np.array([[0, 1, 4, 4]], np.int32),          # two live lanes
               np.array([[3, 5, 0, 0]], np.int32))
    load = eng.moe_load()[0]
    assert load["counts"].shape == (12,)                 # every output
    assert load["tokens"] == 4 and load["counts"].sum() == 4 * CFG.top_k
    zero = metrics.counter("bluefog_serve_moe_zero_pairs_total").total()
    assert zero == load["counts"][8:].sum() > 0
    assert metrics.counter("bluefog_serve_moe_held_pairs_total").total() \
        == load["counts"][:4].sum()
    # a cached vector a token and attention SUBLAYER, all landed at once
    assert {k: v.shape for k, v in eng.cache.items()} == {
        "ckv": (1, 4, 5, 32, 16), "kr": (1, 4, 5, 32, 8)}
    assert metrics.gauge("bluefog_serve_cache_bytes_per_token").value() \
        == 4 * CFG.latent_dim * 4
    mem = eng.program_memory()
    assert mem["decode S=4"]["cache_writes"] == 4 * 2
    assert mem["prefill Tpad=16"]["cache_writes"] == 4 * 2
    # the selections and logits that only a comparison reads
    assert np.asarray(eng.decode_chosen(0)[1]).shape == (1, 2, 4, 3)
    assert np.asarray(eng.prefill_chosen(0)).shape == (2, 16, 3)
    assert np.asarray(eng.decode_logits(0)[1]).shape == (1, 4, 128)
    # the scopes of a double layer: the identity path has its own, and no
    # shared expert occurs
    scopes = {s for key, table in tracing.device_scopes().items()
              if key in mem for s, _ in table["ops"].values()}
    assert {"mla.project", "mla.attend", "ffn", "moe.route", "moe.experts",
            "moe.zero", "readout", "cache.read", "cache.write"} <= scopes
    assert "moe.shared" not in scopes


def test_a_prompt_in_chunks_of_tokens_goes_through_the_same_experts(
        cpu_devices, monkeypatch):
    prompt = prompts_of((13,))[0]
    whole = np.asarray(make_engine(cpu_devices).prefill(0, 0, prompt)[1])
    monkeypatch.setattr(ServeEngine, "_PROMPT_FFN_CHUNK", 8)
    monkeypatch.setattr(ServeEngine, "_PROMPT_ROWS_BYTES", 0)
    eng = make_engine(cpu_devices)
    np.testing.assert_allclose(eng.prefill(0, 0, prompt)[1], whole,
                               rtol=1e-5, atol=1e-6)
    assert "while" in eng._prefill_jit.lower(*eng._args(eng._expand(
        "prefill", eng._pack(np.zeros((1, 16), np.int32), [0], [1], [0],
                             [0])))).as_text()


@pytest.mark.parametrize("bad, match", [
    (dict(dense_layers=1), "latent_shortcut"),
    (dict(streams=2), "latent_shortcut"),
    (dict(router="sigmoid"), "latent_router"),
    (dict(n_group=2), "latent_router"),
    (dict(zero_experts=9), "latent_zero_experts"),
    (dict(zero_experts=-1), "latent_zero_experts"),
    (dict(q_scale=0.0), "latent_mla_scales"),
    (dict(kv_scale=float("inf")), "latent_mla_scales"),
    (dict(shortcut=False), "latent_dense_layers"),
])
def test_the_config_refuses_what_its_new_fields_cannot_mean(cpu_devices, bad,
                                                            match):
    with pytest.raises(ValueError, match=match):
        make_engine(cpu_devices, dataclasses.replace(CFG, **bad))


def test_a_plain_latent_model_takes_the_softmax_router_and_identity_experts(
        cpu_devices):
    """The router, the identity experts and the handed-out selections are
    the configuration's, not the double layer's: a latent model with a
    leading dense layer, single layers and a shared expert serves under
    them what its own block computes over the whole sequence."""
    cfg = dataclasses.replace(CFG, layers=3, dense_layers=1, shortcut=False,
                              shared_expert=True, q_scale=1.0, kv_scale=1.0)
    eng = make_engine(cpu_devices, cfg)
    prompt = prompts_of((9,))[0]
    (gen, first, logits), = serve(eng, [prompt], 4)
    p = jax.tree.map(lambda a: a[0], eng.params)
    toks = jnp.asarray(prompt + gen)
    pos, live = jnp.arange(len(toks)), jnp.ones(len(toks), bool)
    hook = lambda lp: lambda qn, qr, lat: (
        decoder.mla_unabsorbed(cfg, lp, qn, qr, lat), None)
    x, _, _ = decoder.latent_block(cfg, p["first"], p["shared"]["embed"][toks],
                                   pos, hook(p["first"]),
                                   decoder.dense_gated_ffn)
    for i in range(cfg.expert_layers):
        lp = jax.tree.map(lambda a: a[i], p["blocks"])
        x, _, idx = decoder.latent_block(
            cfg, lp, x, pos, hook(lp), lambda lp, h: (
                lambda y, idx, w: (y, idx))(*moe_layers.held_moe_ffn(
                    cfg, lp, h, live)))
    want = np.asarray(decoder.latent_logits(cfg, p["shared"], x))
    last = len(prompt) - 1
    np.testing.assert_allclose(first, want[last], rtol=1e-3, atol=2e-5)
    for j in logits:
        np.testing.assert_allclose(logits[j], want[last + j], rtol=1e-3,
                                   atol=2e-5)
    assert (np.asarray(idx) >= 8).any()             # identity outputs met
    assert np.asarray(eng.prefill_chosen(0)).shape == (2, 16, 3)
