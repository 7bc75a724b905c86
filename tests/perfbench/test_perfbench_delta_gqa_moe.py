"""Family ``delta_gqa_moe`` at the configuration file's ``tiny`` sizes on the
CPU: the program against the plain reference through a Scheduler, the
mechanism controls that must FAIL the comparison (beta undoubled, the gate
before the norm, rotary switched on, a scalar decay a head, no attention
gate, no router bias, no ``dt_bias``, no delta gate, int8 weights, a
bfloat16 state), the tie rule, the four shares of an expert layer against
the uncut reference layer, the reference's recurrence, the bytes and
operations behind the roofline shares, the two new metrics' readers, and
that the state-space family's programs are still the parent's."""
import dataclasses
import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import _shape  # noqa: E402
import test_perfbench_ssm_latent_moe as pr43  # noqa: E402
from perfbench.harness import manifest, program_spans  # noqa: E402
from perfbench.reference import delta_gqa_moe as reference  # noqa: E402

CELL = "solar-open2.serve-closed32-p16384"
NEW_METRICS = ("ssm.prefill_scan_device_s_per_ktok",
               "attn.prefill_key_blocks_per_call")
FAMILY = manifest.load_module("families", "delta_gqa_moe")


def sized(tiny=True, cell=CELL):
    return pr43.sized(tiny, cell)


def build(cfg=None, seed=7):
    tiny, traffic = sized()
    return FAMILY.build_serve(cfg or tiny, traffic,
                              jax.devices("cpu")[:1], seed)


def check(prog, alter=None):
    """The cell's check on ``prog``, its ENGINE given ``alter``ed weights:
    the reference keeps the sound ones."""
    if alter is not None:
        prog.engine.update_params(alter(prog.params))
    asked = sized()[1]["check"]
    rng = np.random.default_rng(3)
    return prog.reference_check(
        [rng.integers(0, prog.vocab, n).tolist()
         for n in asked["prompt_tokens"]], asked["output_tokens"])


def _entry():
    entry, = [c for c in manifest.load()["configs"]
              if c["name"] == "solar-open2"]
    return entry


def test_the_tiny_sizes_keep_every_mechanism_and_the_file_the_published():
    from bluefog_tpu.models import decoder
    cfg, _ = sized()
    lm = FAMILY.ssm_config(cfg)
    assert set(lm.plan) == {"delta", "full", "experts"} and lm.route_bias
    assert lm.plan.count("delta") == 2 and lm.recurrent == "delta"
    assert lm.attn_gate and lm.expert_form == "gated_silu" and not lm.latent
    assert lm.delta_beta_max == 2.0 and lm.chunk == 4 and lm.delta_rank > 1
    assert lm.heads > lm.kv_heads > 1
    assert lm.held_experts < lm.num_experts and lm.top_k == lm.held_experts
    full, traffic = sized(False)
    big = FAMILY.ssm_config(full)
    assert "".join(c for c in reference.plan(full) if c != "E") == "GLLLGLLL"
    assert big.plan == ("full", "experts", "delta", "experts", "delta",
                        "experts", "delta", "experts") * 2
    assert (big.d_model, big.ssm_heads, big.ssm_head_dim, big.ssm_state,
            big.conv_kernel, big.chunk, big.delta_rank) == (
        4096, 64, 128, 128, 4, 16, 128)
    assert (big.heads, big.kv_heads, big.head_dim) == (64, 8, 128)
    assert (big.latent, big.expert_ffn, big.shared_ffn) == (0, 1280, 1280)
    assert (big.num_experts, big.held_experts, big.held_start, big.top_k,
            big.route_scale) == (320, 20, 0, 8, 1)
    assert (big.vocab, big.eps, big.ssm_eps) == (24576, 1e-5, 1e-5)
    assert decoder.ssm_param_count(big) \
        == full["deployment"]["held_parameters"]
    # every key of the catalog row under its own name, three of them cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["source_url"] == _entry()["source"]]
        differ = sorted(k for k, v in row["config"].items()
                        if full.get(k, "absent") != v)
        assert differ == sorted(_entry()["reduced"]), differ
    assert full["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_size": 196608}
    assert full["gqa_layers"] == list(range(0, 48, 4))    # whole, as published
    assert full["deployment"]["chips_per_layer"] == 16
    assert full["deployment"]["vocab_slice"] == [0, 24576]
    whole = manifest.load_json(os.path.join(ROOT, _entry()["file"]))
    for key in ("reduced_why", "departures", "assumed", "tiny"):
        assert whole[key]
    assert set(full["reduced_why"]) == set(_entry()["reduced"])
    # the cell as the issue states it
    eng = traffic["engine"]
    assert (traffic["clients"], traffic["cycle"], eng["slots"],
            eng["max_len"]) == (32, 32, 32, 16640)
    assert eng["batch_buckets"] == [32] and eng["dtype"] == "bfloat16"
    assert eng["prefill_buckets"] == [1024, 2048, 4096, 8192, 16384]
    assert all(b % big.chunk == 0 for b in eng["prefill_buckets"])
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 512,
                                        "hi": 16384}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 64,
                                        "hi": 256}
    assert traffic["ramp_output_tokens"] == {"dist": "uniform", "lo": 8,
                                             "hi": 256}
    assert traffic["traced_seconds"] == 3.0 and traffic["loop"] == "closed"
    assert isinstance(traffic["length_order"]["seed"], int)
    assert len(traffic["length_order"]["why"]) > 200
    # one asked prompt past one block of keys, one inside it
    from bluefog_tpu.serve import ServeEngine
    short, long = traffic["check"]["prompt_tokens"]
    assert short < 1024 and ServeEngine._FLASH_KEY_BLOCK < long < 16384


def test_prefill_then_decode_through_the_scheduler_agree_with_the_reference():
    prog = build()
    asked = sized()[1]["check"]
    ref = check(prog)
    assert ref["ok"], ref
    c = ref["compared"]
    # float32 on the CPU: the program IS the reference's function
    assert c["prefill_logit_err_share"][0] < 1e-5
    assert c["decode_logit_err_share"][0] < 1e-5
    assert c["decode_logit_gap_share"][0] < 1e-5
    assert c["route_faults"] == [0, 0] and c["route_tied_share"][0] == 0
    assert c["requests_off_length"] == [0, 0]
    # what each slot held of matrix state after its last decode call is
    # the reference's state after the same tokens, in every delta layer
    assert c["ssm_state_err_share"][0] < 1e-5
    assert c["ssm_state_bfloat16_share"][0] < 0.01      # float32 sums
    for row in ref["requests"]:
        assert len(row["ssm_state_rel_err"]) == FAMILY.layers_of(
            sized()[0], "L") and max(row["ssm_state_rel_err"]) < 1e-5
    # every asked length is compared, and every decoded position of it
    assert [r["prompt_tokens"] for r in ref["requests"]] \
        == asked["prompt_tokens"]
    expert_layers = FAMILY.expert_layers(sized()[0])
    for row in ref["requests"]:
        assert row["decode_positions"] == asked["output_tokens"] - 1
        assert row["selections"] == expert_layers * (
            row["prompt_tokens"] + row["decode_positions"])


zeroed = pr43.zeroed
fake_int8 = pr43.fake_int8
turned = pr43.turned


def gate_before_the_norm(cfg, lp, o, z):
    """The other choice ``assumed.kda_out`` names: the gate first, the
    per-head RMSNorm behind it."""
    lead = z.shape[:-1]
    gate = jax.nn.sigmoid(z @ lp["wgb"]).reshape(o.shape)
    y = o.astype(jnp.float32) * gate
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.ssm_eps) \
        * lp["g_o"]
    return y.reshape(lead + (-1,)).astype(z.dtype) @ lp["w_out"]


def scalar_decay(discretize):
    """The decay the gated delta NET has: one number a head (here the mean
    of the head's channels), not one a channel."""
    def one_a_head(cfg, lp, f, b, live=None):
        g, beta = discretize(cfg, lp, f, b, live)
        return jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape), beta
    return one_a_head


def undoubled(discretize):
    def half(cfg, lp, f, b, live=None):
        g, beta = discretize(cfg, lp, f, b, live)
        return g, beta / 2
    return half


def ungated(cfg, lp, att, h):
    return att.reshape(h.shape[0], -1) @ lp["wo"]


@pytest.mark.parametrize("control", [
    "beta_undoubled", "gate_before_the_norm", "rotary_switched_on",
    "scalar_decay_a_head", "no_gqa_gate", "no_router_bias", "no_dt_bias",
    "no_delta_gate", "int8_weights", "bfloat16_state"])
def test_a_changed_mechanism_fails_the_comparison(control, monkeypatch):
    """The same engine with ``beta`` in (0, 1), with the gate before the
    per-head norm, with rotary switched on in its attention layers, with
    one decay a head, without the attention gate, without its router bias,
    ``dt_bias`` or the delta layers' gate (``wgb`` zeroed: a constant half),
    with its weights through int8, or with its matrix state kept in
    bfloat16 (the cache's dtype alone), is NOT the reference's function:
    each by the program's code, weights or cache alone, the reference as it
    stands."""
    from bluefog_tpu.models import decoder
    from bluefog_tpu.serve import kv_cache
    alter = None
    if control == "bfloat16_state":
        dtypes = kv_cache.SsmCacheConfig.dtypes
        monkeypatch.setattr(
            kv_cache.SsmCacheConfig, "dtypes",
            lambda self: {**dtypes(self), "ssm": jnp.bfloat16})
    elif control == "beta_undoubled":
        monkeypatch.setattr(decoder, "delta_discretize",
                            undoubled(decoder.delta_discretize))
    elif control == "scalar_decay_a_head":
        monkeypatch.setattr(decoder, "delta_discretize",
                            scalar_decay(decoder.delta_discretize))
    elif control == "gate_before_the_norm":
        monkeypatch.setattr(decoder, "delta_gate_out", gate_before_the_norm)
    elif control == "no_gqa_gate":
        monkeypatch.setattr(decoder, "gqa_out", ungated)
    elif control == "rotary_switched_on":
        monkeypatch.setattr(decoder, "gqa_project",
                            turned(decoder.gqa_project))
    else:
        alter = {"no_router_bias": zeroed("eb"),
                 "no_dt_bias": zeroed("dt_bias"),
                 "no_delta_gate": zeroed("wgb"),
                 "int8_weights": fake_int8}[control]
    ref = check(build(), alter)
    assert not ref["ok"], ref["compared"]
    c = ref["compared"]
    over = {k for k, (value, limit) in c.items() if value > limit}
    if control == "no_router_bias":
        # the logits agree (the reference follows the program's
        # selections); the selections are no rounding ties
        assert over == {"route_faults", "route_tie_distance"}
        assert c["route_faults"][0] > 50
    elif control == "bfloat16_state":
        assert {"ssm_state_err_share", "ssm_state_bfloat16_share"} <= over
        assert c["ssm_state_bfloat16_share"][0] == 1.0
    else:
        assert over & {"prefill_logit_err_share", "decode_logit_err_share"}
    if control in ("beta_undoubled", "scalar_decay_a_head", "no_dt_bias"):
        assert "ssm_state_err_share" in over
    assert c["requests_off_length"] == [0, 0]


def test_the_tie_rule_passes_a_planted_tie_and_fails_a_far_swap():
    """``selection_report`` (the state-space family's, taken as it is): a
    selection that differs from the reference's own by an expert within
    ``delta`` of its k-th score is a tie; one farther off is a fault."""
    E, k, delta = 16, 4, 0.01
    by = np.tile(np.linspace(1.0, 0.25, E).astype(np.float32), (2, 3, 1))
    by[0, 1, k] = by[0, 1, k - 1] - 0.004          # the 4th and 5th nearly tie
    picked = np.tile(np.arange(k), (2, 3, 1))
    assert FAMILY.selection_report(by, picked, picked.copy(), delta) \
        == (0, 0, 6, 0)
    tie = picked.copy()
    tie[0, 1, k - 1] = k                           # took the 5th for the 4th
    differing, faults, pairs, farthest = FAMILY.selection_report(
        by, picked, tie, delta)
    assert (differing, faults, pairs) == (1, 0, 6)
    assert farthest == pytest.approx(0.004, rel=1e-3)
    far = picked.copy()
    far[1, 2, 0] = E - 1                 # the lowest score for the highest
    differing, faults, pairs, farthest = FAMILY.selection_report(
        by, picked, far, delta)
    assert (differing, pairs) == (1, 6) and faults == 2     # both are far
    assert farthest == pytest.approx(by[1, 2, k - 1] - by[1, 2, E - 1])


def layer_weights(cfg, seed, experts):
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E = cfg["deployment"]["router_outputs"]
    ks = jax.random.split(jax.random.key(seed), 8)
    n = lambda k, s: 0.3 * jax.random.normal(k, s, jnp.float32)
    return {"wr": n(ks[0], (D, E)), "eb": n(ks[1], (E,)) / 3,
            "weg": n(ks[2], (experts, D, F)), "weu": n(ks[3], (experts, D, F)),
            "wed": n(ks[4], (experts, F, D)), "wsg": n(ks[5], (D, F)),
            "wsu": n(ks[6], (D, F)), "wsd": n(ks[7], (F, D))}


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """Chips that hold a quarter of the experts each from ``held_start`` 0,
    1/4, 1/2 and 3/4 of the router's outputs: the reference's shares and
    the program's (moe.layers.held_moe_ffn, both of its forms), the shared
    expert counted once, add up to the reference's layer with every expert
    held."""
    from bluefog_tpu.moe.layers import held_moe_ffn
    cfg, _ = sized()
    E, k = cfg["deployment"]["router_outputs"], cfg["num_experts_per_tok"]
    w = layer_weights(cfg, 11, E)
    h = jax.random.normal(jax.random.key(12), (24, cfg["hidden_size"]))
    whole, by, picked = reference.moe_ffn(cfg, w, h)
    assert picked.shape == (24, k) and by.shape == (24, E)
    lm = dataclasses.replace(FAMILY.ssm_config(cfg), held_experts=E // 4)
    shared = reference.gated(h, w["wsg"], w["wsu"], w["wsd"])
    ref_sum = prog_sum = grouped_sum = shared
    names = ("weg", "weu", "wed")
    for start in range(0, E, E // 4):
        cut = dict(w, **{n: w[n][start:start + E // 4] for n in names})
        ref_sum = ref_sum + reference.moe_ffn(cfg, cut, h, start,
                                              shared=False)[0]
        at = dataclasses.replace(lm, held_start=start)
        part, idx, _ = held_moe_ffn(at, cut, h, form="gated_silu")
        prog_sum = prog_sum + (part - shared)
        assert np.array_equal(np.sort(np.asarray(idx), -1),
                              np.sort(np.asarray(picked), -1))
        stacked = dict(cut, **{n: cut[n][None] for n in names})
        part, _, _ = held_moe_ffn(at, stacked, h, layer=jnp.int32(0),
                                  form="gated_silu")
        grouped_sum = grouped_sum + (part - shared)
    np.testing.assert_allclose(ref_sum, whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(prog_sum, whole, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(grouped_sum, whole, rtol=1e-3, atol=1e-3)
    # without the bias it is another layer
    other = reference.moe_ffn(cfg, dict(w, eb=jnp.zeros(E)), h)
    assert not np.array_equal(np.sort(np.asarray(other[2]), -1),
                              np.sort(np.asarray(picked), -1))


def test_the_references_recurrence_is_the_delta_rule():
    """One head, one key channel that never decays and ``beta = 1``: the
    state forgets a key's old value and holds its new one (``S^T k = v``
    right after a write), and with ``true_len`` the state is the one the
    sequence cut there leaves."""
    T, H, K, V = 7, 2, 4, 3
    rng = np.random.default_rng(0)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = draw(T, H, K), draw(T, H, K), draw(T, H, V)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    zero, one = jnp.zeros((T, H, K)), jnp.ones((T, H))
    o, S = reference.recurrence(k, k, v, zero, one)
    np.testing.assert_allclose(o, v, rtol=1e-5, atol=1e-5)  # read what it wrote
    np.testing.assert_allclose(jnp.einsum("hkv,hk->hv", S, k[-1]), v[-1],
                               rtol=1e-5, atol=1e-5)
    g, beta = -jnp.abs(draw(T, H, K)), 2 * jax.nn.sigmoid(draw(T, H))
    n = 5
    o, S = reference.recurrence(q, k, v, g, beta, true_len=n)
    o_cut, S_cut = reference.recurrence(q[:n], k[:n], v[:n], g[:n], beta[:n])
    np.testing.assert_allclose(S, S_cut, rtol=1e-6)
    np.testing.assert_allclose(o[:n], o_cut, rtol=1e-6)
    whole = reference.recurrence(q, k, v, g, beta)[1]
    assert float(jnp.max(jnp.abs(whole - S))) > 1e-3
    # by hand, two steps of one head: decay, correct, write
    S1 = beta[0, 0] * jnp.outer(k[0, 0], v[0, 0])
    Sd = jnp.exp(g[1, 0])[:, None] * S1
    S2 = Sd + beta[1, 0] * jnp.outer(k[1, 0], v[1, 0] - Sd.T @ k[1, 0])
    np.testing.assert_allclose(
        reference.recurrence(q[:2], k[:2], v[:2], g[:2], beta[:2])[1][0], S2,
        rtol=1e-5, atol=1e-6)


def test_the_references_attention_in_query_blocks_is_the_masked_softmax(
        monkeypatch):
    cfg, _ = sized()
    T, D = 12, cfg["hidden_size"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ks = jax.random.split(jax.random.key(2), 6)
    n = lambda k, s: 0.3 * jax.random.normal(k, s, jnp.float32)
    w = {"wq": n(ks[0], (D, H * Dh)), "wk": n(ks[1], (D, Hkv * Dh)),
         "wv": n(ks[2], (D, Hkv * Dh)), "wgate": n(ks[3], (D, H * Dh)),
         "wo": n(ks[4], (H * Dh, D))}
    u = jax.random.normal(ks[5], (T, D))
    whole = reference.attention(cfg, w, u)
    monkeypatch.setattr(reference, "SCORE_BYTES", H * 3 * T * 4)  # blocks of 3
    np.testing.assert_allclose(reference.attention(cfg, w, u), whole,
                               rtol=1e-5, atol=1e-6)
    # causal: a later token moves no earlier row; gated: the gate matters
    moved = reference.attention(cfg, w, u.at[-1].add(1.0))
    np.testing.assert_allclose(moved[:-1], whole[:-1], rtol=1e-5, atol=1e-6)
    plain = reference.attention(dict(cfg, use_gqa_gate=False), w, u)
    assert float(jnp.max(jnp.abs(plain - whole))) > 1e-2


def test_the_floors_count_what_the_chip_holds():
    from bluefog_tpu.models import decoder
    from bluefog_tpu.serve import kv_cache as kv
    cfg, traffic = sized(False)
    lm = FAMILY.ssm_config(cfg)
    shapes = decoder.ssm_param_shapes(lm)
    held = decoder.ssm_param_count(lm)
    assert held == cfg["deployment"]["held_parameters"] == 3_898_793_600
    groups = shapes["layers"] + (shapes["shared"],)
    size = lambda pick: sum(int(np.prod(s)) for g in groups
                            for n, s in g.items() if pick(n))
    in_f32 = size(lambda n: n in decoder.FLOAT32_LEAVES)
    routed = size(lambda n: n in ("weg", "weu", "wed"))
    embed = size(lambda n: n == "embed")
    assert FAMILY.weight_bytes(cfg) == \
        2 * (held - routed - embed - in_f32) + 4 * in_f32
    assert FAMILY.expert_bytes(cfg) == 2 * routed // (8 * 20)
    assert (FAMILY.held_experts(cfg), FAMILY.expert_layers(cfg)) == (20, 8)
    assert [FAMILY.layers_of(cfg, c) for c in "LGE"] == [6, 2, 8]
    # what the chip holds: 7.82 GB of weights beside 5.36 GB of state and rows
    weights = 2 * (held - in_f32) + 4 * in_f32
    assert 7.81e9 < weights < 7.83e9
    assert round(weights / 1e9, 2) == cfg["deployment"]["held_bf16_gb"]
    scfg = FAMILY.serve_config(traffic)
    cc = kv.SsmCacheConfig.of(lm, scfg.slots, scfg.max_len, scfg.dtype)
    assert 5.35e9 < cc.bytes() < 5.38e9
    assert cc.bytes_per_slot()["ssm"] == 6 * (64 * 128 * 128 * 4
                                              + 3 * 24576 * 2)
    # the states' bytes: each lane's, read and written, in 6 layers
    per_lane = 2 * cc.bytes_per_slot()["ssm"]
    assert FAMILY.ssm_state_bytes(cfg, 32) == 32 * per_lane
    assert 1.6e9 < FAMILY.ssm_state_bytes(cfg, 32) < 1.7e9
    floor = FAMILY.decode_floor_bytes(cfg, calls=2, experts_hit=100,
                                      positions=1000, state_lanes=60)
    assert floor == 2 * FAMILY.weight_bytes(cfg) \
        + 100 * FAMILY.expert_bytes(cfg) + 60 * per_lane \
        + 1000 * 2 * FAMILY.position_bytes(cfg)
    assert FAMILY.position_bytes(cfg) == 2 * 8 * 128 * 2
    # every expert hit, every lane live at the rows' full length: the most a
    # call of the floor can be is under what the chip holds with the states
    # counted once
    most = FAMILY.decode_floor_bytes(cfg, 1, 160, 32 * 16640, 32)
    assert most - FAMILY.ssm_state_bytes(cfg, 32) / 2 < weights + cc.bytes()
    # the delta rule: 7 flops a state element, token and layer
    assert FAMILY.ssm_scan_flops(cfg, 1000) == 1000 * 6 * 7 * 64 * 128 * 128
    # a prompt's operations: 2 per active parameter and token, plus the
    # delta rule and attention that grows with the length
    active = held - routed - 2 * embed - in_f32 \
        + 8 * 4096 * 320 + 8 * 0.5 * 3 * 4096 * 1280
    for t in (512, 16384):
        attention = 2 * 2 * 2 * 64 * 128 * t * (t + 1) // 2
        assert FAMILY.prefill_flops(cfg, t) == pytest.approx(
            2 * active * t + FAMILY.ssm_scan_flops(cfg, t) + attention
            + 2 * 4096 * 24576, rel=0.01)
    assert 2.3e9 < FAMILY.prefill_flops(cfg, 512) / 512 < 2.8e9


LIMITS = {"prefill_logit_err_share": FAMILY.SERVE_LOGIT_TOL,
          "decode_logit_err_share": FAMILY.DECODE_LOGIT_TOL,
          "decode_logit_gap_share": FAMILY.DECODE_GAP_TOL,
          "route_tie_distance": FAMILY.ROUTE_TIE_DELTA,
          "ssm_state_err_share": FAMILY.STATE_TOL}
# the room a limit keeps to each of its two readings, as a factor
ROOM = {}


@pytest.mark.parametrize("name", sorted(LIMITS))
def test_every_chip_limit_lies_between_its_two_readings(name):
    """A bf16 limit stands over what the sound program read at most on the
    chip and under what the nearest control read, with room on both sides
    (the family file's CHIP_READINGS; PERF.md section 6 has the runs)."""
    sound, control = FAMILY.CHIP_READINGS[name]
    limit = LIMITS[name]["bfloat16"]
    room = ROOM.get(name, 1.5)
    assert room * sound <= limit <= control / room, (sound, limit, control)
    assert LIMITS[name]["float32"] < sound


class _Calls:
    """The program's prefill spans of a traced tail, made by hand."""

    def __init__(self, calls):
        self.calls = calls

    def named(self, name):
        return self.calls if name == "bf:engine.prefill_call" else []


def _call(**attrs):
    return program_spans.Span("bf:engine.prefill_call", 0, 1, attrs, None)


def test_the_new_metrics_read_the_scope_table_and_the_spans():
    cfg, _ = sized(False)
    ana = pr43._Analysis(
        {("prefill Tpad=16384", "ssm.scan", ""): 0.030,
         ("prefill Tpad=16384", "ssm.project", ""): 0.040,
         ("prefill Tpad=1024", "ssm.scan", ""): 0.003,
         ("prefill Tpad=1024", "ssm.conv", ""): 0.001,
         ("prefill Tpad=1024", "moe.experts", ""): 0.1,
         ("decode S=32", "ssm.scan", ""): 0.060},
        {"prefill Tpad=16384": 1, "prefill Tpad=1024": 2, "decode S=32": 4},
        {"prefill Tpad=16384": 12000, "prefill Tpad=1024": 1200})
    calls = _Calls([_call(Tpad=16384, tokens=12000, key_blocks=2),
                    _call(Tpad=1024, tokens=600, key_blocks=1),
                    _call(Tpad=1024, tokens=600, key_blocks=1)])
    run = {"config": cfg, "workload": CELL, "device_scopes": ana,
           "program_spans": calls,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    read = lambda name, run=run: manifest.load_module(
        "metrics", name).read(run)
    assert read(NEW_METRICS[0]) == pytest.approx(0.033 / 13.2)
    assert read(NEW_METRICS[1]) == pytest.approx(4 / 3)
    # the scan's share of its roofline by this family's count
    want = FAMILY.ssm_scan_flops(cfg, 13200) / (0.033 * 197e12)
    assert read("ssm.scan_mxu_roofline_share") == pytest.approx(want)
    assert 0 < want < 1
    # a program without recurrent layers, a run off the chip, spans without
    # the attribute (the parent's): nothing to read, no error
    plain = pr43._Analysis({("prefill Tpad=256", "ffn", ""): 0.1},
                           {"prefill Tpad=256": 1}, {"prefill Tpad=256": 200})
    assert read(NEW_METRICS[0], dict(run, device_scopes=plain)) is None
    assert read(NEW_METRICS[0], dict(
        run, device={"platform": "cpu", "kind": "cpu"})) is None
    assert read(NEW_METRICS[1], dict(run, program_spans=_Calls(
        [_call(Tpad=256, tokens=200)]))) is None
    assert read(NEW_METRICS[1], dict(run, program_spans=_Calls([]))) is None
    from bluefog_tpu.utils import tracing
    assert "ssm.scan" in tracing.DEVICE_SCOPES
    for hook in ("build_serve", "held_experts", "expert_layers", "layers_of",
                 "decode_floor_bytes", "prefill_flops", "ssm_state_bytes",
                 "ssm_scan_flops", "aot_programs"):
        assert callable(getattr(FAMILY, hook))


# sha256 of the StableHLO the state-space family's programs lower to at its
# files' tiny sizes, taken at the parent commit 87c0e15 with this function
# (the three other held-experts families' are in the state-space family's
# test file, and hold): the delta mixer, the expert form, the attention gate
# and the key blocks are Python branches that configuration never enters.
PARENT_PROGRAMS = {
    "nemotron-3-super.serve-closed160-p2048": {
        "decode": "fb7bed4310e095c6eff2e4c3e627c744edb5e9941551d89cf52186a0da3af010",
        "prefill": "800aa96f86fd55864e22f658836172c686693c56d6891b342e26a4270110cbab"},
}


def lowered_programs(cell):
    """StableHLO of the decode and the largest prefill program of the
    state-space cell at its files' tiny sizes, from shapes alone."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.models import decoder
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import ServeEngine
    from perfbench.families.composed_lm import serve_config
    cfg, traffic = sized(cell=cell)
    family = manifest.load_module("families", cfg["family"])
    lm, scfg = family.ssm_config(cfg), serve_config(traffic)
    m = compose.compose_parallelism(1, 1, 1, 1,
                                    devices=jax.devices("cpu")[:1])
    eng = ServeEngine.__new__(ServeEngine)      # bodies only: no arrays
    eng._moe = eng._latent = eng._hybrid = False
    eng._share = eng._ssm = True
    eng.m, eng.cfg, eng.scfg = m, lm, scfg
    sh = NamedSharding(m.mesh, m.spec)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        (1,) + tuple(shape), dtype, sharding=sh)
    leaves = lambda grp: {k: sds(s, jnp.float32) for k, s in grp.items()}
    shapes = decoder.ssm_param_shapes(lm)
    params = {"layers": tuple(leaves(g) for g in shapes["layers"]),
              "shared": leaves(shapes["shared"])}
    cc = family._cache_config(lm, scfg)
    state = lambda: ({k: sds(shape, cc.dtypes()[k])
                      for k, shape in cc.shapes().items()},
                     sds((cc.rows, 2), jnp.uint32))
    S, T = scfg.batch_buckets[0], scfg.prefill_buckets[-1]
    return {"decode": eng._build(eng._ssm_decode_body).lower(
                params, *state(), sds((S, 1 + 4), jnp.int32)).as_text(),
            "prefill": eng._build(eng._ssm_prefill_body).lower(
                params, *state(), sds((T + 4,), jnp.int32)).as_text()}


@pytest.mark.parametrize("cell", sorted(PARENT_PROGRAMS))
def test_the_state_space_familys_programs_are_the_parents(cell):
    got = {name: hashlib.sha256(text.encode()).hexdigest()
           for name, text in lowered_programs(cell).items()}
    assert got == PARENT_PROGRAMS[cell]


# what the cell reports besides the metrics this file's PR wrote for it
# (``token_gap_p90_s`` by ISSUE 47's condition: two sets of six runs under
# half its bound, 0.68 % and 0.53 %; with it the three per-layer metrics that
# move it)
REPORTS = ("serve_tok_per_s", "ttft_p50_s", "token_gap_p90_s", "setup_s")
# the lists PR 47 put the cell into beside those every serving cell is in
SHARED = ("moe.tokens_per_held_expert", "moe.pad_share",
          "engine.decode_call_s_p50.tok_per_s", "token_gap_p80_s",
          "moe.prefill_experts_device_s_per_ktok",
          "ssm.decode_device_s_per_call", "ssm.prefill_device_s_per_ktok",
          "ssm.state_hbm_roofline_share", "ssm.scan_mxu_roofline_share",
          "engine.decode_hbm_roofline_share.ssm",
          "engine.prefill_mxu_roofline_share.ssm")
MOVE_THE_GAP = ("engine.decode_call_s_p50", "engine.decode_collect_s_p50",
                "token_gap_p99_s")
EVERY_SERVING_CELL = pr43.EVERY_SERVING_CELL + (pr43.CELL,)


def manifest_rule(man, root=ROOT):
    """The cell, its configuration and the two metrics PR 47 wrote, however
    much has been appended since: the cell IN every list that holds all
    five serving cells before it, in the eleven lists ISSUE 47 names, and
    in the two of its own, which stand in their order."""
    everywhere = tuple(
        m["name"] for m in man["end_to_end"] + man["per_layer"]
        if set(EVERY_SERVING_CELL) <= set(m.get("workloads", ())))
    want = dict(config="solar-open2", chips=1,
                traffic="serve-closed32-p16384")
    bad = _shape.written_for(man, CELL, metrics=everywhere, **want)
    bad += _shape.written_for(man, CELL, metrics=SHARED, **want)
    bad += _shape.written_for(man, CELL, metrics=NEW_METRICS, **want)
    bad += _shape.written_for(man, CELL, metrics=REPORTS, **want)
    bad += _shape.written_for(man, CELL, metrics=MOVE_THE_GAP, **want)
    by_name = {m["name"]: m for m in man["per_layer"]}
    sources = dict(zip(NEW_METRICS, ("device_trace", "program_span")))
    for n in NEW_METRICS:
        if n in by_name and (by_name[n]["moves"], by_name[n]["source"]) \
                != ("ttft_p50_s", sources[n]):
            bad.append(f"{n} moves {by_name[n]['moves']}, read from "
                       f"{by_name[n]['source']}")
    entry = [c for c in man["configs"] if c["name"] == "solar-open2"]
    if [c["reduced"] for c in entry] != [[
            "num_hidden_layers", "n_routed_experts", "vocab_size"]]:
        bad.append(f"solar-open2's entry is {entry}")
    return bad


def test_the_cell_and_its_metrics_stand_as_their_pr_wrote_them():
    man = manifest.load()
    assert manifest_rule(man) == []
    assert _shape.complaints(man) == []
    # the rule sees the cell taken out of a list it shares or owns
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in ("moe.pad_share", "ssm.state_hbm_roofline_share",
                 "device.serve_scoped_share", NEW_METRICS[1]):
        by_name[name]["workloads"].remove(CELL)
        assert manifest_rule(man) == [f"{name} does not list {CELL}"]
        by_name[name]["workloads"].append(CELL)
    # where this family's mark does not carry what a reader reads, the cell
    # is not listed: a ring's positions, other families' programs
    for name in ("attn.decode_positions_read_per_lane",
                 "engine.decode_hbm_roofline_share",
                 "engine.decode_hbm_roofline_share.kv",
                 "engine.prefill_mxu_roofline_share"):
        assert CELL not in _shape.cells_of(man, name)
    # a further metric behind the two, a further cell behind this one,
    # break nothing
    man["per_layer"].append(dict(by_name[NEW_METRICS[0]], name="a.further"))
    assert manifest_rule(man) == []
