"""Family ``ssm_latent_moe`` at the configuration file's ``tiny`` sizes on the
CPU: the program against the plain reference through a Scheduler, the
mechanism controls that must FAIL the comparison (the gate after the norm,
rotary switched on, no router bias, no skip, no convolution bias, int8
weights), the rule that holds a differing expert selection to a rounding
tie, the reference evaluated under given selections, the four shares of an
expert layer against the uncut reference layer, the bytes and operations
behind the roofline shares, the new metrics' readers, and that the three
other held-experts families' programs are still the parent's."""
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
from perfbench.harness import manifest, program_spans, scopes  # noqa: E402
from perfbench.reference import ssm_latent_moe as reference  # noqa: E402

CELL = "nemotron-3-super.serve-closed160-p2048"
NEW_METRICS = ("ssm.decode_device_s_per_call",
               "ssm.prefill_device_s_per_ktok",
               "ssm.state_hbm_roofline_share",
               "ssm.scan_mxu_roofline_share",
               "engine.decode_hbm_roofline_share.ssm",
               "engine.prefill_mxu_roofline_share.ssm")
FAMILY = manifest.load_module("families", "ssm_latent_moe")


def sized(tiny=True, cell=CELL):
    resolved = manifest.resolve_cell(manifest.load(), cell)
    return (manifest.sized(resolved["config"], tiny),
            manifest.sized(resolved["traffic"], tiny))


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


def test_the_tiny_sizes_keep_every_mechanism_and_the_file_the_published():
    cfg, _ = sized()
    lm = FAMILY.ssm_config(cfg)
    assert set(lm.plan) == {"ssm", "full", "experts"} and lm.route_bias
    assert lm.ssm_heads > lm.ssm_groups > 1 and lm.chunk < 8
    assert lm.heads > lm.kv_heads > 1 and lm.latent < lm.d_model
    assert lm.held_experts < lm.num_experts and lm.top_k == lm.held_experts
    full, traffic = sized(False)
    big = FAMILY.ssm_config(full)
    assert big.plan == tuple(FAMILY.KINDS[c] for c in "MEMEMEM*EME")
    assert (big.d_model, big.ssm_heads, big.ssm_head_dim, big.ssm_groups,
            big.ssm_state, big.conv_kernel, big.chunk) == (
        4096, 128, 64, 8, 128, 4, 128)
    assert (big.heads, big.kv_heads, big.head_dim) == (32, 2, 128)
    assert (big.latent, big.expert_ffn, big.shared_ffn) == (1024, 2688, 5376)
    assert (big.num_experts, big.held_experts, big.held_start, big.top_k,
            big.route_scale) == (512, 128, 0, 22, 5)
    assert (big.vocab, big.eps, big.ssm_eps) == (32768, 1e-5, 1e-5)
    # every key of the catalog row under its own name, four of them cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["source_url"] == _entry()["source"]]
        differ = sorted(k for k, v in row["config"].items()
                        if full.get(k, "absent") != v)
        assert differ == sorted(_entry()["reduced"]), differ
    assert full["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "num_nextn_predict_layers": 1}
    assert len(full["hybrid_override_pattern"]) == 88     # whole, as published
    assert full["deployment"]["chips_per_layer"] == 4
    assert full["deployment"]["vocab_slice"] == [0, 32768]
    whole = manifest.load_json(os.path.join(ROOT, _entry()["file"]))
    for key in ("reduced_why", "departures", "assumed", "tiny"):
        assert whole[key]
    assert set(full["reduced_why"]) == set(_entry()["reduced"])
    # the cell as the issue states it
    eng = traffic["engine"]
    assert (traffic["clients"], traffic["cycle"], eng["slots"],
            eng["max_len"]) == (160, 160, 160, 2304)
    assert eng["batch_buckets"] == [160] and eng["dtype"] == "bfloat16"
    assert eng["prefill_buckets"] == [128, 256, 512, 1024, 2048]
    assert all(b % big.chunk == 0 for b in eng["prefill_buckets"])
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 64,
                                        "hi": 2048}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 64,
                                        "hi": 256}
    assert traffic["ramp_output_tokens"] == {"dist": "uniform", "lo": 8,
                                             "hi": 256}
    assert traffic["traced_seconds"] == 3.0 and traffic["loop"] == "closed"
    assert isinstance(traffic["length_order"]["seed"], int)
    assert len(traffic["length_order"]["why"]) > 200


def _entry():
    entry, = [c for c in manifest.load()["configs"]
              if c["name"] == "nemotron-3-super"]
    return entry


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
    # what each slot held of recurrent state after its last decode call is
    # the reference's state after the same tokens, in every layer
    assert c["ssm_state_err_share"][0] < 1e-5
    for row in ref["requests"]:
        assert len(row["ssm_state_rel_err"]) == FAMILY.layers_of(
            sized()[0], "M") and max(row["ssm_state_rel_err"]) < 1e-5
    # every asked length is compared, and every decoded position of it
    assert [r["prompt_tokens"] for r in ref["requests"]] \
        == asked["prompt_tokens"]
    expert_layers = FAMILY.expert_layers(sized()[0])
    for row in ref["requests"]:
        assert row["decode_positions"] == asked["output_tokens"] - 1
        assert row["selections"] == expert_layers * (
            row["prompt_tokens"] + row["decode_positions"])


def zeroed(*names):
    def alter(params):
        layers = tuple({k: jnp.zeros_like(v) if k in names else v
                        for k, v in lp.items()} for lp in params["layers"])
        return {"layers": layers, "shared": params["shared"]}
    return alter


def unscaled(params):
    factor = sized()[0]["routed_scaling_factor"]
    layers = tuple({k: v / factor if k == "wup" else v
                    for k, v in lp.items()} for lp in params["layers"])
    return {"layers": layers, "shared": params["shared"]}


def fake_int8(tree):
    """Every weight matrix through symmetric per-tensor int8 and back."""
    def q(a):
        if a.ndim < 3:
            return a                       # norm scales, biases, one a head
        scale = jnp.max(jnp.abs(a)) / 127.0
        return (jnp.round(a / scale) * scale).astype(a.dtype)
    return jax.tree.map(q, tree)


def gate_after_the_norm(cfg, lp, y, z):
    """The other choice ``assumed.mamba`` names: the grouped RMSNorm first,
    the gate behind it."""
    lead = z.shape[:-1]
    v = y.reshape(lead + (cfg.ssm_groups, -1)).astype(jnp.float32)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + cfg.ssm_eps)
    v = v.reshape(lead + (-1,)) * lp["g_y"] * jax.nn.silu(z)
    return v.astype(z.dtype) @ lp["w_out"]


def turned(project):
    """The other choice ``assumed.attention`` names: q and k turned by
    rotary over the whole head at the file's rope_theta (a prompt's tokens
    by their positions; a control, so a decode lane by its place)."""
    from bluefog_tpu.models import decoder

    def with_rotary(cfg, lp, h):
        q, k, v = project(cfg, lp, h)
        at = jnp.arange(q.shape[0])
        return decoder.rope(q, at, 10000.0), decoder.rope(k, at, 10000.0), v
    return with_rotary


@pytest.mark.parametrize("control", [
    "gate_after_the_norm", "rotary_switched_on", "no_router_bias",
    "no_skip", "no_conv_bias", "no_scaling_factor", "int8_weights",
    "bfloat16_state"])
def test_a_changed_mechanism_fails_the_comparison(control, monkeypatch):
    """The same engine with the gate behind the grouped norm, with rotary
    switched on in its attention layer, without its router bias, without
    ``Dskip`` or ``b_conv``, with the routed experts' sum left unscaled
    (``wup`` over the factor), with its weights through int8, or with its
    recurrent state kept in bfloat16 (the cache's dtype alone), is NOT the
    reference's function: each by the program's code, weights or cache
    alone, the reference as it stands."""
    from bluefog_tpu.models import decoder
    from bluefog_tpu.serve import kv_cache
    alter = None
    if control == "bfloat16_state":
        dtypes = kv_cache.SsmCacheConfig.dtypes
        monkeypatch.setattr(
            kv_cache.SsmCacheConfig, "dtypes",
            lambda self: {**dtypes(self), "ssm": jnp.bfloat16})
    elif control == "gate_after_the_norm":
        monkeypatch.setattr(decoder, "mamba_gate_out", gate_after_the_norm)
    elif control == "rotary_switched_on":
        monkeypatch.setattr(decoder, "gqa_project",
                            turned(decoder.gqa_project))
    else:
        alter = {"no_router_bias": zeroed("eb"), "no_skip": zeroed("Dskip"),
                 "no_conv_bias": zeroed("b_conv"),
                 "no_scaling_factor": unscaled,
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
        assert "ssm_state_err_share" in over
    else:
        assert over & {"prefill_logit_err_share", "decode_logit_err_share"}
    assert c["requests_off_length"] == [0, 0]


def test_the_tie_rule_passes_a_planted_tie_and_fails_a_far_swap():
    """``selection_report``: a selection that differs from the reference's
    own by experts within ``delta`` of its k-th score is a tie; one expert
    farther off is a fault, whichever side of the cut it lies on."""
    E, k, delta = 16, 4, 0.01
    by = np.tile(np.linspace(1.0, 0.25, E).astype(np.float32), (2, 3, 1))
    by[0, 1, k] = by[0, 1, k - 1] - 0.004          # the 4th and 5th nearly tie
    picked = np.tile(np.arange(k), (2, 3, 1))
    same = picked.copy()
    assert FAMILY.selection_report(by, picked, same, delta) == (0, 0, 6, 0)
    tie = picked.copy()
    tie[0, 1, k - 1] = k                           # took the 5th for the 4th
    differing, faults, pairs, farthest = FAMILY.selection_report(
        by, picked, tie, delta)
    assert (differing, faults, pairs) == (1, 0, 6)
    assert farthest == pytest.approx(0.004, rel=1e-3)
    # the order inside a selection does not matter
    assert FAMILY.selection_report(by, picked, tie[..., ::-1],
                                   delta)[:3] == (1, 0, 6)
    far = picked.copy()
    far[1, 2, 0] = E - 1                 # the lowest score for the highest
    differing, faults, pairs, farthest = FAMILY.selection_report(
        by, picked, far, delta)
    assert (differing, pairs) == (1, 6) and faults == 2     # both are far
    assert farthest == pytest.approx(by[1, 2, k - 1] - by[1, 2, E - 1])
    # the same swap at the tie's position is a fault too: one expert lies
    # within delta of the cut, the other does not
    off = tie.copy()
    off[0, 1, 0] = E - 1
    assert FAMILY.selection_report(by, picked, off, delta)[1] >= 1
    # and a wider delta makes ties of what a narrower one faults
    assert FAMILY.selection_report(by, picked, tie, 0.001)[1] == 1


def layer_weights(cfg, seed, experts):
    D, La = cfg["hidden_size"], cfg["moe_latent_size"]
    F, Fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    E = cfg["deployment"]["router_outputs"]
    ks = jax.random.split(jax.random.key(seed), 8)
    n = lambda k, s: 0.3 * jax.random.normal(k, s, jnp.float32)
    return {"wr": n(ks[0], (D, E)), "eb": n(ks[1], (E,)) / 3,
            "wdn": n(ks[2], (D, La)), "wup": n(ks[3], (La, D)),
            "we1": n(ks[4], (experts, La, F)),
            "we2": n(ks[5], (experts, F, La)),
            "ws1": n(ks[6], (D, Fs)), "ws2": n(ks[7], (Fs, D))}


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """Chips that hold a quarter of the experts each from ``held_start`` 0,
    1/4, 1/2 and 3/4 of the router's outputs: the reference's shares and
    the program's (moe.layers.held_moe_ffn, both of its forms), latent
    projections included and the shared expert counted once, add up to the
    reference's layer with every expert held."""
    from bluefog_tpu.moe.layers import held_moe_ffn
    cfg, _ = sized()
    E, k = cfg["deployment"]["router_outputs"], cfg["num_experts_per_tok"]
    w = layer_weights(cfg, 11, E)
    h = jax.random.normal(jax.random.key(12), (24, cfg["hidden_size"]))
    whole, by, picked = reference.moe_ffn(cfg, w, h)
    assert picked.shape == (24, k) and by.shape == (24, E)
    lm = dataclasses.replace(FAMILY.ssm_config(cfg), held_experts=E // 4)
    shared = reference.relu2(h, w["ws1"], w["ws2"])
    ref_sum = prog_sum = grouped_sum = shared
    for start in range(0, E, E // 4):
        cut = dict(w, we1=w["we1"][start:start + E // 4],
                   we2=w["we2"][start:start + E // 4])
        ref_sum = ref_sum + reference.moe_ffn(cfg, cut, h, start,
                                              shared=False)[0]
        at = dataclasses.replace(lm, held_start=start)
        part, idx, _ = held_moe_ffn(at, cut, h, form="relu2")
        prog_sum = prog_sum + (part - shared)
        assert np.array_equal(np.sort(np.asarray(idx), -1),
                              np.sort(np.asarray(picked), -1))
        stacked = dict(cut, we1=cut["we1"][None], we2=cut["we2"][None])
        part, _, _ = held_moe_ffn(at, stacked, h, layer=jnp.int32(0),
                                  form="relu2")
        grouped_sum = grouped_sum + (part - shared)
    np.testing.assert_allclose(ref_sum, whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(prog_sum, whole, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(grouped_sum, whole, rtol=1e-3, atol=1e-3)
    # without the bias, or without the scaling factor, it is another layer
    other = reference.moe_ffn(cfg, dict(w, eb=jnp.zeros(E)), h)
    assert not np.array_equal(np.sort(np.asarray(other[2]), -1),
                              np.sort(np.asarray(picked), -1))
    plain = reference.moe_ffn(dict(cfg, routed_scaling_factor=1), w, h)[0]
    assert float(jnp.max(jnp.abs(plain - whole))) > 1e-2


def test_the_reference_follows_given_selections_with_its_own_weights():
    cfg, _ = sized()
    E, k = cfg["deployment"]["router_outputs"], cfg["num_experts_per_tok"]
    w = layer_weights(cfg, 5, E)
    h = jax.random.normal(jax.random.key(6), (7, cfg["hidden_size"]))
    own, by, picked = reference.moe_ffn(cfg, w, h)
    again, by2, picked2 = reference.moe_ffn(cfg, w, h, chosen=picked)
    np.testing.assert_allclose(again, own, rtol=1e-6, atol=1e-6)
    # another selection changes the result, not what the reference reports
    # of its own choice; a token with no selection (-1) gets no routed expert
    given = np.asarray(picked).copy()
    given[0] = np.arange(E - k, E)
    given[1] = -1
    other, by3, picked3 = reference.moe_ffn(cfg, w, h, chosen=jnp.asarray(given))
    assert np.array_equal(picked3, picked) and np.allclose(by3, by)
    assert float(jnp.max(jnp.abs(other[0] - own[0]))) > 1e-3
    np.testing.assert_allclose(other[1], reference.relu2(
        h[1:2], w["ws1"], w["ws2"])[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(other[2:], own[2:], rtol=1e-6, atol=1e-6)
    # the weights of a given selection are the reference's own scores
    _, _, weight = reference.route(cfg, h, w["wr"], w["eb"],
                                   jnp.asarray(given))
    s = np.asarray(by - w["eb"])[0, given[0]]
    np.testing.assert_allclose(
        np.asarray(weight)[0, given[0]],
        cfg["routed_scaling_factor"] * s / s.sum(), rtol=1e-5)


def test_the_references_recurrence_is_the_closed_form():
    """Constant inputs: ``S_T = d x (x) B (1 - a^T) / (1 - a)``."""
    T, H, P, N = 9, 2, 3, 4
    x, B = np.full((H, P), 0.5, np.float32), np.full((H, N), 2.0, np.float32)
    a, d = np.float32(0.8), np.float32(0.1)
    rep = lambda t: jnp.asarray(np.broadcast_to(t, (T,) + t.shape))
    y, S = reference.recurrence(
        rep(x), rep(B), rep(B), rep(np.full((H,), d)), rep(np.full((H,), a)),
        jnp.full((H,), 3.0))
    want = d * 0.5 * 2.0 * (1 - a ** T) / (1 - a)
    np.testing.assert_allclose(S, np.full((H, P, N), want), rtol=1e-5)
    np.testing.assert_allclose(y[-1], want * 2.0 * N + 3.0 * 0.5, rtol=1e-5)


def test_the_references_state_is_the_one_after_the_last_real_token():
    """``true_len``: what follows it is padding and moves no state, so the
    state is the one the same sequence cut there leaves (what the check
    holds a served slot's state to)."""
    T, n, H, P, N = 9, 6, 2, 3, 4
    rng = np.random.default_rng(0)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x, B, C = draw(T, H, P), draw(T, H, N), draw(T, H, N)
    d = jnp.abs(draw(T, H))
    a, skip = jnp.exp(-d), draw(H)
    y, S = reference.recurrence(x, B, C, d, a, skip, true_len=n)
    y_cut, S_cut = reference.recurrence(x[:n], B[:n], C[:n], d[:n], a[:n],
                                        skip)
    np.testing.assert_allclose(S, S_cut, rtol=1e-6)
    np.testing.assert_allclose(y[:n], y_cut, rtol=1e-6)
    whole = reference.recurrence(x, B, C, d, a, skip)[1]
    assert float(jnp.max(jnp.abs(whole - S))) > 1e-3


def test_the_floors_count_what_the_chip_holds():
    from bluefog_tpu.models import decoder
    cfg, traffic = sized(False)
    lm = FAMILY.ssm_config(cfg)
    shapes = decoder.ssm_param_shapes(lm)
    held = decoder.ssm_param_count(lm)
    assert held == cfg["deployment"]["held_parameters"] == 4_648_163_712
    groups = shapes["layers"] + (shapes["shared"],)
    size = lambda pick: sum(int(np.prod(s)) for g in groups
                            for n, s in g.items() if pick(n))
    in_f32 = size(lambda n: n in decoder.FLOAT32_LEAVES)
    routed = size(lambda n: n in ("we1", "we2"))
    embed = size(lambda n: n == "embed")
    assert FAMILY.weight_bytes(cfg) == \
        2 * (held - routed - embed - in_f32) + 4 * in_f32
    assert FAMILY.expert_bytes(cfg) == 2 * routed // (5 * 128)
    assert (FAMILY.held_experts(cfg), FAMILY.expert_layers(cfg)) == (128, 5)
    assert [FAMILY.layers_of(cfg, c) for c in "M*E"] == [5, 1, 5]
    # what the chip holds: 9.32 GB of weights beside 3.81 GB of state and rows
    weights = 2 * (held - in_f32) + 4 * in_f32
    assert 9.31e9 < weights < 9.33e9
    assert round(weights / 1e9, 2) == cfg["deployment"]["held_bf16_gb"]
    scfg = FAMILY.serve_config(traffic)
    cc = FAMILY._cache_config(lm, scfg)
    assert 3.80e9 < cc.bytes() < 3.82e9
    assert cc.bytes_per_slot()["ssm"] == 5 * (128 * 64 * 128 * 4
                                              + 3 * 10240 * 2)
    # the states' bytes: each lane's, read and written, in 5 layers
    per_lane = 2 * cc.bytes_per_slot()["ssm"]
    assert FAMILY.ssm_state_bytes(cfg, 160) == 160 * per_lane
    assert 6.8e9 < FAMILY.ssm_state_bytes(cfg, 160) < 6.9e9
    floor = FAMILY.decode_floor_bytes(cfg, calls=2, experts_hit=100,
                                      positions=1000, state_lanes=300)
    assert floor == 2 * FAMILY.weight_bytes(cfg) \
        + 100 * FAMILY.expert_bytes(cfg) + 300 * per_lane + 1000 * 1024
    # every expert hit, every lane live at the rows' full length: the most
    # a call of the floor can be, under what the chip holds (the state is
    # counted twice: read and written)
    most = FAMILY.decode_floor_bytes(cfg, 1, 640, 160 * 2304, 160)
    assert 16.2e9 < most < 16.5e9
    assert most - FAMILY.ssm_state_bytes(cfg, 160) / 2 < weights + cc.bytes()
    # the recurrence: an update and a read-out of every state element
    assert FAMILY.ssm_scan_flops(cfg, 1000) == 1000 * 5 * 4 * 128 * 64 * 128
    # a prompt's operations: about 2 per active parameter and token, plus
    # the recurrence and attention that grows with the length
    short, long = (FAMILY.prefill_flops(cfg, t) for t in (128, 2048))
    active = held - routed - 2 * embed - in_f32 \
        + 5 * 4096 * 512 + 5 * 5.5 * 2 * 1024 * 2688
    for flops, t in ((short, 128), (long, 2048)):
        attention = 2 * 2 * 32 * 128 * t * (t + 1) // 2
        assert flops == pytest.approx(
            2 * active * t + FAMILY.ssm_scan_flops(cfg, t) + attention
            + 2 * 4096 * 32768, rel=0.01)
    assert 2.0e9 < short / 128 - 2 * 4096 * 32768 / 128 < 2.2e9


LIMITS = {"prefill_logit_err_share": FAMILY.SERVE_LOGIT_TOL,
          "decode_logit_err_share": FAMILY.DECODE_LOGIT_TOL,
          "decode_logit_gap_share": FAMILY.DECODE_GAP_TOL,
          "route_tie_distance": FAMILY.ROUTE_TIE_DELTA,
          "ssm_state_err_share": FAMILY.STATE_TOL}
# the room a limit keeps to each of its two readings, as a factor: the
# state's two readings lie 1.31 apart (the family file says why it is the
# median head of the first layer, and how tight both readings are)
ROOM = {"ssm_state_err_share": 1.1}


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


class _Analysis(scopes.Analysis):
    """A traced tail's split by scope, made by hand."""

    def __init__(self, by, calls, tokens):
        self.by, self.calls, self.tokens = by, calls, tokens


class _Marks:
    """The program's spans of a traced tail, made by hand."""
    shift_ns = 0

    def __init__(self, marks):
        self.marks = marks

    def named(self, name):
        return self.marks if name == "bf:engine.held_work" else []


def _mark(**attrs):
    return program_spans.Span("bf:engine.held_work", 0, 1, attrs, None)


def test_the_state_metrics_read_the_scope_table_and_the_marks():
    cfg, _ = sized(False)
    ana = _Analysis(
        {("prefill Tpad=2048", "ssm.scan", ""): 0.010,
         ("prefill Tpad=2048", "ssm.project", ""): 0.016,
         ("prefill Tpad=2048", "ssm.conv", ""): 0.001,
         ("prefill Tpad=2048", "moe.experts", ""): 0.1,
         ("decode S=160", "ssm.scan", ""): 0.060,
         ("decode S=160", "ssm.conv", ""): 0.004,
         ("decode S=160", "ssm.project", ""): 0.008,
         ("decode S=160", "moe.experts", ""): 0.05},
        {"prefill Tpad=2048": 2, "decode S=160": 4},
        {"prefill Tpad=2048": 3000})
    marks = _Marks([_mark(state_lanes=160, pairs=1, rows=1),
                    _mark(state_lanes=150, pairs=1, rows=1)])
    run = {"config": cfg, "workload": CELL, "device_scopes": ana,
           "program_spans": marks,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    read = lambda name, run=run: manifest.load_module(
        "metrics", name).read(run)
    assert read("ssm.decode_device_s_per_call") == pytest.approx(0.072 / 4)
    assert read("ssm.prefill_device_s_per_ktok") == pytest.approx(0.027 / 3)
    # the marks' mean lanes a call, times the module events
    want = FAMILY.ssm_state_bytes(cfg, 155 * 4) / (0.064 * 819e9)
    assert read("ssm.state_hbm_roofline_share") == pytest.approx(want)
    assert 0 < want < 1
    want = FAMILY.ssm_scan_flops(cfg, 3000) / (0.010 * 197e12)
    assert read("ssm.scan_mxu_roofline_share") == pytest.approx(want)
    assert 0 < want < 0.1
    # a program without state-space layers, a mark without state lanes, a
    # run off the chip, a family without the hook: nothing to read, no error
    plain = _Analysis({("decode S=128", "ffn", ""): 0.1,
                       ("prefill Tpad=256", "ffn", ""): 0.1},
                      {"decode S=128": 1, "prefill Tpad=256": 1},
                      {"prefill Tpad=256": 200})
    for name in NEW_METRICS[:4]:
        assert read(name, dict(run, device_scopes=plain)) is None
        assert read(name, dict(
            run, device={"platform": "cpu", "kind": "cpu"})) is None
    assert read("ssm.state_hbm_roofline_share", dict(
        run, program_spans=_Marks([_mark(pairs=1, rows=1)]))) is None
    other = dict(run, config=dict(cfg, family="latent_moe"))
    for name in NEW_METRICS[2:4]:
        assert read(name, other) is None
    for name in NEW_METRICS[4:]:
        assert read(name, dict(
            run, device={"platform": "cpu", "kind": "cpu"})) is None
        assert read(name, dict(run, program_spans=_Marks([]))) is None


def test_the_readers_name_the_programs_scopes_and_hooks():
    from bluefog_tpu.serve import ServeEngine
    from bluefog_tpu.utils import tracing
    mod = manifest.load_module("metrics",
                               "engine.decode_hbm_roofline_share.ssm")
    assert mod.PROGRAM == "jit_" + ServeEngine._ssm_decode_body.__name__
    mod = manifest.load_module("metrics",
                               "engine.prefill_mxu_roofline_share.ssm")
    assert mod.PROGRAM == "jit_" + ServeEngine._ssm_prefill_body.__name__
    assert _shape.family_hooks("engine.prefill_mxu_roofline_share.ssm") == [
        "prefill_flops"]
    for name in NEW_METRICS[:3]:
        mod = manifest.load_module("metrics", name)
        assert set(getattr(mod, "SSM", ()) + getattr(mod, "STATE", ())) \
            <= tracing.DEVICE_SCOPES
    assert {"ssm.project", "ssm.conv", "ssm.scan", "moe.latent"} \
        <= tracing.DEVICE_SCOPES
    assert _shape.family_hooks("ssm.state_hbm_roofline_share") == [
        "ssm_state_bytes"]
    assert _shape.family_hooks("ssm.scan_mxu_roofline_share") == [
        "ssm_scan_flops"]
    assert _shape.family_hooks("engine.decode_hbm_roofline_share.ssm") == [
        "decode_floor_bytes", "ssm_state_bytes"]
    for hook in ("build_serve", "held_experts", "expert_layers",
                 "decode_floor_bytes", "prefill_flops", "ssm_state_bytes",
                 "ssm_scan_flops", "aot_programs"):
        assert callable(getattr(FAMILY, hook))


# sha256 of the StableHLO the three OTHER held-experts families' programs
# lower to at their files' tiny sizes, taken at the parent commit e27a178
# with this function: the expert form, the latent projections and the
# decode-form rule are Python branches such a configuration never enters
# (or enters the way it always did), so what the compiler is handed is the
# parent's program.  A PR that means to change those programs takes the
# hashes anew.
PARENT_PROGRAMS = {
    "a.x-k1.serve-closed128-p2048": {
        "decode": "f6d71278b11e7771d2575d07d5ce66d9ba6af4dc72deab9ec6540e9fe1ceaf6c",
        "prefill": "2a643bb09e1bd80a39b1cbf5ffd4caba81ad1ccfb28d08d3f4b10a8de7290c95"},
    "k-exaone.serve-closed48-p8192": {
        "decode": "5492371217a555b562d6c440704ab41f48d9b2e9743e1588a9136aaa7c9a2758",
        "prefill": "ccc6bc1007df7741316fcc5d238dcfc58eaf2704dd211213952f29200b740a6c"},
    "xing4.0.serve-closed96-p4096": {
        "decode": "9e24d388574bb84b9b5c4a83b2cebebb560ac43a6a87de5bc04472e9ca05e852",
        "prefill": "835a74fcac9ab222de77b3600f93d7594a85fa7f1a711ae017126350a4554f6b"},
}


def lowered_programs(cell):
    """StableHLO of the decode and the largest prefill program of a
    held-experts cell at its files' tiny sizes, from shapes alone."""
    from jax.sharding import NamedSharding
    from bluefog_tpu.models import decoder
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import ServeEngine
    from bluefog_tpu.serve import kv_cache as kv
    from perfbench.families.composed_lm import serve_config
    cfg, traffic = sized(cell=cell)
    family = manifest.load_module("families", cfg["family"])
    scfg = serve_config(traffic)
    m = compose.compose_parallelism(1, 1, 1, 1,
                                    devices=jax.devices("cpu")[:1])
    eng = ServeEngine.__new__(ServeEngine)      # bodies only: no arrays
    sh = NamedSharding(m.mesh, m.spec)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        (1,) + tuple(shape), dtype, sharding=sh)
    leaves = lambda grp: {k: sds(s, jnp.float32) for k, s in grp.items()}
    hybrid = hasattr(family, "hybrid_config")
    if hybrid:
        lm = family.hybrid_config(cfg)
        shapes = decoder.hybrid_param_shapes(lm)
        params = {"layers": tuple(leaves(g) for g in shapes["layers"]),
                  "shared": leaves(shapes["shared"])}
        cc = kv.HybridCacheConfig(
            full_layers=lm.layers_of("full"),
            window_layers=lm.layers_of("window"), slots=scfg.slots,
            max_len=scfg.max_len, window=lm.window, kv_heads=lm.kv_heads,
            head_dim=lm.head_dim, dtype=scfg.dtype)
        bodies = eng._hybrid_decode_body, eng._hybrid_prefill_body
    else:
        lm = family.latent_config(cfg)
        params = {g: leaves(grp)
                  for g, grp in decoder.latent_param_shapes(lm).items()}
        cc = kv.LatentCacheConfig(
            layers=lm.layers, slots=scfg.slots, max_len=scfg.max_len,
            kv_rank=lm.kv_rank, rope_dim=lm.rope_dim, dtype=scfg.dtype)
        bodies = eng._latent_decode_body, eng._latent_prefill_body
    eng._moe, eng._share = False, True
    eng._latent, eng._hybrid = not hybrid, hybrid
    eng.m, eng.cfg, eng.scfg = m, lm, scfg
    state = lambda: ({k: sds(shape, scfg.dtype)
                      for k, shape in cc.shapes().items()},
                     sds((cc.rows, 2), jnp.uint32))
    S, T = scfg.batch_buckets[0], scfg.prefill_buckets[-1]
    return {"decode": eng._build(bodies[0]).lower(
                params, *state(), sds((S, 1 + 4), jnp.int32)).as_text(),
            "prefill": eng._build(bodies[1]).lower(
                params, *state(), sds((T + 4,), jnp.int32)).as_text()}


@pytest.mark.parametrize("cell", sorted(PARENT_PROGRAMS))
def test_the_other_held_expert_families_programs_are_the_parents(cell):
    got = {name: hashlib.sha256(text.encode()).hexdigest()
           for name, text in lowered_programs(cell).items()}
    assert got == PARENT_PROGRAMS[cell]


# what the cell reports besides the metrics this file's PR wrote for it
REPORTS = ("serve_tok_per_s", "ttft_p50_s", "setup_s")
# the lists PR 43 put the cell into beside those every serving cell is in
SHARED = ("moe.tokens_per_held_expert", "moe.pad_share",
          "engine.decode_call_s_p50.tok_per_s", "token_gap_p80_s",
          "moe.prefill_experts_device_s_per_ktok")
EVERY_SERVING_CELL = ("pythia-410m.serve-closed32",
                      "a.x-k1.serve-closed128-p2048",
                      "k-exaone.serve-closed48-p8192",
                      "xing4.0.serve-closed96-p4096")


def manifest_rule(man, root=ROOT):
    """The cell, its configuration and the six metrics PR 43 wrote (ISSUE
    43's five and, after its review, the prefill program's share of the
    matrix unit's peak), however much has been appended since: the cell
    IN every list that holds all four serving cells before it, in the
    five lists ISSUE 43 names, and in the six of its own, which stand in
    their order."""
    everywhere = tuple(
        m["name"] for m in man["end_to_end"] + man["per_layer"]
        if set(EVERY_SERVING_CELL) <= set(m.get("workloads", ())))
    own = tuple(n for n in NEW_METRICS if n not in everywhere)
    want = dict(config="nemotron-3-super", chips=1,
                traffic="serve-closed160-p2048")
    bad = _shape.written_for(man, CELL, metrics=everywhere, **want)
    bad += _shape.written_for(man, CELL, metrics=SHARED, **want)
    bad += _shape.written_for(man, CELL, metrics=own, **want)
    bad += _shape.written_for(man, CELL, metrics=REPORTS, **want)
    by_name = {m["name"]: m for m in man["per_layer"]}
    moves = dict(zip(NEW_METRICS, (
        "serve_tok_per_s", "ttft_p50_s", "serve_tok_per_s", "ttft_p50_s",
        "serve_tok_per_s", "ttft_p50_s")))
    bad += [f"{n} moves {by_name[n]['moves']}" for n in NEW_METRICS
            if n in by_name and by_name[n]["moves"] != moves[n]]
    bad += [f"{n} is read from {by_name[n]['source']}" for n in NEW_METRICS
            if n in by_name and by_name[n]["source"] != "device_trace"]
    entry = [c for c in man["configs"] if c["name"] == "nemotron-3-super"]
    if [c["reduced"] for c in entry] != [[
            "num_hidden_layers", "n_routed_experts", "vocab_size",
            "num_nextn_predict_layers"]]:
        bad.append(f"nemotron-3-super's entry is {entry}")
    return bad


def test_the_cell_and_its_metrics_stand_as_their_pr_wrote_them():
    man = manifest.load()
    assert manifest_rule(man) == []
    assert _shape.complaints(man) == []
    # the rule sees the cell taken out of a list it shares or owns
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in ("moe.pad_share", "ssm.state_hbm_roofline_share",
                 "device.serve_scoped_share"):
        by_name[name]["workloads"].remove(CELL)
        assert manifest_rule(man) == [f"{name} does not list {CELL}"]
        by_name[name]["workloads"].append(CELL)
    # where this family's mark does not carry what a reader reads, the cell
    # is not listed: a ring's positions, the latent programs' events
    for name in ("attn.decode_positions_read_per_lane",
                 "engine.decode_hbm_roofline_share",
                 "engine.decode_hbm_roofline_share.kv", "token_gap_p90_s"):
        assert CELL not in _shape.cells_of(man, name)
    # a further metric behind the six breaks nothing
    man["per_layer"].append(dict(by_name[NEW_METRICS[0]], name="a.further"))
    assert manifest_rule(man) == []


# Three tests of files this PR may not edit hold BENCHMARK.json to the
# count or the end it had when they were written, and tests/conftest.py
# expects them to fail (``STALE_PINS``).  What each asserts is asserted
# here for ANY manifest, so that nothing they guard goes unguarded.

def _four_chip_fault(man):
    """``man`` with one four-chip cell more than a benchmark of its size
    may hold (a quarter of the cells, rounded down, and always one), and
    that count."""
    allowed = max(1, len(man["workloads"]) // 4)
    for w in man["workloads"][1:]:
        if sum(c["chips"] == 4 for c in man["workloads"]) <= allowed:
            w["chips"] = 4
    return man, allowed + 1


def test_pr_41s_cell_and_metrics_stand_whatever_was_appended_behind_them():
    """``test_perfbench_latent_hc_moe.py::test_the_cell_and_its_metrics_
    stand_as_their_pr_wrote_them``, its last line restated: PR 41's four
    metrics stand side by side in their order behind what was there, and
    what stands behind THEM is what later PRs appended (this one's six
    first)."""
    import test_perfbench_latent_hc_moe as pr41
    man = manifest.load()
    assert pr41.manifest_rule(man) == []
    assert _shape.complaints(man) == []
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in ("moe.pad_share", "hc.mix_hbm_roofline_share"):
        by_name[name]["workloads"].remove(pr41.CELL)
        assert pr41.manifest_rule(man) == [
            f"{name} does not list {pr41.CELL}"]
        by_name[name]["workloads"].append(pr41.CELL)
    names = [m["name"] for m in man["per_layer"]]
    first = names.index(pr41.NEW_METRICS[0])
    assert names[first:first + 4] == list(pr41.NEW_METRICS)
    behind = names[first + 4:]
    assert behind[:len(NEW_METRICS)] == list(NEW_METRICS)


def test_check_catches_a_broken_manifest_of_any_size():
    """``test_perfbench_manifest.py::test_check_catches_a_broken_manifest``
    with its four-chip fault made for the manifest's size."""
    bad = json.loads(json.dumps(manifest.load()))
    bad["workloads"][0]["chips"] = 2
    bad["end_to_end"][0]["unit"] = "items per second"
    bad["per_layer"][0]["moves"] = "nothing"
    bad, four = _four_chip_fault(bad)
    complaints = "\n".join(manifest.check(bad))
    for needle in ("chips 2", "bad unit", "moves", f"{four} four-chip"):
        assert needle in complaints


def test_the_four_chip_rule_sees_one_cell_too_many_and_no_fewer():
    """``test_a_rule_sees_what_it_guards[a_second_four_chip_cell]`` for a
    manifest of any size: one four-chip cell over a quarter of the cells
    is ``own_check``'s to see, and the quarter itself is nobody's fault
    (under eight cells a second one is allowed, which is why the old
    needle went stale)."""
    man, four = _four_chip_fault(json.loads(json.dumps(manifest.load())))
    said = _shape.complaints(man)
    assert any(c.startswith("own_check:") and f"{four} four-chip" in c
               for c in said), said
    # take the last one back: as many as allowed, and no complaint
    [w for w in man["workloads"] if w["chips"] == 4][-1]["chips"] = 1
    assert not [c for c in _shape.complaints(man) if "four-chip" in c]
