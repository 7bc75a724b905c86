"""Family ``hybrid_moe`` at the configuration file's ``tiny`` sizes on the
CPU: the program against the plain reference through a Scheduler (rings
wrapped, seams crossed), the share test that ties one chip's cut to the
uncut layer, the controls that must FAIL the comparison (int8 weights, a
dropped norm or head scale), the routing margin, the bytes and the
operations of the two roofline shares, and the new metrics' readers."""
import dataclasses
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
from perfbench.harness import manifest, program_spans  # noqa: E402
from perfbench.reference import hybrid_moe as reference  # noqa: E402

CELL = "k-exaone.serve-closed48-p8192"
NEW_METRICS = ("attn.decode_positions_read_per_lane",
               "engine.decode_hbm_roofline_share.kv",
               "engine.prefill_mxu_roofline_share")
# the cells and configurations the benchmark had before this family
ACCEPTED_CELLS = ["resnet50.train-b256", "pythia-410m.train-seq2048",
                  "pythia-410m.gossip4-seq2048", "pythia-410m.serve-closed32",
                  "a.x-k1.serve-closed128-p2048"]
FAMILY = manifest.load_module("families", "hybrid_moe")


def sized(tiny=True):
    cell = manifest.resolve_cell(manifest.load(), CELL)
    return (manifest.sized(cell["config"], tiny),
            manifest.sized(cell["traffic"], tiny))


def build(seed=7):
    cfg, traffic = sized()
    return FAMILY.build_serve(cfg, traffic, jax.devices("cpu")[:1], seed)


def prompts(prog, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, prog.vocab, n).tolist() for n in (5, 12)]


def check(alter=None, seed=7):
    _, traffic = sized()
    p = build(seed)
    if alter is not None:
        p.engine.update_params(alter(p.params))
    return p.reference_check(prompts(p), traffic["check"]["output_tokens"])


def test_the_tiny_sizes_keep_every_mechanism_and_the_real_ones_every_width():
    cfg, traffic = sized()
    lm = FAMILY.hybrid_config(cfg)
    assert {k for k, _ in lm.plan} == {"window", "full"}
    assert lm.plan[0][1] == "dense" and lm.expert_layers == lm.layers - 1
    assert lm.kv_heads < lm.heads and lm.held_experts < lm.num_experts
    # the check's prompts and outputs outrun the window: rings wrap
    asked = traffic["check"]
    assert max(asked["prompt_tokens"]) > 2 * lm.window
    assert asked["output_tokens"] >= 2 * lm.window
    full, real = sized(False)
    big = FAMILY.hybrid_config(full)
    assert (big.d_model, big.heads, big.kv_heads, big.head_dim) == (
        6144, 64, 8, 128)
    assert (big.window, big.dense_ffn, big.expert_ffn) == (128, 18432, 2048)
    assert (big.num_experts, big.held_experts, big.held_start, big.top_k) == (
        128, 8, 0, 8)
    assert (big.n_group, big.topk_group, big.route_scale) == (1, 1, 2.5)
    assert (big.rope_base, big.eps, big.vocab) == (1e6, 1e-5, 19200)
    assert [k for k, _ in big.plan] == ["window"] * 3 + ["full"] \
        + ["window"] * 3 + ["full"]
    assert [f for _, f in big.plan] == ["dense"] + ["experts"] * 7
    assert real["check"]["prompt_tokens"] == [300, 6000]
    assert real["check"]["output_tokens"] == 64
    eng = real["engine"]
    assert (eng["slots"], eng["max_len"], eng["batch_buckets"]) == (
        48, 8704, [48])
    assert eng["prefill_buckets"] == [512, 1024, 2048, 4096, 8192]


def test_the_file_keeps_every_number_of_the_source_but_the_reduced_keys():
    man = manifest.load()
    entry = [c for c in man["configs"] if c["name"] == "k-exaone"][0]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "num_nextn_predict_layers"]
    cfg, _ = sized(False)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 153600,
                                "num_nextn_predict_layers": 1}
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 48
    assert cfg["deployment"]["chips_per_layer"] == 16
    assert cfg["deployment"]["held_experts"] == [0, 8]
    assert cfg["deployment"]["vocab_slice"] == [0, 19200]
    for key in ("norm_placement", "qk_norm", "rotary", "router", "weights"):
        assert key in cfg["assumed"]
    assert len(cfg["departures"]) == 3


def test_prefill_then_decode_through_the_scheduler_agree_with_the_reference():
    _, traffic = sized()
    asked = traffic["check"]
    ref = check()
    assert ref["ok"], ref
    c = ref["compared"]
    # float32 on the CPU: the program IS the reference's function
    assert c["prefill_logit_err_share"][0] < 1e-5
    assert c["decode_logit_err_share"][0] < 1e-5
    assert c["decode_logit_gap_share"][0] < 1e-5
    assert c["requests_off_length"][0] == 0
    assert c["prefill_lengths_not_compared"] == [0, 0]
    assert c["decode_positions_short_of_floor"] == [0, 0]
    assert [r["prompt_tokens"] for r in ref["requests"]] == [5, 12]
    for row in ref["requests"]:
        assert row["candidates"] == asked["candidates"]
        assert 1 <= row["prefills_compared"] <= row["candidates"]


@pytest.mark.parametrize("starve", ["prefill", "decode"])
def test_a_check_that_compared_too_little_is_not_correct(monkeypatch, starve):
    _, traffic = sized()
    prog = build()
    if starve == "prefill":
        monkeypatch.setattr(FAMILY, "ROUTE_MARGIN", 1.0)
    else:
        monkeypatch.setattr(prog, "decode_floor", 1000)
    ref = prog.reference_check(prompts(prog),
                               traffic["check"]["output_tokens"])
    c = ref["compared"]
    assert not ref["ok"]
    assert c["prefill_logit_err_share"][0] <= c["prefill_logit_err_share"][1]
    if starve == "prefill":
        assert c["prefill_lengths_not_compared"] == [2, 0]
    else:
        assert c["decode_positions_short_of_floor"][0] > 900


def fake_int8(tree):
    """Every weight matrix through symmetric per-tensor int8 and back."""
    def q(a):
        if a.ndim < 3:
            return a                       # [n, D] norm scales stay
        scale = jnp.max(jnp.abs(a)) / 127.0
        return (jnp.round(a / scale) * scale).astype(a.dtype)
    return jax.tree.map(q, tree)


def by_the_logits(ref):
    c = ref["compared"]
    assert c["prefill_lengths_not_compared"] == [0, 0]
    assert c["decode_positions_short_of_floor"] == [0, 0]
    return c["prefill_logit_err_share"][0] > c["prefill_logit_err_share"][1] \
        or c["decode_logit_gap_share"][0] > c["decode_logit_gap_share"][1]


def test_int8_weights_fail_the_tolerance():
    ref = check(fake_int8)
    assert not ref["ok"] and by_the_logits(ref)


def through_int8(x):
    from bluefog_tpu.serve import kv_cache as kv
    q, s = kv.quantize_rows(x, "int8")
    return kv.dequantize_rows(q, s, x.dtype)


def int8_cache(kv, real):
    """K and V of both kinds of cache through the int8 page recipe and
    back: what a store in the precision below the stated one would hold."""
    return {"hybrid_prefill": lambda c, kind, l, slot, k, v, n:
            real["hybrid_prefill"](c, kind, l, slot, through_int8(k),
                                   through_int8(v), n),
            "token_pages": lambda k, v, store, dt: real["token_pages"](
                through_int8(k), through_int8(v), store, dt)}


def ring_written_one_off(kv, real):
    """Decode lands a ring's token one entry late: the oldest position
    that the window still sees is overwritten a step early."""
    def append(cache, slots, lengths, new):
        W = cache["kw"].shape[3]
        out = real["hybrid_append_tokens"](cache, slots, lengths, new)
        for name in kv.KIND_TENSORS["window"]:
            out[name] = kv._write_tokens(cache[name], slots,
                                         (lengths + 1) % W, new[name])
        return out
    return {"hybrid_append_tokens": append}


def rings_left_unwritten(kv, real):
    """Decode writes the full layers alone: a ring keeps the prompt's end
    and goes stale a position a step."""
    def append(cache, slots, lengths, new):
        out = real["hybrid_append_tokens"](cache, slots, lengths, new)
        return {**out, **{n: cache[n] for n in kv.KIND_TENSORS["window"]}}
    return {"hybrid_append_tokens": append}


def full_rows_read_one_short(kv, real):
    """Decode's mask on a full layer drops the newest cached position."""
    def attend(q, kt, vt, slots, lengths, new, *, ring=False):
        return real["attend_slots"](
            q, kt, vt, slots, lengths if ring else jnp.maximum(lengths - 1, 0),
            new, ring=ring)
    return {"attend_slots": attend}


@pytest.mark.parametrize("fault", [int8_cache, ring_written_one_off,
                                   rings_left_unwritten,
                                   full_rows_read_one_short])
def test_a_cache_at_fault_fails_by_the_decode_logits(monkeypatch, fault):
    """What only decode reads: prefill logits never touch the cache, so
    these controls pass the prefill limit and have to fail on the decode
    program's own logits."""
    from bluefog_tpu.serve import kv_cache as kv
    real = {n: getattr(kv, n) for n in (
        "hybrid_prefill", "token_pages", "hybrid_append_tokens",
        "attend_slots")}
    for name, fn in fault(kv, real).items():
        monkeypatch.setattr(kv, name, fn)
    c = check()["compared"]
    assert c["prefill_lengths_not_compared"] == [0, 0]
    assert c["decode_positions_short_of_floor"] == [0, 0]
    assert c["prefill_logit_err_share"][0] <= c["prefill_logit_err_share"][1]
    assert c["decode_logit_err_share"][0] > 10 * c["decode_logit_err_share"][1]


@pytest.mark.parametrize("scale", ["gq", "gk", "g1", "g2"])
def test_a_dropped_norm_scale_fails_the_comparison(scale):
    def drop(params):
        layers = tuple(dict(lp, **{scale: jnp.ones_like(lp[scale])})
                       for lp in params["layers"])
        return {"layers": layers, "shared": params["shared"]}
    assert not check(drop)["ok"]


def layer_weights(cfg, seed, experts):
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ks = jax.random.split(jax.random.key(seed), 8)
    n = lambda k, s: 0.2 * jax.random.normal(k, s, jnp.float32)
    return {"wr": n(ks[0], (D, experts)), "wsg": n(ks[1], (D, F)),
            "wsu": n(ks[2], (D, F)), "wsd": n(ks[3], (F, D)),
            "weg": n(ks[4], (experts, D, F)), "weu": n(ks[5], (experts, D, F)),
            "wed": n(ks[6], (experts, F, D))}


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The shares' routed parts plus the shared expert counted once are the
    uncut reference's layer, for the reference's own cut and for the
    program's (moe.layers.held_moe_ffn under the HybridConfig)."""
    from bluefog_tpu.moe.layers import held_moe_ffn
    cfg, _ = sized()
    E, held = cfg["deployment"]["router_outputs"], cfg["num_experts"]
    assert E // held == cfg["deployment"]["chips_per_layer"]
    w = layer_weights(cfg, 11, E)
    h = jax.random.normal(jax.random.key(12), (24, cfg["hidden_size"]))
    whole, sel = reference.moe_ffn(cfg, w, h)          # all experts held
    assert int(sel.sum()) == 24 * cfg["num_experts_per_tok"]
    lm = FAMILY.hybrid_config(cfg)
    shared = reference.gated(h, w["wsg"], w["wsu"], w["wsd"])
    ref_sum, prog_sum = shared, shared
    for start in range(0, E, held):
        cut = dict(w, **{k: w[k][start:start + held]
                         for k in ("weg", "weu", "wed")})
        ref_sum = ref_sum + reference.moe_ffn(cfg, cut, h, start,
                                              shared=False)[0]
        y, _, _ = held_moe_ffn(dataclasses.replace(lm, held_start=start),
                               cut, h)
        prog_sum = prog_sum + (y - shared)
    np.testing.assert_allclose(ref_sum, whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(prog_sum, whole, rtol=1e-3, atol=1e-4)
    one = reference.moe_ffn(cfg, dict(w, **{
        k: w[k][:held] for k in ("weg", "weu", "wed")}), h)[0]
    assert float(jnp.max(jnp.abs(one - whole))) > 1e-2


def test_a_near_tie_on_a_held_expert_is_not_decided():
    cfg, _ = sized()
    E, k = cfg["deployment"]["router_outputs"], cfg["num_experts_per_tok"]
    wr = jnp.eye(E, E)                     # h picks out router logits as is
    base = np.linspace(2.0, -3.0, E).astype(np.float32)
    tie = base.copy()
    tie[k - 1] = tie[k] + 1e-4             # the k-th and (k+1)-th all but tie
    h = jnp.asarray(np.stack([base, tie]))
    margin = np.asarray(reference.held_margin(cfg, h, wr, 0, k + 1))
    assert margin[0] >= 0.03 and margin[1] < 0.03
    # experts no near-tie touches: held elsewhere, both rows are decided
    other = np.asarray(reference.held_margin(cfg, h, wr, k + 2, 4))
    assert other.min() >= 0.03
    _, sel, weight = reference.route(cfg, h, wr)
    assert np.asarray(sel).sum(-1).tolist() == [k, k]
    np.testing.assert_allclose(np.asarray(weight).sum(-1),
                               cfg["routed_scaling_factor"], rtol=1e-6)


def test_the_reference_masks_and_turns_by_the_layers_kind():
    keep = np.asarray(reference.mask_of("sliding_attention", 6, 3))
    assert keep.sum(-1).tolist() == [1, 2, 3, 3, 3, 3]
    assert keep[5].tolist() == [False] * 3 + [True] * 3
    assert np.asarray(reference.mask_of("full_attention", 6, 3)).sum() == 21
    cfg, _ = sized()
    D, Dh = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ks = jax.random.split(jax.random.key(0), 6)
    n = lambda k, s: 0.3 * jax.random.normal(k, s, jnp.float32)
    w = {"wq": n(ks[0], (D, H * Dh)), "wk": n(ks[1], (D, Hkv * Dh)),
         "wv": n(ks[2], (D, Hkv * Dh)), "wo": n(ks[3], (H * Dh, D)),
         "gq": jnp.ones(Dh), "gk": jnp.ones(Dh)}
    x = n(ks[4], (10, D))
    # a full layer turns nothing: shifting every position by a prefix that
    # it cannot see (none) is not at issue; permuting earlier tokens is
    full = reference.attention(cfg, w, x, "full_attention")
    perm = jnp.concatenate([x[:4][::-1], x[4:]])
    again = reference.attention(cfg, w, perm, "full_attention")
    np.testing.assert_allclose(full[4:], again[4:], atol=1e-5)
    slid = reference.attention(cfg, w, x, "sliding_attention")
    assert float(jnp.abs(slid[9] - full[9]).max()) > 1e-3


def test_the_roofline_floors_count_what_the_program_cannot_avoid():
    from bluefog_tpu.models import decoder
    cfg, _ = sized(False)
    lm = FAMILY.hybrid_config(cfg)
    shapes = decoder.hybrid_param_shapes(lm)
    count = lambda name: sum(int(np.prod(l[name])) for l in shapes["layers"]
                             if name in l)
    routed = count("weg") + count("weu") + count("wed")
    router = count("wr")
    embed = int(np.prod(shapes["shared"]["embed"]))
    total = decoder.hybrid_param_count(lm)
    assert total == 3_865_419_776
    want = 2 * (total - routed - router - embed) + 4 * router
    assert FAMILY.weight_bytes(cfg) == want
    assert FAMILY.expert_bytes(cfg) == 2 * routed // (7 * 8)
    assert FAMILY.position_bytes(cfg) == 4096
    floor = FAMILY.decode_floor_bytes(cfg, 2, 100, 1000, 300)
    assert floor == 2 * want + 100 * FAMILY.expert_bytes(cfg) \
        + 4096 * (1000 * 2 + 300 * 6)
    # every expert hit and every position of the cell's rows: the most a
    # call of the floor can be, under what the chip holds
    most = FAMILY.decode_floor_bytes(cfg, 1, 56, 48 * 8704, 48 * 128)
    assert 11.0e9 < most < 11.4e9
    # a prompt's operations: 2 a multiply-add, held experts at their
    # expected share (8 x 8 / 128 = half an expert a token), the band far
    # under the causal triangle
    head = 2 * 6144 * 19200
    attn_mm = 2 * 6144 * 64 * 128 + 2 * 6144 * 8 * 128
    per_token = 2 * (8 * attn_mm + 3 * 6144 * 18432
                     + 7 * (6144 * 128 + 1.5 * 3 * 6144 * 2048))
    assert FAMILY.prefill_flops(cfg, 1) == pytest.approx(
        per_token + head + 8 * 4 * 64 * 128, rel=1e-9)
    assert 3.2e9 < per_token < 3.4e9 and 3.5e9 < per_token + head < 3.6e9
    keys_met = 2 * 8192 * 8193 // 2 + 6 * (128 * 129 // 2 + (8192 - 128) * 128)
    assert FAMILY.prefill_flops(cfg, 8192) == pytest.approx(
        8192 * per_token + 4 * 64 * 128 * keys_met + head, rel=1e-9)
    # a full layer at 8,192: 1.1 TFLOP; a window layer: a sixty-fourth
    assert 4 * 64 * 128 * 8192 * 8193 // 2 == pytest.approx(1.1e12, rel=0.01)


def marks(rows):
    """A traced tail's bf:engine.held_work marks as a hand-made trace."""
    events, t = [["pb:window", 0, 10_000_000, {}]], 1000
    for attrs in rows:
        events.append(["bf:engine.decode_call", t, 5000, {"S": 48}])
        events.append(["bf:engine.held_work", t + 4000, 10, attrs])
        t += 10_000
    return program_spans.Analysis({"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": events}]}]})


def test_the_new_metrics_read_the_marks_and_nothing_where_there_are_none():
    cfg, _ = sized(False)
    mark = {"pairs": 1000, "rows": 48 * 8 * 7, "experts_hit": 50,
            "positions": 100_000, "positions_window": 6_000,
            "positions_read_full": 49 * 8704, "positions_read_window": 49 * 128}
    run = {"config": cfg, "workload": CELL,
           "device": {"platform": "cpu", "kind": "cpu"},
           "program_spans": marks([mark, mark])}
    read = lambda name: manifest.load_module("metrics", name).read(run)
    assert read("attn.decode_positions_read_per_lane") == pytest.approx(
        (49 * 8704 * 2 + 49 * 128 * 6) / 48)
    assert read("moe.tokens_per_held_expert") == pytest.approx(
        2000 / (8 * 7 * 2))
    assert read("moe.pad_share") == pytest.approx(1 - 1000 / (48 * 56))
    # a roofline share is a device number: none without the chip
    assert read("engine.decode_hbm_roofline_share.kv") is None
    assert read("engine.prefill_mxu_roofline_share") is None
    # the parent's program writes no such attribute or mark: nothing to
    # read, and no error
    for rows in ([], [{"pairs": 1, "rows": 8, "experts_hit": 1,
                       "positions": 5}]):
        run["program_spans"] = marks(rows)
        for name in NEW_METRICS:
            assert read(name) is None


# what the cell reports end to end (the gap's 90th percentile since the
# review: six runs read it inside its bound here) and what moves the gap
REPORTS = ("serve_tok_per_s", "ttft_p50_s", "token_gap_p90_s", "setup_s")
GAP = ("engine.decode_call_s_p50", "engine.decode_collect_s_p50",
       "token_gap_p99_s")


def manifest_rule(man, root=ROOT):
    """The cell and what PR 35 wrote for it, however much has been
    appended since: the accepted cells and configurations first and in
    their order with this one behind them, the cell IN the lists of its
    end-to-end metrics, of what moves the gap and of its three new
    metrics."""
    bad = _shape.written_for(man, CELL, config="k-exaone", chips=1,
                             traffic="serve-closed48-p8192",
                             metrics=REPORTS + GAP + NEW_METRICS)
    if [w["name"] for w in man["workloads"]][:6] != ACCEPTED_CELLS + [CELL]:
        bad.append("the first six cells are not the first six")
    if [c["name"] for c in man["configs"]][:4] != [
            "resnet50", "pythia-410m", "a.x-k1", "k-exaone"]:
        bad.append("the first four configurations are not the first four")
    return bad


def test_the_manifest_keeps_the_accepted_entries_first_and_in_order():
    """Derived, not pinned to a count: the accepted cells and
    configurations come first and in their order, whatever is appended;
    the rules of any manifest are ``_shape``'s."""
    man = manifest.load()
    assert manifest_rule(man) == [] and _shape.complaints(man) == []
    per_layer = [m["name"] for m in man["per_layer"]]
    at = per_layer.index("token_gap_p80_s")
    assert tuple(per_layer[at + 1:at + 4]) == NEW_METRICS
    # the rule sees the cell taken out of a list
    by_name = {m["name"]: m for m in man["per_layer"]}
    by_name[NEW_METRICS[0]]["workloads"].remove(CELL)
    assert manifest_rule(man) == [f"{NEW_METRICS[0]} does not list {CELL}"]


def test_the_cell_is_listed_where_a_reader_can_read_it():
    man = manifest.load()
    by_name = {m["name"]: m for m in man["per_layer"] + man["end_to_end"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"][0] == CELL
    assert by_name["engine.prefill_mxu_roofline_share"]["moves"] \
        == "ttft_p50_s"
    # every metric the other held-experts cell reports, this one reports
    # too, but the latent family's own roofline share (its reader names
    # that family's program)
    # (of the metrics the benchmark has with this family: a later one is
    # its own PR's to decide)
    per_layer = [m["name"] for m in man["per_layer"]]
    known = set(per_layer[:per_layer.index(NEW_METRICS[-1]) + 1]) | {
        m["name"] for m in man["end_to_end"][:5]}
    other = {n for n in known if "a.x-k1.serve-closed128-p2048"
             in by_name[n].get("workloads", [])}
    mine = {n for n in known if CELL in by_name[n].get("workloads", [])}
    assert other - mine == {"engine.decode_hbm_roofline_share"}
    # six runs read the gap's 90th percentile inside its bound here (ISSUE
    # 35's condition), so the cell reports it and what moves it
    gap = {"token_gap_p90_s", "token_gap_p99_s", "engine.decode_call_s_p50",
           "engine.decode_collect_s_p50"}
    assert mine - other == set(NEW_METRICS) | gap
    assert all(by_name[n].get("moves", "token_gap_p90_s")
               == "token_gap_p90_s" for n in gap)
