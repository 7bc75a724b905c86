"""Family ``latent_hc_moe`` at the configuration file's ``tiny`` sizes on the
CPU: the program against the plain reference through a Scheduler, the
controls that must FAIL the comparison by the program's weights or sizes
alone (one Sinkhorn round, gains of zero, no router bias, int8 weights), the
share test with one share, the margin on the biased scores, the bytes and
operations behind the roofline metrics, the new metrics' readers, and that
the latent family's own programs are still the parent's."""
import dataclasses
import hashlib
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
from perfbench.harness import manifest, scopes  # noqa: E402
from perfbench.reference import latent_hc_moe as reference  # noqa: E402

CELL = "xing4.0.serve-closed96-p4096"
LIKE = "a.x-k1.serve-closed128-p2048"
NEW_METRICS = ("hc.decode_mix_device_s_per_call",
               "hc.prefill_mix_device_s_per_ktok",
               "hc.mix_hbm_roofline_share",
               "engine.prefill_mxu_roofline_share.latent")
FAMILY = manifest.load_module("families", "latent_hc_moe")


def sized(tiny=True, cell=CELL):
    resolved = manifest.resolve_cell(manifest.load(), cell)
    return (manifest.sized(resolved["config"], tiny),
            manifest.sized(resolved["traffic"], tiny))


def build(cfg=None, seed=7):
    tiny, traffic = sized()
    return FAMILY.build_serve(cfg or tiny, traffic,
                              jax.devices("cpu")[:1], seed)


def prompts(prog, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, prog.vocab, n).tolist() for n in (5, 12)]


def check(prog, alter=None):
    """The cell's check on ``prog``, its ENGINE given ``alter``ed weights:
    the reference keeps the sound ones."""
    if alter is not None:
        prog.engine.update_params(alter(prog.params))
    return prog.reference_check(
        prompts(prog), sized()[1]["check"]["output_tokens"])


def test_the_tiny_sizes_keep_every_mechanism_and_the_file_the_published():
    cfg, _ = sized()
    lm = FAMILY.latent_config(cfg)
    assert lm.streams == 4 and lm.sinkhorn_iters == 20 and lm.res_clamp == 30
    assert lm.dense_layers == 2 and lm.expert_layers >= 2 and lm.route_bias
    assert lm.held_experts == lm.num_experts >= 16 and lm.top_k == 4
    assert lm.q_rank < lm.d_model and lm.kv_rank < lm.d_model
    assert lm.rope_dim > 0 and lm.nope_dim > 0 and lm.rope_factor > 1
    full, traffic = sized(False)
    big = FAMILY.latent_config(full)
    assert (big.d_model, big.heads, big.q_rank, big.kv_rank) == (
        3584, 32, 768, 512)
    assert (big.nope_dim, big.rope_dim, big.v_dim) == (128, 64, 128)
    assert (big.dense_ffn, big.expert_ffn, big.top_k) == (9216, 1024, 4)
    assert (big.num_experts, big.held_experts, big.held_start) == (64, 64, 0)
    assert (big.layers, big.dense_layers, big.vocab) == (7, 2, 131072)
    assert (big.streams, big.sinkhorn_iters, big.hc_eps) == (4, 20, 1e-6)
    assert (big.n_group, big.topk_group, big.route_scale) == (1, 1, 2)
    assert big.rope_factor == 64 and big.route_bias
    # every key of the catalog row under its own name, two of them cut
    assert full["published"] == {"num_hidden_layers": 40,
                                 "num_nextn_predict_layers": 1}
    assert full["num_nextn_predict_layers"] == 0
    assert full["first_k_dense_replace"] == 2 and full["hc_mult"] == 4
    assert full["topk_method"] == "noaux_tc"
    for key in ("deployment", "reduced_why", "departures", "assumed",
                "hc_init"):
        assert full[key]
    # the cell as the issue states it
    eng = traffic["engine"]
    assert (traffic["clients"], traffic["cycle"], eng["slots"],
            eng["max_len"]) == (96, 96, 96, 4224)
    assert eng["batch_buckets"] == [96] and eng["dtype"] == "bfloat16"
    assert eng["prefill_buckets"] == [512, 1024, 2048, 4096]
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 256,
                                        "hi": 4096}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 32,
                                        "hi": 128}
    assert traffic["ramp_output_tokens"] == {"dist": "uniform", "lo": 8,
                                             "hi": 128}
    assert traffic["traced_seconds"] == 3.0 and traffic["loop"] == "closed"


def test_prefill_then_decode_through_the_scheduler_agree_with_the_reference():
    prog = build()
    asked = sized()[1]["check"]
    ref = check(prog)
    assert ref["ok"], ref
    c = ref["compared"]
    # float32 on the CPU: the program IS the reference's function
    assert c["prefill_logit_err_share"][0] < 1e-5
    assert c["decode_logit_gap_share"][0] < 1e-5
    assert c["route_flip_share"][0] == 0
    assert c["prefill_logit_err_share_largest"][0] < 1e-5
    assert c["requests_off_length"][0] == 0
    assert c["prefill_lengths_not_compared"] == [0, 0]
    assert c["decode_positions_short_of_floor"] == [0, 0]
    assert [r["prompt_tokens"] for r in ref["requests"]] == [5, 12]
    for row in ref["requests"]:
        assert row["candidates"] == asked["candidates"]
        # two compared prefills at least: the largest error and the
        # largest but one are each held to a limit of their own
        assert 2 <= row["prefills_compared"] <= row["candidates"]
        assert row["prefill_logit_abs_err_but_one"] \
            <= row["prefill_logit_max_abs_err"]
        assert row["selection_flips_at_decided"] == 0
    assert sum(r["decode_positions_decided"] for r in ref["requests"]) \
        >= asked["decode_positions_floor"]


LIMITED = {"prefill_largest": "prefill_logit_err_share_largest",
           "prefill_but_one": "prefill_logit_err_share",
           "decode_gap": "decode_logit_gap_share"}


@pytest.mark.parametrize("name", ["decode_gap", "prefill_largest"])
def test_a_number_is_held_by_a_limit_of_its_own(name):
    """The largest prefill error, the largest but one and the decoded
    tokens' gap are three numbers under three limits: with ONE limit under
    its reading the run is not correct by that number alone (decode is
    another compiled program, and one wrong prefill of a length is one
    slot's or one bucket's fault)."""
    prog = build()
    prog.limits = dict(prog.limits, **{name: -1.0})
    ref = check(prog)
    over = [k for k, (value, limit) in ref["compared"].items()
            if value > limit]
    assert not ref["ok"] and over == [LIMITED[name]], ref["compared"]
    assert ref["tolerance"] == prog.limits


@pytest.mark.parametrize("name", sorted(LIMITED))
def test_every_chip_limit_lies_between_its_two_readings(name):
    """A bf16 limit stands over what the sound program read at most on the
    chip and under what it read with its weights through int8 and back,
    with room on both sides (the family file's CHIP_READINGS)."""
    sound, int8 = FAMILY.CHIP_READINGS[name]
    limit = FAMILY.SERVE_LIMITS["bfloat16"][name]
    assert 1.5 * sound <= limit <= int8 / 1.5, (sound, limit, int8)
    assert FAMILY.SERVE_LIMITS["float32"][name] < sound


def zeroed(*names):
    def alter(params):
        out = jax.tree.map(lambda a: a, params)
        for group in out.values():
            for name in names:
                if name in group:
                    group[name] = jnp.zeros_like(group[name])
        return out
    return alter


def fake_int8(tree):
    """Every weight matrix through symmetric per-tensor int8 and back."""
    def q(a):
        if a.ndim < 3:
            return a                       # norm scales, gains and biases
        scale = jnp.max(jnp.abs(a)) / 127.0
        return (jnp.round(a / scale) * scale).astype(a.dtype)
    return jax.tree.map(q, tree)


@pytest.mark.parametrize("control", ["one_sinkhorn_round", "gains_of_zero",
                                     "no_router_bias", "phi_zeroed",
                                     "int8_weights"])
def test_a_dropped_mechanism_fails_the_comparison(control):
    """The same engine with one Sinkhorn round, with the maps' gains (or
    their phi) zeroed, without its router bias, or with its weights
    through int8, is NOT the reference's function: each by the program's
    sizes or weights alone, the reference as it stands."""
    cfg, _ = sized()
    if control == "one_sinkhorn_round":
        prog = build(dict(cfg, hc_sinkhorn_iters=1))
        prog.cfg = cfg                       # the reference makes twenty
        ref = check(prog)
    else:
        ref = check(build(), {
            "gains_of_zero": zeroed("h1a", "h2a"),
            "no_router_bias": zeroed("eb"),
            "phi_zeroed": zeroed("h1p", "h2p"),
            "int8_weights": fake_int8}[control])
    assert not ref["ok"], ref["compared"]
    c = ref["compared"]
    # by the logits, with every length and enough decode positions compared
    assert any(c[k][0] > c[k][1] for k in LIMITED.values())
    assert c["prefill_lengths_not_compared"] == [0, 0]
    assert c["decode_positions_short_of_floor"] == [0, 0]


def layer_weights(cfg, seed, experts):
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ks = jax.random.split(jax.random.key(seed), 8)
    n = lambda k, s: 0.2 * jax.random.normal(k, s, jnp.float32)
    return {"wr": n(ks[0], (D, experts)), "wsg": n(ks[1], (D, F)),
            "wsu": n(ks[2], (D, F)), "wsd": n(ks[3], (F, D)),
            "weg": n(ks[4], (experts, D, F)), "weu": n(ks[5], (experts, D, F)),
            "wed": n(ks[6], (experts, F, D)),
            "eb": 0.1 * jax.random.normal(ks[7], (experts,))}


def test_the_one_share_is_the_uncut_layer_and_parts_of_it_add_up():
    """``held_experts == num_experts``: the program's layer
    (moe.layers.held_moe_ffn, both of its forms) IS the uncut reference
    layer; and cut in four, the reference's and the program's shares with
    the shared expert counted once add up to it."""
    from bluefog_tpu.moe.layers import held_moe_ffn
    cfg, _ = sized()
    E = cfg["deployment"]["router_outputs"]
    assert E == cfg["n_routed_experts"] == FAMILY.held_experts(cfg)
    w = layer_weights(cfg, 11, E)
    h = jax.random.normal(jax.random.key(12), (24, cfg["hidden_size"]))
    whole, sel = reference.moe_ffn(cfg, w, h)
    assert int(sel.sum()) == 24 * cfg["num_experts_per_tok"]
    lm = FAMILY.latent_config(cfg)
    y, idx, _ = held_moe_ffn(lm, w, h)
    np.testing.assert_allclose(y, whole, rtol=1e-3, atol=1e-4)
    picked = np.zeros((24, E), bool)
    np.put_along_axis(picked, np.asarray(idx), True, 1)
    assert np.array_equal(picked, np.asarray(sel))
    stacked = dict(w, **{k: w[k][None] for k in ("weg", "weu", "wed")})
    y, _, _ = held_moe_ffn(lm, stacked, h, layer=jnp.int32(0))
    np.testing.assert_allclose(y, whole, rtol=1e-3, atol=1e-4)
    # without the bias it is another layer
    other, osel = reference.moe_ffn(cfg, dict(w, eb=jnp.zeros(E)), h)
    assert not np.array_equal(np.asarray(osel), np.asarray(sel))
    assert float(jnp.max(jnp.abs(other - whole))) > 1e-2
    shared = reference.gated(h, w["wsg"], w["wsu"], w["wsd"])
    ref_sum, prog_sum = shared, shared
    for start in range(0, E, E // 4):
        cut = dict(w, **{k: w[k][start:start + E // 4]
                         for k in ("weg", "weu", "wed")})
        ref_sum = ref_sum + reference.moe_ffn(cfg, cut, h, start,
                                              shared=False)[0]
        part, _, _ = held_moe_ffn(dataclasses.replace(
            lm, held_start=start, held_experts=E // 4), cut, h)
        prog_sum = prog_sum + (part - shared)
    np.testing.assert_allclose(ref_sum, whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(prog_sum, whole, rtol=1e-3, atol=1e-4)


def test_a_near_tie_on_the_biased_scores_is_not_decided():
    cfg, _ = sized()
    E, k = cfg["deployment"]["router_outputs"], cfg["num_experts_per_tok"]
    wr = jnp.eye(E, E)                     # h picks out router logits as is
    base = np.tile(np.linspace(2.0, -2.0, E).astype(np.float32), (3, 1))
    tie = base.copy()
    tie[1, k] = tie[1, k - 1] - 1e-4       # raw: the k-th and (k+1)-th tie
    h = jnp.asarray(np.stack([base[0], tie[1], base[2]]))
    none = jnp.zeros(E)
    margin = np.asarray(reference.held_margin(cfg, h, wr, none, 0, E))
    assert margin[0] >= 0.03 and margin[2] >= 0.03 and margin[1] < 0.03
    # a bias that parts the tie decides it; one that makes a tie of two
    # clear scores undecides them
    s = 1 / (1 + np.exp(-base[0]))
    apart = np.zeros(E, np.float32)
    apart[k] = -0.2
    assert reference.held_margin(cfg, h, wr, jnp.asarray(apart), 0,
                                 E)[1] >= 0.03
    level = np.zeros(E, np.float32)
    level[k] = s[k - 1] - s[k]             # lifts the (k+1)-th onto the k-th
    assert reference.held_margin(cfg, h, wr, jnp.asarray(level), 0,
                                 E)[0] < 0.03
    # and the selection follows the biased scores
    lift = np.zeros(E, np.float32)
    lift[E - 1] = 1.0                      # the lowest raw score, lifted
    _, sel, weight = reference.route(cfg, h[:1], wr, jnp.asarray(lift))
    assert bool(sel[0, E - 1]) and int(sel.sum()) == k
    picked = np.asarray(sel[0])
    np.testing.assert_allclose(
        np.asarray(weight[0])[picked],
        cfg["routed_scaling_factor"] * s[picked] / s[picked].sum(), rtol=1e-5)


def test_the_reference_sinkhorn_is_the_equations():
    m0 = np.exp(np.random.default_rng(0).normal(size=(5, 4, 4)) * 2.6)
    got = np.asarray(reference.sinkhorn(jnp.asarray(m0, jnp.float32), 20,
                                        1e-6))
    want = m0.copy()
    for _ in range(20):
        want = want / (want.sum(1, keepdims=True) + 1e-6)   # columns: over i
        want = want / (want.sum(2, keepdims=True) + 1e-6)   # rows: over j
    np.testing.assert_allclose(got, want, rtol=1e-4)
    np.testing.assert_allclose(got.sum(2), 1.0, atol=1e-5)
    assert np.abs(got.sum(1) - 1.0).max() < 0.1


def test_the_floors_count_what_the_chip_holds():
    from bluefog_tpu.models import decoder
    cfg, _ = sized(False)
    lm = FAMILY.latent_config(cfg)
    shapes = decoder.latent_param_shapes(lm)
    held = decoder.latent_param_count(lm)
    assert held == cfg["deployment"]["held_parameters"] == 4_920_866_746
    size = lambda group, name: int(np.prod(shapes[group][name]))
    in_f32 = sum(size(g, n) for g in shapes for n in shapes[g]
                 if n in FAMILY.FLOAT32)
    routed = sum(size("blocks", n) for n in ("weg", "weu", "wed"))
    embed = size("shared", "embed")
    assert FAMILY.weight_bytes(cfg) == \
        2 * (held - routed - embed - in_f32) + 4 * in_f32
    assert FAMILY.expert_bytes(cfg) == 2 * routed // (5 * 64)
    assert (FAMILY.held_experts(cfg), FAMILY.expert_layers(cfg)) == (64, 5)
    # what the chip holds: 9.85 GB of weights beside 3.30 GB of cache
    assert 9.85e9 < 2 * (held - in_f32) + 4 * in_f32 < 9.86e9
    floor = FAMILY.decode_floor_bytes(cfg, calls=2, experts_hit=100,
                                      live_positions=1000)
    assert floor == 2 * FAMILY.weight_bytes(cfg) \
        + 100 * FAMILY.expert_bytes(cfg) + 1000 * 7 * 576 * 2
    # every expert hit and every position of the cell's rows: the most a
    # call of the floor can be, under what the chip holds
    most = FAMILY.decode_floor_bytes(cfg, 1, 320, 96 * 4224)
    assert 12.1e9 < most < 12.3e9
    # the stream maps' floor: 14 sublayers of (3 n + 2) vectors a token
    n, D = 4, 3584
    assert FAMILY.hc_floor_bytes(cfg, 1000, 0) == 14 * 1000 * 14 * D * 2
    phi = 14 * (n * D * 24 + 3 + 24) * 4
    assert FAMILY.hc_floor_bytes(cfg, 1000, 3) == \
        FAMILY.hc_floor_bytes(cfg, 1000, 0) + 3 * phi
    # a prompt's operations: about 2 per active parameter and token, plus
    # attention that grows with the length
    short, long = (FAMILY.prefill_flops(cfg, t) for t in (256, 4096))
    active = (held - routed - 2 * embed
              + 5 * 4 * 3 * D * cfg["moe_intermediate_size"])
    for flops, t in ((short, 256), (long, 4096)):
        attention = 2 * 7 * 32 * 320 * t * (t + 1) // 2
        assert flops == pytest.approx(
            2 * active * t + attention + 2 * D * 131072, rel=0.01)
    assert long / 4096 > 1.15 * short / 256


class _Analysis(scopes.Analysis):
    """A traced tail's split by scope, made by hand."""

    def __init__(self, by, calls, tokens):
        self.by, self.calls, self.tokens = by, calls, tokens


def test_the_stream_metrics_read_the_scope_table():
    cfg, _ = sized(False)
    ana = _Analysis(
        {("prefill Tpad=2048", "hc.coef", ""): 0.004,
         ("prefill Tpad=2048", "hc.mix", ""): 0.006,
         ("prefill Tpad=2048", "ffn", ""): 0.1,
         ("decode S=96", "hc.coef", ""): 0.003,
         ("decode S=96", "hc.mix", ""): 0.001,
         ("decode S=96", "moe.experts", ""): 0.05},
        {"prefill Tpad=2048": 2, "decode S=96": 4},
        {"prefill Tpad=2048": 3000})
    run = {"config": cfg, "workload": CELL, "device_scopes": ana,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    read = lambda name: manifest.load_module("metrics", name).read(run)
    assert read("hc.decode_mix_device_s_per_call") == pytest.approx(0.001)
    assert read("hc.prefill_mix_device_s_per_ktok") == pytest.approx(
        0.010 / 3.0)
    want = FAMILY.hc_floor_bytes(cfg, 3000, 2) / (0.010 * 819e9)
    assert read("hc.mix_hbm_roofline_share") == pytest.approx(want)
    assert 0 < want < 1
    # a program without streams (the latent family's own cell): nothing to
    # read, no error; and no device number off the chip
    plain = _Analysis({("decode S=128", "ffn", ""): 0.1,
                       ("prefill Tpad=256", "ffn", ""): 0.1},
                      {"decode S=128": 1, "prefill Tpad=256": 1},
                      {"prefill Tpad=256": 200})
    for name in NEW_METRICS[:3]:
        assert manifest.load_module("metrics", name).read(
            dict(run, device_scopes=plain)) is None
        assert manifest.load_module("metrics", name).read(
            dict(run, device={"platform": "cpu", "kind": "cpu"})) is None
    # a family without the hook
    other = dict(run, config=dict(cfg, family="latent_moe"))
    assert manifest.load_module(
        "metrics", "hc.mix_hbm_roofline_share").read(other) is None


def test_the_prefill_roofline_reader_names_the_latent_program():
    mod = manifest.load_module("metrics",
                               "engine.prefill_mxu_roofline_share.latent")
    from bluefog_tpu.serve import ServeEngine
    assert mod.PROGRAM == "jit_" + ServeEngine._latent_prefill_body.__name__
    assert _shape.family_hooks(
        "engine.prefill_mxu_roofline_share.latent") == ["prefill_flops"]
    assert _shape.family_hooks("hc.mix_hbm_roofline_share") == [
        "hc_floor_bytes"]
    # off the chip, or with no prefill call in the trace: nothing to read
    from perfbench.harness import program_spans
    run = {"config": sized(False)[0], "workload": CELL,
           "device": {"platform": "cpu", "kind": "cpu"},
           "program_spans": program_spans.Analysis({"planes": []})}
    assert mod.read(run) is None


# sha256 of the StableHLO the latent family's OWN programs lower to at the
# a.x-k1 file's tiny sizes (one stream, one dense layer, no bias), taken at
# the parent commit 06d8f15 with this function: the streamed block, the
# further dense layers and the router bias are Python branches that such a
# configuration never enters, so what the compiler is handed is the
# parent's program, and what any compiler makes of it is the parent's too
# (compared by compiled.as_text() once by hand, CHANGES.md, PR 41).  A PR
# that means to change the latent programs takes the hashes anew.
PARENT_PROGRAMS = {
    "decode": "f6d71278b11e7771d2575d07d5ce66d9ba6af4dc72deab9ec6540e9fe1ceaf6c",
    "prefill": "2a643bb09e1bd80a39b1cbf5ffd4caba81ad1ccfb28d08d3f4b10a8de7290c95",
}


def lowered_latent_programs():
    from jax.sharding import NamedSharding
    from bluefog_tpu.models import decoder
    from bluefog_tpu.parallel import compose
    from bluefog_tpu.serve import ServeEngine
    from bluefog_tpu.serve import kv_cache as kv
    from perfbench.families import latent_moe
    from perfbench.families.composed_lm import serve_config
    cfg, traffic = sized(cell=LIKE)
    lm, scfg = latent_moe.latent_config(cfg), serve_config(traffic)
    m = compose.compose_parallelism(1, 1, 1, 1,
                                    devices=jax.devices("cpu")[:1])
    eng = ServeEngine.__new__(ServeEngine)      # bodies only: no arrays
    eng._moe, eng._latent, eng._hybrid, eng._share = False, True, False, True
    eng.m, eng.cfg, eng.scfg = m, lm, scfg
    sh = NamedSharding(m.mesh, m.spec)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        (1,) + tuple(shape), dtype, sharding=sh)
    params = {g: {k: sds(shape, jnp.float32) for k, shape in grp.items()}
              for g, grp in decoder.latent_param_shapes(lm).items()}
    cc = kv.LatentCacheConfig(layers=lm.layers, slots=scfg.slots,
                              max_len=scfg.max_len, kv_rank=lm.kv_rank,
                              rope_dim=lm.rope_dim, dtype=scfg.dtype)
    state = lambda: ({k: sds(shape, scfg.dtype)
                      for k, shape in cc.shapes().items()},
                     sds((cc.rows, 2), jnp.uint32))
    S, T = scfg.batch_buckets[0], scfg.prefill_buckets[-1]
    return {
        "decode": eng._build(eng._latent_decode_body).lower(
            params, *state(), sds((S, 1 + 4), jnp.int32)).as_text(),
        "prefill": eng._build(eng._latent_prefill_body).lower(
            params, *state(), sds((T + 4,), jnp.int32)).as_text()}


def test_the_latent_familys_own_programs_are_the_parents():
    got = {name: hashlib.sha256(text.encode()).hexdigest()
           for name, text in lowered_latent_programs().items()}
    assert got == PARENT_PROGRAMS


# what the cell reports besides the metrics this file's PR wrote for it
REPORTS = ("serve_tok_per_s", "ttft_p50_s", "setup_s")


def manifest_rule(man, root=ROOT):
    """The cell, its configuration and the four metrics PR 41 wrote,
    however much has been appended since: the cell IN every list the
    latent family's cell is in (it runs the same programs: a reader that
    finds something there finds it here) and in the four of its own."""
    shared = tuple(m["name"] for m in man["end_to_end"] + man["per_layer"]
                   if "workloads" in m and LIKE in m["workloads"])
    own = tuple(n for n in NEW_METRICS if n not in shared)
    bad = _shape.written_for(man, CELL, config="xing4.0", chips=1,
                             traffic="serve-closed96-p4096",
                             metrics=shared + own)
    bad += _shape.written_for(man, CELL, config="xing4.0", chips=1,
                              traffic="serve-closed96-p4096",
                              metrics=REPORTS)
    by_name = {m["name"]: m for m in man["per_layer"]}
    moves = dict(zip(NEW_METRICS, ("serve_tok_per_s", "ttft_p50_s",
                                   "ttft_p50_s", "ttft_p50_s")))
    bad += [f"{n} moves {by_name[n]['moves']}" for n in NEW_METRICS
            if n in by_name and by_name[n]["moves"] != moves[n]]
    bad += [f"{n} is read from {by_name[n]['source']}" for n in NEW_METRICS
            if n in by_name and by_name[n]["source"] != "device_trace"]
    entry = [c for c in man["configs"] if c["name"] == "xing4.0"]
    if [c["reduced"] for c in entry] != [["num_hidden_layers",
                                          "num_nextn_predict_layers"]]:
        bad.append(f"xing4.0's entry is {entry}")
    return bad


def test_the_cell_and_its_metrics_stand_as_their_pr_wrote_them():
    man = manifest.load()
    assert manifest_rule(man) == []
    assert _shape.complaints(man) == []
    # the rule sees the cell taken out of a list it shares or owns
    by_name = {m["name"]: m for m in man["per_layer"]}
    by_name["moe.pad_share"]["workloads"].remove(CELL)
    assert manifest_rule(man) == [f"moe.pad_share does not list {CELL}"]
    by_name["moe.pad_share"]["workloads"].append(CELL)
    by_name["hc.mix_hbm_roofline_share"]["workloads"].remove(CELL)
    assert manifest_rule(man) == [
        f"hc.mix_hbm_roofline_share does not list {CELL}"]
    # the new metrics stand behind the ones that were there
    names = [m["name"] for m in man["per_layer"]]
    assert names[-4:] == list(NEW_METRICS)
