"""Family ``latent_scmoe`` at the configuration file's ``tiny`` sizes on the
CPU: the cell and its entries as their PR wrote them (a rule of a manifest
of any size), the configuration's keys against the catalog row, the
program against the plain reference through a Scheduler, the controls that
must FAIL the comparison (the reference's leaves through int8, the identity
experts dropped, the experts' result joined a sublayer early, a scale left
out), the tie rule, the bytes and operations behind the roofline shares,
and the new metric's reader."""
import dataclasses
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
from perfbench.harness import manifest, program_spans  # noqa: E402
from perfbench.reference import latent_scmoe as reference  # noqa: E402

CELL = "longcat-flash.serve-closed64-p4096"
CONFIG, TRAFFIC = "longcat-flash", "serve-closed64-p4096"
NEW_METRIC = "moe.zero_pair_share"
FAMILY = manifest.load_module("families", "latent_scmoe")
# what the cell reports end to end, and the per-layer lists ISSUE 50 put it
# into: every one the latent family's cell is in, the latent prefill's
# share of the matrix unit, and its own metric, last
REPORTS = ("serve_tok_per_s", "ttft_p50_s", "setup_s")
SHARED = (
    "engine.prefill_call_s_p50", "scheduler.lanes_per_decode_call",
    "device.serve_idle_share", "engine.decode_stage_in_s_p50",
    "engine.decode_dispatch_s_p50", "engine.prefill_pad_share",
    "scheduler.step_host_s_p50", "scheduler.queue_wait_s_p50",
    "scheduler.decode_bucket_fill", "device.serve_idle_named_share",
    "scheduler.tok_per_s_slice_p50", "moe.tokens_per_held_expert",
    "moe.pad_share", "engine.decode_hbm_roofline_share",
    "engine.decode_call_s_p50.tok_per_s", "token_gap_p80_s",
    "engine.decode_attend_device_s_per_call",
    "engine.decode_cache_write_device_s_per_call",
    "engine.decode_ffn_device_s_per_call",
    "engine.decode_readout_device_s_per_call",
    "engine.prefill_attend_device_s_per_ktok",
    "engine.prefill_ffn_device_s_per_ktok",
    "moe.prefill_experts_device_s_per_ktok", "device.serve_scoped_share",
    "engine.prefill_mxu_roofline_share.latent")
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]


def sized(tiny=True):
    resolved = manifest.resolve_cell(manifest.load(), CELL)
    return (manifest.sized(resolved["config"], tiny),
            manifest.sized(resolved["traffic"], tiny))


def build(seed=7):
    cfg, traffic = sized()
    return FAMILY.build_serve(cfg, traffic, jax.devices("cpu")[:1], seed)


def check(prog):
    asked = sized()[1]["check"]
    rng = np.random.default_rng(3)
    return prog.reference_check(
        [rng.integers(0, prog.vocab, n).tolist()
         for n in asked["prompt_tokens"]], asked["output_tokens"])


def manifest_rule(man, root=ROOT):
    """The cell, its configuration and the metric PR 50 wrote, however much
    has been appended since: the cell with its traffic on one chip, IN the
    three end-to-end lists and the twenty-five per-layer lists ISSUE 50
    names, and in its own metric's, which moves the decode rate and is
    read from the program's counters."""
    want = dict(config=CONFIG, traffic=TRAFFIC, chips=1)
    bad = _shape.written_for(man, CELL, metrics=REPORTS, **want)
    bad += _shape.written_for(man, CELL, metrics=SHARED + (NEW_METRIC,),
                              **want)
    own = [m for m in man["per_layer"] if m["name"] == NEW_METRIC]
    if [(m["unit"], m["better"], m["source"], m["layer"], m["moves"])
            for m in own] != [("share", "higher", "program_counter",
                               "serving engine", "serve_tok_per_s")]:
        bad.append(f"{NEW_METRIC} is {own}")
    entry = [c for c in man["configs"] if c["name"] == CONFIG]
    if [(c["reduced"], c["file"]) for c in entry] != [
            (REDUCED, "perfbench/configs/longcat-flash.json")]:
        bad.append(f"{CONFIG}'s entry is {entry}")
    return bad


def test_the_cell_and_its_metric_stand_as_their_pr_wrote_them():
    man = manifest.load()
    assert manifest_rule(man) == []
    assert _shape.complaints(man) == []
    by_name = {m["name"]: m for m in man["end_to_end"] + man["per_layer"]}
    # the rule sees the cell taken out of a list it shares or owns
    for name in ("ttft_p50_s", "moe.pad_share",
                 "engine.prefill_mxu_roofline_share.latent", NEW_METRIC):
        by_name[name]["workloads"].remove(CELL)
        assert manifest_rule(man) == [f"{name} does not list {CELL}"]
        by_name[name]["workloads"].append(CELL)
    # where the latent programs' mark does not carry what a reader reads,
    # or the reader names another family's program, the cell is not listed
    for name in ("attn.decode_positions_read_per_lane",
                 "engine.decode_hbm_roofline_share.kv",
                 "engine.prefill_mxu_roofline_share",
                 "hc.mix_hbm_roofline_share", "token_gap_p90_s"):
        assert CELL not in _shape.cells_of(man, name)
    # a further metric behind its own, a further cell behind this one,
    # break nothing
    man["per_layer"].append(dict(by_name[NEW_METRIC], name="a.further"))
    man["workloads"].append(dict(man["workloads"][-1], name="a.further.cell",
                                 traffic="serve-closed32"))
    assert manifest_rule(man) == []
    assert len(next(w for w in man["workloads"]
                    if w["name"] == CELL)["why"]) <= 200


def test_the_file_holds_the_catalog_rows_keys_and_the_cut_the_issue_states():
    from bluefog_tpu.models import decoder
    full, traffic = sized(False)
    entry, = [c for c in manifest.load()["configs"] if c["name"] == CONFIG]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["source_url"] == entry["source"]]
        differ = sorted(k for k, v in row["config"].items()
                        if full.get(k, "absent") != v)
        assert differ == sorted(REDUCED), differ
    assert full["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                 "vocab_size": 131072}
    dep = full["deployment"]
    assert (dep["chips_per_layer"], dep["router_outputs"],
            dep["held_experts"], dep["vocab_slice"]) == (
        32, 768, [0, 16], [0, 16384])
    whole = manifest.load_json(os.path.join(ROOT, entry["file"]))
    for key in ("reduced_why", "departures", "assumed", "tiny"):
        assert whole[key]
    assert set(full["reduced_why"]) == set(REDUCED)
    # the sizes select the double layer, the softmax router, the identity
    # outputs, the absent shared expert and the scales
    big = FAMILY.latent_config(full)
    big.validate(None)
    assert (big.shortcut, big.router, big.zero_experts, big.shared_expert,
            big.dense_layers, big.route_bias) == (
        True, "softmax", 256, False, 0, True)
    assert (big.d_model, big.heads, big.layers, big.attn_layers, big.q_rank,
            big.kv_rank, big.nope_dim, big.rope_dim, big.v_dim,
            big.dense_ffn, big.expert_ffn) == (
        6144, 64, 4, 8, 1536, 512, 128, 64, 128, 12288, 2048)
    assert (big.num_experts, big.held_experts, big.held_start, big.top_k,
            big.route_scale, big.vocab, big.eps, big.rope_base) == (
        768, 16, 0, 12, 6.0, 16384, 1e-5, 1e7)
    assert big.q_scale == 2.0 and big.kv_scale == pytest.approx(12 ** 0.5)
    assert big.softmax_scale == pytest.approx(192 ** -0.5)   # no YaRN
    assert decoder.latent_param_count(big) == dep["held_parameters"] \
        == 5172749312
    # the tiny sizes keep every mechanism
    tiny = FAMILY.latent_config(sized()[0])
    assert (tiny.layers, tiny.d_model, tiny.heads, tiny.num_experts,
            tiny.zero_experts, tiny.held_experts, tiny.top_k) == (
        2, 64, 4, 12, 4, 4, 3)
    assert tiny.shortcut and tiny.q_scale != 1 != tiny.kv_scale
    # the cell as the issue states it
    eng = traffic["engine"]
    assert (traffic["loop"], traffic["clients"], traffic["cycle"],
            eng["slots"], eng["max_len"]) == ("closed", 64, 64, 64, 4608)
    assert eng["batch_buckets"] == [64] and eng["dtype"] == "bfloat16"
    assert eng["prefill_buckets"] == [512, 1024, 2048, 4096]
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 256,
                                        "hi": 4096}
    assert traffic["output_tokens"] == {"dist": "uniform", "lo": 128,
                                        "hi": 512}
    assert traffic["ramp_output_tokens"] == {"dist": "uniform", "lo": 8,
                                             "hi": 128}
    assert (traffic["slice_seconds"], traffic["traced_seconds"]) == (2.0, 3.0)
    assert traffic["check"]["prompt_tokens"] == [600, 3800]
    assert traffic["check"]["output_tokens"] == 64
    assert isinstance(traffic["length_order"]["seed"], int)
    assert len(traffic["length_order"]["why"]) > 200
    assert len(traffic["check"]["why"]) > 200


def test_prefill_then_decode_through_the_scheduler_agree_with_the_reference():
    ref = check(build())
    assert ref["ok"], ref
    c = ref["compared"]
    # float32 on the CPU: the program IS the reference's function
    for name in ("prefill_logit_err_share", "decode_logit_err_share",
                 "decode_logit_gap_share"):
        assert c[name][0] < 1e-5, (name, c[name])
    assert c["route_far_share"] == [0, 0] and c["requests_off_length"] == [0, 0]
    asked = sized()[1]["check"]
    assert [r["prompt_tokens"] for r in ref["requests"]] \
        == asked["prompt_tokens"]
    # every decoded position of both lengths is compared, by its logits
    assert all(r["decode_positions"] == asked["output_tokens"] - 1
               for r in ref["requests"])
    # both kinds of output are met: identity experts and held ones
    assert all(0.1 < r["zero_pair_share"] < 0.7 for r in ref["requests"])


def _through_int8(tree):
    def q(a):
        a = a.astype(jnp.float32)
        if a.ndim < 2:
            return a
        step = jnp.max(jnp.abs(a)) / 127.0
        return jnp.round(a / step) * step
    return jax.tree.map(q, tree)


def _join_after_the_first_half(cfg, lp, lp2, x, positions, attend,
                               attend2_of, moe):
    from bluefog_tpu.models import decoder

    def first_ffn(lp, h):
        m, faux = moe(lp, h)
        return decoder.dense_gated_ffn(lp, h)[0] + m, faux
    x, aux, faux = decoder.latent_block(cfg, lp, x, positions, attend,
                                        first_ffn)
    x, aux2, _ = decoder.latent_block(cfg, lp2, x, positions,
                                      attend2_of(aux),
                                      decoder.dense_gated_ffn)
    return x, (aux, aux2), faux


def alterations():
    """name -> (module, attribute, replacement): what a control changes,
    on the reference's side for int8 (the nearest precision below the
    served one), on the program's side for a mechanism."""
    from bluefog_tpu.models import decoder
    from bluefog_tpu.moe import layers
    config = FAMILY.latent_config
    return {
        "int8_weights": (reference, "f32", _through_int8),
        "identity_experts_dropped": (
            layers, "zero_expert_part",
            lambda h, idx, weight, first: jnp.zeros_like(h)),
        "experts_joined_a_sublayer_early": (
            decoder, "latent_double_block", _join_after_the_first_half),
        "kv_scale_left_out": (
            FAMILY, "latent_config",
            lambda cfg: dataclasses.replace(config(cfg), kv_scale=1.0)),
    }


@pytest.mark.parametrize("control", [
    "int8_weights", "identity_experts_dropped",
    "experts_joined_a_sublayer_early", "kv_scale_left_out"])
def test_a_lower_precision_or_a_changed_mechanism_fails_the_comparison(
        control, monkeypatch):
    monkeypatch.setattr(*alterations()[control])
    reference._steps.cache_clear()      # its steps look ``f32`` up when traced
    try:
        ref = check(build())
    finally:
        reference._steps.cache_clear()
    assert not ref["ok"], ref["compared"]
    c = ref["compared"]
    assert max(c["prefill_logit_err_share"][0],
               c["decode_logit_err_share"][0]) > 3e-3, c


def test_the_tie_rule_passes_a_planted_tie_and_fails_a_far_swap():
    """Outputs by which a selection differs from the reference's own have
    to lie within the tie distance of the cut."""
    p = np.array([[[0.30, 0.25, 0.2001, 0.2, 0.0499]]])     # [1, 1, 5]
    by = jnp.asarray(p)                         # no bias: selection by p
    picked = jnp.asarray([[[0, 1, 2]]])         # the cut is output 2
    dist = np.asarray(reference.tie_distance(
        jnp.asarray(p), by, picked, jnp.asarray([1.0])))
    assert dist[0, 0, 2] == 0 and dist[0, 0, 3] == pytest.approx(
        1e-4 / (0.2 * 0.8 + 0.2001 * 0.7999), rel=1e-3)
    report = lambda chosen: FAMILY.selection_report(
        dist, picked, np.array([[chosen]]), 1e-3)
    assert report([1, 0, 2]) == (0, 0, 0, 1, 0.0)       # a set, not an order
    tie = report([0, 1, 3])         # outputs 2 and 3 swapped: both at the cut
    assert tie[:4] == (1, 2, 0, 1) and tie[4] == pytest.approx(dist[0, 0, 3])
    far = report([0, 1, 4])         # output 4 lies far under the cut
    assert far[:4] == (1, 2, 1, 1) and far[4] > 0.5
    assert report([-1, -1, -1])[1:3] == (3, 2)   # outputs 0 and 1 lie far off


def test_the_floors_count_what_the_chip_holds():
    """My arithmetic at the published sizes, by hand: a decode call reads
    both halves' attentions and dense FFNs, the routers, the head; a hit
    expert 75.5 MB; a live position 9,216 bytes; an identity output
    nothing."""
    full, _ = sized(False)
    mla = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
           + 64 * 128 * 6144)
    assert mla == 90570752                                  # 90.57 M
    norms = 2 * 6144 + 1536 + 512
    dense = 3 * 6144 * 12288                                # 226.49 M
    weights = (4 * 2 * (mla + norms + dense) + 6144 * 16384 + 6144) * 2 \
        + 4 * (6144 + 1) * 768 * 4
    assert FAMILY.weight_bytes(full) == weights
    assert FAMILY.expert_bytes(full) == 3 * 6144 * 2048 * 2
    assert FAMILY.position_bytes(full) == 9216
    assert FAMILY.decode_floor_bytes(full, 2, 5, 1000) == \
        2 * weights + 5 * FAMILY.expert_bytes(full) + 1000 * 9216
    assert FAMILY.held_experts(full) == 16 and FAMILY.expert_layers(full) == 4
    # a prompt: a quarter of a held expert a token and layer, both
    # sublayers' causal attention, the head once
    per_token = 4 * (2 * (mla + dense) + 6144 * 768
                     + 0.25 * 3 * 6144 * 2048)
    T = 1000
    assert FAMILY.prefill_flops(full, T) == pytest.approx(
        2 * per_token * T + 2 * 64 * 320 * 8 * T * (T + 1) // 2
        + 2 * 6144 * 16384)
    assert 5.0e9 < 2 * per_token < 5.3e9                    # ISSUE 50's 5.1


def test_every_chip_limit_lies_between_its_two_readings():
    """A bf16 limit has room over the most the sound program read on the
    chip and under the least its control read."""
    limits = dict(FAMILY.SERVE_LIMITS["bfloat16"],
                  **FAMILY.ROUTE_TIE["bfloat16"])
    del limits["delta"]             # the distance the far share counts past
    assert set(FAMILY.CHIP_READINGS) == set(limits)
    for name, (sound, control) in FAMILY.CHIP_READINGS.items():
        assert 1.25 * sound <= limits[name] <= control / 1.25, name


class _Analysis:
    def __init__(self, marks):
        self.marks = marks

    def attr_sum(self, name, key):
        vals = [m[key] for m in self.marks if key in m]
        return sum(vals) if vals and name == "bf:engine.held_work" else None


def test_the_new_metric_reads_the_marks_and_nothing_where_they_lack_it():
    reader = manifest.load_module("metrics", NEW_METRIC)
    cfg, _ = sized(False)
    run = {"config": cfg, "program_spans": _Analysis(
        [{"pairs": 5, "zero_pairs": 1000, "token_layers": 256},
         {"pairs": 7, "zero_pairs": 1048, "token_layers": 256}])}
    assert reader.read(run) == pytest.approx(2048 / (12 * 512))
    # a program whose marks carry no identity pairs (every other family's,
    # and the parent of the PR that wrote the metric): nothing to read
    run["program_spans"] = _Analysis([{"pairs": 5}])
    assert reader.read(run) is None
    assert program_spans.Analysis({"planes": []}).attr_sum(
        "bf:engine.held_work", "zero_pairs") is None
    assert _shape.family_hooks(NEW_METRIC) == []
