"""Family ``latent_moe`` at the configuration file's ``tiny`` sizes on the
CPU: the program against the plain reference through a Scheduler, the share
test that ties one chip's cut to the uncut layer, the controls that must
FAIL the comparison (int8 weights, a dropped norm scale), the routing
margin, the bytes of the decode floor, and the new metrics' readers."""
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
from perfbench.reference import latent_moe as reference  # noqa: E402

CELL = "a.x-k1.serve-closed128-p2048"
NEW_METRICS = ("moe.tokens_per_held_expert", "moe.pad_share",
               "engine.decode_hbm_roofline_share",
               "engine.decode_call_s_p50.tok_per_s", "token_gap_p80_s")
FAMILY = manifest.load_module("families", "latent_moe")


def sized(tiny=True):
    cell = manifest.resolve_cell(manifest.load(), CELL)
    return (manifest.sized(cell["config"], tiny),
            manifest.sized(cell["traffic"], tiny))


@pytest.fixture(scope="module")
def prog():
    cfg, traffic = sized()
    return FAMILY.build_serve(cfg, traffic, jax.devices("cpu")[:1], 7)


@pytest.fixture
def prog_again():
    """A check gives its cache back: every further check takes a program
    of its own."""
    cfg, traffic = sized()
    return FAMILY.build_serve(cfg, traffic, jax.devices("cpu")[:1], 7)


def prompts(prog, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, prog.vocab, n).tolist() for n in (5, 12)]


def check(cfg, traffic, seed, alter=None):
    p = FAMILY.build_serve(cfg, traffic, jax.devices("cpu")[:1], seed)
    if alter is not None:
        p.engine.update_params(alter(p.params))
    return p.reference_check(prompts(p), traffic["check"]["output_tokens"])


def test_the_tiny_sizes_keep_every_mechanism():
    cfg, _ = sized()
    lm = FAMILY.latent_config(cfg)
    assert lm.layers >= 3 and lm.num_experts >= 16 and lm.n_group >= 2
    assert lm.held_experts < lm.num_experts
    assert lm.q_rank < lm.d_model and lm.kv_rank < lm.d_model
    assert lm.rope_dim > 0 and lm.nope_dim > 0 and lm.rope_factor > 1
    full, _ = sized(False)
    big = FAMILY.latent_config(full)
    assert (big.d_model, big.heads, big.q_rank, big.kv_rank) == (
        7168, 64, 1536, 512)
    assert (big.nope_dim, big.rope_dim, big.v_dim) == (128, 64, 128)
    assert (big.dense_ffn, big.expert_ffn, big.top_k) == (18432, 2048, 8)
    assert (big.num_experts, big.held_experts, big.held_start) == (192, 12, 0)
    assert (big.n_group, big.topk_group, big.route_scale) == (8, 4, 2.5)


def test_prefill_then_decode_through_the_scheduler_agree_with_the_reference(
        prog):
    _, traffic = sized()
    asked = traffic["check"]
    ref = prog.reference_check(prompts(prog), asked["output_tokens"])
    assert ref["ok"], ref
    c = ref["compared"]
    # float32 on the CPU: the program IS the reference's function
    assert c["prefill_logit_err_share"][0] < 1e-5
    assert c["decode_logit_gap_share"][0] < 1e-5
    assert c["route_flip_share"][0] == 0
    assert c["requests_off_length"][0] == 0
    # the coverage the check makes for itself: every asked length has a
    # prefill compared, and the decode positions reach their floor
    assert c["prefill_lengths_not_compared"] == [0, 0]
    assert c["decode_positions_short_of_floor"] == [0, 0]
    assert [r["prompt_tokens"] for r in ref["requests"]] == [5, 12]
    for row in ref["requests"]:
        assert row["candidates"] == asked["candidates"]
        assert 1 <= row["prefills_compared"] <= row["candidates"]
        assert row["held_selection_flips_at_decided"] == 0
    assert sum(r["decode_positions_decided"] for r in ref["requests"]) \
        >= asked["decode_positions_floor"]


@pytest.mark.parametrize("starve", ["prefill", "decode"])
def test_a_check_that_compared_too_little_is_not_correct(
        prog_again, monkeypatch, starve):
    """Nothing wrong with the program: `correct` is false because the
    reference decides no prompt's last position (a margin nothing meets),
    or fewer decode positions than the floor."""
    _, traffic = sized()
    if starve == "prefill":
        monkeypatch.setattr(FAMILY, "ROUTE_MARGIN", 1.0)
    else:
        monkeypatch.setattr(prog_again, "decode_floor", 1000)
    ref = prog_again.reference_check(prompts(prog_again),
                                     traffic["check"]["output_tokens"])
    c = ref["compared"]
    assert not ref["ok"]
    assert c["prefill_logit_err_share"][0] <= c["prefill_logit_err_share"][1]
    assert c["decode_logit_gap_share"][0] <= c["decode_logit_gap_share"][1]
    if starve == "prefill":
        assert c["prefill_lengths_not_compared"] == [2, 0]
        assert all(r["prefills_compared"] == 0 for r in ref["requests"])
    else:
        assert c["prefill_lengths_not_compared"] == [0, 0]
        assert c["decode_positions_short_of_floor"][0] > 900


def fake_int8(tree):
    """Every weight matrix through symmetric per-tensor int8 and back."""
    def q(a):
        if a.ndim < 3:
            return a                       # [n, D] norm scales stay
        scale = jnp.max(jnp.abs(a)) / 127.0
        return (jnp.round(a / scale) * scale).astype(a.dtype)
    return jax.tree.map(q, tree)


def test_int8_weights_fail_the_tolerance():
    cfg, traffic = sized()
    ref = check(cfg, traffic, 7, fake_int8)
    assert not ref["ok"]
    c = ref["compared"]
    # by the logits, with every length and enough decode positions compared
    assert c["prefill_logit_err_share"][0] > c["prefill_logit_err_share"][1] \
        or c["decode_logit_gap_share"][0] > c["decode_logit_gap_share"][1]
    assert c["prefill_lengths_not_compared"] == [0, 0]
    assert c["decode_positions_short_of_floor"] == [0, 0]


def test_a_dropped_norm_scale_fails_the_comparison():
    cfg, traffic = sized()

    def drop(params):
        out = jax.tree.map(lambda a: a, params)
        out["blocks"]["gkv"] = jnp.ones_like(out["blocks"]["gkv"])
        return out
    ref = check(cfg, traffic, 7, drop)
    assert not ref["ok"]


def layer_weights(cfg, seed, experts):
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ks = jax.random.split(jax.random.key(seed), 8)
    n = lambda k, s: 0.2 * jax.random.normal(k, s, jnp.float32)
    return {"wr": n(ks[0], (D, experts)), "wsg": n(ks[1], (D, F)),
            "wsu": n(ks[2], (D, F)), "wsd": n(ks[3], (F, D)),
            "weg": n(ks[4], (experts, D, F)), "weu": n(ks[5], (experts, D, F)),
            "wed": n(ks[6], (experts, F, D))}


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The 4 shares' routed parts plus the shared expert counted once are
    the uncut reference's layer, for the reference's own cut and for the
    program's (moe.layers.held_moe_ffn)."""
    from bluefog_tpu.moe.layers import held_moe_ffn
    import dataclasses
    cfg, _ = sized()
    E, held = cfg["deployment"]["router_outputs"], cfg["n_routed_experts"]
    w = layer_weights(cfg, 11, E)
    h = jax.random.normal(jax.random.key(12), (24, cfg["hidden_size"]))
    whole, sel = reference.moe_ffn(cfg, w, h)          # all experts held
    assert int(sel.sum()) == 24 * cfg["num_experts_per_tok"]
    lm = FAMILY.latent_config(cfg)
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
    # one share alone is NOT the layer
    one = reference.moe_ffn(cfg, dict(w, **{
        k: w[k][:held] for k in ("weg", "weu", "wed")}), h)[0]
    assert float(jnp.max(jnp.abs(one - whole))) > 1e-2


def test_a_near_tie_on_a_held_expert_is_not_decided():
    cfg, _ = sized()
    E = cfg["deployment"]["router_outputs"]
    k = cfg["num_experts_per_tok"]
    D = E                                  # h picks out router logits as is
    wr = jnp.eye(D, E)
    per = E // cfg["n_group"]
    base = np.full((3, E), -3.0, np.float32)
    base[:, :per] = np.linspace(2.0, -1.0, per)      # group 0 wins clearly
    tie = base.copy()
    tie[1, k - 1] = tie[1, k] + 1e-4       # the k-th and (k+1)-th all but tie
    far = base.copy()
    far[2, :per] = -6.0                    # group 0 clearly dropped
    far[2, per:2 * per] = np.linspace(2.0, -1.0, per)
    h = jnp.asarray(np.stack([base[0], tie[1], far[2]]))
    margin = np.asarray(reference.held_margin(cfg, h, wr, 0, per // 2))
    assert margin[0] >= 0.03 and margin[2] >= 0.03
    assert margin[1] < 0.03
    # experts no near-tie touches: held elsewhere, the same rows are decided
    other = np.asarray(reference.held_margin(cfg, h, wr, per, per // 2))
    assert other[1] >= 0.03


def test_the_decode_floor_counts_weights_hit_experts_and_live_positions():
    from bluefog_tpu.models import decoder
    cfg, _ = sized(False)
    lm = FAMILY.latent_config(cfg)
    shapes = decoder.latent_param_shapes(lm)
    count = lambda names, group: sum(
        int(np.prod(shapes[group][n])) for n in names)
    routed = count(("weg", "weu", "wed"), "blocks")
    router = count(("wr",), "blocks")
    embed = count(("embed",), "shared")
    want = 2 * (decoder.latent_param_count(lm) - routed - router - embed) \
        + 4 * router
    assert FAMILY.weight_bytes(cfg) == want
    assert FAMILY.expert_bytes(cfg) == 2 * routed // (5 * 12)
    assert decoder.latent_param_count(lm) == 4_166_294_528
    floor = FAMILY.decode_floor_bytes(cfg, calls=2, experts_hit=100,
                                      live_positions=1000)
    assert floor == 2 * want + 100 * FAMILY.expert_bytes(cfg) \
        + 1000 * 6 * 576 * 2
    # every expert hit in every call and every position of the cell's
    # rows: the most a call of the floor can be, under what the chip holds
    most = FAMILY.decode_floor_bytes(cfg, 1, 60, 128 * 2560)
    assert 10.2e9 < most < 10.4e9


def marks(rows):
    """A traced tail's bf:engine.held_work marks as a hand-made trace."""
    events, t = [["pb:window", 0, 10_000_000, {}]], 1000
    for attrs in rows:
        events.append(["bf:engine.decode_call", t, 5000, {"S": 128}])
        events.append(["bf:engine.held_work", t + 4000, 10, attrs])
        t += 10_000
    return program_spans.Analysis({"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": events}]}]})


def test_the_expert_layer_metrics_read_the_held_work_marks():
    cfg, _ = sized(False)
    run = {"config": cfg, "workload": CELL,
           "device": {"platform": "cpu", "kind": "cpu"},
           "program_spans": marks([
               {"pairs": 300, "rows": 5120, "experts_hit": 58,
                "positions": 90_000},
               {"pairs": 340, "rows": 5120, "experts_hit": 60,
                "positions": 91_000}])}
    read = lambda name: manifest.load_module("metrics", name).read(run)
    assert read("moe.pad_share") == pytest.approx(1 - 640 / 10240)
    assert read("moe.tokens_per_held_expert") == pytest.approx(
        640 / (12 * 5 * 2))
    # a roofline share is a device number: none without the chip
    assert read("engine.decode_hbm_roofline_share") is None
    # the parent's program writes no such mark: nothing to read, no error
    run["program_spans"] = marks([])
    for name in ("moe.pad_share", "moe.tokens_per_held_expert",
                 "engine.decode_hbm_roofline_share"):
        assert read(name) is None


@pytest.mark.parametrize("cell, pairs, lanes, on_the_chip", [
    (CELL, 316, 128, 5.26), ("k-exaone.serve-closed48-p8192", 168, 48, 3.0)])
def test_tokens_per_held_expert_takes_the_familys_word(cell, pairs, lanes,
                                                       on_the_chip):
    """Held experts and expert layers come from the family adapter
    (``held_experts``, ``expert_layers``), not from one family's key names:
    both held-experts cells read what they read when the reader divided
    by ``n_routed_experts * (num_hidden_layers - 1)`` (12 x 5 and 8 x 7;
    ledger, PR 39: 5.26 and 3.00), and a family without the hook reads
    nothing."""
    resolved = manifest.resolve_cell(manifest.load(), cell)
    cfg = manifest.sized(resolved["config"], False)
    assert "n_routed_experts" not in cfg or cfg["family"] == "latent_moe"
    family = manifest.load_module("families", cfg["family"])
    held, layers = family.held_experts(cfg), family.expert_layers(cfg)
    assert (held, layers) == {CELL: (12, 5)}.get(cell, (8, 7))
    mark = {"pairs": pairs, "rows": lanes * held * layers, "experts_hit": 1,
            "positions": 1}
    run = {"config": cfg, "workload": cell,
           "device": {"platform": "cpu", "kind": "cpu"},
           "program_spans": marks([mark, mark, mark])}
    read = manifest.load_module("metrics", "moe.tokens_per_held_expert").read
    assert read(run) == pytest.approx(pairs / (held * layers))
    # the pairs chosen are what the chip's traced tails read per call
    assert read(run) == pytest.approx(on_the_chip, rel=0.01)
    run["config"] = dict(cfg, family="composed_lm")
    assert read(run) is None


@pytest.mark.parametrize("name, want", [
    ("engine.decode_call_s_p50.tok_per_s", 0.035),
    ("token_gap_p80_s", 0.136)])
def test_the_decode_call_and_the_gap_inside_a_cluster_have_readers(name, want):
    """What the cell reports in place of the gap's 90th percentile and the
    metrics that move it: the decode call's span, and the gap inside the
    one-large-prefill cluster."""
    from perfbench.harness import spans
    rec = spans.Spans()
    rec.records = [("decode_call", t, t + d) for t, d in
                   ((1.0, 0.034), (2.0, 0.035), (3.0, 0.036), (9.5, 0.5))]
    gaps = [0.036] * 48 + [0.07] * 28 + [0.136] * 13 + [0.19] * 11
    run = {"facts": {"window": (0.5, 9.0)}, "spans": rec,
           "readings": {"token_gap_s": gaps}}
    read = manifest.load_module("metrics", name).read
    assert read(run) == pytest.approx(want)
    # nothing to read: no window, no gaps
    assert read({"facts": {}, "spans": rec, "readings": {}}) is None


# what the cell reports besides the metrics this file's PR wrote for it
REPORTS = ("serve_tok_per_s", "ttft_p50_s", "setup_s")


def manifest_rule(man, root=ROOT):
    """The cell and the five metrics PR 33 wrote, however much has been
    appended since: the cell IN their lists, each moving the rate (the
    cell reports no gap percentile end to end, so ``_shape.moves_inside``
    keeps it out of every list that moves one), and the configuration
    with the three keys it cut."""
    bad = _shape.written_for(man, CELL, config="a.x-k1", chips=1,
                             traffic="serve-closed128-p2048",
                             metrics=REPORTS + NEW_METRICS)
    by_name = {m["name"]: m for m in man["per_layer"]}
    bad += [f"{n} moves {by_name[n]['moves']}" for n in NEW_METRICS
            if n in by_name and by_name[n]["moves"] != "serve_tok_per_s"]
    entry = [c for c in man["configs"] if c["name"] == "a.x-k1"]
    if [c["reduced"] for c in entry] != [["num_hidden_layers",
                                          "n_routed_experts", "vocab_size"]]:
        bad.append(f"a.x-k1's entry is {entry}")
    return bad


def test_the_cell_and_its_metrics_stand_as_their_pr_wrote_them():
    man = manifest.load()
    assert manifest_rule(man) == []
    # the rule sees a cell taken out of a list, and not a cell put in
    by_name = {m["name"]: m for m in man["per_layer"]}
    by_name["moe.pad_share"]["workloads"].append("a.further-cell")
    assert manifest_rule(man) == []
    by_name["moe.pad_share"]["workloads"].remove(CELL)
    assert manifest_rule(man) == [f"moe.pad_share does not list {CELL}"]


def test_the_cell_reports_no_gap_percentile_and_nothing_that_moves_one():
    """On this traffic the gap's 90th percentile sits on the edge of the
    one-large-prefill cluster in every order (PERF.md): the cell is in no
    list of ``token_gap_p90_s`` or of a metric that moves it, which is
    ``_shape.moves_inside`` once the first holds."""
    man = manifest.load()
    assert CELL not in _shape.cells_of(man, "token_gap_p90_s")
    assert _shape.moves_inside(man) == []
    moving = [m["name"] for m in man["per_layer"]
              if m["moves"] == "token_gap_p90_s"]
    assert len(moving) >= 3
    assert not any(CELL in _shape.cells_of(man, n) for n in moving)
