"""The benchmark's arithmetic on hand-made series (perfbench/harness)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import estimators, peaks, traffic, wire  # noqa: E402


def test_one_stalled_block_moves_the_whole_window_rate_not_the_median():
    steady = [(1536, 0.75)] * 39
    stalled = steady[:20] + [(1536, 3.0)] + steady[20:]       # one 4x block
    m0 = estimators.median(estimators.block_rates(steady + [(1536, 0.75)]))
    m1 = estimators.median(estimators.block_rates(stalled))
    assert m1 == m0 == 2048.0
    w0 = estimators.whole_window_rate(steady + [(1536, 0.75)])
    w1 = estimators.whole_window_rate(stalled)
    assert w0 == 2048.0 and w1 < 0.94 * w0


def test_slow_throughout_moves_both():
    slow = [(1536, 0.75 * 1.014)] * 40
    assert estimators.median(estimators.block_rates(slow)) == pytest.approx(
        2048 / 1.014)
    assert estimators.whole_window_rate(slow) == pytest.approx(2048 / 1.014)


@pytest.mark.parametrize("q", [0, 5, 25, 50, 75, 95, 100])
def test_percentile_is_numpys(q):
    xs = list(np.random.default_rng(q).gamma(2.0, size=157))
    assert estimators.percentile(xs, q) == pytest.approx(
        float(np.percentile(xs, q)), rel=1e-12)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        estimators.percentile([], 50)


def test_summary_and_spread():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0]
    s = estimators.summary(xs, whole_window=11.5)
    assert (s["n"], s["min"], s["q1"], s["median"], s["q3"], s["max"]) == (
        5, 10.0, 11.0, 12.0, 13.0, 14.0)
    assert s["whole_window"] == 11.5
    sp = estimators.spread(xs)
    assert sp["iqr"] == pytest.approx(3 / 12)      # quartiles 10.5 and 13.5
    assert sp["range"] == pytest.approx(4 / 12)


def test_slice_rates_do_not_jump_by_a_delivery():
    # 32 tokens every 0.14 s: 228.57 tokens/s whatever the slice boundaries
    times = [0.14 * i for i in range(1, 80)]
    counts = [32] * len(times)
    rates = estimators.slice_rates(times, counts, t_open=1.0, t_close=10.0)
    assert len(rates) == 9
    assert max(rates) - min(rates) < 1e-6
    assert rates[0] == pytest.approx(32 / 0.14)


def test_slice_rates_see_a_stall_in_one_slice_only():
    times = [0.1 * i for i in range(1, 31)] + [4.0 + 0.1 * i
                                               for i in range(1, 31)]
    rates = estimators.slice_rates(times, [10] * len(times), 0.0, 7.0)
    assert estimators.median(rates) == pytest.approx(100.0)
    assert min(rates) < 20


def test_traffic_is_the_same_work_whatever_the_seed():
    spec = {"clients": 8, "cycle": 16,
            "prompt_tokens": {"dist": "loguniform", "lo": 32, "hi": 512},
            "output_tokens": {"dist": "uniform", "lo": 32, "hi": 128},
            "ramp_output_tokens": {"dist": "uniform", "lo": 4, "hi": 48}}
    cycles = []
    for seed in (1, 2):
        gen = traffic.Requests(spec, vocab=1000, seed=seed)
        reqs = [gen.next() for _ in range(16)]
        assert all(32 <= len(p) <= 512 and 32 <= o <= 128 for p, o in reqs)
        assert all(0 <= t < 1000 for p, _ in reqs for t in p)
        cycles.append((sorted(len(p) for p, _ in reqs),
                       sorted(o for _, o in reqs), [len(p) for p, _ in reqs]))
    ramp = [gen.next(ramp=True) for _ in range(8)]
    assert sorted(o for _, o in ramp) == [6, 12, 18, 23, 29, 34, 40, 46]
    assert cycles[0][:2] == cycles[1][:2]          # same multiset of lengths
    assert cycles[0][2] != cycles[1][2]            # another order
    assert min(cycles[0][0]) < 40 and max(cycles[0][0]) > 400


def test_a_fixed_length_order_repeats_whatever_the_seed():
    spec = {"cycle": 8, "length_order": {"seed": 9},
            "prompt_tokens": {"dist": "loguniform", "lo": 4, "hi": 64},
            "output_tokens": {"dist": "uniform", "lo": 2, "hi": 9}}
    runs = []
    for seed in (1, 2):
        gen = traffic.Requests(spec, vocab=100, seed=seed)
        runs.append([gen.next() for _ in range(20)])
    assert [(len(p), o) for p, o in runs[0]] == [(len(p), o)
                                                 for p, o in runs[1]]
    assert [p for p, _ in runs[0]] != [p for p, _ in runs[1]]    # tokens differ


def test_traffic_shared_prefix_and_arrivals():
    spec = {"cycle": 4, "shared_prefix_tokens": 6,
            "prompt_tokens": {"dist": "constant", "value": 10},
            "output_tokens": {"dist": "constant", "value": 3}}
    gen = traffic.Requests(spec, vocab=50, seed=3)
    a, b = gen.next()[0], gen.next()[0]
    assert len(a) == len(b) == 10 and a[:6] == b[:6] and a[6:] != b[6:]
    arr = gen.arrivals(rate_per_s=100.0, horizon_s=5.0)
    assert 400 < len(arr) < 600 and arr == sorted(arr) and arr[-1] < 5.0
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf"}, 0.5)


def test_peak_table_by_device_kind():
    assert peaks.peak("TPU v5 lite") == 197e12
    assert peaks.peak("TPU v5p") == 459e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")


HLO = """
  %cp = (f32[1024]{0}, f32[1024]{0}, u32[], u32[]) collective-permute-start(%x), channel_id=1, source_target_pairs={{0,1},{1,0}}
  %cpd = f32[1024]{0} collective-permute-done(%cp)
  %p2 = f32[512]{0:T(512)} collective-permute(%y), channel_id=2, source_target_pairs={{0,2}}
  %ar1 = f32[64]{0} all-reduce(%z), channel_id=3, replica_groups={{0},{1},{2},{3}}, to_apply=%add
  %ar4 = f32[64]{0} all-reduce(%z), channel_id=4, replica_groups={{0,1,2,3}}, to_apply=%add
"""


def test_wire_stats_counts_what_crosses_chips():
    counts, bytes_ = wire.wire_stats(HLO)
    assert counts == {"collective-permute": 2, "all-reduce": 1}
    assert bytes_ == {"collective-permute": 4096 + 2048, "all-reduce": 256}


def clustered(shares, at=(0.0164, 0.0243, 0.0322), n=10000):
    """Token gaps in three clusters: a decode call alone, with one prefill
    call, with two (the serving cell's, PERF.md section 6, PR 32)."""
    out = []
    for share, value in zip(shares, at):
        out += [value] * int(round(n * share))
    return out


KEPT = {"p90": lambda g: estimators.percentile(g, 90),
        "p50": lambda g: estimators.percentile(g, 50)}


@pytest.mark.parametrize("name", list(KEPT))
@pytest.mark.parametrize("moved", [(0.60, 0.34, 0.06), (0.60, 0.36, 0.04)])
def test_a_point_of_share_between_the_last_clusters_leaves_the_kept_gap(
        name, moved):
    """The reason on record for ``token_gap_p90_s``: the 90th percentile
    lies inside the one-prefill cluster, five points from its end."""
    base = KEPT[name](clustered((0.60, 0.35, 0.05)))
    assert abs(KEPT[name](clustered(moved)) / base - 1) < 0.02


def test_the_same_point_moves_p95_by_a_third_and_the_slow_tenth_by_less():
    base = clustered((0.60, 0.35, 0.05))
    more = clustered((0.60, 0.34, 0.06))       # one point more two-prefill
    p95 = [estimators.percentile(g, 95) for g in (base, more)]
    assert p95[0] == pytest.approx(0.0243 + 0.05 * 0.0079)     # on the edge
    assert p95[1] == 0.0322 and p95[1] / p95[0] - 1 > 0.15
    # the mean of the slowest tenth moves too, with the tail's weight: the
    # candidate that was priced and not kept
    tail_mean = _tool("price_estimators").tail_mean
    slow = [tail_mean(g, 0.10) for g in (base, more)]
    assert slow[0] == pytest.approx(0.5 * 0.0322 + 0.5 * 0.0243)
    assert 0.02 < slow[1] / slow[0] - 1 < 0.04


def test_tail_mean():
    tail_mean = _tool("price_estimators").tail_mean
    xs = [float(i) for i in range(1, 101)]
    assert tail_mean(xs, 0.10) == pytest.approx(95.5)
    assert tail_mean(xs, 1.0) == pytest.approx(50.5)
    assert tail_mean([3.0, 1.0], 0.01) == 3.0     # at least one
    with pytest.raises(ValueError):
        tail_mean([], 0.1)


def _tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "perfbench_tools_" + name,
        os.path.join(ROOT, "perfbench", "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_sets_spread_is_the_contracts_and_the_drivers():
    import statistics
    values = [100.0, 101.0, 99.5, 100.5, 100.2, 104.0]
    sp = estimators.spread(values)
    q = statistics.quantiles(values, n=4)
    assert sp["median"] == statistics.median(values)
    assert sp["iqr"] == pytest.approx((q[2] - q[0]) / sp["median"])
    # the run farthest from the median (104) left out
    assert sp["range5"] == pytest.approx((101.0 - 99.5) / sp["median"])
    assert sp["range"] == pytest.approx(4.5 / sp["median"])
    assert sp["iqr5"] < sp["iqr"]


def test_clusters_say_where_a_percentile_sits():
    price = _tool("price_estimators")
    got = price.clusters(clustered((0.67, 0.28, 0.05)), prefill_s=0.0079)
    assert got["decode_call_s"] == pytest.approx(0.0164)
    assert got["share_decode_only"] == pytest.approx(0.67)
    assert got["share_one_prefill"] == pytest.approx(0.28)
    assert got["share_two_or_more"] == pytest.approx(0.05)
    assert got["one_prefill_ends_at_percentile"] == pytest.approx(95.0)


def test_candidates_of_a_kept_series():
    price = _tool("price_estimators")
    gaps = clustered((0.67, 0.28, 0.05), n=1000)
    doc = {"seconds": 10.0, "series": {
        "token_gap_s": {"readings": gaps},
        "ttft_s": {"readings": [0.025, 0.026, 0.027]},
        "serve_tok_per_s": {"summary": {"whole_window": 1600.0}}},
        "deliveries": [[0.02 * i, 32] for i in range(1, 501)]}
    got = price.candidates(doc, 0.0079)
    assert got["token_gap_p90_s"] == 0.0243 and got["ttft_p50_s"] == 0.026
    assert got["token_gap_p99_s"] == 0.0322
    for width in ("1", "2", "3"):
        assert got[f"tok_per_s_slice{width}s"] == pytest.approx(1600.0)


def _served(seconds, step=0.02, lanes=4, tokens=10, stall=None):
    """A kept series as runners/serve.py writes it: ``lanes`` clients, each
    request ``tokens`` steps long, a step every ``step`` s; ``stall`` =
    (at, for) holds every lane for a while."""
    t, deliveries, requests = 0.0, [], []
    open_ = [[0.0, []] for _ in range(lanes)]
    while t < seconds:
        t += step
        if stall and stall[0] <= t < stall[0] + step:
            t += stall[1]
        deliveries.append([t, lanes])
        for lane in open_:
            lane[1].append(t)
            if len(lane[1]) == tokens:
                requests.append([lane[0], False, lane[1]])
                lane[:] = [t, []]
    requests += [[due, True, times] for due, times in open_ if times]
    return {"seconds": seconds, "deliveries": deliveries,
            "requests": requests, "series": {}}


@pytest.mark.parametrize("seconds", [3.0, 4.0, 5.1])
def test_a_cut_prices_the_first_seconds_of_a_longer_window(seconds):
    price = _tool("price_estimators")
    got = price.candidates(price.cut(_served(5.1), seconds), 0.0079)
    assert got["tok_per_s_whole_window"] == pytest.approx(200.0, rel=0.01)
    assert got["token_gap_p90_s"] == pytest.approx(0.02)
    assert got["ttft_p50_s"] == pytest.approx(0.02)


def test_the_whole_windows_rate_shows_a_stall_that_the_slices_median_hides():
    """Why ``serve_tok_per_s`` is all the tokens over all the time (PR 32's
    review): a stall of 0.6 s in 6 s is a tenth of the rate gone, and the
    median of one-second slices does not move."""
    price = _tool("price_estimators")
    calm, stalled = (price.candidates(price.cut(_served(6.0, stall=st), 6.0),
                                      0.0079) for st in (None, (2.5, 0.6)))
    assert stalled["tok_per_s_slice1s"] == pytest.approx(
        calm["tok_per_s_slice1s"], rel=0.015)
    assert stalled["tok_per_s_whole_window"] < 0.92 * calm[
        "tok_per_s_whole_window"]


def _reader(name):
    from perfbench.harness import manifest
    return manifest.load_module("metrics", name).read


def test_the_serving_rate_is_every_token_over_the_whole_window():
    """One slice in five stalled: the end-to-end rate is tokens over time,
    the slices' median (the per-layer statistic) stays where it was."""
    run = {"facts": {"tokens_in_window": 9000, "window": (100.0, 110.0)},
           "readings": {"serve_tok_per_s": [1000.0, 1000.0, 500.0, 1000.0,
                                            1000.0],
                        "token_gap_s": [0.016] * 95 + [0.024] * 4 + [0.033]}}
    assert _reader("serve_tok_per_s")(run) == 900.0
    assert _reader("scheduler.tok_per_s_slice_p50")(run) == 1000.0
    assert _reader("token_gap_p90_s")(run) == 0.016
    assert 0.024 < _reader("token_gap_p99_s")(run) < 0.033
    empty = {"facts": {}, "readings": {}}
    for name in ("serve_tok_per_s", "scheduler.tok_per_s_slice_p50",
                 "token_gap_p99_s"):
        assert _reader(name)(empty) is None


def test_the_longest_steps_say_which_call_a_stall_sat_in():
    from perfbench.runners import serve
    ends = [10.0 + 0.02 * i for i in range(50)]
    ends[30:] = [t + 0.13 for t in ends[30:]]       # one stall of 130 ms
    records = [("decode_call", b - 0.016, b - 0.001)
               for b in ends[1:]]
    records.append(("prefill_call", ends[29] + 0.001, ends[29] + 0.132))
    got = serve.longest_steps(ends, records, ends[0] - 0.001, ends[-1], k=2)
    assert got[0]["seconds"] == pytest.approx(0.15)
    assert got[0]["at_s"] == pytest.approx(0.581, abs=0.002)
    assert got[0]["spans"]["prefill_call"] == pytest.approx(0.131)
    assert got[0]["spans"]["decode_call"] == pytest.approx(0.015)
    assert got[0]["spans"]["other"] == pytest.approx(0.004, abs=1e-5)
    assert got[1]["seconds"] == pytest.approx(0.02)
    assert "prefill_call" not in got[1]["spans"]
