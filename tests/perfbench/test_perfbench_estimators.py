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
    assert sp["iqr_over_median"] == pytest.approx(2 / 12)
    assert sp["range_over_median"] == pytest.approx(4 / 12)


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
