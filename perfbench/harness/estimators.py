"""Arithmetic from readings to a run's numbers.  Pure Python, no JAX.

A training run's rate is the MEDIAN of many block readings, so one
stalled block moves the whole-window rate (total over elapsed) and leaves
the metric alone; a serving run's rate is the whole window's, with the
median of its slices beside it as a per-layer statistic.  Every run keeps
its series and prints this summary, so a run that stands apart from its set
can be told apart afterwards: one stall (a few readings low, median
untouched) or slow throughout (every reading shifted).
"""
import math
import statistics


def percentile(values, q):
    """Linear-interpolation percentile (numpy's default), q in [0, 100]."""
    if not values:
        raise ValueError("percentile of an empty series")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50.0)


def block_rates(blocks):
    """[(items, seconds), ...] -> one rate reading per block."""
    return [items / seconds for items, seconds in blocks]


def whole_window_rate(blocks):
    """Total over elapsed: the estimator that one stall moves."""
    return sum(i for i, _ in blocks) / sum(s for _, s in blocks)


def summary(readings, whole_window=None):
    """Count, minimum, quartiles, maximum of a series (and the whole-window
    rate beside them, where the series is of rates)."""
    out = {"n": len(readings), "min": min(readings),
           "q1": percentile(readings, 25), "median": median(readings),
           "q3": percentile(readings, 75), "max": max(readings)}
    if whole_window is not None:
        out["whole_window"] = whole_window
    return out


def spread(values):
    """How widely one set of runs reads, as shares of its median: ``iqr``,
    the distance between the quartiles as ``statistics.quantiles(n=4)``
    gives them (the benchmark contract's measure); ``iqr5`` the same with
    the run farthest from the median left out (the driver's reading of
    whether a bound is too tight); ``range`` and ``range5`` likewise."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))[:-1] \
        if len(values) > 2 else list(values)

    def iqr(xs):
        q = statistics.quantiles(xs, n=4)
        return (q[2] - q[0]) / med
    return {"median": med, "iqr": iqr(values), "iqr5": iqr(kept),
            "range5": (max(kept) - min(kept)) / med,
            "range": (max(values) - min(values)) / med}


def slice_rates(event_times, event_counts, t_open, t_close, width=1.0):
    """Per-slice rates of a stream of deliveries.

    ``event_times[i]`` is when a batch of ``event_counts[i]`` items became
    visible; the items of one delivery are taken as produced evenly since
    the delivery before it (the cumulative count is interpolated between
    deliveries), so a slice's rate does not jump by a whole delivery when a
    boundary falls just before or after one.  Returns the rates of the
    whole slices inside [t_open, t_close).
    """
    if len(event_times) < 2:
        return []
    cum, total = [], 0.0
    for c in event_counts:
        total += c
        cum.append(total)

    def at(t):
        # cumulative count at time t, linear between deliveries
        if t <= event_times[0]:
            return cum[0]
        if t >= event_times[-1]:
            return cum[-1]
        lo, hi = 0, len(event_times) - 1
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if event_times[mid] <= t:
                lo = mid
            else:
                hi = mid
        t0, t1 = event_times[lo], event_times[hi]
        if t1 <= t0:
            return cum[hi]
        return cum[lo] + (cum[hi] - cum[lo]) * (t - t0) / (t1 - t0)

    rates = []
    n = int((min(t_close, event_times[-1]) - t_open) // width)
    for i in range(n):
        a, b = t_open + i * width, t_open + (i + 1) * width
        rates.append((at(b) - at(a)) / width)
    return rates
