"""Price candidate summaries of a serving run on KEPT series, with no
further runs: for every set of a survey (perfbench/tools/survey.py keeps
each run's series under chiprun_out/survey/series/<label>.<cell>...json)
the candidates' value per run, and per set their spread as the driver
reads it.

    python3 perfbench/tools/price_estimators.py [--dir DIR] [--labels A B C] [--cut S]

``--cut S`` prices the first S seconds of each window alone (series kept
since the runner records every request's times): a run of 51 s then says
what a run of 30 s would have read on the same machine in the same minute.

Candidates of the token gap: p50, p90, p95, p99 and the mean of the
slowest tenth; of the rate: the median over slices of 1, 2 and 3 s and
the whole window; of the time to first token: p50.  Beside them, where the
gaps lie: the share of gaps in each cluster (a decode call alone; with one
prefill call in the step; with two or more) and the cumulative share at
which each cluster ends, which says how far a percentile is from an edge.

Spread of a set: harness/estimators.py's ``spread`` (quartile distance
and range over the median, each also with the run farthest from the median
left out).  Pure Python, no JAX.
"""
import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import estimators  # noqa: E402


def tail_mean(values, share):
    """Mean of the largest ``share`` (0..1] of the values: the slowest
    tenth of the gaps for ``share`` 0.1 (at least one value)."""
    if not values:
        raise ValueError("tail mean of an empty series")
    xs = sorted(values)
    k = max(1, int(round(len(xs) * share)))
    return sum(xs[-k:]) / k


def cut(doc, seconds):
    """The kept series of a run as a window of its first ``seconds`` would
    have kept them, by runners/serve.py's own rules: first tokens of
    requests due in the window and seen in it, gaps of requests wholly
    inside it, tokens delivered in it."""
    # the window closes with the return of the first step past the mark
    t_close = next((t for t, _ in doc["deliveries"] if t >= seconds),
                   doc["deliveries"][-1][0])
    ttft = [times[0] - due for due, _live, times in doc["requests"]
            if 0 <= due < t_close and times[0] <= t_close]
    gaps = [b - a for due, live, times in doc["requests"]
            if due >= 0 and not live and times[-1] <= t_close
            for a, b in zip(times, times[1:])]
    tokens = sum(n for t, n in doc["deliveries"] if 0 < t <= t_close)
    return {**doc, "seconds": seconds, "series": {
        "token_gap_s": {"readings": gaps}, "ttft_s": {"readings": ttft},
        "serve_tok_per_s": {"summary": {"whole_window": tokens / t_close}}}}


def clusters(gaps, prefill_s):
    """Shares of the gaps that hold no, one, two or more prefill calls,
    cut half a prefill call above each cluster's foot (the foot of the
    first is the median of the fastest half: a decode call alone)."""
    foot = estimators.median(sorted(gaps)[:len(gaps) // 2])
    cuts = [foot + 0.5 * prefill_s, foot + 1.5 * prefill_s]
    n = len(gaps)
    c0 = sum(g < cuts[0] for g in gaps) / n
    c1 = sum(cuts[0] <= g < cuts[1] for g in gaps) / n
    return {"decode_call_s": foot, "share_decode_only": c0,
            "share_one_prefill": c1, "share_two_or_more": 1 - c0 - c1,
            "one_prefill_ends_at_percentile": 100 * (c0 + c1)}


def candidates(doc, prefill_s):
    gaps = doc["series"]["token_gap_s"]["readings"]
    ttft = doc["series"]["ttft_s"]["readings"]
    times = [t for t, _ in doc["deliveries"]]
    counts = [n for _, n in doc["deliveries"]]
    out = {"ttft_p50_s": estimators.median(ttft),
           "ttft_p90_s": estimators.percentile(ttft, 90),
           "token_gap_slow10_mean_s": tail_mean(gaps, 0.10),
           "tok_per_s_whole_window":
               doc["series"]["serve_tok_per_s"]["summary"]["whole_window"]}
    for q in (50, 90, 95, 99):
        out[f"token_gap_p{q}_s"] = estimators.percentile(gaps, q)
    for width in (1.0, 2.0, 3.0):
        out[f"tok_per_s_slice{width:g}s"] = estimators.median(
            estimators.slice_rates(times, counts, 0.0, doc["seconds"], width))
    out.update(clusters(gaps, prefill_s))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dir", default=os.path.join(ROOT, "chiprun_out",
                                                  "survey", "series"))
    ap.add_argument("--labels", nargs="*", default=None)
    ap.add_argument("--cell", default="pythia-410m.serve-closed32")
    ap.add_argument("--cut", type=float, default=None,
                    help="price the first CUT seconds of each window alone")
    ap.add_argument("--prefill-s", type=float, default=0.0079,
                    help="a prefill call, for the cluster cuts")
    args = ap.parse_args(argv)
    sets = {}
    for path in sorted(glob.glob(os.path.join(
            args.dir, f"*.{args.cell}.run*.trace0.json"))):
        label = os.path.basename(path).split(".", 1)[0]
        if args.labels is None or label in args.labels:
            sets.setdefault(label, []).append(path)
    for label, paths in sets.items():
        rows = []
        for path in paths:
            with open(path) as f:
                doc = json.load(f)
            if args.cut is not None:
                doc = cut(doc, args.cut)
            rows.append({"seed": doc["seed"],
                         **candidates(doc, args.prefill_s)})
            print(json.dumps({"set": label, **rows[-1]}))
        if len(rows) < 3:
            continue
        for name in rows[0]:
            if name != "seed":
                print(json.dumps({"set": label, "candidate": name, "n": len(
                    rows), **estimators.spread([r[name] for r in rows])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
