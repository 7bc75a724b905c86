"""Gap between consecutive tokens of one request, 80th percentile over all
gaps of requests that ran wholly in the window, for a cell whose prompts
pad to 2,048 tokens a quarter of the time: the gaps lie in clusters a
prefill apart (a decode call alone 36 ms, 48 % of them; with smaller
prefills up to 76 %; with one 2,048-token prefill 136 ms, up to 88-92 %),
and the 80th percentile lies inside the one-large-prefill cluster, where
the 90th sits on its end (six runs: 0.1352-0.1360 s, quartile distance
0.35 %, against the 90th's 1.5 %; PERF.md, PR 33).  What a client feels
when a long prompt is admitted beside it: a decode call and the prefill
that stalled it."""
from perfbench.harness import estimators


def read(run):
    readings = run["readings"].get("token_gap_s")
    return estimators.percentile(readings, 80) if readings else None
