"""Gap between consecutive tokens of one request, 99th percentile over all
gaps of requests that ran wholly in the window: inside the cluster of gaps
that hold two prefill calls or more (5.1 % of them), which the end-to-end
``token_gap_p90_s`` does not reach.  It spreads 1.0-1.4 % in a calm set of
runs and 5 % in a disturbed one, so it carries no bound (PERF.md, PR 32)."""
from perfbench.harness import estimators


def read(run):
    readings = run["readings"].get("token_gap_s")
    return estimators.percentile(readings, 99) if readings else None
