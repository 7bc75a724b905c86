"""Gap between consecutive tokens of one request as the client sees them:
90th percentile over all gaps of requests that ran wholly in the window.

The gaps lie in clusters: a decode call alone (two thirds of them), a
decode call and one prefill call (28 %), two prefill calls or more (5 %).
The 90th percentile lies inside the second cluster, five points from its
end; a 95th sits on the edge and jumps by a fifth when one point of share
crosses it (PERF.md, PR 32: why this and not ``token_gap_p95_s``)."""
from perfbench.harness import estimators


def read(run):
    readings = run["readings"].get("token_gap_s")
    return estimators.percentile(readings, 90) if readings else None
