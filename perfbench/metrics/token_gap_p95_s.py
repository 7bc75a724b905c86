"""Gap between consecutive tokens of one request as the client sees them:
95th percentile over all gaps of requests that ran wholly in the window."""
from perfbench.harness import estimators


def read(run):
    readings = run["readings"].get("token_gap_s")
    return estimators.percentile(readings, 95) if readings else None
