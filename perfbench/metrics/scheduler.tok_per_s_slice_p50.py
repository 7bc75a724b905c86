"""Completed output tokens per second, the MEDIAN over the window's slices
(``slice_seconds`` of the traffic file; deliveries interpolated between
scheduler steps, see estimators.slice_rates): what ``serve_tok_per_s`` was
until PR 32.  It sits still through a stall that the whole window's rate
shows, so the two together say whether a slow run was slow throughout."""
from perfbench.harness import estimators


def read(run):
    readings = run["readings"].get("serve_tok_per_s")
    return estimators.median(readings) if readings else None
