"""The benchmark's span around each ServeEngine.prefill call in the
window: median."""
from perfbench.harness import estimators


def read(run):
    if "window" not in run["facts"]:
        return None
    d = run["spans"].durations("prefill_call", *run["facts"]["window"])
    return estimators.median(d) if d else None
