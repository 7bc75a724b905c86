"""The benchmark's span around each ServeEngine.decode call in the window
(host work, program call and the transfer back that ends it): median."""
from perfbench.harness import estimators


def read(run):
    if "window" not in run["facts"]:
        return None
    d = run["spans"].durations("decode_call", *run["facts"]["window"])
    return estimators.median(d) if d else None
