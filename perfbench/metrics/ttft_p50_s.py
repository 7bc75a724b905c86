"""Submit (or due time) to first token seen by the client: median over
every request submitted in the window."""
from perfbench.harness import estimators


def read(run):
    readings = run["readings"].get("ttft_s")
    return estimators.median(readings) if readings else None
