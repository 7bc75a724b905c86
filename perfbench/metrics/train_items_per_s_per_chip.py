"""Images or tokens per second per chip: the MEDIAN of the block readings."""
from perfbench.harness import estimators


def read(run):
    readings = run["readings"].get("train_items_per_s_per_chip")
    return estimators.median(readings) if readings else None
