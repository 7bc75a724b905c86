"""1 - device busy time over the traced window, between 0 and 1: the same
number, in the same unit, as 1 - busy_s / window_s of the last line's
``device`` object."""
from perfbench.harness import trace


def read(run):
    return trace.idle_share(run.get("trace"))
