"""device.idle_share for serving cells (a per-layer metric names one
end-to-end metric it moves, and that one exists only where requests are
served): 1 - device busy time over the traced window, between 0 and 1."""
from perfbench.harness import trace


def read(run):
    return trace.idle_share(run.get("trace"))
