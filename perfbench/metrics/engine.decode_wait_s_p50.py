"""The program's bf:engine.wait stage (beneath bf:engine.collect beneath each
bf:engine.decode_call) over the whole UNTRACED window, from the program's
stage ring: the wait for ONE device program as the program sees it, the
scheduler running one call ahead.  Median."""
from perfbench.harness import stage_ring


def read(run):
    return stage_ring.of(run).decode_wait_s_p50
