"""The program's bf:engine.read_back stage (beside bf:engine.wait beneath a
decode call's bf:engine.collect) over the whole UNTRACED window, from the
program's stage ring: the transfer of the call's tokens (and a routed
model's carrier) to host arrays alone.  Median."""
from perfbench.harness import stage_ring


def read(run):
    return stage_ring.of(run).decode_read_back_s_p50
