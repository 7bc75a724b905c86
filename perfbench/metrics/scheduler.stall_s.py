"""The sum of the excesses of the UNTRACED window's stalls (instances more
than 0.1 s and three medians over their group's median; each is a line
``perfbench: stall ...`` on standard error), from the program's stage ring:
0 in a calm run; over the window's seconds it is the share of
serve_tok_per_s that stalls took."""
from perfbench.harness import stage_ring


def read(run):
    return stage_ring.of(run).stall_s
