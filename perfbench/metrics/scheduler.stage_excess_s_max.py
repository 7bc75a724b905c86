"""The UNTRACED window's largest excess of one stage instance over the median
of its group (path and bucket; harness/stage_ring.py has the rule), from the
program's stage ring: milliseconds in a calm run, seconds in a run that met
a stall.  Read beside scheduler.tok_per_s_slice_p50."""
from perfbench.harness import stage_ring


def read(run):
    return stage_ring.of(run).excess_s_max
