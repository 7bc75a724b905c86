"""Share of the cache positions its rows reserve that the dense decode
program's attention reads: `positions_read` over `positions_reserved`,
both summed over the program's bf:engine.decode_call spans in the traced
tail (entry attributes: layers x fused steps x rows x the bound the call's
read stops at, counted by the host from the lengths it staged with the
program's own rule, over the same with every row whole).  1.0 where every
row is read whole; None where the spans carry no such attributes."""
from perfbench.harness import program_spans


def read(run):
    return program_spans.of(run).attr_ratio(
        "bf:engine.decode_call", "positions_read", "positions_reserved")
