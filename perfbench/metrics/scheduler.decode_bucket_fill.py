"""Share of the decode programs' lanes that carry a request: sum of `lanes`
(the busiest replica's) over sum of `S` (the bucket chosen), the entry
attributes of the program's bf:serve.pack spans in the traced tail."""
from perfbench.harness import program_spans


def read(run):
    return program_spans.of(run).attr_ratio("bf:serve.pack", "lanes", "S")
