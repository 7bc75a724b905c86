"""Share of the rows the held experts' matmuls compute that are not real
work: 1 - sum of `pairs` (token-expert pairs of live lanes on held experts)
over sum of `rows` (a decode call sends every lane through every held
expert of every expert layer), the attributes of the program's
bf:engine.held_work marks in the traced tail."""
from perfbench.harness import program_spans


def read(run):
    filled = program_spans.of(run).attr_ratio("bf:engine.held_work",
                                              "pairs", "rows")
    return None if filled is None else 1.0 - filled
