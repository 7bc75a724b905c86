"""Share of the prefill programs' token positions that are padding: 1 - sum
of `tokens` over sum of `Tpad`, the entry attributes of the program's
bf:engine.prefill_call spans in the traced tail."""
from perfbench.harness import program_spans


def read(run):
    filled = program_spans.of(run).attr_ratio("bf:engine.prefill_call",
                                              "tokens", "Tpad")
    return None if filled is None else 1.0 - filled
