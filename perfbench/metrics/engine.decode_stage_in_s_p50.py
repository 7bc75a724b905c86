"""The program's bf:engine.stage_in span under each bf:engine.decode_call of
the traced tail (prefix arguments, sampler keys and every host array put
onto the device, before the jitted call): median."""
from perfbench.harness import program_spans


def read(run):
    return program_spans.of(run).median_s("bf:engine.stage_in",
                                          "bf:engine.decode_call")
