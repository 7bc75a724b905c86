"""The program's bf:engine.collect span under each bf:engine.decode_call of
the traced tail (the wait for the device, the transfer back, the sampler
keys scattered and the retrace check): median."""
from perfbench.harness import program_spans


def read(run):
    return program_spans.of(run).median_s("bf:engine.collect",
                                          "bf:engine.decode_call")
