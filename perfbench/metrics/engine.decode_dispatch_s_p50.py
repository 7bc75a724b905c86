"""The program's bf:engine.dispatch span under each bf:engine.decode_call of
the traced tail (the jitted call itself, until it returns to Python; the
launch cost ROADMAP A2 asks about): median."""
from perfbench.harness import program_spans


def read(run):
    return program_spans.of(run).median_s("bf:engine.dispatch",
                                          "bf:engine.decode_call")
