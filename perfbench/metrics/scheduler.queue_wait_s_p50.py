"""Time from submit (or requeue) to admission, the `waited_us` entry
attribute of the program's bf:serve.prefill spans in the traced tail:
median, in seconds."""
from perfbench.harness import program_spans


def read(run):
    us = program_spans.of(run).attr_median("bf:serve.prefill", "waited_us")
    return None if us is None else us / 1e6
