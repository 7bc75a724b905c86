"""Host time of one Scheduler.step outside the engine's calls: the program's
bf:serve.step span minus the bf:engine.* calls beneath it (what is left is
the step's own time and its admit, prefill, pack and deliver stages),
median per step of the traced tail."""
from perfbench.harness import program_spans


def read(run):
    return program_spans.of(run).outside_s("bf:serve.step", "bf:engine.")
