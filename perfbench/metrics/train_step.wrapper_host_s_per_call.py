"""Host time the train step's wrapper adds to a call: the program's
bf:train.train_step span minus the bf:train.dispatch beneath it (flight
recorder, metrics registry, step-time table, retrace sentinel), mean over
the calls of the traced tail."""
from perfbench.harness import program_spans


def read(run):
    return program_spans.of(run).outside_s("bf:train.train_step",
                                           "bf:train.dispatch", "mean")
