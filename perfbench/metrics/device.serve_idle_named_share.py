"""Share of the device's idle time in the traced tail that falls inside a
leaf stage span of the program (a bf: span with no span beneath it): how
much of the idle time the program's own spans put a name to."""
from perfbench.harness import program_spans


def read(run):
    return program_spans.of(run).idle_named_share()
