"""Device time a decode call spends at the vocabulary's two ends: op self
time of the decode programs under ``readout`` (embedding lookup, final
norm, head, sampler and its key update), over their module events."""
from perfbench.harness import scopes


def read(run):
    ana = scopes.on_chip(run)
    return ana and ana.per_call("decode ", ("readout",))
